"""A kernel's share of its roofline, in percent: the least time the chip
could take for one step's calls (the larger of operations over the peak
rate and bytes over the peak memory rate; ``cost`` names the entry of the
job's ``kernel_costs``, computed from shapes by the configuration's file)
over the device time of the matching operations. Notes which of the two
bounds it."""

import trace_ops


def read(ctx, spec):
    cost = ctx["record"]["kernel_costs"].get(spec["cost"])
    found = trace_ops.per_step_ms(ctx, {**spec, "what": "self"})
    if cost is None or not found or not sum(found) or ctx["peaks"] is None:
        return None
    by_compute = cost["flops"] / ctx["peaks"]["bf16_flops_per_s"]
    by_memory = cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"{spec['cost']}: least time {max(by_compute, by_memory) * 1e3:.3f} "
        f"ms a step, bound by "
        f"{'compute' if by_compute >= by_memory else 'memory'} "
        f"({by_compute * 1e3:.3f} ms at peak FLOP/s, "
        f"{by_memory * 1e3:.3f} ms at peak bytes/s)")
    measured_s = sum(found) / len(found) / 1e3
    return max(by_compute, by_memory) / measured_s * 100.0
