"""Model FLOP/s utilization, in percent: the operations the forward and
backward passes need for a sample (from shapes, by the configuration's
file; recomputation not counted) times the samples a second a chip of the
untraced window, over the chip's published bf16 peak."""


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    return (ctx["record"]["flops_per_sample"]
            * ctx["samples_per_s_per_chip"]
            / ctx["peaks"]["bf16_flops_per_s"] * 100.0)
