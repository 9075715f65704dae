"""The process that holds a chip: builds the cell's job, warms it up, runs
the measured window and writes what it saw to ``<out>/record.json``.

Started by run.py, one process for all the chips of the cell. It prints
nothing the driver reads; the parent reduces the record to the result
line.

The loop is the user's loop with a logged loss: it enqueues step i+1, then
waits for the loss of step i. Every wait is a completion barrier, and its
time on the host's monotonic clock is when step i completed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

T_PROCESS = time.monotonic()

import files                # noqa: E402  (this directory is sys.path[0])
from job import Env         # noqa: E402

WARMUP_ROUND = 3        # steps between two looks at the compile counter
WARMUP_ROUNDS_MIN = 2   # the eager path compiles fresh variants through call 3
WARMUP_ROUNDS_MAX = 6
CALIBRATION_STEPS = 8   # sets the number of steps of the window
SETTLE_STEPS = 2        # after the calibration, before the window opens
TRACED_STEPS = 8        # a handful inside the trace, not the window
PROBE_STEPS = 5


def log(msg: str) -> None:
    print(f"bench worker: {msg}", file=sys.stderr, flush=True)


class CompileMonitor:
    """Counts the programs jax builds (compiled or read from the persistent
    cache: either stalls a step) and the cache's requests and hits."""

    def __init__(self):
        import jax.monitoring
        self.built = self.requests = self.hits = 0
        self.build_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event: str, seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1
            self.build_seconds += seconds


def run_steps(job, state, first: int, n: int, stamps: list, losses: list):
    """Steps ``first`` .. ``first + n - 1``, one enqueued ahead of the wait;
    returns the state with everything completed."""
    import jax
    from jax.profiler import TraceAnnotation as span

    def enqueue(i, state):
        with span("bench.input"):
            batch = job.batch(i)
        with span("bench.step"):
            return job.step(state, batch)

    state, pending = enqueue(first, state)
    for i in range(first + 1, first + n):
        state, following = enqueue(i, state)
        with span("bench.wait"):
            losses.append(float(pending))
        stamps.append(time.monotonic())
        pending = following
    with span("bench.wait"):
        jax.block_until_ready(state)
        losses.append(float(pending))
    stamps.append(time.monotonic())
    return state


def replicas_equal(state) -> dict:
    """Whether every chip that holds a copy of a piece of the state holds
    the same bits: each array's shards are grouped by the slice they
    cover, and every copy of a slice is moved to the chip of the first
    and compared with it whole. A program that declares its outputs
    replicated is believed by jax; a gradient that was not summed over all
    the chips shows here and nowhere else."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def same(a, b):
        bits = jnp.dtype(f"uint{8 * a.dtype.itemsize}")
        return jnp.array_equal(jax.lax.bitcast_convert_type(a, bits),
                               jax.lax.bitcast_convert_type(b, bits))

    compared = differing = 0
    for leaf in jax.tree_util.tree_leaves(state):
        copies = {}
        for shard in leaf.addressable_shards:
            copies.setdefault(repr(shard.index), []).append(shard)
        for first, *others in copies.values():
            for other in others:
                compared += 1
                differing += not bool(same(first.data, jax.device_put(
                    other.data, first.device)))
    return {"ok": compared > 0 and differing == 0,
            "error": {"copies_compared": compared, "differing": differing},
            "tolerance": {"differing": 0}}


def memory_in_use(devices) -> int:
    """Bytes of the fullest chip that are taken right now: the arrays that
    live on it (``bytes_in_use``) and what its loaded programs keep for
    their temporaries (``bytes_reserved``; on the v5e ``peak_bytes_in_use``
    never sees a program's temporaries, and a reservation stays while its
    program is loaded: my chip run, PR 22). 0 where the backend says
    nothing (the CPU)."""
    found = [d.memory_stats() or {} for d in devices]
    return int(max(m.get("bytes_in_use", 0) + m.get("bytes_reserved", 0)
                   for m in found))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--t0", type=float, required=True,
                    help="the parent's start on the monotonic clock")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    cell = files.cell(args.workload)
    spec = files.load_json(files.config_path(cell["config"]))
    traffic = files.load_json(files.traffic_path(cell["traffic"]))
    monitor = CompileMonitor()

    import jax
    platforms = sorted({d.platform for d in jax.devices()})
    if not args.rehearse and platforms != ["tpu"]:
        log(f"device gate: visible platforms {platforms}, need only 'tpu'")
        return 1
    if jax.device_count() < cell["chips"]:
        log(f"device gate: the cell needs {cell['chips']} chip(s); jax "
            f"sees {jax.device_count()} device(s)")
        return 1
    used = jax.devices()[:cell["chips"]]
    phases = [["process", T_PROCESS - args.t0],
              ["devices", time.monotonic() - args.t0]]

    def phase(name):        # where set-up goes, seconds from the parent's start
        phases.append([name, time.monotonic() - args.t0])

    record = {"device": {"platform": used[0].platform,
                         "kind": used[0].device_kind,
                         "count": cell["chips"]},
              "phases": phases}
    log(f"{jax.device_count()} x {used[0].device_kind} "
        f"({used[0].platform}), compile cache at "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR', 'none')}")

    env = Env(seed=args.seed, chips=cell["chips"], rehearse=args.rehearse,
              reference=files.reference_module(cell["config"]))
    model = files.config_module(cell["config"])
    step_file = os.path.splitext(files.config_path(cell["config"]))[0] \
        + f".{traffic['mode']}.py"
    job = files.load_module(step_file, "bench_step").build(
        model, spec, traffic, env)

    phase("job")
    state = jax.block_until_ready(job.init())
    phase("weights")
    checks = job.reference_checks(state)
    if len(used) > 1:
        state, checks["mesh_step"] = job.mesh_check(state)
    log(f"checks: {checks}")
    phase("checks")
    memory = [memory_in_use(used)]

    # warm-up: until a round of steps builds no program
    done, scratch = 0, []

    def run(n, stamps, losses):
        nonlocal state, done
        state = run_steps(job, state, done, n, stamps, losses)
        done += n

    for round_ in range(WARMUP_ROUNDS_MAX):
        built = monitor.built
        run(WARMUP_ROUND, scratch, scratch)
        if round_ + 1 >= WARMUP_ROUNDS_MIN and monitor.built == built:
            break
    else:
        log(f"not warm after {done} steps (programs built {monitor.built})")
        return 1
    record["warmup_steps"] = done
    phase("warm")

    # the number of steps is fixed before the window
    stamps = []
    t_open = time.monotonic()
    run(CALIBRATION_STEPS, stamps, scratch)
    step_s = (stamps[-1] - t_open) / CALIBRATION_STEPS
    n_steps = max(CALIBRATION_STEPS, int(args.seconds / step_s))
    run(SETTLE_STEPS, scratch, scratch)
    record["compile"] = {"built": monitor.built,
                         "seconds": monitor.build_seconds,
                         "cache_requests": monitor.requests,
                         "cache_hits": monitor.hits}
    phase("window")

    # the window
    built = monitor.built
    stamps, losses = [], []
    memory.append(memory_in_use(used))
    t_open = time.monotonic()
    run(n_steps, stamps, losses)
    record["window"] = {
        "t_open": t_open - args.t0, "steps": n_steps,
        "stamps": [t - args.t0 for t in stamps], "losses": losses,
        "failed": sum(not math.isfinite(x) for x in losses),
        "built": monitor.built - built}
    memory.append(memory_in_use(used))
    record["memory_peak_bytes"] = max(memory)
    record["samples_per_step"] = job.samples_per_step
    record["flops_per_sample"] = job.flops_per_sample
    record["kernel_costs"] = job.kernel_costs

    if args.trace:
        import xplane
        trace_dir = os.path.join(args.out, "trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans, not every python call
        stamps = []
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        run(TRACED_STEPS, stamps, scratch)
        jax.profiler.stop_trace()
        t_walk = time.monotonic()
        trace = xplane.summarize_file(xplane.newest_xplane(trace_dir))
        record["traced"] = {
            "steps": TRACED_STEPS, "stamps": stamps, "trace": trace,
            "walk_s": time.monotonic() - t_walk}
        if job.probe is not None:
            record["probes"] = []
            for _ in range(PROBE_STEPS):
                state, taken = job.probe(state, job.batch(done))
                record["probes"].append(taken)
                done += 1

    if len(used) > 1:
        checks["replicas_equal"] = replicas_equal(state)
        log(f"replicas: {checks['replicas_equal']}")
    record["checks"] = checks
    job.close()
    path = os.path.join(args.out, "record.json")
    with open(path + ".tmp", "w") as f:
        json.dump(record, f)
    os.replace(path + ".tmp", path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
