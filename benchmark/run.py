"""benchmark/run.py — runs ONE cell of BENCHMARK.json ONCE and prints the
result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

This process never imports jax: a chip belongs to one process, and the
process that needs it is its child, benchmark/worker.py, which drives all
the chips of the cell. The child writes a record; this file reduces the
record to the metrics (end to end with ``--trace 0``, per layer with
``--trace 1``), decides ``correct`` and prints, as the last line of its
standard output, the object the driver reads. Earlier lines (``bench:``)
name the platform, device kind and count, and say what the checks found.

``--rehearse`` runs the same code at the tiny widths of the configuration
files on the CPU (forced host devices) and prints counts only: it proves
the control flow, never a speed. ``--out <dir>`` keeps the record
and the profiler's files in ``<dir>`` for reading by hand.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

T0 = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "readers")]

import files        # noqa: E402
import stats        # noqa: E402
import tracecalc    # noqa: E402

DEADLINE_S = 1150       # the contract: 1200 s with compilation, 360 s after


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


def child_env(out: str, rehearse: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [files.ROOT, env.get("PYTHONPATH")]))
    # the flight recorder's dumps belong to this run, not to the checkout
    env["HOROVOD_TPU_TRACE_DUMP_DIR"] = out
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            "platform_device_count=4").strip()
        # a rehearsal leaves no CPU programs in the chip's cache
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        # a fixed path inside the checkout (the path is part of the key);
        # every program is cached, however quick it was to compile
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.join(files.ROOT, ".jax_cache"))
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    return env


def run_worker(args, out: str) -> int:
    cmd = [sys.executable, os.path.join(HERE, "worker.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--out", out, "--t0", repr(T0)]
    if args.rehearse:
        cmd.append("--rehearse")
    # the child's output goes to this process's stderr: stdout carries the
    # result and nothing else
    proc = subprocess.Popen(cmd, cwd=files.ROOT, env=child_env(
        out, args.rehearse), stdout=sys.stderr, stderr=sys.stderr,
        start_new_session=True)
    try:
        rc = proc.wait(timeout=DEADLINE_S - (time.monotonic() - T0))
    except subprocess.TimeoutExpired:
        say(f"out of time after {DEADLINE_S} s")
        rc = 124
    finally:
        # the whole group, whatever the child started; then wait for it
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    return rc


def context(cell: dict, rec: dict) -> dict:
    """What the readers get: the worker's record, and the window as the
    user saw it."""
    win = rec["window"]
    gaps = [b - a for a, b in zip([win["t_open"]] + win["stamps"][:-1],
                                  win["stamps"])]
    device = rec["device"]
    return {
        "cell": cell, "record": rec, "chips": cell["chips"],
        "steps": win["steps"], "setup_s": win["t_open"],
        "gaps_ms": [g * 1e3 for g in gaps],
        "samples_per_s_per_chip": win["steps"] * rec["samples_per_step"]
        / (win["stamps"][-1] - win["t_open"]) / cell["chips"],
        "peaks": files.peaks(device["kind"])
        if device["platform"] == "tpu" else None,
        "notes": []}


def end_to_end(ctx: dict) -> dict:
    return {
        "samples_per_s_per_chip": ctx["samples_per_s_per_chip"],
        "step_ms_p50": stats.median(ctx["gaps_ms"]),
        "peak_hbm_gb": ctx["record"]["memory_peak_bytes"] / 1e9,
        "setup_s": ctx["setup_s"]}


def per_layer(ctx: dict, declared: list) -> dict:
    """Every per-layer metric declared for this cell: an entry without
    ``workloads`` is every cell's, one with the list is theirs alone. The
    driver wants each of them on the line, so a reader that finds nothing
    (its metric is then left out) is said aloud."""
    out = {}
    for m in declared:
        if "workloads" in m and ctx["cell"]["name"] not in m["workloads"]:
            continue
        spec, read = files.layer_metric(m["name"])
        value = read(ctx, spec)
        if value is None:
            say(f"{m['name']}: declared for this cell and nothing to read; "
                f"left out of the line")
        else:
            out[m["name"]] = value
    return out


def loss_fell(losses: list, pool: int) -> bool:
    """The loss is lower over the window's last steps than over its first:
    as many steps as the pool has batches, so that both ends see the same
    inputs."""
    k = max(1, min(pool, len(losses) // 2))
    return sum(losses[-k:]) < sum(losses[:k])


def judge(rec: dict, traffic: dict) -> dict:
    """Every check by name; ``correct`` is all of them."""
    checks = {name: c["ok"] for name, c in rec["checks"].items()}
    checks["loss_finite"] = rec["window"]["failed"] == 0
    checks["loss_fell"] = loss_fell(rec["window"]["losses"],
                                    traffic["pool_batches"])
    return checks


def traced_device(ctx: dict) -> dict:
    """busy_s and window_s, averaged over the traced chips."""
    found = []
    for dev in ctx["record"]["traced"]["trace"]["devices"]:
        lo, hi = tracecalc.window(dev)
        found.append((stats.total(tracecalc.busy(dev)) / 1e9,
                      (hi - lo) / 1e9))
    if not found:
        raise SystemExit("bench: the trace holds no device operation")
    return {"busy_s": sum(b for b, _ in found) / len(found),
            "window_s": sum(w for _, w in found) / len(found)}


def breakdown(ctx: dict) -> dict:
    """Of the first chip of the trace: the operations that took most time
    by pass, scope and name, and the idle gaps by span."""
    trace = ctx["record"]["traced"]["trace"]
    dev = trace["devices"][0]
    return {"device_ops": tracecalc.top_ops(dev),
            "idle_gaps": tracecalc.idle_by_span(dev, trace["spans"])}


def reduce(cell: dict, rec: dict, trace: int, rehearse: bool):
    """The result line's object from the worker's record, or None where the
    record allows no result. Says on ``bench:`` lines what it found."""
    bench = files.benchmark_json()
    traffic = files.load_json(files.traffic_path(cell["traffic"]))
    ctx = context(cell, rec)
    device = dict(rec["device"])
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"devices={device['count']} workload={cell['name']} "
        f"steps={ctx['steps']} warmup_steps={rec['warmup_steps']}")
    say("set-up, seconds from the start: " + ", ".join(
        f"{name} {t:.1f}" for name, t in rec["phases"])
        + f"; programs built {rec['compile']['built']} in "
        f"{rec['compile']['seconds']:.1f} s, cache "
        f"{rec['compile']['cache_hits']}/{rec['compile']['cache_requests']}")
    if rec["window"]["built"]:
        say(f"no result: {rec['window']['built']} program(s) were built "
            f"inside the window")
        return None
    checks = judge(rec, traffic)
    say(f"checks: {json.dumps(checks)}")
    for name, check in rec["checks"].items():
        say(f"{name}: {json.dumps(check)}")

    result = {"correct": all(checks.values()), "attempted": ctx["steps"],
              "failed": rec["window"]["failed"]}
    e2e = end_to_end(ctx)
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if rehearse:
        # counts only: a time from a CPU run is not a device metric
        result.update(rehearsal=True, metrics={}, counts={
            "steps": ctx["steps"], "programs_built": rec["compile"]["built"]})
    elif trace:
        layer = per_layer(ctx, bench["per_layer"])
        traced = [b - a for a, b in zip(rec["traced"]["stamps"][:-1],
                                        rec["traced"]["stamps"][1:])]
        say(f"tracing: step_ms_p50 {stats.median(traced) * 1e3:.4f} traced "
            f"against {e2e['step_ms_p50']:.4f} untraced "
            f"({rec['traced']['steps']} traced steps); the profiler's file "
            f"walked in {rec['traced'].get('walk_s', float('nan')):.2f} s")
        say("end to end (untraced window of this run): " + json.dumps(e2e))
        for note in ctx["notes"]:
            say(note)
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in layer.items()}
        device.update(traced_device(ctx))
        result["breakdown"] = breakdown(ctx)
    else:
        result["metrics"] = {k: {"value": v, "unit": units[k]}
                             for k, v in e2e.items()}
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    result["device"] = device
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="keep the record and the trace here")
    args = ap.parse_args()

    cell = files.cell(args.workload)
    out = args.out or tempfile.mkdtemp(prefix="bench_")
    os.makedirs(out, exist_ok=True)
    try:
        rc = run_worker(args, out)
        path = os.path.join(out, "record.json")
        if rc != 0 or not os.path.exists(path):
            say(f"no result: the worker ended with code {rc}")
            return rc or 1
        rec = files.load_json(path)
    finally:
        if not args.out:
            shutil.rmtree(out, ignore_errors=True)
    result = reduce(cell, rec, args.trace, args.rehearse)
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
