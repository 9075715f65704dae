"""Offline straggler / critical-path analyzer for merged cluster traces.

Input: a Chrome-trace JSON file as served by ``GET /trace`` on the
rendezvous/KV server (object form with ``traceEvents``), a bare event
array (e.g. a per-rank timeline or flight-recorder dump), or a
crash-truncated file — loading goes through the tolerant
``horovod_tpu.trace.load_trace_events``.

Report (``python tools/trace_report.py TRACE.json``):

- **per-collective arrival skew** — for every correlation id seen on >= 2
  ranks, the gap between the first-arrival and last-arrival rank,
  aggregated per op kind (count / mean / p50 / max);
- **top-straggler ranking** — ranks ordered by how often they arrived
  last, with their mean lateness;
- **per-step wire-vs-gap breakdown** — per rank, mean STEP span time
  split into dispatch (wire) time vs everything else (gap);
- **critical-path estimate** — dispatch time plus the arrival skew the
  whole world waited out, attributed to the rank that caused each wait.

Schema self-check (``--check``, the ``check_metric_names.py`` /
``check_fault_names.py`` lint pattern, run from a tier-1 test): validates
event structure, B/E balance per (pid, tid), correlation-id format, and
the once-per-phase-per-rank invariant. Exit code 0 means clean.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

VALID_PHASES = ("B", "E", "X", "i", "C", "M", "b", "e")


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def _corr_of(ev: dict) -> Optional[str]:
    args = ev.get("args")
    if isinstance(args, dict):
        c = args.get("corr")
        if isinstance(c, str):
            return c
    return None


def arrival_skew(events: List[dict]) -> Dict[str, dict]:
    """Per-correlation-id arrival skew from the merged "B" (enqueue)
    events: ``corr -> {kind, arrivals: {pid: ts_us}, first, last,
    skew_us}``. Only ids seen on >= 2 pids count."""
    arrivals: Dict[str, dict] = {}
    for ev in events:
        if ev.get("ph") != "B":
            continue
        corr = _corr_of(ev)
        if corr is None:
            continue
        ent = arrivals.setdefault(corr, {"kind": ev.get("name", ""),
                                         "arrivals": {}})
        ent["arrivals"].setdefault(int(ev.get("pid", 0)), float(ev["ts"]))
    out: Dict[str, dict] = {}
    for corr, ent in arrivals.items():
        ranks = ent["arrivals"]
        if len(ranks) < 2:
            continue
        first = min(ranks, key=ranks.get)
        last = max(ranks, key=ranks.get)
        out[corr] = {"kind": ent["kind"], "arrivals": ranks,
                     "first": first, "last": last,
                     "skew_us": ranks[last] - ranks[first]}
    return out


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(int(q * (len(sorted_vals) - 1)), len(sorted_vals) - 1)
    return sorted_vals[i]


def skew_by_kind(skews: Dict[str, dict]) -> Dict[str, dict]:
    by_kind: Dict[str, List[float]] = {}
    for ent in skews.values():
        by_kind.setdefault(ent["kind"], []).append(ent["skew_us"])
    out = {}
    for kind, vals in by_kind.items():
        vals.sort()
        out[kind] = {"count": len(vals),
                     "mean_us": sum(vals) / len(vals),
                     "p50_us": _percentile(vals, 0.5),
                     "max_us": vals[-1]}
    return out


def wire_by_link(events: List[dict]) -> Dict[str, dict]:
    """Per-kind cluster wire bytes by fabric link (ISSUE 10), summed from
    the ``link_bytes`` split the engine stamps on enqueue (B) events:
    ``kind -> {"ici"/"dcn"/"flat": bytes}``. Hierarchical legs surface as
    separate ici/dcn rows — the observable face of the 1/local_size
    cross-slice traffic reduction; traces from older runs (no stamps)
    yield an empty table."""
    out: Dict[str, dict] = {}
    for ev in events:
        if ev.get("ph") != "B":
            continue
        args = ev.get("args")
        lb = args.get("link_bytes") if isinstance(args, dict) else None
        if not isinstance(lb, dict):
            continue
        ent = out.setdefault(str(ev.get("name", "")), {})
        for link, b in lb.items():
            try:
                ent[str(link)] = ent.get(str(link), 0) + int(b)
            except (TypeError, ValueError):
                continue
    return out


def straggler_ranking(skews: Dict[str, dict]) -> List[dict]:
    """Ranks ordered by how often they arrived last (ties by total
    lateness): ``[{rank, last_count, total_late_us, mean_late_us}]``."""
    per_rank: Dict[int, dict] = {}
    for ent in skews.values():
        r = ent["last"]
        acc = per_rank.setdefault(r, {"rank": r, "last_count": 0,
                                      "total_late_us": 0.0})
        acc["last_count"] += 1
        acc["total_late_us"] += ent["skew_us"]
    out = sorted(per_rank.values(),
                 key=lambda a: (-a["last_count"], -a["total_late_us"]))
    for acc in out:
        acc["mean_late_us"] = acc["total_late_us"] / acc["last_count"]
    return out


def wire_vs_gap(events: List[dict]) -> Dict[int, dict]:
    """Per rank: mean per-step breakdown of STEP span time into dispatch
    ("wire", the X dispatch spans inside the step window) vs everything
    else ("gap": host time, stragglers, input pipeline). Ranks without
    STEP spans report totals over the whole trace instead."""
    steps: Dict[int, List[Tuple[float, float]]] = {}
    dispatch: Dict[int, List[Tuple[float, float]]] = {}
    span: Dict[int, Tuple[float, float]] = {}
    for ev in events:
        pid = int(ev.get("pid", 0))
        if ev.get("ph") != "X":
            if ev.get("ph") in ("B", "E"):
                t = float(ev.get("ts", 0.0))
                lo, hi = span.get(pid, (t, t))
                span[pid] = (min(lo, t), max(hi, t))
            continue
        t0 = float(ev.get("ts", 0.0))
        dur = float(ev.get("dur", 0.0))
        lo, hi = span.get(pid, (t0, t0 + dur))
        span[pid] = (min(lo, t0), max(hi, t0 + dur))
        if ev.get("name") == "STEP":
            steps.setdefault(pid, []).append((t0, t0 + dur))
        elif ev.get("cat") == "dispatch" or \
                str(ev.get("name", "")).startswith("XLA_"):
            dispatch.setdefault(pid, []).append((t0, t0 + dur))
    out: Dict[int, dict] = {}
    for pid in sorted(set(steps) | set(dispatch) | set(span)):
        d = dispatch.get(pid, [])
        st = steps.get(pid, [])
        if st:
            total = sum(b - a for a, b in st)
            wire = sum(min(b, sb) - max(a, sa)
                       for a, b in d for sa, sb in st
                       if min(b, sb) > max(a, sa))
            n = len(st)
        else:
            lo, hi = span.get(pid, (0.0, 0.0))
            total = hi - lo
            wire = sum(b - a for a, b in d)
            n = 1 if total > 0 else 0
        out[pid] = {"steps": len(st), "total_us": total,
                    "wire_us": min(wire, total),
                    "gap_us": max(total - wire, 0.0),
                    "per_step_total_us": total / n if n else 0.0}
    return out


def gap_attribution(events: List[dict],
                    skews: Optional[Dict[str, dict]] = None
                    ) -> Dict[int, dict]:
    """Per-rank attribution of step time into its four sinks (ISSUE 14 /
    ROADMAP item 5: the post-tune report must prove where the remaining
    MFU gap lives):

    - **dispatch** — host time spent inside XLA launches (the X spans of
      ``cat == "dispatch"`` clipped to STEP windows): per-launch
      overhead, the thing replay/overlap/fusion shrink;
    - **straggler_wait** — time this rank sat waiting for LATER arrivals
      at correlated collectives (per corr id: last-arrival ts minus this
      rank's arrival ts, clipped into the step windows' total): load
      imbalance, input-pipeline skew;
    - **wire** — collective in-flight time (B→E spans clipped to STEP
      windows) beyond what dispatch and straggler-wait already explain:
      actual byte movement on the critical path, the thing
      compression/topology-selection shrink;
    - **compute** — everything else: the model's math plus any host gap.
      After the tuner has flattened the other three, this is the MFU
      numerator's home.

    Ranks without STEP spans attribute over their whole trace span (the
    ``wire_vs_gap`` convention). All figures are totals across the
    rank's steps, with a ``pct`` breakdown of the step total."""
    if skews is None:
        skews = arrival_skew(events)
    steps: Dict[int, List[Tuple[float, float]]] = {}
    dispatch: Dict[int, List[Tuple[float, float]]] = {}
    opens: Dict[Tuple[int, str], float] = {}
    inflight: Dict[int, List[Tuple[float, float]]] = {}
    span: Dict[int, Tuple[float, float]] = {}

    def _grow(pid, lo, hi):
        a, b = span.get(pid, (lo, hi))
        span[pid] = (min(a, lo), max(b, hi))

    for ev in events:
        ph = ev.get("ph")
        pid = int(ev.get("pid", 0))
        if ph == "X":
            t0 = float(ev.get("ts", 0.0))
            dur = float(ev.get("dur", 0.0))
            _grow(pid, t0, t0 + dur)
            if ev.get("name") == "STEP":
                steps.setdefault(pid, []).append((t0, t0 + dur))
            elif ev.get("cat") == "dispatch" or \
                    str(ev.get("name", "")).startswith("XLA_"):
                dispatch.setdefault(pid, []).append((t0, t0 + dur))
        elif ph in ("B", "E"):
            t = float(ev.get("ts", 0.0))
            _grow(pid, t, t)
            corr = _corr_of(ev)
            if corr is None:
                continue
            if ph == "B":
                opens[(pid, corr)] = t
            else:
                t0 = opens.pop((pid, corr), None)
                if t0 is not None and t > t0:
                    inflight.setdefault(pid, []).append((t0, t))
    # per-rank straggler wait: how long each correlated collective's
    # last arrival made THIS rank wait past its own arrival
    waited: Dict[int, float] = {}
    for ent in skews.values():
        last_ts = ent["arrivals"][ent["last"]]
        for pid, ts in ent["arrivals"].items():
            if last_ts > ts:
                waited[pid] = waited.get(pid, 0.0) + (last_ts - ts)

    def _clip_total(spans, windows):
        if not windows:
            return sum(b - a for a, b in spans)
        return sum(min(b, wb) - max(a, wa)
                   for a, b in spans for wa, wb in windows
                   if min(b, wb) > max(a, wa))

    out: Dict[int, dict] = {}
    for pid in sorted(set(steps) | set(dispatch) | set(inflight)
                      | set(span)):
        st = steps.get(pid, [])
        if st:
            total = sum(b - a for a, b in st)
            n = len(st)
        else:
            lo, hi = span.get(pid, (0.0, 0.0))
            total, n = hi - lo, 1 if span.get(pid) else 0
        disp = min(_clip_total(dispatch.get(pid, []), st), total)
        wait = min(waited.get(pid, 0.0), max(total - disp, 0.0))
        infl = _clip_total(inflight.get(pid, []), st)
        wire = min(max(infl - disp - wait, 0.0),
                   max(total - disp - wait, 0.0))
        compute = max(total - disp - wait - wire, 0.0)
        row = {"steps": len(st), "total_us": total,
               "compute_us": compute, "dispatch_us": disp,
               "wire_us": wire, "straggler_wait_us": wait}
        row["pct"] = {
            k[:-3]: (round(100.0 * row[k] / total, 2) if total > 0
                     else 0.0)
            for k in ("compute_us", "dispatch_us", "wire_us",
                      "straggler_wait_us")}
        row["per_step_total_us"] = total / n if n else 0.0
        out[pid] = row
    return out


def critical_path(events: List[dict],
                  skews: Dict[str, dict]) -> dict:
    """A coarse critical-path estimate: total dispatch (wire) time plus
    the arrival skew the world waited out per collective, attributed to
    the last-arrival rank of each. ``{total_us, wire_us, wait_us,
    wait_by_rank: {rank: us}}``."""
    wire = sum(float(ev.get("dur", 0.0)) for ev in events
               if ev.get("ph") == "X" and ev.get("cat") == "dispatch")
    wait_by_rank: Dict[int, float] = {}
    for ent in skews.values():
        wait_by_rank[ent["last"]] = \
            wait_by_rank.get(ent["last"], 0.0) + ent["skew_us"]
    wait = sum(wait_by_rank.values())
    return {"total_us": wire + wait, "wire_us": wire, "wait_us": wait,
            "wait_by_rank": wait_by_rank}


def overlap_report(events: List[dict]) -> dict:
    """Comm/compute-overlap summary (ISSUE 6): how much wire time sits on
    the step critical path, and how much of the collectives' in-flight
    time was hidden off it.

    - ``wire_on_critical_path_pct`` — dispatch (wire-blocking) span time
      as a fraction of total step time: the share of the step the host/
      device spent *inside* collective launches instead of math. Lower
      with overlap on = wire left the critical path.
    - ``overlap_efficiency_pct`` — 1 − wire_on_cp / collective in-flight
      time (B→E spans): a collective that is in flight for 10 ms but only
      blocks the step for 1 ms was 90% hidden. None when the trace has no
      closed collective spans.

    Fed a merged PR 5 trace-ring segment of the same world and model with
    overlap on and with it off, the two ratios are what a change of the
    overlap schedule is judged by. On the chip: not measured (no benchmark
    cell makes the engine reduce; ROADMAP queue 3)."""
    wg = wire_vs_gap(events)
    total_us = sum(r["total_us"] for r in wg.values())
    wire_us = sum(r["wire_us"] for r in wg.values())
    opens: Dict[Tuple[int, str], float] = {}
    inflight_us = 0.0
    spans = 0
    for ev in events:
        corr = _corr_of(ev)
        if corr is None:
            continue
        pid = int(ev.get("pid", 0))
        if ev.get("ph") == "B":
            opens[(pid, corr)] = float(ev.get("ts", 0.0))
        elif ev.get("ph") == "E":
            t0 = opens.pop((pid, corr), None)
            if t0 is not None:
                inflight_us += max(float(ev.get("ts", 0.0)) - t0, 0.0)
                spans += 1
    return {
        "total_us": total_us,
        "wire_us": wire_us,
        "inflight_us": inflight_us,
        "collective_spans": spans,
        "wire_on_critical_path_pct": (
            round(100.0 * wire_us / total_us, 2) if total_us > 0 else None),
        "overlap_efficiency_pct": (
            round(100.0 * max(0.0, 1.0 - wire_us / inflight_us), 2)
            if inflight_us > 0 else None),
    }


def analyze(events: List[dict]) -> dict:
    """The full report as a plain dict (what ``main`` prints; tests and
    notebooks call this directly)."""
    skews = arrival_skew(events)
    ranking = straggler_ranking(skews)
    by_kind = skew_by_kind(skews)
    links = wire_by_link(events)
    for kind, ent in by_kind.items():
        if kind in links:
            ent["wire_bytes_by_link"] = links[kind]
    return {
        "events": len(events),
        "ranks": sorted({int(e.get("pid", 0)) for e in events
                         if e.get("ph") in ("B", "E", "X")}),
        "correlated_collectives": len(skews),
        "skew_by_kind": by_kind,
        "wire_by_link": links,
        "stragglers": ranking,
        "top_straggler": ranking[0]["rank"] if ranking else None,
        "wire_vs_gap": wire_vs_gap(events),
        "gap_attribution": gap_attribution(events, skews),
        "critical_path": critical_path(events, skews),
        "overlap": overlap_report(events),
    }


# ---------------------------------------------------------------------------
# --check: trace schema + correlation-invariant lint
# ---------------------------------------------------------------------------

def check_events(events: List[dict]) -> List[str]:
    """Validate the merged-trace schema; returns error strings (empty =
    clean):

    - every event is an object with a known ``ph``, a numeric ``ts``
      (metadata excepted) and an integer ``pid``;
    - "B"/"E" balance per (pid, tid), with no dangling end;
    - every correlation id parses as ``name#world_version#seq``;
    - per (pid, corr): at most one enqueue (B) and one complete (E) —
      the exactly-once-per-phase invariant the merger guarantees."""
    from horovod_tpu.trace import parse_corr
    errors: List[str] = []
    depth: Dict[Tuple[int, int], int] = {}
    seen: Dict[Tuple[int, str], Dict[str, int]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if ph not in VALID_PHASES:
            errors.append(f"event {i}: unknown phase {ph!r}")
            continue
        if ph != "M" and not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"event {i}: missing numeric ts")
        if not isinstance(ev.get("pid"), int):
            errors.append(f"event {i}: missing integer pid")
            continue
        key = (ev.get("pid"), ev.get("tid", 0))
        if ph == "B":
            depth[key] = depth.get(key, 0) + 1
        elif ph == "E":
            if depth.get(key, 0) <= 0:
                errors.append(f"event {i}: dangling E on pid/tid {key}")
            else:
                depth[key] -= 1
        corr = _corr_of(ev)
        if corr is not None:
            try:
                parse_corr(corr)
            except (ValueError, TypeError):
                errors.append(f"event {i}: malformed correlation id "
                              f"{corr!r}")
                continue
            if ph in ("B", "E"):
                phases = seen.setdefault((ev["pid"], corr), {})
                phases[ph] = phases.get(ph, 0) + 1
                if phases[ph] > 1:
                    errors.append(
                        f"event {i}: correlation id {corr!r} appears "
                        f"{phases[ph]}x in phase {ph} on pid {ev['pid']}")
    for key, d in depth.items():
        if d != 0:
            errors.append(f"pid/tid {key}: {d} unclosed B span(s)")
    return errors


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _fmt_us(us: float) -> str:
    return f"{us / 1e3:.2f} ms" if us >= 1e3 else f"{us:.0f} us"


def _b36(n: int) -> str:
    digits = "0123456789abcdefghijklmnopqrstuvwxyz"
    return digits[n % 36]


def schedule_timeline(schedule: str, n_stages: int, n_micro: int,
                      n_virtual: int = 1) -> str:
    """ASCII render of a pipeline schedule's static tick table (ISSUE 16):
    one F/B(/W under zb) row per stage, one column per tick, base-36
    microbatch index in active slots, '.' when the slot idles. The render
    is the ground truth the executor scans — generated from the same
    ``build_schedule_tables`` rows — so what prints here is literally what
    dispatches."""
    from horovod_tpu.parallel.pipeline import (build_schedule_tables,
                                               pipeline_bubble_fraction,
                                               resolve_pipeline_schedule)
    sched, v = resolve_pipeline_schedule(schedule, n_stages, n_micro,
                                         n_virtual)
    tb = build_schedule_tables(sched, n_stages, n_micro, v)
    lines = [f"schedule {sched}  p={n_stages} m={n_micro} v={v}  "
             f"ticks={tb.ticks}  predicted bubble "
             f"{pipeline_bubble_fraction(n_stages, n_micro, sched, v) * 100:.1f}%"]
    slot_rows = [("F", "f_active", "f_m"), ("B", "b_active", "b_m")]
    if tb.split_bw:
        slot_rows.append(("W", "w_active", "w_m"))
    for s in range(n_stages):
        for i, (label, act, mrow) in enumerate(slot_rows):
            head = f"stage {s}  " if i == 0 else " " * 9
            cells = "".join(
                _b36(int(tb.rows[mrow][t, s]))
                if tb.rows[act][t, s] else "."
                for t in range(tb.ticks))
            lines.append(f"{head}{label} {cells}")
    return "\n".join(lines)


def anomaly_report(events: List[dict],
                   meta: Optional[dict] = None) -> dict:
    """Cross-reference an anomaly's flight dump with ``gap_attribution``
    (ISSUE 20): for each rank in the dump, name the culprit phase — the
    dominant sink (compute / dispatch / wire / straggler_wait) of the
    step time the trace ring captured around the anomaly — plus the
    arrival-skew straggler ranking over the same window."""
    skews = arrival_skew(events)
    attr = gap_attribution(events, skews)
    culprits = {}
    for pid, g in attr.items():
        pct = g.get("pct", {})
        if not pct:
            continue
        phase = max(pct, key=lambda k: pct[k])
        culprits[pid] = {"phase": phase, "pct": pct[phase],
                         "per_step_total_us": g.get("per_step_total_us")}
    return {
        "meta": meta or {},
        "events": len(events),
        "culprit_phase": culprits,
        "stragglers": straggler_ranking(skews)[:5],
        "gap_attribution": attr,
    }


def _load_dump_meta(path: str) -> dict:
    """The flight dump's ``otherData`` block (rank, dropped-event count,
    flight_recorder marker) — tolerant of array-form/truncated files."""
    import json as _json
    try:
        with open(path) as f:
            obj = _json.load(f)
        if isinstance(obj, dict):
            return obj.get("otherData", {}) or {}
    except Exception:
        pass
    return {}


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        description="Straggler / critical-path report over a merged "
                    "cluster trace (GET /trace output), or a static "
                    "pipeline-schedule timeline (--schedule-timeline)")
    p.add_argument("trace", nargs="?", default=None,
                   help="trace JSON file (object or array form; "
                        "truncated files are recovered)")
    p.add_argument("--check", action="store_true",
                   help="validate the event schema and correlation-id "
                        "invariants instead of reporting")
    p.add_argument("--top", type=int, default=5,
                   help="stragglers to list (default 5)")
    p.add_argument("--json", action="store_true",
                   help="emit the report as JSON")
    p.add_argument("--schedule-timeline", metavar="SCHED",
                   help="render the static tick table for a pipeline "
                        "schedule (1f1b|interleaved|zb|auto) instead of "
                        "reading a trace")
    p.add_argument("--stages", type=int, default=4,
                   help="pipeline stages for --schedule-timeline")
    p.add_argument("--micro", type=int, default=8,
                   help="microbatches for --schedule-timeline")
    p.add_argument("--virtual", type=int, default=1,
                   help="virtual chunks per stage for --schedule-timeline")
    p.add_argument("--anomaly", metavar="DUMP",
                   help="cross-reference an anomaly's flight dump "
                        "(hvd_tpu_flight_rank<r>.json) with "
                        "gap_attribution: name the culprit phase of the "
                        "step window the trace ring captured")
    args = p.parse_args(argv)

    if args.schedule_timeline:
        print(schedule_timeline(args.schedule_timeline, args.stages,
                                args.micro, args.virtual))
        return 0
    if args.anomaly:
        from horovod_tpu.trace import load_trace_file
        events = load_trace_file(args.anomaly)
        rep = anomaly_report(events, _load_dump_meta(args.anomaly))
        if args.json:
            print(json.dumps(rep, indent=2, sort_keys=True))
            return 0
        meta = rep["meta"]
        print(f"anomaly flight dump: {args.anomaly}")
        print(f"  rank={meta.get('rank', '?')}  "
              f"events={rep['events']}  "
              f"dropped={meta.get('dropped', 0)}  "
              f"flight_recorder={meta.get('flight_recorder', False)}")
        if rep["culprit_phase"]:
            print("\nculprit phase per rank (dominant step-time sink in "
                  "the captured window):")
            for pid, c in sorted(rep["culprit_phase"].items()):
                print(f"  rank {pid:<4} {c['phase']:<16} "
                      f"{c['pct']:5.1f}% of step "
                      f"(per-step {_fmt_us(c['per_step_total_us'])})")
        else:
            print("\nno step windows in the dump — nothing to attribute")
        if rep["stragglers"]:
            print("\nstragglers in the captured window:")
            for acc in rep["stragglers"][:args.top]:
                print(f"  rank {acc['rank']:<4} last-arrival "
                      f"{acc['last_count']:>4}x   mean lateness "
                      f"{_fmt_us(acc['mean_late_us'])}")
        return 0
    if args.trace is None:
        p.error("a trace file is required unless --schedule-timeline "
                "or --anomaly is given")

    from horovod_tpu.trace import load_trace_file
    events = load_trace_file(args.trace)
    if args.check:
        errors = check_events(events)
        if errors:
            print(f"{len(errors)} trace schema error(s):")
            for e in errors[:50]:
                print(f"  - {e}")
            return 1
        print(f"{len(events)} events OK (schema, B/E balance, "
              f"correlation ids once per phase per rank)")
        return 0

    rep = analyze(events)
    if args.json:
        print(json.dumps(rep, indent=2, sort_keys=True))
        return 0
    print(f"events: {rep['events']}   ranks: {rep['ranks']}   "
          f"correlated collectives: {rep['correlated_collectives']}")
    if rep["skew_by_kind"]:
        print("\narrival skew by kind (first-arrival vs last-arrival rank):")
        for kind, s in sorted(rep["skew_by_kind"].items()):
            links = s.get("wire_bytes_by_link")
            tail = ("  wire[" + " ".join(
                f"{k}={v}" for k, v in sorted(links.items())) + "]"
                if links else "")
            print(f"  {kind:<22} n={s['count']:<5} "
                  f"mean={_fmt_us(s['mean_us']):<10} "
                  f"p50={_fmt_us(s['p50_us']):<10} "
                  f"max={_fmt_us(s['max_us'])}{tail}")
    if rep["wire_by_link"]:
        print("\nwire bytes by fabric link (cluster total, per kind):")
        for kind, links in sorted(rep["wire_by_link"].items()):
            row = "  ".join(f"{k}={v}" for k, v in sorted(links.items()))
            print(f"  {kind:<22} {row}")
    if rep["stragglers"]:
        print(f"\ntop stragglers (of {rep['correlated_collectives']} "
              f"correlated collectives):")
        for acc in rep["stragglers"][:args.top]:
            print(f"  rank {acc['rank']:<4} last-arrival "
                  f"{acc['last_count']:>4}x   mean lateness "
                  f"{_fmt_us(acc['mean_late_us'])}")
    if rep["wire_vs_gap"]:
        print("\nwire vs gap per rank:")
        for pid, w in sorted(rep["wire_vs_gap"].items()):
            print(f"  rank {pid:<4} steps={w['steps']:<4} "
                  f"wire={_fmt_us(w['wire_us']):<10} "
                  f"gap={_fmt_us(w['gap_us']):<10} "
                  f"(per-step {_fmt_us(w['per_step_total_us'])})")
    if rep["gap_attribution"]:
        print("\ngap attribution (per-step time -> compute / dispatch / "
              "wire / straggler-wait):")
        for pid, g in sorted(rep["gap_attribution"].items()):
            pct = g["pct"]
            print(f"  rank {pid:<4} steps={g['steps']:<4} "
                  f"compute={pct['compute']:5.1f}%  "
                  f"dispatch={pct['dispatch']:5.1f}%  "
                  f"wire={pct['wire']:5.1f}%  "
                  f"straggler={pct['straggler_wait']:5.1f}%  "
                  f"(per-step {_fmt_us(g['per_step_total_us'])})")
    cp = rep["critical_path"]
    print(f"\ncritical-path estimate: {_fmt_us(cp['total_us'])} "
          f"(wire {_fmt_us(cp['wire_us'])} + straggler waits "
          f"{_fmt_us(cp['wait_us'])})")
    for r, us in sorted(cp["wait_by_rank"].items(),
                        key=lambda kv: -kv[1]):
        print(f"  waits attributed to rank {r}: {_fmt_us(us)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
