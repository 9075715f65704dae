"""Cluster health report over the root KV server's observability
endpoints (ISSUE 18 — the operator's one-stop view of the hierarchical
telemetry fabric).

Input: the root server's base URL (``--url http://host:port``, the same
server that serves the merged ``GET /metrics`` / ``GET /trace``). The
report pulls three endpoints:

- ``GET /agg`` — aggregator registrations, per-stream rollup freshness,
  and the server's own per-(verb, scope) request accounting;
- ``GET /metrics`` — the merged Prometheus scrape (fallback / shed /
  failover counters, per-rank step counts);
- ``GET /trace`` — the merged cluster trace (straggler ranking via
  ``tools/trace_report.py`` analysis).

Sections (``python tools/health_report.py --url http://host:port``):

- **per-slice telemetry freshness** — each slice's aggregator address
  and the age of its last ``metrics``/``trace``/``stall`` rollup (a
  slice whose rollups stopped aging forward is a dead or wedged
  aggregator; ranks then show up in the fallback counts instead);
- **stragglers** — the trace analyzer's last-arrival ranking;
- **degradation counters** — aggregator fallbacks
  (``hvd_tpu_agg_fallback_total``), shed telemetry bytes
  (``hvd_tpu_kv_shed_bytes_total``), KV failovers/breaker trips, lost
  acked writes — every way the control plane degrades, with the
  convention that nonzero is worth a look and zero is healthy;
- **control-plane load** — ``hvd_tpu_kv_requests_total`` by verb and
  scope plus requests-per-step (total KV requests over total cluster
  steps): the number the aggregator tier exists to keep O(slices);
- **driver replication** (ISSUE 19) — the elastic driver's journal head
  (``GET /driver/head``), the KV replica role/epoch and standby apply
  lag (``GET /_repl/status``), and the promotion/failover counters
  (``hvd_tpu_driver_{journal_writes,promotions,failovers}_total``,
  ``hvd_tpu_elastic_recoveries_total{kind="driver_failover"}``) — the
  at-a-glance answer to "could a standby take over right now, and has
  one ever had to?";
- **step health / SLO** (ISSUE 20) — cluster p50/p99 step time from the
  merged ``hvd_tpu_step_seconds`` histogram, the anomaly inventory by
  class and rank (``hvd_tpu_step_anomalies_total``), flight dumps by
  trigger, and per-rank HBM headroom (``hvd_tpu_hbm_bytes``).

``--json`` emits the assembled report as one JSON object.
``--format=json`` instead emits the *evaluated* report in the
``tools/check.py`` shape — ``{"ok": bool, "checks": {section:
{"ok", "errors", "stats"}}}`` — and the process exits nonzero when any
section is red, so CI and chaos jobs can assert on cluster health
machine-readably.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_SERIES_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>[^\s]+)\s*$')
_LABEL_RE = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse_prometheus(text: str) -> Dict[str, List[Tuple[dict, float]]]:
    """Parse a Prometheus text exposition into
    ``name -> [(labels, value)]``. Tolerant: unparseable lines are
    skipped (the report must work against future scrapes)."""
    out: Dict[str, List[Tuple[dict, float]]] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SERIES_RE.match(line)
        if m is None:
            continue
        try:
            value = float(m.group("value"))
        except ValueError:
            continue
        labels = {k: v for k, v in _LABEL_RE.findall(m.group("labels") or "")}
        out.setdefault(m.group("name"), []).append((labels, value))
    return out


def _fetch(url: str, timeout: float = 10.0) -> bytes:
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _total(series: Dict[str, list], name: str, **match) -> float:
    tot = 0.0
    for labels, v in series.get(name, []):
        if all(labels.get(k) == str(want) for k, want in match.items()):
            tot += v
    return tot


def _by_label(series: Dict[str, list], name: str, label: str
              ) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for labels, v in series.get(name, []):
        key = labels.get(label, "")
        out[key] = out.get(key, 0.0) + v
    return out


def histogram_quantile(series: Dict[str, list], name: str,
                       q: float) -> Optional[float]:
    """Quantile estimate from merged Prometheus histogram ``_bucket``
    series (the bucket upper bound the q-th observation falls in —
    log2 buckets, so the estimate is within 2x). Cumulative counts are
    summed across every rank's series per ``le`` bound."""
    by_le: Dict[float, float] = {}
    for labels, v in series.get(name + "_bucket", []):
        le = labels.get("le", "")
        bound = float("inf") if le in ("+Inf", "inf") else float(le)
        by_le[bound] = by_le.get(bound, 0.0) + v
    if not by_le:
        return None
    total = by_le.get(float("inf"), max(by_le.values()))
    if total <= 0:
        return None
    target = q * total
    for bound in sorted(by_le):
        if by_le[bound] >= target:
            return bound
    return float("inf")


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------

def slice_freshness(agg_summary: dict, now: Optional[float] = None) -> dict:
    """Per-slice aggregator registration + rollup ages in seconds:
    ``slice -> {addr, ranks, rollup_age: {stream: seconds|None}}``."""
    if now is None:
        now = time.time()
    slices = agg_summary.get("slices", {}) or {}
    rollups = agg_summary.get("rollups", {}) or {}
    out: Dict[str, dict] = {}
    for k, reg in sorted(slices.items(), key=lambda kv: str(kv[0])):
        reg = reg if isinstance(reg, dict) else {}
        ent = {"addr": reg.get("addr"), "ranks": reg.get("ranks"),
               "rollup_age": {}}
        for stream, per_slice in rollups.items():
            roll = (per_slice or {}).get(str(k))
            ts = roll.get("ts") if isinstance(roll, dict) else None
            ent["rollup_age"][stream] = (
                round(now - float(ts), 1)
                if isinstance(ts, (int, float)) else None)
        out[str(k)] = ent
    return out


def degradation_counters(series: Dict[str, list]) -> dict:
    """Every counter that records a control-plane degradation, totalled
    (and split by stream/scope where the labels carry attribution).
    Zero everywhere = healthy."""
    return {
        "agg_fallbacks": {
            "total": _total(series, "hvd_tpu_agg_fallback_total"),
            "by_stream": _by_label(series, "hvd_tpu_agg_fallback_total",
                                   "stream")},
        "shed_bytes": {
            "total": _total(series, "hvd_tpu_kv_shed_bytes_total"),
            "by_scope": _by_label(series, "hvd_tpu_kv_shed_bytes_total",
                                  "scope")},
        "kv_failovers": _total(series, "hvd_tpu_kv_failover_total"),
        "kv_breaker_trips": _total(series, "hvd_tpu_kv_breaker_open_total"),
        "kv_backpressure": _total(series, "hvd_tpu_kv_backpressure_total"),
        "kv_gave_up": _total(series, "hvd_tpu_kv_gave_up_total"),
        "kv_acked_writes_lost": _total(
            series, "hvd_tpu_kv_acked_writes_lost_total"),
        "watchdog_escalations": _total(
            series, "hvd_tpu_watchdog_escalations_total"),
        "stall_publish_failures": _total(
            series, "hvd_tpu_stall_publish_failures_total"),
        "trace_publish_failures": _total(
            series, "hvd_tpu_trace_publish_failures_total"),
    }


def control_plane_load(series: Dict[str, list],
                       agg_summary: Optional[dict] = None) -> dict:
    """KV request volume at the root by verb and scope, normalized per
    cluster step — the O(slices)-vs-O(ranks) headline number."""
    requests = _by_label(series, "hvd_tpu_kv_requests_total", "scope")
    req_bytes = _by_label(series, "hvd_tpu_kv_request_bytes_total", "scope")
    by_verb = _by_label(series, "hvd_tpu_kv_requests_total", "verb")
    steps_by_rank = {
        labels.get("rank", ""): v
        for labels, v in series.get("hvd_tpu_steps_total", [])
        if labels.get("rank", "") not in ("", "driver")}
    total_steps = max(steps_by_rank.values()) if steps_by_rank else 0.0
    total_requests = sum(requests.values())
    out = {
        "requests_by_scope": requests,
        "request_bytes_by_scope": req_bytes,
        "requests_by_verb": by_verb,
        "total_requests": total_requests,
        "cluster_steps": total_steps,
        "steps_by_rank": steps_by_rank,
        "requests_per_step": (
            round(total_requests / total_steps, 2)
            if total_steps > 0 else None),
    }
    if agg_summary:
        out["server_request_stats"] = agg_summary.get("request_stats", {})
    return out


def driver_replication(series: Dict[str, list],
                       repl_status: Optional[dict],
                       journal_head: Optional[int]) -> dict:
    """Driver fault-domain health (ISSUE 19): journal head, replica
    role/epoch, standby apply lag, and the promotion/failover history.
    ``journal_head is None`` means no elastic driver has journaled yet
    (non-elastic job, or journaling disabled)."""
    st = repl_status or {}
    seq = st.get("seq")
    applied = st.get("applied_seq")
    lag = (max(0, int(seq) - int(applied))
           if isinstance(seq, (int, float)) and
           isinstance(applied, (int, float)) else None)
    return {
        "journal_head": journal_head,
        "repl_role": st.get("role"),
        "repl_epoch": st.get("epoch"),
        "standby_lag": lag,
        "journal_writes": {
            "total": _total(series, "hvd_tpu_driver_journal_writes_total"),
            "by_kind": _by_label(
                series, "hvd_tpu_driver_journal_writes_total", "kind")},
        "promotions": _total(series, "hvd_tpu_driver_promotions_total"),
        "failovers": _total(series, "hvd_tpu_driver_failovers_total"),
        "failover_recoveries": _total(
            series, "hvd_tpu_elastic_recoveries_total",
            kind="driver_failover"),
        "discovery_failures": _total(
            series, "hvd_tpu_discovery_failures_total"),
    }


def step_health(series: Dict[str, list]) -> dict:
    """Step health / SLO (ISSUE 20): cluster step-time percentiles from
    the merged ``hvd_tpu_step_seconds`` histogram, the anomaly
    inventory by class and rank, flight dumps by trigger, and per-rank
    HBM headroom."""
    count = _total(series, "hvd_tpu_step_seconds_count")
    ssum = _total(series, "hvd_tpu_step_seconds_sum")
    p50 = histogram_quantile(series, "hvd_tpu_step_seconds", 0.50)
    p99 = histogram_quantile(series, "hvd_tpu_step_seconds", 0.99)
    anomalies_by_rank: Dict[str, Dict[str, float]] = {}
    for labels, v in series.get("hvd_tpu_step_anomalies_total", []):
        rank = labels.get("rank", "")
        cls = labels.get("class", "")
        anomalies_by_rank.setdefault(rank, {})
        anomalies_by_rank[rank][cls] = \
            anomalies_by_rank[rank].get(cls, 0.0) + v
    hbm: Dict[str, dict] = {}
    for labels, v in series.get("hvd_tpu_hbm_bytes", []):
        rank = labels.get("rank", "")
        hbm.setdefault(rank, {})[labels.get("kind", "")] = v
    headroom = {}
    for rank, kinds in hbm.items():
        limit, in_use = kinds.get("limit"), kinds.get("in_use")
        if limit and in_use is not None:
            # a loaded program's temporaries are "reserved", not "in_use"
            headroom[rank] = limit - in_use - kinds.get("reserved", 0)
    return {
        "steps_observed": count,
        "step_time_mean_ms": (
            round(1e3 * ssum / count, 3) if count else None),
        "step_time_p50_ms": (
            round(1e3 * p50, 3) if p50 not in (None, float("inf"))
            else None),
        "step_time_p99_ms": (
            round(1e3 * p99, 3) if p99 not in (None, float("inf"))
            else None),
        "anomalies_total": _total(
            series, "hvd_tpu_step_anomalies_total"),
        "anomalies_by_class": _by_label(
            series, "hvd_tpu_step_anomalies_total", "class"),
        "anomalies_by_rank": anomalies_by_rank,
        "flight_dumps": {
            "total": _total(series, "hvd_tpu_flight_dumps_total"),
            "by_trigger": _by_label(
                series, "hvd_tpu_flight_dumps_total", "trigger")},
        "hbm_bytes": hbm,
        "hbm_headroom_bytes": headroom,
        "hbm_min_headroom_bytes": (
            min(headroom.values()) if headroom else None),
    }


def assemble(url: str, timeout: float = 10.0) -> dict:
    """Fetch all three endpoints and assemble the report dict. Each
    endpoint degrades independently — a root without the /agg route (flat
    topology, older server) still yields the metrics/trace sections."""
    report: dict = {"url": url, "ts": time.time(), "errors": {}}
    agg_summary: dict = {}
    try:
        agg_summary = json.loads(_fetch(url.rstrip("/") + "/agg", timeout))
    except Exception as e:
        report["errors"]["agg"] = str(e)
    series: Dict[str, list] = {}
    try:
        series = parse_prometheus(
            _fetch(url.rstrip("/") + "/metrics", timeout).decode(
                "utf-8", "replace"))
    except Exception as e:
        report["errors"]["metrics"] = str(e)
    # Optional subsystems: a 404 just means "not replicated" / "no
    # elastic driver journaling yet", not an unhealthy endpoint.
    repl_status: Optional[dict] = None
    try:
        repl_status = json.loads(
            _fetch(url.rstrip("/") + "/_repl/status", timeout))
    except Exception:
        pass
    journal_head: Optional[int] = None
    try:
        journal_head = int(
            _fetch(url.rstrip("/") + "/driver/head", timeout))
    except Exception:
        pass
    report["slices"] = slice_freshness(agg_summary)
    report["degradation"] = degradation_counters(series)
    report["control_plane"] = control_plane_load(series, agg_summary)
    report["driver_replication"] = driver_replication(
        series, repl_status, journal_head)
    report["step_health"] = step_health(series)
    try:
        from horovod_tpu.trace import load_trace_events
        from tools.trace_report import arrival_skew, straggler_ranking
        events = load_trace_events(
            _fetch(url.rstrip("/") + "/trace", timeout).decode(
                "utf-8", "replace"))
        ranking = straggler_ranking(arrival_skew(events))
        report["stragglers"] = ranking[:5]
        report["trace_events"] = len(events)
    except Exception as e:
        report["errors"]["trace"] = str(e)
        report["stragglers"] = []
    return report


def evaluate(report: dict, stale_after: float = 120.0) -> dict:
    """Red/green the assembled report per section, in the
    ``tools/check.py`` shape: ``{"ok", "checks": {section: {"ok",
    "errors", "stats"}}}``. Green everywhere is the steady healthy
    state; every red line names the evidence."""
    checks: Dict[str, dict] = {}

    def add(name: str, errors: List[str], stats: dict):
        checks[name] = {"ok": not errors, "errors": errors, "stats": stats}

    errs = []
    if "metrics" in report.get("errors", {}):
        errs.append("metrics endpoint unavailable: "
                    f"{report['errors']['metrics']}")
    add("endpoints", errs, {"errors": report.get("errors", {})})

    errs = []
    for k, ent in report.get("slices", {}).items():
        for stream, age in ent.get("rollup_age", {}).items():
            if age is not None and age > stale_after:
                errs.append(f"slice {k} {stream} rollup is {age:.0f}s "
                            f"stale (> {stale_after:.0f}s)")
    add("slices", errs, {"slices": len(report.get("slices", {}))})

    deg = report.get("degradation", {})
    errs = []
    for key, label in (("kv_acked_writes_lost", "acked KV writes lost"),
                       ("kv_gave_up", "KV publishes gave up"),
                       ("watchdog_escalations", "watchdog escalations")):
        if deg.get(key, 0):
            errs.append(f"{label}: {deg[key]:.0f}")
    add("degradation", errs, deg)

    sh = report.get("step_health", {})
    errs = []
    if sh.get("anomalies_total", 0):
        by_cls = ", ".join(f"{c}={v:.0f}" for c, v in
                           sorted(sh.get("anomalies_by_class", {}).items()))
        errs.append(f"{sh['anomalies_total']:.0f} step anomalies "
                    f"({by_cls})")
    for rank, hr in sorted(sh.get("hbm_headroom_bytes", {}).items()):
        if hr < 0:
            errs.append(f"rank {rank} HBM over limit by {-hr:.0f} bytes")
    add("step_health", errs, sh)

    add("control_plane", [], report.get("control_plane", {}))
    add("driver_replication", [], report.get("driver_replication", {}))

    return {"ok": all(c["ok"] for c in checks.values()), "checks": checks}


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _fmt_age(age) -> str:
    return "never" if age is None else f"{age:.1f}s ago"


def render(report: dict) -> str:
    lines = [f"cluster health @ {report['url']}"]
    for endpoint, err in sorted(report.get("errors", {}).items()):
        lines.append(f"  !! {endpoint} endpoint unavailable: {err}")
    slices = report.get("slices", {})
    lines.append("")
    if slices:
        lines.append("per-slice telemetry freshness:")
        for k, ent in slices.items():
            ages = "  ".join(
                f"{s}={_fmt_age(a)}"
                for s, a in sorted(ent["rollup_age"].items()))
            lines.append(f"  slice {k:<3} agg={ent['addr']}  "
                         f"ranks={ent['ranks']}  {ages}")
    else:
        lines.append("per-slice telemetry: no aggregators registered "
                     "(flat topology or HOROVOD_TPU_AGG_ENABLE=0) — "
                     "publishes go direct to the root")
    stragglers = report.get("stragglers", [])
    lines.append("")
    if stragglers:
        lines.append("top stragglers (last arrival at correlated "
                     "collectives):")
        for acc in stragglers:
            lines.append(f"  rank {acc['rank']:<4} "
                         f"last {acc['last_count']}x  "
                         f"mean lateness {acc['mean_late_us']:.0f} us")
    else:
        lines.append("stragglers: none detected")
    deg = report.get("degradation", {})
    lines.append("")
    lines.append("degradation counters (zero = healthy):")
    fb = deg.get("agg_fallbacks", {})
    by_stream = " ".join(f"{s}={v:.0f}" for s, v
                         in sorted(fb.get("by_stream", {}).items()))
    lines.append(f"  aggregator fallbacks: {fb.get('total', 0):.0f}"
                 + (f"  ({by_stream})" if by_stream else ""))
    shed = deg.get("shed_bytes", {})
    lines.append(f"  shed telemetry bytes: {shed.get('total', 0):.0f}")
    for key, label in (("kv_failovers", "kv failovers"),
                       ("kv_breaker_trips", "kv breaker trips"),
                       ("kv_backpressure", "kv backpressure hits"),
                       ("kv_gave_up", "kv gave-up publishes"),
                       ("kv_acked_writes_lost", "acked writes lost"),
                       ("watchdog_escalations", "watchdog escalations")):
        lines.append(f"  {label}: {deg.get(key, 0):.0f}")
    cp = report.get("control_plane", {})
    lines.append("")
    lines.append("control-plane load at the root:")
    per_step = cp.get("requests_per_step")
    lines.append(f"  kv requests: {cp.get('total_requests', 0):.0f} total"
                 + (f", {per_step} per step" if per_step is not None
                    else " (no steps recorded yet)"))
    scopes = cp.get("requests_by_scope", {})
    if scopes:
        row = "  ".join(f"{s}={v:.0f}" for s, v in sorted(scopes.items()))
        lines.append(f"  by scope: {row}")
    verbs = cp.get("requests_by_verb", {})
    if verbs:
        row = "  ".join(f"{v}={n:.0f}" for v, n in sorted(verbs.items()))
        lines.append(f"  by verb: {row}")
    dr = report.get("driver_replication", {})
    lines.append("")
    lines.append("driver replication:")
    head = dr.get("journal_head")
    if head is None:
        lines.append("  journal: no driver journal at this server "
                     "(non-elastic job, or HOROVOD_TPU_DRIVER_JOURNAL=0)")
    else:
        jw = dr.get("journal_writes", {})
        by_kind = " ".join(f"{k}={v:.0f}" for k, v
                           in sorted(jw.get("by_kind", {}).items()))
        lines.append(f"  journal head: seq {head}"
                     + (f"  ({by_kind})" if by_kind else ""))
    role = dr.get("repl_role")
    if role is None:
        lines.append("  kv replication: not enabled at this server")
    else:
        lag = dr.get("standby_lag")
        lines.append(
            f"  kv replica: role={role} epoch={dr.get('repl_epoch')}  "
            f"standby lag={'?' if lag is None else f'{lag} entries'}")
    lines.append(
        f"  promotions: {dr.get('promotions', 0):.0f}  "
        f"failovers: {dr.get('failovers', 0):.0f}  "
        f"failover recoveries: {dr.get('failover_recoveries', 0):.0f}  "
        f"discovery failures: {dr.get('discovery_failures', 0):.0f}")
    sh = report.get("step_health", {})
    lines.append("")
    lines.append("step health / SLO:")
    if sh.get("steps_observed"):
        def _ms(v):
            return "?" if v is None else f"{v:.1f} ms"
        lines.append(
            f"  step time: p50 {_ms(sh.get('step_time_p50_ms'))}  "
            f"p99 {_ms(sh.get('step_time_p99_ms'))}  "
            f"mean {_ms(sh.get('step_time_mean_ms'))}  "
            f"({sh['steps_observed']:.0f} steps observed)")
    else:
        lines.append("  step time: no hvd_tpu_step_seconds samples yet "
                     "(HOROVOD_TPU_STEP_HEALTH=0, or no steps bracketed)")
    anom = sh.get("anomalies_total", 0)
    if anom:
        by_cls = "  ".join(
            f"{c}={v:.0f}" for c, v in
            sorted(sh.get("anomalies_by_class", {}).items()))
        lines.append(f"  anomalies: {anom:.0f}  ({by_cls})")
        for rank, classes in sorted(sh.get("anomalies_by_rank",
                                           {}).items()):
            row = "  ".join(f"{c}={v:.0f}"
                            for c, v in sorted(classes.items()))
            lines.append(f"    rank {rank:<4} {row}")
    else:
        lines.append("  anomalies: none")
    dumps = sh.get("flight_dumps", {})
    if dumps.get("total"):
        by_trig = "  ".join(
            f"{t}={v:.0f}" for t, v in
            sorted(dumps.get("by_trigger", {}).items()))
        lines.append(f"  flight dumps: {dumps['total']:.0f}  ({by_trig})")
    headroom = sh.get("hbm_headroom_bytes", {})
    if headroom:
        for rank, hr in sorted(headroom.items()):
            kinds = sh.get("hbm_bytes", {}).get(rank, {})
            lines.append(
                f"  rank {rank:<4} HBM headroom {hr / 2**30:.2f} GiB "
                f"(in use {kinds.get('in_use', 0) / 2**30:.2f} + "
                f"reserved {kinds.get('reserved', 0) / 2**30:.2f} / "
                f"limit {kinds.get('limit', 0) / 2**30:.2f} GiB)")
    else:
        lines.append("  hbm: no device memory stats published "
                     "(CPU rig, or HOROVOD_TPU_HBM=0)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    p = argparse.ArgumentParser(
        description="Cluster health report over the root KV server's "
                    "/agg, /metrics and /trace endpoints")
    p.add_argument("--url", required=True,
                   help="root server base URL, e.g. http://host:port")
    p.add_argument("--timeout", type=float, default=10.0,
                   help="per-endpoint fetch timeout (seconds)")
    p.add_argument("--json", action="store_true",
                   help="emit the raw assembled report as JSON")
    p.add_argument("--format", choices=("text", "json"), default="text",
                   help="'json' emits the evaluated report in the "
                        "tools/check.py shape ({ok, checks}); the exit "
                        "code is nonzero when any section is red")
    args = p.parse_args(argv)
    report = assemble(args.url, timeout=args.timeout)
    verdict = evaluate(report)
    if args.format == "json":
        print(json.dumps(verdict, indent=2, sort_keys=True))
    elif args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(render(report))
        if not verdict["ok"]:
            red = [name for name, c in sorted(verdict["checks"].items())
                   if not c["ok"]]
            print(f"\nRED sections: {', '.join(red)}")
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
