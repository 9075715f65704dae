"""Reduction of a trace summary (xplane.py) to busy time, the time of
named operations, the part of it nothing else hides, and the idle gaps by
what the host was doing. Plain python: the parent process runs it.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Tuple

import stats

_SUFFIX = re.compile(r"(\.(\d+|clone\d*|remat\d*|sunk))+$")


def op_name(label: str) -> str:
    return label.split(" | ")[0]


def group_name(label: str) -> str:
    """A name that survives recompilation: the operation's name without its
    numbering, its opcode where that says more, and the largest array it
    produces (``fusion kOutput bf16[4,2048,50257]``,
    ``splash_mha_fwd_residuals f32[4,16,2048,128]``)."""
    parts = label.split(" | ")
    stem = _SUFFIX.sub("", parts[0])
    if len(parts) < 3:
        return stem
    op = parts[1]
    what = op if op.split()[0] == stem else (
        stem if op.startswith("custom-call") else f"{stem} ({op})")
    return f"{what} {parts[2]}".strip()


def window(dev: dict) -> Tuple[float, float]:
    """First operation's start to last operation's end on this chip."""
    if not dev["ops"]:
        raise ValueError(f"no device operation in the trace of "
                         f"{dev['plane']}")
    return (min(o[0] for o in dev["ops"]),
            max(o[0] + o[1] for o in dev["ops"]))


def busy(dev: dict) -> List[stats.Interval]:
    return stats.union((o[0], o[0] + o[1]) for o in dev["ops"])


def is_leaf(op) -> bool:
    return op[1] - op[2] < 0.5      # nothing nested took time inside it


def matched_intervals(dev: dict,
                      match: Callable[[str], bool]) -> List[stats.Interval]:
    """Intervals of the operations whose label matches: those of the
    ``XLA Ops`` line and the asynchronous ones, which the trace records from
    their ``-start`` to their ``-done`` (the transfer runs in between)."""
    found = [(o[0], o[0] + o[1]) for o in dev["ops"]
             if match(dev["labels"][o[3]])]
    found += [(a[0], a[0] + a[1]) for a in dev.get("async", [])
              if match(dev["labels"][a[2]])]
    return stats.union(found)


def matched_self_ns(dev: dict, match: Callable[[str], bool]) -> float:
    """Device time of the matching operations themselves."""
    return sum(o[2] for o in dev["ops"] if match(dev["labels"][o[3]]))


def exposed(dev: dict, match: Callable[[str], bool]) -> List[stats.Interval]:
    """The part of the matching operations' time during which no other
    operation runs on this chip."""
    others = stats.union((o[0], o[0] + o[1]) for o in dev["ops"]
                         if is_leaf(o) and not match(dev["labels"][o[3]]))
    return stats.subtract(matched_intervals(dev, match), others)


def top_ops(dev: dict, n: int = 10) -> List[List]:
    """[[group, seconds], ...] by self time, largest first."""
    by_group: Dict[str, float] = {}
    for _, _, self_ns, label in dev["ops"]:
        g = group_name(dev["labels"][label])
        by_group[g] = by_group.get(g, 0.0) + self_ns
    ranked = sorted(by_group.items(), key=lambda kv: -kv[1])[:n]
    return [[g, t / 1e9] for g, t in ranked]


def span_at(spans: List[List], t: float) -> str:
    """The innermost benchmark-side span that holds time ``t``."""
    best = None
    for name, start, dur in spans:
        if not start <= t <= start + dur:
            continue
        if best is None or dur < best[1]:
            best = (name, dur)
    return best[0] if best else "(no span)"


def idle_by_span(dev: dict, spans: List[List], n: int = 10) -> List[List]:
    """[[span, seconds], ...]: idle time of this chip inside the traced
    window, by the benchmark-side span each gap's middle falls in."""
    lo, hi = window(dev)
    by_span: Dict[str, float] = {}
    for s, e in stats.gaps(busy(dev), lo, hi):
        name = span_at(spans, (s + e) / 2)
        by_span[name] = by_span.get(name, 0.0) + (e - s)
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
    return [[g, t / 1e9] for g, t in ranked]
