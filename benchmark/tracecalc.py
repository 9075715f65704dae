"""Reduction of a trace summary (xplane.py) to busy time, the time of
named operations, the part of it nothing else hides, the operations that
take most time by pass, scope and name, and the idle gaps by what the host
was doing. Plain python: the parent process runs it.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

import stats

_SUFFIX = re.compile(r"(\.(\d+|clone\d*|remat\d*|sunk))+$")
# the parts of an op_name that say nothing of the layer: what jax wraps
# around every operation of a program, and an einsum's spec
NOISE = re.compile(r"^(jit\(.*\)|shard_map|while|body|cond|branch_\d+_fun|"
                   r"closed_call|checkpoint|rematted_computation|.*->.*)$")
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_OPTIMIZER = re.compile(r"(^|[/(])optimizer([/)]|$)")


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


Match = Callable[[str], bool]


def matching(dev: dict, match: Match, scope: Optional[Match] = None) -> list:
    """Of each operation of ``labels``: whether its label matches and,
    where ``scope`` is given and the summary has scopes (a record written
    before PR 36 has none), its op_name does too."""
    scopes = dev.get("scopes") if scope else None
    return [match(label) and (scopes is None or scope(scopes[i]))
            for i, label in enumerate(dev["labels"])]


def matched_intervals(dev: dict, match: Match,
                      scope: Optional[Match] = None) -> List[stats.Interval]:
    """Intervals of the matching operations: those of the ``XLA Ops`` line
    and the asynchronous ones, which the trace records from their ``-start``
    to their ``-done`` (the transfer runs in between)."""
    hit = matching(dev, match, scope)
    found = [(o[0], o[0] + o[1]) for o in dev["ops"] if hit[o[3]]]
    found += [(a[0], a[0] + a[1]) for a in dev.get("async", []) if hit[a[2]]]
    return stats.union(found)


def matched_self_ns(dev: dict, match: Match,
                    scope: Optional[Match] = None) -> float:
    """Device time of the matching operations themselves."""
    hit = matching(dev, match, scope)
    return sum(o[2] for o in dev["ops"] if hit[o[3]])


def exposed(dev: dict, match: Match,
            scope: Optional[Match] = None) -> List[stats.Interval]:
    """The part of the matching operations' time during which no other
    operation runs on this chip."""
    hit = matching(dev, match, scope)
    others = stats.union((o[0], o[0] + o[1]) for o in dev["ops"]
                         if is_leaf(o) and not hit[o[3]])
    return stats.subtract(matched_intervals(dev, match, scope), others)


def pass_of(scope: str) -> str:
    """Which pass of the step an op_name belongs to: ``opt`` (under the
    scope ``optimizer``, whatever jax wrapped around it), ``remat`` (what
    jax.checkpoint runs again), ``bwd`` (``transpose(``), ``fwd`` (``jvp(``
    alone) or ``-``."""
    if _OPTIMIZER.search(scope):
        return "opt"
    if "rematted_computation" in scope:
        return "remat"
    if "transpose(" in scope:
        return "bwd"
    return "fwd" if "jvp(" in scope else "-"


def scope_path(scope: str) -> str:
    """The op_name without its last part (the primitive) and without what
    jax wraps around every operation (NOISE; the ``jvp(`` / ``transpose(`` /
    ``vmap(`` parentheses): ``layers/ffn`` of ``jit(train_step)/
    transpose(jvp())/layers/while/body/closed_call/ffn/btd,df->btf/
    dot_general``."""
    kept = []
    for part in scope.split("/")[:-1]:
        while (m := _WRAPPED.match(part)):
            part = m[1]
        if part and not NOISE.match(part) and part not in kept[-1:]:
            kept.append(part)
    return "/".join(kept)


def top_ops(dev: dict, n: int = 10) -> List[List]:
    """[[``<pass> <scope path> | <group>``, seconds], ...] by self time,
    largest first; the group alone where the summary has no scopes."""
    scopes = dev.get("scopes")
    names = [group_name(label) if scopes is None else
             f"{pass_of(scopes[i])} {scope_path(scopes[i])}".strip()
             + " | " + group_name(label)
             for i, label in enumerate(dev["labels"])]
    by_group: Dict[str, float] = {}
    for _, _, self_ns, i in dev["ops"]:
        by_group[names[i]] = by_group.get(names[i], 0.0) + self_ns
    ranked = sorted(by_group.items(), key=lambda kv: -kv[1])[:n]
    return [[g, t / 1e9] for g, t in ranked]


def span_at(spans: List[List], t: float) -> str:
    """The innermost span (the benchmark's ``bench.*``, the program's
    ``hvd.*`` inside it) that holds time ``t``."""
    best = None
    for name, start, dur in spans:
        if not start <= t <= start + dur:
            continue
        if best is None or dur < best[1]:
            best = (name, dur)
    return best[0] if best else "(no span)"


def idle_by_span(dev: dict, spans: List[List], n: int = 10) -> List[List]:
    """[[span, seconds], ...]: idle time of this chip inside the traced
    window, by the innermost span each gap's middle falls in."""
    lo, hi = window(dev)
    by_span: Dict[str, float] = {}
    for s, e in stats.gaps(busy(dev), lo, hi):
        name = span_at(spans, (s + e) / 2)
        by_span[name] = by_span.get(name, 0.0) + (e - s)
    ranked = sorted(by_span.items(), key=lambda kv: -kv[1])[:n]
    return [[g, t / 1e9] for g, t in ranked]
