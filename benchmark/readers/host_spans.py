"""Host time of the spans the PROGRAM wrote on the profiler's clock
(``horovod_tpu/common/scopes.py`` ``host_span``: ``hvd.*`` on the calling
thread's line of the trace's ``/host:CPU`` plane, kept by xplane.py beside
the benchmark's own ``bench.*``): the summed duration of the spans whose
name matches ``span`` or, with ``"self": true``, that duration less the
kept spans nested inside them, in milliseconds a traced step.

Nothing to read (None) where the record has no trace or no span matched: a
record written before the program wrote spans, a cell whose loop does not
enter the code.

``split`` names the spans to tell apart inside the matched ones: each
one's milliseconds a step and the matched spans' self time (what the spans
inside do not cover) are said on a ``bench:`` line, with ``beside`` (the
benchmark's own span around the same call) for comparison.
"""

import re

import stats


def per_step_ms(spans, steps, pattern, own=False):
    """Summed duration (self time with ``own``) of the spans whose name
    matches, in ms a step; None where none does."""
    match = re.compile(pattern).search
    times = stats.self_times([(s, d) for _, s, d in spans]) if own \
        else [d for _, _, d in spans]
    found = [t for (name, _, _), t in zip(spans, times) if match(name)]
    return sum(found) / 1e6 / steps if found else None


def names(spans, pattern):
    """The names the pattern matched, as the note says them."""
    match = re.compile(pattern).search
    return ", ".join(sorted({name for name, _, _ in spans if match(name)}))


def inside(spans, pattern):
    """The spans that lie within one whose name matches, those left out."""
    match = re.compile(pattern).search
    outer = [(s, s + d) for name, s, d in spans if match(name)]
    return [sp for sp in spans if not match(sp[0]) and any(
        lo <= sp[1] and sp[1] + sp[2] <= hi for lo, hi in outer)]


def say_the_split(ctx, spec, spans, steps, found):
    parts = {}
    split = re.compile(spec["split"]).search
    for name, _, dur in inside(spans, spec["span"]):
        if split(name):
            parts[name] = parts.get(name, 0.0) + dur / 1e6 / steps
    left = per_step_ms(spans, steps, spec["span"], own=True)
    note = (f"host spans: {names(spans, spec['span'])} {found:.3f} ms a "
            f"step = " + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
            + f" + self {left:.3f}")
    if "beside" in spec:
        beside = per_step_ms(spans, steps, spec["beside"])
        if beside is not None:
            note += (f"; {names(spans, spec['beside'])} {beside:.3f}: "
                     f"{found - beside:+.3f} ms")
    ctx["notes"].append(note)


def read(ctx, spec):
    traced = ctx["record"].get("traced")
    if not traced:
        return None
    # [name, start_ns, dur_ns] of every kept span, parents before children
    spans, steps = traced["trace"]["spans"], traced["steps"]
    found = per_step_ms(spans, steps, spec["span"], spec.get("self", False))
    if found is not None and "split" in spec:
        say_the_split(ctx, spec, spans, steps, found)
    return found
