"""Device time of the operations whose ``op_name`` (the scope the program
wrote, ``horovod_tpu/common/scopes.py``; jax adds ``jvp(`` and
``transpose(``) matches one of ``match`` and none of ``exclude``: self
time on the ``XLA Ops`` line, in milliseconds a step, averaged over the
traced chips. Collective operations, by the opcode pattern of
``collective_ms_per_step``, are left out: they have metrics of their own,
and without them a cell over four chips reads what its one-chip twin does.

A fusion has one ``op_name``, its root's: where XLA fused across a scope's
edge, the whole fusion's time goes to the root's scope.

It reads the ``scopes`` that xplane.py's summary holds beside ``labels``
(the worker records them since PR 36). Nothing to read (None) where the
record has none (a record written before) or no operation matched: a
program without the scope.

``closes`` names the metrics that, with this one and the collectives, must
add up to the chip's busy time; the difference is said on a ``bench:``
line.
"""

import files
import stats
import tracecalc
from common import traced_devices


def scoped_devices(ctx):
    """(device entry with ``scopes``, traced steps) of every traced chip."""
    return [(dev, steps) for dev, _, steps in traced_devices(ctx)
            if "scopes" in dev]


def collective():
    return stats.matcher(
        files.layer_metric("collective_ms_per_step")[0]["match"])


def mean(values):
    return sum(values) / len(values)


def per_step_ms(ctx, spec):
    """One reading a traced chip."""
    match = stats.matcher(spec["match"])
    exclude = stats.matcher(spec.get("exclude", []))
    moves = collective()
    return [tracecalc.matched_self_ns(
        dev, lambda label: not moves(label),
        lambda scope: match(scope) and not exclude(scope)) / 1e6 / steps
        for dev, steps in scoped_devices(ctx)]


def say_what_is_left(ctx, spec, own):
    """The metrics of ``closes``, this one and the collectives' self time
    against the busy time, a step, on a note."""
    parts = {name: mean(per_step_ms(ctx, files.layer_metric(name)[0]))
             for name in spec["closes"]}
    devices, moves = scoped_devices(ctx), collective()
    parts["collectives"] = mean([
        tracecalc.matched_self_ns(dev, moves) / 1e6 / steps
        for dev, steps in devices])
    busy = mean([stats.total(tracecalc.busy(dev)) / 1e6 / steps
                 for dev, steps in devices])
    total = own + sum(parts.values())
    ctx["notes"].append(
        "scopes: " + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
        + f" + this {own:.3f} = {total:.3f} ms a step against "
        f"{busy:.3f} ms busy: {(total - busy) / busy * 100:+.4f}%")


def read(ctx, spec):
    found = per_step_ms(ctx, spec)
    if not found or not sum(found):
        return None
    if "closes" in spec:
        say_what_is_left(ctx, spec, mean(found))
    return mean(found)
