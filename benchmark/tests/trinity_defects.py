"""Wrong models for the checks of ``trinity-spmd-1chip-ep8share-8k``, and
the checks run on them. A defect is put into a copy of the plain reference,
so that the program and the reference differ by it; the program's files are
not touched. On the chip at the published widths, where the bands of
``configs/trinity-mini*.py`` were set::

    chiprun --chips 1 -- python3 benchmark/tests/trinity_defects.py <seed> float8

(a third argument ``rehearse`` runs the rehearsal's widths instead).
"""

from __future__ import annotations

import json
import os
import sys

import jax.numpy as jnp

if __name__ == "__main__":      # as a script the benchmark is not on the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.dirname(_bench), _bench,
                    os.path.join(_bench, "readers")]

import files
from job import Env

CONFIG, TRAFFIC = "trinity-mini", "spmd-1chip-1x8192-remat"


def rounded(dtype):
    """The reference with what its norms, attention, FFNs and head return
    rounded to ``dtype``: a model computed in that precision."""
    def defect(ref):
        def r(x):
            return x.astype(dtype).astype(x.dtype)
        rms, att, swi, head = (ref.rmsnorm, ref.attention, ref.swiglu,
                               ref.head)
        ref.rmsnorm = lambda x, s: r(rms(x, s))
        ref.attention = lambda *a, **k: r(att(*a, **k))
        ref.swiglu = lambda *a: r(swi(*a))
        ref.head = lambda w, h: r(head(w, h))
    return defect


def no_window(ref):
    """Every layer sees the whole past."""
    attend = ref.attend
    ref.attend = lambda q, k, v, lo, *, window: attend(q, k, v, lo, window=0)


def rope_everywhere(ref):
    """The full-attention layers rotate too."""
    att = ref.attention
    ref.attention = lambda x, lw, window, rotate, wrap=lambda f: f: att(
        x, lw, window, True, wrap)


def dropped(leaf):
    """Every layer's ``leaf`` takes no gradient: what ``step_grad`` reads of
    a leaf that is missing on one side, or of a state the step left as it
    was."""
    def defect(ref):
        grads = ref.grads

        def without(*args, **kwargs):
            found = grads(*args, **kwargs)
            return {**found, "layers": [
                {k: jnp.zeros_like(g) if k == leaf else g
                 for k, g in lw.items()} for lw in found["layers"]]}
        ref.grads = without
    return defect


DEFECTS = {"float8": rounded(jnp.float8_e4m3fn), "no_window": no_window,
           "rope_everywhere": rope_everywhere,
           "q_norm_dropped": dropped("q_norm"),
           "experts_dropped": dropped("ewu")}


def cell_checks(seed: int, defect=None, rehearse: bool = True):
    """(job, state, checks): ``reference`` and ``step_grad`` as the worker
    runs them, the reference a copy with ``defect`` put into it."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    step_file = os.path.splitext(files.config_path(CONFIG))[0] + ".spmd.py"
    module = files.load_module(step_file, "bench_step_under_test")
    ref = files.reference_module(CONFIG)
    if defect is not None:
        defect(ref)
    job = module.build(files.config_module(CONFIG), spec, traffic, Env(
        seed=seed, chips=1, rehearse=rehearse, reference=ref))
    state = job.init()
    return job, state, job.reference_checks(state)


def readings(checks: dict) -> dict:
    """{limit: (what was read, the limit)} over both checks."""
    found = {}
    for check in checks.values():
        found.update({k: (check["error"][k], check["tolerance"][k])
                      for k in check["tolerance"]})
    return found


def say(name, found):
    print(name, {k: f"{v:.3g} of {b:.3g}" for k, (v, b) in found.items()},
          flush=True)


if __name__ == "__main__":
    import jax
    print(jax.devices()[0].device_kind, flush=True)
    checks = cell_checks(int(sys.argv[1]), DEFECTS[sys.argv[2]],
                         rehearse=sys.argv[3:] == ["rehearse"])[2]
    say(sys.argv[2], readings(checks))
    print(json.dumps(checks))
