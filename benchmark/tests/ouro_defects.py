"""Wrong models for the checks of ``ouro-spmd-1chip-loop4``, and the checks
run on them. A defect is put into a copy of the plain reference, so that
the program and the reference differ by it; the program's files are not
touched. The tests (here and ``tests/test_looped_lm.py``) run them on the
CPU at the rehearsal's widths. On the chip at the published widths, where
the bands of ``configs/ouro-2.6b*.py`` were set::

    chiprun --chips 1 -- python3 benchmark/tests/ouro_defects.py <seed> float8

(a third argument ``rehearse`` runs the rehearsal's widths instead).
"""

from __future__ import annotations

import json
import os
import sys

import jax.numpy as jnp
from jax import lax

if __name__ == "__main__":      # as a script the benchmark is not on the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.dirname(_bench), _bench,
                    os.path.join(_bench, "readers")]

import files
from job import Env

CONFIG, TRAFFIC = "ouro-2.6b", "spmd-1chip-1x4096-remat"


def rounded(dtype):
    """The reference with what its norms, attention, FFN and head return
    rounded to ``dtype``: a model computed in that precision."""
    def defect(ref):
        def r(x):
            return x.astype(dtype).astype(x.dtype)
        rms, att, swi, head = (ref.rmsnorm, ref.attention, ref.swiglu,
                               ref.head)
        ref.rmsnorm = lambda x, s: r(rms(x, s))
        ref.attention = lambda x, lw: r(att(x, lw))
        ref.swiglu = lambda x, lw: r(swi(x, lw))
        ref.head = lambda w, h: r(head(w, h))
    return defect


def no_rope(ref):
    ref.rope = lambda x: x


def one_pass_of_four(ref):
    """Every exit's loss differentiated through its own pass only."""
    def states(weights, tokens, passes=ref.PASSES, run_layer=ref.layer):
        h = weights["embed"][tokens].astype(jnp.float32)
        out = []
        for _ in range(passes):
            h = lax.stop_gradient(h) if out else h
            for lw in weights["layers"]:
                h = run_layer(h, lw)
            h = ref.rmsnorm(h, weights["ln_f"])
            out.append(h)
        return out
    ref.states = states


def gate_left_out(ref):
    """The exit distribution a constant of the loss."""
    plain = ref.exit_probs
    ref.exit_probs = lambda lam: lax.stop_gradient(plain(lam))


DEFECTS = {"float8": rounded(jnp.float8_e4m3fn), "no_rope": no_rope,
           "one_pass_of_four": one_pass_of_four,
           "gate_left_out": gate_left_out}


def cell_checks(seed: int, defect=None, rehearse: bool = True):
    """(job, state, checks): ``reference`` and ``loop_grad`` as the worker
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
    err, band = checks["reference"]["error"], checks["reference"]["tolerance"]
    found = {k: (err[k], band[k]) for k in band}
    found["first_moment"] = (
        checks["loop_grad"]["error"]["first_moment"],
        checks["loop_grad"]["tolerance"]["first_moment"])
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
