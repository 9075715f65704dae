"""Wrong models for the checks of ``qwen3next-spmd-1chip-ep32share-8k``, and
the checks run on them. A defect is put into a copy of the plain reference,
so that the program and the reference differ by it; two are put into the
program instead, for the time of their reading: the solve cut short (its
chunked rule) and the step routed by sigmoid (its train step). The
``WITNESSES`` are no defects: they put the program's own mixers into the
reference, to tell where the forward check's error comes from. On the chip at the published widths, where the limits of
``configs/qwen3-next-80b-a3b*.py`` were set::

    python3 benchmark/tests/qwen3next_defects.py \
        <seed>[,<seed>...] none float8 [more defects]

(``none`` for a defect's name reads the checks of the program as it is; a
last argument ``rehearse`` runs the rehearsal's widths instead). ``forward``
in front of the seeds reads the forward pass's check alone (``reference``:
no ``step_grad``, whose float32 gradient program takes minutes to build),
which is all that a defect of the forward pass needs::

    ... qwen3next_defects.py forward <seed> none float8 ...
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

if __name__ == "__main__":      # as a script the benchmark is not on the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.dirname(_bench), _bench,
                    os.path.join(_bench, "readers")]

import files
from job import Env

CONFIG, TRAFFIC = "qwen3-next-80b-a3b", "spmd-1chip-2x8192-remat"


def rounded(dtype):
    """The reference with what its norms, mixers (the recurrence among
    them), experts and head return rounded to ``dtype``: a model computed
    in that precision."""
    def defect(ref):
        def r(x):
            return x.astype(dtype).astype(x.dtype)
        rms, recurrence, mixer, swiglu, head = (
            ref.rmsnorm, ref.recurrence, ref.mixer, ref.swiglu, ref.head)
        ref.rmsnorm = lambda x, w: r(rms(x, w))
        ref.recurrence = lambda *a, **k: r(recurrence(*a, **k))
        ref.mixer = lambda *a, **k: r(mixer(*a, **k))
        ref.swiglu = lambda *a: r(swiglu(*a))
        ref.head = lambda w, h: r(head(w, h))
    return defect


def qk_not_normed(ref):
    """q and k of the rule taken as the taps leave them (q still scaled)."""
    ref.l2norm = lambda x: x


def value_head_by_remainder(ref):
    """Value head ``j`` reads key head ``j % Hk`` where it reads ``j //
    (Hv / Hk)``."""
    ref.key_head_of = lambda head, value_heads, key_heads: head % key_heads


def delta_from_the_undecayed_state(ref):
    """What the state gives ``k_t`` read BEFORE the decay."""
    def recur(state, q_t, k_t, v_t, g_t, beta_t):
        read = jnp.sum(state * k_t[..., :, None], axis=-2)
        state = jnp.exp(g_t)[..., None, None] * state \
            + k_t[..., :, None] * (beta_t[..., None] * (v_t - read))[
                ..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)
    ref.recur = recur


def beta_left_out_of_the_write(ref):
    """The state written with ``v_t - read`` whole, no write strength."""
    recur = ref.recur
    ref.recur = lambda state, q_t, k_t, v_t, g_t, beta_t: recur(
        state, q_t, k_t, v_t, g_t, jnp.ones_like(beta_t))


def gate_before_the_norm(ref):
    """``RMSNorm(o * silu(z))``: the gate inside the norm (Mamba-2's
    order), not after it."""
    def gated_norm(o, z, lw):
        gated = o * ref.silu(z)
        normed = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1,
                                           keepdims=True) + ref.EPS)
        return (normed * lw["norm"]).reshape(o.shape[:2] + (-1,))
    ref.gated_norm = gated_norm


def rotation_on_the_whole_head(ref):
    """Every column of q and k rotated, the 192 that pass too."""
    ref.ROTARY = 1.0


def sigmoid_routing(ref):
    """Scores ``sigmoid(x Wr)`` in place of the softmax over the experts."""
    ref.scores = lambda x, lw: ref.sigmoid(x @ lw["router"])


def shared_gate_left_out(ref):
    ref.shared = lambda x, lw: ref.swiglu(x, lw["shared_wg"],
                                          lw["shared_wu"], lw["shared_wd"])


def solve_cut_short(ref):
    """The PROGRAM's inverse with one factor of six left out: the series
    stops at the 31st power of ``L`` (forward only). Returns the undo."""
    from horovod_tpu.parallel import gated_delta
    whole = gated_delta.unit_lower_inverse

    def five_factors(strict):
        eye = jnp.eye(strict.shape[-1], dtype=jnp.float32)
        power = -strict
        inverse = eye + power
        for _ in range(4):
            power = gated_delta._mul(power, power)
            inverse = gated_delta._mul(inverse, eye + power)
        return inverse

    gated_delta.unit_lower_inverse = five_factors
    return lambda: setattr(gated_delta, "unit_lower_inverse", whole)


def half_of_the_batch(ref):
    """The gradient of the first half of the rows alone, as a step would
    return that left the other half out of its sum."""
    grads = ref.grads

    def of_half(weights, inputs, targets, first_expert, wrap, top_k, given,
                **kwargs):
        half = inputs.shape[0] // 2
        return grads(weights, inputs[:half], targets[:half], first_expert,
                     wrap, top_k, [g[:half] for g in given], **kwargs)
    ref.grads = of_half


def step_routes_by_sigmoid(ref):
    """IN THE PROGRAM: the train step scores its experts by sigmoid, where
    the forward-only program of the same weights scores them by softmax
    (the same ten chosen in the first layer, other weights of them, and so
    other choices after it): it moves ``flips_step_vs_forward`` (a
    reading with no limit) and fails ``first_moment``. Returns what takes
    it off."""
    from horovod_tpu.models import transformer
    make = transformer.make_train_step

    def make_train_step(mesh, cfg, *args, **kwargs):
        return make(mesh, dataclasses.replace(cfg, route_score="sigmoid"),
                    *args, **kwargs)

    transformer.make_train_step = make_train_step
    return lambda: setattr(transformer, "make_train_step", make)


# Not defects: the program's own mixers put into the reference in place of
# its float32 ones (at the program's matmul precision: the reference's
# ``highest`` is no precision the attention kernel compiles at). Their
# rounding then enters on both sides, drawn apart (the inputs differ by
# the rest's), so ``logits`` rises by the square root of one plus the
# mixer's share of its square (PERF.md section 4, the logits' error)
def programs_delta_rule_layers(ref, cfg):
    """Every delta-rule layer's mixer the program's ``_gdn_mix`` in
    ``cfg.dtype``, on the reference's input to it."""
    from horovod_tpu.models.transformer import _gdn_mix
    model = files.config_module(CONFIG)

    def gdn(x, lw, wrap=None, key_heads=None):
        with jax.default_matmul_precision("default"):
            return _gdn_mix(x.astype(cfg.dtype), model.layer_from_reference(
                lw, cfg), cfg=cfg).astype(jnp.float32)
    ref.gdn = gdn


def programs_attention_layer(ref, cfg):
    """The attention layer's mixer the program's ``_attn_mix`` (splash on a
    TPU, heads of 256) in ``cfg.dtype``, on the reference's input to it."""
    from horovod_tpu.models import transformer
    model = files.config_module(CONFIG)

    def attention(x, lw, wrap=None):
        rope = transformer._rope_tables(cfg, x.shape[1], None)
        with jax.default_matmul_precision("default"):
            return transformer._attn_mix(
                x.astype(cfg.dtype), model.layer_from_reference(lw, cfg),
                cfg=cfg, rope=rope, causal=True, window=0,
                scope=None).astype(jnp.float32)
    ref.attention = attention


DEFECTS = {"float8": rounded(jnp.float8_e4m3fn),
           "qk_not_normed": qk_not_normed,
           "value_head_by_remainder": value_head_by_remainder,
           "delta_from_the_undecayed_state": delta_from_the_undecayed_state,
           "beta_left_out_of_the_write": beta_left_out_of_the_write,
           "gate_before_the_norm": gate_before_the_norm,
           "rotation_on_the_whole_head": rotation_on_the_whole_head,
           "sigmoid_routing": sigmoid_routing,
           "shared_gate_left_out": shared_gate_left_out,
           "solve_cut_short": solve_cut_short,
           "half_of_the_batch": half_of_the_batch,
           "step_routes_by_sigmoid": step_routes_by_sigmoid}
# (ref, cfg) -> None: see above
WITNESSES = {"programs_delta_rule_layers": programs_delta_rule_layers,
             "programs_attention_layer": programs_attention_layer}


def cell_checks(seed: int, defect=None, rehearse: bool = True,
                with_step_grad: bool = True):
    """``reference`` (and ``step_grad``) as the worker runs them, the
    reference a copy with ``defect`` put into it."""
    from horovod_tpu.models.transformer import (forward_routes,
                                                make_spmd_loss)
    from horovod_tpu.parallel.mesh import training_mesh
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    step_file = os.path.splitext(files.config_path(CONFIG))[0] + ".spmd.py"
    ref = files.reference_module(CONFIG)
    model = files.config_module(CONFIG)
    if defect in WITNESSES.values():
        defect = functools.partial(defect, cfg=model.transformer_config(
            spec, traffic, rehearse))
    undo = defect(ref) if defect is not None else None
    try:
        job = files.load_module(step_file, "bench_step_under_test").build(
            model, spec, traffic,
            Env(seed=seed, chips=1, rehearse=rehearse, reference=ref))
        state = job.init()
        if with_step_grad:
            return job.reference_checks(state)
        cfg = model.transformer_config(spec, traffic, rehearse)
        mesh = training_mesh({"data": 1, "seq": 1, "tensor": 1},
                             jax.devices()[:1])
        rows = model.sizes(traffic, rehearse)["rows_per_chip"]
        check, _ = model.reference_check(
            cfg, state[0][0], model.with_key_heads(ref, cfg), seed, rows,
            jax.jit(lambda p, x: forward_routes(p, x, cfg)),
            jax.jit(make_spmd_loss(mesh, cfg)))
        return {"reference": check}
    finally:
        if undo is not None:
            undo()


def readings(checks: dict) -> dict:
    """{limit: (what was read, the limit)} over the checks."""
    found = {}
    for check in checks.values():
        found.update({k: (check["error"][k], check["tolerance"][k])
                      for k in check["tolerance"]})
    return found


def failed(found: dict) -> set:
    """The limits a reading is not under (a nan is under none)."""
    return {k for k, (v, band) in found.items() if not v <= band}


def say(name, found):
    print(name, {k: f"{v:.3g} of {b:.3g}" for k, (v, b) in found.items()},
          "FAILS", sorted(failed(found)), flush=True)


if __name__ == "__main__":
    print(jax.devices()[0].device_kind, flush=True)
    rehearse = sys.argv[-1] == "rehearse"
    args = sys.argv[1:len(sys.argv) - rehearse]
    forward = args[0] == "forward"
    args = args[forward:]
    for seed in map(int, args[0].split(",")):
        for name in args[1:]:
            t0 = time.monotonic()
            try:
                checks = cell_checks(seed, DEFECTS.get(
                    name, WITNESSES.get(name)), rehearse, not forward)
            except Exception as e:      # the next reading all the same
                print(name, "seed", seed, "RAISED", repr(e)[:2000],
                      flush=True)
                continue
            say("%s seed %d (%s; %.0f s)" % (
                name, seed, "forward" if forward else "whole",
                time.monotonic() - t0), readings(checks))
            print(json.dumps({"defect": name, "seed": seed,
                              "checks": checks}, default=float), flush=True)
