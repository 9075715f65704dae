"""Wrong models for the checks of ``nemotron3-spmd-1chip-ep16share-8k``, and
the checks run on them. A defect is put into a copy of the plain reference,
so that the program and the reference differ by it; the program's files are
not touched (three defects can only be planted in the program: the scan's
products returned in bfloat16, their sums kept in bfloat16, a step that
routes without the settled bias; each is put on the imported module for the
length of the check and taken off again). On the chip at the published
widths, where the bands of ``configs/nemotron-3-nano-30b-a3b*.py`` were set::

    chiprun --chips 1 -- python3 benchmark/tests/nemotron3_defects.py \
        <seed>[,<seed>...] float8 [more defects]

(``none`` for a defect's name reads the checks of the program as it is; a
last argument ``rehearse`` runs the rehearsal's widths instead). ``scan`` in
the seeds' place reads the FIRST state-space layer's two limits alone
(:func:`scan_readings`), seconds a seed where the cell's checks take
minutes::

    ... nemotron3_defects.py scan <seed>[,<seed>...] none sums_in_bfloat16
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp

if __name__ == "__main__":      # as a script the benchmark is not on the path
    _bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.dirname(_bench), _bench,
                    os.path.join(_bench, "readers")]

import files
from job import Env
# a leaf's gradient dropped, a state left unchanged, the readings of a check
from lfm2_defects import dropped, readings, state_unchanged

CONFIG, TRAFFIC = "nemotron-3-nano-30b-a3b", "spmd-1chip-2x8192-remat"


def rounded(dtype):
    """The reference with what its norms, mixers, experts and head return
    rounded to ``dtype``: a model computed in that precision."""
    def defect(ref):
        def r(x):
            return x.astype(dtype).astype(x.dtype)
        rms, gated, scan, mamba, att, relu2, head = (
            ref.rmsnorm, ref.gated_norm, ref.scan, ref.mamba, ref.attention,
            ref.relu2, ref.head)
        ref.rmsnorm = lambda x, s: r(rms(x, s))
        ref.gated_norm = lambda *a: r(gated(*a))
        ref.scan = lambda *a, **k: r(scan(*a, **k))
        ref.mamba = lambda *a, **k: r(mamba(*a, **k))
        ref.attention = lambda *a, **k: r(att(*a, **k))
        ref.relu2 = lambda *a: r(relu2(*a))
        ref.head = lambda w, h: r(head(w, h))
    return defect


def dt_without_softplus(ref):
    ref.step_size = lambda dt, lw: dt + lw["ssm_dt_bias"]


def decay_on_the_input_too(ref):
    """``S_t = exp(dt A) (S_{t-1} + dt X B^T)``."""
    def recur(state, x_t, dt_t, a, b_t, c_t):
        state = jnp.exp(dt_t * a)[..., None, None] * (
            state + (dt_t[..., None] * x_t)[..., :, None]
            * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)
    ref.recur = recur


def heads_to_groups_by_remainder(ref):
    ref.group_of = lambda head, heads, groups: head % groups


def relu_without_the_square(ref):
    ref.relu2 = lambda x, wu, wd: jnp.maximum(x @ wu, 0.0) @ wd


def shared_expert_at_the_routed_width(ref):
    relu2 = ref.relu2

    def shared(x, lw):
        f = lw["ewu"].shape[-1]
        return relu2(x, lw["shared_wu"][:, :f], lw["shared_wd"][:f])
    ref.shared = shared


def skip_left_out(ref):
    ref.skip = lambda xs, lw: jnp.zeros_like(xs)


def norm_before_the_gate(ref):
    def gated_norm(y, z, lw, groups):
        grouped = y.reshape(y.shape[:-1] + (groups, -1))
        normed = grouped / jnp.sqrt(jnp.mean(
            grouped * grouped, axis=-1, keepdims=True) + ref.EPS)
        return normed.reshape(y.shape) * lw["ssm_norm"] * ref.silu(z)
    ref.gated_norm = gated_norm


def conv_bias_left_out(ref):
    def causal_conv(u, lw):
        taps = lw["ssm_conv_w"]
        return ref.silu(sum(taps[j] * ref.delayed(u, taps.shape[0] - 1 - j)
                            for j in range(taps.shape[0])))
    ref.causal_conv = causal_conv


def rotation_switched_on(ref):
    ref.ROTATE = True


def scan_products(einsum):
    """IN THE PROGRAM: ``einsum(spec, left, right)`` in bfloat16 in place of
    ``ssd_chunked``'s four products. Returns what takes it off."""
    def defect(ref):
        from horovod_tpu.parallel import ssd

        class Numpy:
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def einsum(spec, left, right, preferred_element_type=None):
                return einsum(spec, left.astype(jnp.bfloat16), right.astype(
                    jnp.bfloat16)).astype(preferred_element_type)

        ssd.jnp = Numpy()
        return lambda: setattr(ssd, "jnp", jnp)
    return defect


def returned_in_bfloat16(spec, left, right):
    """As an ``einsum`` of bfloat16 operands does where its
    ``preferred_element_type`` is left out: the MXU sums in float32, the
    result is rounded."""
    return jnp.einsum(spec, left, right,
                      preferred_element_type=jnp.bfloat16)


def summed_in_bfloat16(spec, left, right):
    """A RUNNING sum kept in bfloat16: one term of the contraction at a
    time, each product rounded and added to a bfloat16 total, which is
    rounded again (a contraction of 128 here: 128 roundings a result)."""
    (a, b), out = spec.split("->")[0].split(","), spec.split("->")[1]
    (over,) = (set(a) & set(b)) - set(out)
    term = "%s,%s->%s" % (a.replace(over, ""), b.replace(over, ""), out)

    def add(total, factors):
        return total + jnp.einsum(
            term, *factors, preferred_element_type=jnp.bfloat16), None

    shape = jax.eval_shape(lambda x, y: jnp.einsum(spec, x, y), left, right)
    return jax.lax.scan(add, jnp.zeros(shape.shape, jnp.bfloat16), (
        jnp.moveaxis(left, a.index(over), 0),
        jnp.moveaxis(right, b.index(over), 0)))[0]


def step_routes_without_the_bias(ref):
    """IN THE PROGRAM: the train step routes with the selection bias at
    zero, where the forward-only program of the same weights routes by the
    settled one: what ``flips_step_vs_forward`` is there to tell. Returns
    what takes it off."""
    from horovod_tpu.models import transformer

    make = transformer.make_train_step

    def unsettled(stack):
        if isinstance(stack, dict) and "router_bias" in stack:
            return {**stack,
                    "router_bias": jnp.zeros_like(stack["router_bias"])}
        return stack

    def make_train_step(*args, **kwargs):
        step = make(*args, **kwargs)
        return lambda params, *rest: step(
            {k: unsettled(v) for k, v in params.items()}, *rest)

    transformer.make_train_step = make_train_step
    return lambda: setattr(transformer, "make_train_step", make)


def half_of_the_batch(ref):
    """The gradient of the first half of the rows alone, as a step would
    return that left the other half out of its sum."""
    grads = ref.grads

    def of_half(weights, inputs, targets, first_expert, wrap, top_k, given,
                groups):
        half = inputs.shape[0] // 2
        return grads(weights, inputs[:half], targets[:half], first_expert,
                     wrap, top_k, [g[:half] for g in given], groups)
    ref.grads = of_half


DEFECTS = {"float8": rounded(jnp.float8_e4m3fn),
           "dt_without_softplus": dt_without_softplus,
           "decay_on_the_input_too": decay_on_the_input_too,
           "heads_to_groups_by_remainder": heads_to_groups_by_remainder,
           "relu_without_the_square": relu_without_the_square,
           "shared_expert_at_the_routed_width":
           shared_expert_at_the_routed_width,
           "skip_left_out": skip_left_out,
           "norm_before_the_gate": norm_before_the_gate,
           "conv_bias_left_out": conv_bias_left_out,
           "rotation_switched_on": rotation_switched_on,
           "products_return_bfloat16": scan_products(returned_in_bfloat16),
           "sums_in_bfloat16": scan_products(summed_in_bfloat16),
           "step_routes_without_the_bias": step_routes_without_the_bias,
           "half_of_the_batch": half_of_the_batch,
           "state_unchanged": state_unchanged,
           "A_log_dropped": dropped("ssm_A_log"),
           "dt_bias_dropped": dropped("ssm_dt_bias"),
           "conv_b_dropped": dropped("ssm_conv_b")}


def cell_checks(seed: int, defect=None, rehearse: bool = True):
    """``reference`` and ``step_grad`` as the worker runs them, the
    reference a copy with ``defect`` put into it."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    step_file = os.path.splitext(files.config_path(CONFIG))[0] + ".spmd.py"
    ref = files.reference_module(CONFIG)
    undo = defect(ref) if defect is not None else None
    try:
        # (loaded under the defect: the job's file binds the program's
        # entry points by name when it is loaded)
        module = files.load_module(step_file, "bench_step_under_test")
        job = module.build(files.config_module(CONFIG), spec, traffic, Env(
            seed=seed, chips=1, rehearse=rehearse, reference=ref))
        return job.reference_checks(job.init())
    finally:
        if undo is not None:
            undo()


def scan_readings(seed: int, defect=None, rehearse: bool = True,
                  **widths) -> dict:
    """``ssm_mixer`` and ``ssm_scan`` of the FIRST state-space layer alone
    (its input needs no layer before it: the normed embedding), on the
    cell's own rows, a row at a time, through the configuration's own
    ``mixer_errors``: {limit: (the worst row's reading, the limit)}. The
    cell's check takes the worst LAYER, so it reads no less than this.
    ``widths``: fields of the configuration to run at other sizes."""
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    cfg = dataclasses.replace(
        model.transformer_config(spec, traffic, rehearse), **widths)
    rows = model.sizes(traffic, rehearse)["rows_per_chip"]
    ref = files.reference_module(CONFIG)
    undo = defect(ref) if defect is not None else None
    try:
        params = model.make_params(cfg, seed)
        inputs, _ = model.seeded_rows(cfg, seed, rows, cfg.max_seq)

        def first_layer(params, inputs):
            weights = model.to_reference(params, cfg)
            lw = weights["layers"][0]
            with jax.default_matmul_precision("highest"):
                x = ref.rmsnorm(weights["embed"][inputs].astype(
                    jnp.float32), lw["ln1"])
                want = ref.mamba(x, lw, groups=cfg.ssm_groups)
            return jnp.stack(model.mixer_errors(ref, cfg, x, want, lw))

        built = model.built_quickly(first_layer, params, inputs[:1])
        worst = jnp.max(jnp.stack([built(params, inputs[r:r + 1])
                                   for r in range(rows)]), axis=0)
        return {name: (float(read), model.TOLERANCE[name])
                for name, read in zip(("ssm_mixer", "ssm_scan"), worst)}
    finally:
        if undo is not None:
            undo()


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
    scan_alone = args[0] == "scan"
    for seed in map(int, args[scan_alone].split(",")):
        for name in args[scan_alone + 1:]:
            if scan_alone:
                say("scan %s seed %d" % (name, seed), scan_readings(
                    seed, DEFECTS.get(name), rehearse=rehearse))
                continue
            checks = cell_checks(seed, DEFECTS.get(name), rehearse=rehearse)
            say("%s seed %d" % (name, seed), readings(checks))
            print(json.dumps({"defect": name, "seed": seed,
                              "checks": checks}), flush=True)
