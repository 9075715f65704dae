"""The conv/attention sparse-expert decoder (``TransformerConfig`` with
``layers`` whose ``LayerKind.mixer`` is "conv" for three layers in four: the
gated short convolution in place of attention, leaves and a stack of their
own, heads of 64 in the one attention layer, top-4 of 32 sigmoid-scored
experts of which the program holds a quarter) through ``make_train_step``
against the plain float32 reference of the benchmark
(``benchmark/reference/lfm2-8b-a1b.py``, which shares no code with the
program); the conv mixer alone; the share test of the model-configs guide;
the meshes; what refuses a conv layer; and what the new fields leave alone.
"""

import dataclasses
import functools
import hashlib
import importlib
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import flash_attention as fa
from horovod_tpu.parallel import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "readers"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)

CONFIG = "lfm2-8b-a1b"
K = tfm.LayerKind
CONV, ATTN = "conv", "attention"
# entries 0, 2, 3, 4, 5 of the published list: a dense conv layer, then one
# period of expert layers: attention, conv, conv, conv
KINDS = (K(mixer=CONV), K(experts=True), K(experts=True, mixer=CONV),
         K(experts=True, mixer=CONV), K(experts=True, mixer=CONV))
SMALL = tfm.TransformerConfig(
    vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, n_layers=5, d_ff=96,
    max_seq=32, dtype=jnp.float32, attention="flash", positions="rope",
    rope_theta=1e6, ffn="swiglu", norm="pre", norm_eps=1e-5, qk_norm=True,
    layers=KINDS, conv_kernel=3, n_experts=8, moe_top_k=2, d_ff_expert=32,
    route_eps=1e-6, router_bias_rate=1e-3, remat_barrier=True)
TIGHT = 2e-5        # float32 on both sides: the order of the sums differs
# bfloat16 activations against the float32 reference at these widths: the
# leaves' gradients read 2.3e-2 to 5.0e-2 of their largest value, the logits
# 4.7e-2 (counts on the CPU): twice the worst
LOOSE = 1e-1


@pytest.fixture(scope="module")
def reference():
    ref = files.reference_module(CONFIG)
    ref.ROWS = 8        # four blocks of attention rows, through lax.map
    ref.TOP_K = 2
    return ref


@pytest.fixture(scope="module")
def model():
    return files.config_module(CONFIG)


def _params(cfg=SMALL, seed=0):
    """Seeded weights with the norms' scales off 1 and a selection bias off
    0, so that a norm that is skipped or a bias that is ignored shows."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 96))
    params = jax.tree_util.tree_map(
        lambda x: x * jax.random.uniform(next(keys), x.shape, x.dtype,
                                         0.5, 1.5)
        if x.ndim and bool(jnp.all(x == 1.0)) else x, params)
    for stack in tfm._expert_rows(cfg):
        bias = params[stack]["router_bias"]
        params[stack]["router_bias"] = 0.05 * jax.random.normal(
            next(keys), bias.shape)
    return params


def _tokens(rows=2, seq=32, seed=0, vocab=96):
    tok = np.random.RandomState(seed).randint(
        0, vocab, size=(rows, seq + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _mesh(data=1, seq=1, tensor=1):
    devs = np.array(jax.devices()[:data * seq * tensor]).reshape(
        data, seq, tensor)
    return Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))


def _close(got, want, tol=TIGHT):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _sgd_step(cfg, params, inputs, targets, mesh=None):
    """One step of ``make_train_step`` under ``sgd(1.0)``: what it returns,
    and the gradient it applied (the parameters' change, negated)."""
    mesh = mesh or _mesh()
    step = tfm.make_train_step(mesh, cfg, optax.sgd(1.0))
    before = jax.tree_util.tree_map(jnp.array, params)
    new, _, loss, stats = step(tfm.shard_params(params, mesh, cfg),
                               optax.sgd(1.0).init(params), inputs, targets)
    return new, loss, stats, jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), before, new)


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


LEAVES = sorted(_leaves(jax.eval_shape(
    lambda k: tfm.init_params(k, SMALL), jax.random.PRNGKey(0))))


# -- the whole model against the reference ---------------------------------

@pytest.fixture(scope="module")
def wanted(reference, model):
    """The reference's logits, choices, loss and gradients for the seeded
    weights and tokens, in the program's tree."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)

    @jax.jit        # (one program: op by op the float32 reference is slow)
    def run(weights):
        logits, choices = reference.forward(weights, inputs, top_k=2)
        return (logits, jnp.stack(choices),
                reference.loss(weights, inputs, targets, top_k=2),
                reference.grads(weights, inputs, targets, 0, lambda f: f, 2))

    with jax.default_matmul_precision("highest"):
        logits, choices, loss, grads = run(weights)
    return {"logits": logits, "choices": choices, "loss": loss,
            "grads": model.from_reference(grads, SMALL)}


def test_the_reference_given_its_own_choices_is_the_reference(reference,
                                                             model, wanted):
    """``given``: the experts every token takes. Its own: the same logits
    and gradients; another's: another result, and the choices returned stay
    the reference's own."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)
    own = list(wanted["choices"])
    with jax.default_matmul_precision("highest"):
        logits, choices = jax.jit(lambda w: reference.forward(
            w, inputs, top_k=2, given=own))(weights)
        grads = jax.jit(lambda w: reference.grads(
            w, inputs, targets, 0, lambda f: f, 2, own))(weights)
        other = [(c + 1) % 8 for c in own]
        moved, still = jax.jit(lambda w: reference.forward(
            w, inputs, top_k=2, given=other))(weights)
    _close(logits, wanted["logits"], 1e-6)
    assert np.array_equal(np.asarray(jnp.stack(choices)),
                          np.asarray(wanted["choices"]))
    for leaf, want in _leaves(wanted["grads"]).items():
        _close(_leaves(model.from_reference(grads, SMALL))[leaf], want, 1e-5)
    assert float(jnp.max(jnp.abs(moved - wanted["logits"]))) > 1e-3
    assert np.array_equal(np.asarray(still[0]), np.asarray(own[0]))


@pytest.fixture(scope="module")
def stepped():
    with jax.default_matmul_precision("highest"):
        return _sgd_step(SMALL, _params(), *_tokens())


def test_the_stacks_are_one_a_kind_of_layer():
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, SMALL),
                            jax.random.PRNGKey(0))
    assert {k: v["ln1"].shape[0] for k, v in shapes.items()
            if isinstance(v, dict)} == {
        "conv_dense_layers": 1, "layers": 1, "conv_layers": 3}
    assert set(shapes["conv_layers"]) == {
        "ln1", "ln2", "conv_in", "conv_w", "conv_out", "router",
        "router_bias", "ewg", "ewu", "ewd"}
    assert shapes["conv_layers"]["conv_in"].shape == (3, 64, 3, 64)
    assert shapes["conv_layers"]["conv_w"].shape == (3, 3, 64)
    assert not {"wq", "wk", "wv", "wo"} & set(shapes["conv_dense_layers"])
    assert tfm.layer_rows(SMALL) == [
        ("conv_dense_layers", 0), ("layers", 0), ("conv_layers", 0),
        ("conv_layers", 1), ("conv_layers", 2)]
    assert tfm._expert_rows(SMALL) == {"layers": [0],
                                       "conv_layers": [1, 2, 3]}


def test_the_published_parameter_count():
    """At the published widths the cut holds the 507,820,288 parameters
    ISSUE 34 counted, a conv mixer 16,783,360 and the attention mixer
    10,485,888."""
    model = files.config_module(CONFIG)
    cfg = model.transformer_config(
        files.load_json(files.config_path(CONFIG)),
        files.load_json(files.traffic_path("spmd-1chip-2x8192-remat")),
        False)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in  # noqa: E731
                            jax.tree_util.tree_leaves(tree))
    assert size(shapes) == 507_820_288
    conv = shapes["conv_dense_layers"]
    assert size({k: conv[k] for k in ("conv_in", "conv_w", "conv_out")}) \
        == 16_783_360
    attn = shapes["layers"]
    assert size({k: attn[k] for k in ("wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm")}) == 10_485_888
    assert cfg.head_dim == 64 and cfg.held == 8 and cfg.n_experts == 32


@pytest.mark.parametrize("remat", ["none", "block", "attention"])
def test_logits_and_choices_against_the_reference(wanted, remat):
    cfg = dataclasses.replace(SMALL, remat=remat)
    with jax.default_matmul_precision("highest"):
        logits, routes = jax.jit(
            lambda p, x: tfm.forward_routes(p, x, cfg))(_params(),
                                                        _tokens()[0])
    _close(logits, wanted["logits"])
    assert np.array_equal(np.sort(routes.expert, -1),
                          np.sort(wanted["choices"], -1))
    assert routes.counts.shape == (4, 8)


@pytest.mark.parametrize("through", ["lean_lm_loss", "make_spmd_loss",
                                     "make_train_step"])
def test_loss_against_the_reference(wanted, stepped, through):
    params, (inputs, targets) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        if through == "lean_lm_loss":
            got = tfm.lean_lm_loss(params, inputs, targets, SMALL)
        elif through == "make_spmd_loss":
            got = jax.jit(tfm.make_spmd_loss(_mesh(), SMALL))(
                params, inputs, targets)
        else:
            got = stepped[1]
    assert float(got) == pytest.approx(float(wanted["loss"]), rel=TIGHT)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_through_the_train_step(wanted, stepped, leaf):
    """sgd(1.0) applies the gradient itself: the conv leaves, the attention
    layer's, both stacks' experts; the selection bias moves by its own rule
    and the routers' is 0 only on a share (here every expert is held)."""
    applied, want = _leaves(stepped[3])[leaf], _leaves(wanted["grads"])[leaf]
    if leaf.endswith("['router_bias']"):
        assert float(jnp.max(jnp.abs(want))) == 0.0
        return
    assert float(jnp.max(jnp.abs(want))) > 0, leaf
    _close(applied, want, 2e-4)


# every expert chosen: a choice that flips under bfloat16 moves a token's
# output by a whole expert's term, which is the cell's own check (counted
# there as ``choices_off``), not rounding
ALL_CHOSEN = dataclasses.replace(SMALL, moe_top_k=8)


@pytest.fixture(scope="module")
def wanted_all_chosen(reference, model):
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)

    @jax.jit
    def run(weights):
        return (reference.forward(weights, inputs, top_k=8)[0],
                reference.loss(weights, inputs, targets, top_k=8),
                reference.grads(weights, inputs, targets, 0, lambda f: f, 8))

    with jax.default_matmul_precision("highest"):
        logits, loss, grads = run(weights)
    return {"logits": logits, "loss": loss,
            "grads": model.from_reference(grads, SMALL)}


@pytest.fixture(scope="module")
def in_bfloat16():
    cfg = dataclasses.replace(ALL_CHOSEN, dtype=jnp.bfloat16)
    return _sgd_step(cfg, _params(), *_tokens())


@pytest.mark.parametrize("what", ["loss", "logits"] + [
    leaf for leaf in LEAVES if "router" not in leaf])
def test_in_bfloat16_against_the_float32_reference(wanted_all_chosen,
                                                  in_bfloat16, what):
    """The compute dtype the cell states, at the rehearsal's widths: near
    the float32 reference, leaf by leaf."""
    wanted = wanted_all_chosen
    cfg = dataclasses.replace(ALL_CHOSEN, dtype=jnp.bfloat16)
    if what == "loss":
        assert float(in_bfloat16[1]) == pytest.approx(
            float(wanted["loss"]), rel=5e-3)
    elif what == "logits":
        logits, _ = tfm.forward_routes(_params(), _tokens()[0], cfg)
        _close(logits.astype(jnp.float32), wanted["logits"], LOOSE)
    else:
        _close(_leaves(in_bfloat16[3])[what], _leaves(wanted["grads"])[what],
               LOOSE)


def test_the_step_returns_the_counts_and_moves_both_stacks_bias(stepped):
    """The counts come in the order the layers run (the attention layer
    first, then the three conv layers), and each stack's bias moves by its
    own rows of them."""
    new, _, stats, _ = stepped
    counts = np.asarray(stats["expert_counts"])
    assert counts.shape == (4, 8) and (counts.sum(axis=1) == 2 * 32 * 2).all()
    before = _params()
    for stack, rows in (("layers", [0]), ("conv_layers", [1, 2, 3])):
        want = moe.router_bias_update(before[stack]["router_bias"],
                                      counts[rows], 1e-3)
        assert np.array_equal(np.asarray(new[stack]["router_bias"]),
                              np.asarray(want))


def test_the_optimizer_never_touches_either_bias():
    opt = optax.adamw(1e-2, weight_decay=0.5)
    params, (inputs, targets) = _params(), _tokens()
    cfg = dataclasses.replace(SMALL, router_bias_rate=0.0)
    new = tfm.make_train_step(_mesh(), cfg, opt)(
        jax.tree_util.tree_map(jnp.array, params), opt.init(params), inputs,
        targets)[0]
    for stack in ("layers", "conv_layers"):
        assert np.array_equal(np.asarray(new[stack]["router_bias"]),
                              np.asarray(params[stack]["router_bias"]))


# -- a mesh takes the same step --------------------------------------------

@pytest.mark.parametrize("mesh", [(4, 1, 1), (1, 1, 2), (2, 1, 2)],
                         ids=["data4", "tensor2", "data2-tensor2"])
def test_a_mesh_takes_the_same_step(stepped, mesh):
    """Over ``data`` the conv and the attention stacks' gradients are summed
    inside the backward pass (``_sum_in_backward`` on every leaf of the
    expert layers' stacks) and the dense conv stack's after it; over
    ``tensor`` the conv's channels are split like an FFN's hidden dim and
    summed once."""
    rows = 4 if mesh[0] == 4 else 2
    params, (inputs, targets) = _params(), _tokens(rows)
    with jax.default_matmul_precision("highest"):
        want = stepped if rows == 2 else _sgd_step(SMALL, _params(), inputs,
                                                   targets)
        _, loss, stats, applied = _sgd_step(SMALL, params, inputs, targets,
                                            _mesh(*mesh))
    assert float(loss) == pytest.approx(float(want[1]), rel=TIGHT)
    assert np.array_equal(np.asarray(stats["expert_counts"]),
                          np.asarray(want[2]["expert_counts"]))
    got, wanted = _leaves(applied), _leaves(want[3])
    for leaf in LEAVES:
        if not leaf.endswith("['router_bias']"):
            _close(got[leaf], wanted[leaf], 2e-4)


@pytest.mark.parametrize("held, mesh", [(0, (1, 1, 1)), (2, (1, 1, 1)),
                                        (2, (2, 1, 1))],
                         ids=["whole", "share", "share-data2"])
def test_the_step_with_its_row_sums_as_gathers(monkeypatch, held, mesh):
    """The rehearsal's shapes stay under a chunk of live rows, so the steps
    of this file add their rows in chunks; the cell's are gathers
    (``moe.row_sum_form``). The same step in the gather form, through the
    scans, the checkpoints and a mesh: the loss, the counts and every
    leaf's gradient, the routers' too where every expert is held."""
    cfg = dataclasses.replace(SMALL, experts_held=held)
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        want = _sgd_step(cfg, _params(cfg), inputs, targets)
        monkeypatch.setattr(moe, "row_sum_form", lambda *shape: "gather")
        _, loss, stats, applied = _sgd_step(cfg, params, inputs, targets,
                                            _mesh(*mesh))
    assert float(loss) == pytest.approx(float(want[1]), rel=TIGHT)
    assert np.array_equal(np.asarray(stats["expert_counts"]),
                          np.asarray(want[2]["expert_counts"]))
    got, wanted = _leaves(applied), _leaves(want[3])
    assert bool(np.any(wanted["['layers']['router']"])) == (held == 0)
    for leaf in LEAVES:
        if not leaf.endswith("['router_bias']"):
            _close(got[leaf], wanted[leaf], 2e-4)


@pytest.mark.parametrize("form", ["gather", "chunks"])
@pytest.mark.parametrize("sizes, ran", [
    ((48, 128), 48), ((8, 128), 128), ((8, 16), 16)],
    ids=["under-tight", "between", "past-wide"])
def test_the_step_with_a_buffer_fitted_to_its_loads(monkeypatch, sizes, ran,
                                                    form):
    """The rehearsal's 128 assignments a layer make both sizes of the
    dispatch buffer 128 rows, so the steps of this file build no
    conditional; the cell's sizes differ (``moe.topk_buffer_sizes``). The
    same step of a share of 2 of 8 experts (32 held assignments a layer
    under an even router) with sizes that differ, through the scans, the
    checkpoints and the conditional on both passes: a tight buffer that
    holds every layer's, one that holds none beside a wide one that does,
    and a wide one they fill several times over; the loss, the counts and
    every leaf's gradient are the one buffer's, and ``routing_stats``
    reports the size each layer ran."""
    cfg = dataclasses.replace(SMALL, experts_held=2)
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(moe, "row_sum_form", lambda *shape: form)
        want = _sgd_step(cfg, _params(cfg), inputs, targets)
        monkeypatch.setattr(moe, "topk_buffer_sizes", lambda *shape: sizes)
        _, loss, stats, applied = _sgd_step(cfg, params, inputs, targets)
    assert float(loss) == pytest.approx(float(want[1]), rel=TIGHT)
    counts = np.asarray(stats["expert_counts"])
    assert np.array_equal(counts, np.asarray(want[2]["expert_counts"]))
    held = counts[:, :2].sum(axis=1)
    assert ((held > 8) & (held <= 48)).all()
    got = tfm.routing_stats(counts, cfg, inputs.size)
    assert got["buffer_rows"] == [ran] * 4
    assert got["buffer_fill"] == (held / ran).tolist()
    assert (max(got["buffer_fill"]) > 1) == (ran == 16)
    got, wanted = _leaves(applied), _leaves(want[3])
    for leaf in LEAVES:
        if not leaf.endswith("['router_bias']"):
            _close(got[leaf], wanted[leaf], 2e-4)


def test_the_gradient_sums_of_a_data_mesh_cover_the_conv_leaves():
    axes = tfm.grad_reduce_axes(_mesh(4), SMALL)
    early = tfm._in_backward(axes, SMALL)
    assert set(early) == {"layers", "conv_layers"}
    assert set(early["conv_layers"]) >= {"conv_in", "conv_w", "conv_out"}
    assert all(a == ("data",) for a in early["conv_layers"].values())
    assert axes["conv_dense_layers"]["conv_in"] == ("data",)
    share = tfm.grad_reduce_in_backward_share(_mesh(4), SMALL)
    assert 0.5 < share < 1.0


# -- the conv mixer alone --------------------------------------------------

def _conv_leaves(d=16, taps=3, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"conv_in": jax.random.normal(ks[0], (d, 3, d)) / 4,
            "conv_w": jax.random.normal(ks[1], (taps, d)),
            "conv_out": jax.random.normal(ks[2], (d, d)) / 4}, \
        jax.random.normal(ks[3], (2, 24, d))


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_conv_mixer_against_lax_conv(taps):
    """``_conv_mix`` is a depthwise ``lax.conv_general_dilated`` over the
    sequence, left-padded by ``taps - 1``, between the two gates."""
    lp, x = _conv_leaves(taps=taps)
    cfg = dataclasses.replace(SMALL, conv_kernel=taps)
    with jax.default_matmul_precision("highest"):
        got = tfm._conv_mix(x, lp, cfg=cfg)
        b, c, xg = (x @ lp["conv_in"][:, i] for i in range(3))
        v = lax.conv_general_dilated(
            b * xg, lp["conv_w"][:, None, :], window_strides=(1,),
            padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=x.shape[-1])
        want = (c * v) @ lp["conv_out"]
    _close(got, want, 1e-5)


def test_the_conv_mixer_is_causal_to_the_bit():
    """Token t + 1 changed: every output up to t is the same bits."""
    lp, x = _conv_leaves()
    mix = jax.jit(lambda x: tfm._conv_mix(x, lp, cfg=SMALL))
    t = 11
    changed = x.at[:, t + 1].add(1.0)
    got, base = np.asarray(mix(changed)), np.asarray(mix(x))
    assert np.array_equal(got[:, :t + 1], base[:, :t + 1])
    assert not np.array_equal(got[:, t + 1], base[:, t + 1])
    # ... and it reaches conv_kernel - 1 tokens back, no further
    assert not np.array_equal(got[:, t + 3], base[:, t + 3])
    assert np.array_equal(got[:, t + 4:], base[:, t + 4:])


def test_the_conv_sublayer_against_the_reference(reference):
    lp, x = _conv_leaves(d=64)
    with jax.default_matmul_precision("highest"):
        _close(tfm._conv_mix(x, lp, cfg=SMALL),
               reference.short_conv(x, lp), 1e-5)


def test_the_scopes_of_a_conv_layer():
    """``conv_mixer`` where an attention layer has ``attn`` (its two
    products among them), ``short_conv`` inside it (the gates, the taps'
    pad and slices); the attention layer of this model under
    ``attn_full``."""
    params, (inputs, targets) = _params(), _tokens()
    text = tfm.make_train_step(_mesh(), SMALL, optax.sgd(1.0)).lower(
        params, optax.sgd(1.0).init(params), inputs, targets
    ).as_text(debug_info=True)
    for name in ("conv_mixer/btd,dgc->gbtc/dot_general",
                 "conv_mixer/btc,cd->btd/dot_general",
                 "conv_mixer/short_conv/mul", "conv_mixer/short_conv/pad",
                 "conv_mixer/short_conv/slice", "attn/attn_full/",
                 "ffn/while/body/experts/", "transpose(jvp(layers))"):
        assert name in text, name
    assert "short_conv/btd" not in text     # the products are outside it


# -- the share test of the model-configs guide -----------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(reference):
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 (the cell: 0-7, 8-15, 16-23, 24-31
    of 32), each program holding a quarter: the four parts of an expert
    layer's result add up to what the reference gives for the whole layer."""
    whole = dataclasses.replace(SMALL, layers=KINDS[2:3], n_layers=1)
    full = tfm.init_params(jax.random.PRNGKey(4), whole)["conv_layers"]
    lw = {k: v[0] for k, v in full.items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = reference.expert_ffn(x, lw, 0, 2)
        parts = []
        for first in (0, 2, 4, 6):
            cfg = dataclasses.replace(whole, experts_held=2,
                                      first_expert=first)
            share = tfm.init_params(jax.random.PRNGKey(4),
                                    cfg)["conv_layers"]
            # a share draws the experts the whole layer draws
            assert np.array_equal(np.asarray(share["ewg"][0]),
                                  np.asarray(full["ewg"][0, first:first + 2]))
            out, routes = tfm._expert_ffn(
                x, {k: v[0] for k, v in share.items()}, cfg, None)
            parts.append(out)
            assert np.array_equal(np.sort(routes.expert, -1),
                                  np.sort(chosen, -1))
    _close(sum(parts), want, 1e-5)


def test_the_normalisations_epsilon_is_the_models():
    x = jnp.ones((16, 8))
    router = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (8, 4)) - 4.0
    bias = jnp.zeros((4,))
    tiny = moe.topk_route(x, router, bias, 2).weight
    model = moe.topk_route(x, router, bias, 2, eps=1e-6).weight
    # scores of e-13: their sum is far under 1e-6 and far over 1e-20
    assert float(jnp.min(jnp.sum(tiny, -1))) > 0.99
    assert float(jnp.max(jnp.sum(model, -1))) < 1e-3


# -- what cannot run a conv layer says so ----------------------------------

@pytest.mark.parametrize("builder", ["make_pp_train_step",
                                     "make_moe_ep_train_step"])
def test_the_other_builders_refuse_a_conv_layer_by_name(builder):
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    with pytest.raises(ValueError, match="conv mixer"):
        if builder == "make_pp_train_step":
            tfm.make_pp_train_step(mesh, SMALL, optax.sgd(1.0), n_micro=2)
        else:
            class Engine:       # refused before the engine is asked
                pass
            tfm.make_moe_ep_train_step(Engine(), SMALL, optax.sgd(1.0))


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_sequence_parallel_meshes_refuse_a_conv_layer_by_name(attention):
    cfg = dataclasses.replace(SMALL, attention=attention)
    params, (inputs, targets) = _params(), _tokens()
    m = _mesh(1, 2, 1)
    with pytest.raises(ValueError, match="conv mixer under sequence"):
        tfm.make_spmd_loss(m, cfg)(tfm.shard_params(params, m, cfg),
                                   inputs, targets)


@pytest.mark.parametrize("changes, words", [
    ({"layers": (K(mixer="mamba"),) + KINDS[1:]}, "unknown mixer"),
    ({"layers": (K(window=8, mixer=CONV),) + KINDS[1:]}, "has no window"),
    # (a dense layer after the expert layers has run since PR 39; a layer
    # with neither a mixer nor an FFN has no stack)
    ({"layers": KINDS[1:] + (K(mixer="none", experts=None),)},
     "has no stack to live in"),
])
def test_a_pattern_that_cannot_run_is_refused_by_name(changes, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(SMALL, **changes)


# -- heads of 64 -------------------------------------------------------------

@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("HOROVOD_SPLASH", raising=False)


def test_heads_of_64_reach_splash_at_the_causal_blocks(on_tpu):
    found = fa.attention_kernel((2, 32, 8192, 64), (2, 8, 8192, 64),
                                under_remat=True)
    assert found == {"kernel": "splash", "block_q": "1024",
                     "block_kv": "1024", "fused_bwd": "1", "window": "0",
                     "head_size": "64", "v_head_size": "64"}
    assert fa._select_kernel((2, 32, 8192, 96), (2, 8, 8192, 96)) \
        == "materialized"
    assert fa._select_kernel((2, 32, 8192, 32), (2, 32, 8192, 32)) == "flash"


def test_heads_of_64_through_splash_against_float32(on_tpu, monkeypatch):
    """dq, dk, dv of ``flash_attention_local`` at 1 x 4/2 x 1024 x 64, the
    stock splash kernel's MQA form interpreted here, against a float32
    materialized attention: relative L2, the band of heads of 128
    (``tests/test_pallas_kernels.py``)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mqa", functools.partial(
        sk.make_splash_mqa, interpret=True))
    fa._splash_kernel.cache_clear()
    t, d = 1024, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, w = (jax.random.normal(k, (1, 4, t, d)).astype(jnp.bfloat16)
            for k in keys[:2])
    k, v = (jax.random.normal(k, (1, 2, t, d)).astype(jnp.bfloat16)
            for k in keys[2:])

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def kernel(q, k, v):
        return fa.flash_attention_local(q, k, v, layout="bhtk")

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    assert fa.attention_kernel(q.shape, k.shape)["kernel"] == "splash"
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    fa._splash_kernel.cache_clear()
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        err = np.linalg.norm(g - r) / np.linalg.norm(r)
        assert err < 6e-3, (name, err)


# -- the small repair: why attention is materialized, and when it cannot be --

@pytest.mark.parametrize("q, kv, window, words", [
    ((1, 12, 197, 64), (1, 12, 197, 64), 0, "not multiples of 128"),
    ((1, 32, 2048, 96), (1, 8, 2048, 96), 0,
     "no grouped KV heads.*a head of 96"),
    ((1, 4, 640, 128), (1, 4, 640, 128), 128,
     "knows no window.*not a multiple of 1024"),
])
def test_the_warning_gives_the_true_reason(on_tpu, caplog, q, kv, window,
                                           words):
    import re
    fa._warn_materialized_once.cache_clear()
    kernel, why = fa._kernel_and_why(q, kv, window)
    assert kernel == "materialized" and re.search(words, why), why
    x = jnp.zeros((1, 8, 2, 4))
    with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
        fa._warn_materialized_once(why)
    assert re.search(words, caplog.text)
    del x


def test_scores_no_chip_holds_are_refused_before_the_allocator(on_tpu,
                                                              monkeypatch):
    """32 heads x 8,192 x 8,192 fp32 scores a row and their softmax, 17.2
    GB, on a device of 16 GB: a ValueError that says why the shape is
    materialized; the same call under a larger device only warns."""
    monkeypatch.setattr(fa, "_device_bytes", lambda: 16 * 10 ** 9)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 96), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8, 8192, 96), jnp.bfloat16)
    attn = functools.partial(fa.flash_attention_local, layout="bhtk")
    with pytest.raises(ValueError, match=r"a head of 96.*17\.2 GB.*16\.0 GB"):
        jax.eval_shape(attn, q, kv, kv)
    monkeypatch.setattr(fa, "_device_bytes", lambda: 32 * 10 ** 9)
    assert jax.eval_shape(attn, q, kv, kv).shape == q.shape
    monkeypatch.setattr(fa, "_device_bytes", lambda: None)
    assert jax.eval_shape(attn, q, kv, kv).shape == q.shape


# -- the grouped products' tiles ---------------------------------------------

@pytest.mark.parametrize("m, k, n, want", [
    (10240, 2048, 1024, (256, 1024, 1024)),     # the sparse-expert cell's
    (10240, 1024, 2048, (256, 1024, 1024)),
    (40960, 2048, 1792, (256, 1024, 896)),      # this cell's, forward ...
    (40960, 1792, 2048, (256, 896, 1024)),      # ... and where k, n swap
    (40960, 2048, 1536, (256, 1024, 768)),
    (512, 128, 128, (256, 128, 128)),
    (128, 64, 96, (128, 64, 96)),               # nothing divides: whole
])
def test_the_tile_rule_answers_from_the_shape(m, k, n, want):
    assert moe.gmm_tiles(m, k, n) == want


def test_the_grouped_product_with_a_tiling_a_call_against_ragged_dot():
    """``moe._gmm`` (megablox interpreted here) forward and both gradients
    against ``lax.ragged_dot`` at a width whose tile differs between the
    forward's and the backward's calls (k 256, n 384)."""
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    real = backend.gmm, backend.tgmm
    backend.gmm, backend.tgmm = (functools.partial(f, interpret=True)
                                 for f in real)
    try:
        assert moe.gmm_tiles(512, 256, 384) == (256, 256, 384)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        rows = jax.random.normal(ks[0], (512, 256))
        w = jax.random.normal(ks[1], (4, 256, 384)) / 16
        g = jax.random.normal(ks[2], (512, 384))
        sizes = jnp.array([100, 0, 250, 90], jnp.int32)
        live = jnp.arange(512)[:, None] < 440

        def loss(product):
            return lambda rows, w: jnp.sum(jnp.where(
                live, product(rows, w, sizes) * g, 0.0))

        with jax.default_matmul_precision("highest"):
            got = jax.value_and_grad(loss(moe._gmm), (0, 1))(rows, w)
            want = jax.value_and_grad(loss(lax.ragged_dot), (0, 1))(rows, w)
    finally:
        backend.gmm, backend.tgmm = real
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    _close(jnp.where(live, got[1][0], 0), jnp.where(live, want[1][0], 0),
           1e-5)
    _close(got[1][1], want[1][1], 1e-5)


# -- what the new fields leave alone ---------------------------------------

# sha256 of make_train_step's lowered text at the commit before the mixer
# kind existed (8eb97ae), the sparse-expert cell's program at rehearsal
# widths, adamw(3e-4): on a mesh of one and over data=4 (the other two LM
# programs' digests: tests/test_trinity_lm.py)
SPARSE = dict(
    vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_size=16,
    n_layers=5, d_ff=96, max_seq=128, dtype=jnp.bfloat16, attention="flash",
    remat="block", positions="rope", rope_theta=1e4, ffn="swiglu",
    norm="sandwich", norm_eps=1e-5, tie_embeddings=False, qk_norm=True,
    attn_gate=True, embed_scale=8.0,
    layers=(K(32, True, False), K(32, True, True), K(32, True, True),
            K(0, False, True), K(32, True, True)),
    n_experts=8, moe_top_k=2, d_ff_expert=32, n_shared_experts=1,
    route_scale=2.826, experts_held=4, router_bias_rate=1e-3)
SPARSE_DIGESTS = {
    1: "02b2de41c2f0b4171d991f5ebee6ce72fa1ef4d71d383b7b00309a3e3228e095",
    4: "1aeddf3e6d0a8fb4237da9e5d720a792e263969d9b5a43255aed5dc15470a212"}
SPARSE_WEIGHT_SUM = 27451.498046875


@pytest.mark.parametrize("data", [1, 4])
def test_the_sparse_expert_cells_step_lowers_as_before(data):
    cfg = tfm.TransformerConfig(**SPARSE)
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((data, cfg.max_seq), jnp.int32)
    text = tfm.make_train_step(_mesh(data), cfg, opt).lower(
        params, jax.eval_shape(opt.init, params), tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == SPARSE_DIGESTS[data]


def test_the_sparse_expert_cells_weights_are_drawn_as_before():
    cfg = tfm.TransformerConfig(**{**SPARSE, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "ln_f", "lm_head", "dense_layers",
                           "layers"}
    got = float(sum(jnp.sum(jnp.abs(x)) for x in
                    jax.tree_util.tree_leaves(params)))
    assert got == pytest.approx(SPARSE_WEIGHT_SUM, rel=1e-6)


@pytest.mark.parametrize("gauge", [
    "hvd_tpu_lm_layers", "hvd_tpu_moe_row_sum",
    "hvd_tpu_moe_row_sum_rows_over_live", "hvd_tpu_moe_buffer_rows"])
def test_the_examples_gauges_are_declared(gauge):
    from horovod_tpu.metrics import METRIC_SPECS
    assert METRIC_SPECS[gauge][0] == "gauge"
    example = os.path.join(os.path.dirname(BENCH), "examples",
                           "transformer_lm.py")
    with open(example) as fh:
        assert '"%s"' % gauge in fh.read()
