"""The sparse-expert decoder with a per-layer pattern (``TransformerConfig``
with ``layers``: window and full attention layers in one stack, KV heads
fewer than heads, a head size of its own, q/k norm, the output gate, the
embedding's multiplier, and sigmoid top-k routing over experts of which the
program holds a share, with a shared expert and a selection bias) through
``make_train_step`` against the plain float32 reference of the benchmark
(``benchmark/reference/trinity-mini.py``, which shares no code with the
program); each piece alone; the share test of the model-configs guide; and
what the new fields leave alone.

Everything here is float32 on both sides: the two differ in the order of
sums only. What bfloat16 does to the choices of expert is the cell's own
check (``benchmark/configs/trinity-mini*.py``), read on the chip.
"""

import dataclasses
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel import flash_attention as fa
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.ring_attention import local_attention

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "readers"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)

CONFIG = "trinity-mini"
K = tfm.LayerKind
W = 8       # the window, over 32 positions
# one dense layer, then one period of expert layers: window, window, full
# (no rotation), window, as the first five layers of the published list
KINDS = (K(W, True, False), K(W, True, True), K(W, True, True),
         K(0, False, True), K(W, True, True))
SMALL = tfm.TransformerConfig(
    vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_size=16,
    n_layers=5, d_ff=96, max_seq=32, dtype=jnp.float32, attention="flash",
    positions="rope", rope_theta=1e4, ffn="swiglu", norm="sandwich",
    norm_eps=1e-5, tie_embeddings=False, qk_norm=True, attn_gate=True,
    embed_scale=8.0, layers=KINDS, n_experts=8, moe_top_k=2,
    d_ff_expert=32, n_shared_experts=1, route_scale=2.826,
    router_bias_rate=1e-3)
TIGHT = 2e-5        # float32 on both sides: the order of the sums differs


@pytest.fixture(scope="module")
def reference():
    ref = files.reference_module(CONFIG)
    ref.ROWS = 8        # four blocks of attention rows, through lax.map
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
    if "layers" in params and "router_bias" in params["layers"]:
        bias = params["layers"]["router_bias"]
        params["layers"]["router_bias"] = 0.05 * jax.random.normal(
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


def _kinds(cfg):
    return [(kind.window, kind.rope) for kind in cfg.layers]


def _close(got, want, tol=TIGHT):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _sgd_step(cfg, params, inputs, targets, mesh=None):
    """One step of ``make_train_step`` under ``sgd(1.0)``: what it returns,
    and the gradient it applied (the parameters' change, negated)."""
    step = tfm.make_train_step(mesh or _mesh(), cfg, optax.sgd(1.0))
    before = jax.tree_util.tree_map(jnp.array, params)
    new, _, loss, stats = step(params, optax.sgd(1.0).init(params), inputs,
                               targets)
    return new, loss, stats, jax.tree_util.tree_map(
        lambda a, b: a - b, before, new)


# -- the whole model against the reference ---------------------------------

@pytest.fixture(scope="module")
def wanted(reference, model):
    """The reference's logits, choices, loss and gradients for the seeded
    weights and tokens, in the program's tree."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params)
    kinds, k = _kinds(SMALL), SMALL.moe_top_k

    @jax.jit        # (one program: op by op the float32 reference is slow)
    def run(weights):
        logits, choices = reference.forward(weights, inputs, kinds, top_k=k)
        return (logits, jnp.stack(choices),
                reference.loss(weights, inputs, targets, kinds, top_k=k),
                reference.grads(weights, inputs, targets, kinds, 0,
                                lambda f: f, k))

    with jax.default_matmul_precision("highest"):
        logits, choices, loss, grads = run(weights)
    return {"logits": logits, "choices": choices, "loss": loss,
            "grads": model.from_reference(grads, params)}


@pytest.fixture(scope="module")
def stepped():
    with jax.default_matmul_precision("highest"):
        return _sgd_step(SMALL, _params(), *_tokens())


@pytest.mark.parametrize("remat", ["none", "block", "attention"])
def test_logits_and_choices_against_the_reference(wanted, remat):
    cfg = dataclasses.replace(SMALL, remat=remat)
    with jax.default_matmul_precision("highest"):
        logits, routes = jax.jit(lambda p, x: tfm.forward_routes(p, x, cfg))(
            _params(), _tokens()[0])
    _close(logits, wanted["logits"])
    assert routes.expert.shape == (4, 2, 32, 2)
    assert jnp.array_equal(jnp.sort(routes.expert, -1),
                           jnp.sort(wanted["choices"], -1))


@pytest.mark.parametrize("through", ["lean_lm_loss", "make_spmd_loss",
                                     "make_train_step"])
def test_loss_against_the_reference(wanted, stepped, through):
    params, (inputs, targets) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        loss = {"lean_lm_loss": lambda: jax.jit(
                    lambda p: tfm.lean_lm_loss(p, inputs, targets, SMALL))(
                    params),
                "make_spmd_loss": lambda: jax.jit(tfm.make_spmd_loss(
                    _mesh(), SMALL))(params, inputs, targets),
                "make_train_step": lambda: stepped[1]}[through]()
    assert float(abs(loss - wanted["loss"])) <= TIGHT * float(wanted["loss"])


LEAVES = [jax.tree_util.keystr(path) for path, _ in
          jax.tree_util.tree_leaves_with_path(jax.eval_shape(
              lambda: tfm.init_params(jax.random.PRNGKey(0), SMALL)))]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_through_the_train_step(wanted, stepped, leaf):
    """``make_train_step``'s own loss, differentiated inside its shard_map:
    under sgd(1.0) the parameters' change is the gradient. The selection
    bias has none: the step moves it by its own rule (tested below)."""
    got = dict((jax.tree_util.keystr(p), x) for p, x in
               jax.tree_util.tree_leaves_with_path(stepped[3]))[leaf]
    want = dict((jax.tree_util.keystr(p), x) for p, x in
                jax.tree_util.tree_leaves_with_path(wanted["grads"]))[leaf]
    if leaf.endswith("['router_bias']"):
        assert not np.asarray(want).any()
        return
    assert float(jnp.max(jnp.abs(want))) > 0
    _close(got, want, 2e-4)


def test_the_step_returns_the_counts_and_moves_the_bias_by_them(stepped):
    new, _, stats, _ = stepped
    counts = np.asarray(stats["expert_counts"])
    assert counts.shape == (4, 8) and (counts.sum(axis=1) == 2 * 32 * 2).all()
    old = np.asarray(_params()["layers"]["router_bias"])
    want = old + np.float32(1e-3) * np.sign(
        counts.mean(axis=1, keepdims=True) - counts).astype(np.float32)
    assert np.array_equal(np.asarray(new["layers"]["router_bias"]), want)


def test_the_gauges_of_where_the_router_sent_the_step():
    """``routing_stats`` of hand-made counts: a share of 2 of 8 experts,
    256 tokens, top 2, a buffer of 512 rows at either size; layer 0 even,
    layer 1 with every assignment on the held two (the buffer exactly
    full). Under a chunk of live rows the row sums are chunks, and a chunk
    is the whole buffer here; at 64 times the tokens they are a gather of
    the 32,768 choices, whatever the held experts drew, the even layer
    runs the tight buffer of 10,240 rows and the full one the wide one of
    20,480 and a second: the rows ``moe.topk_buffer_rows``, the layer's own
    rule, answers for the same held counts."""
    cfg = dataclasses.replace(SMALL, experts_held=2, first_expert=4)
    assert moe.topk_buffer_sizes(256, 2, 8, 2) == (512, 512)
    counts = np.array([[64] * 8, [0] * 4 + [256] * 2 + [0] * 2])
    got = tfm.routing_stats(counts, cfg, 256)
    assert got == {"held_share": [0.25, 1.0], "buffer_rows": [512, 512],
                   "buffer_fill": [0.25, 1.0], "row_sum_form": "chunks",
                   "row_sum_rows_over_live": [4.0, 1.0],
                   "load_max_over_mean": [1.0, 4.0], "dropped": 0.0}
    got = tfm.routing_stats(counts * 64, cfg, 256 * 64)
    assert got["row_sum_form"] == "gather"
    assert moe.topk_buffer_sizes(256 * 64, 2, 8, 2) == (10240, 20480)
    assert got["buffer_rows"] == [10240, 20480] == [
        int(moe.topk_buffer_rows(256 * 64, 2, 8, 2, n_held))
        for n_held in (8192, 32768)]
    assert got["buffer_fill"] == [0.8, 1.6]
    # (the second layer's 32,768 held assignments fill the wide buffer of
    # 20,480 rows and a second: each gathers every choice)
    assert got["row_sum_rows_over_live"] == [4.0, 2.0]


@pytest.mark.parametrize("shape, sizes", [
    ((16384, 4, 32, 8), (20480, 40960)), ((8192, 8, 128, 8), (5120, 10240)),
    ((16384, 4, 32, 32), (65536, 65536)), ((2048, 2, 8, 1), (1024, 1536)),
    ((256, 2, 8, 4), (512, 512))],
    ids=["conv-attention-cell", "sparse-expert-cell", "whole-layer",
         "one-of-eight", "rehearsal"])
def test_the_buffer_fits_the_held_count(shape, sizes):
    """The two sizes from the shape (1.25 and 2.5 times an even share, in
    512s, at most ``T x k``), and ONE function from a held count to the
    rows that run: the tight size up to and with its last row, the wide one
    past it, for a count, an array of counts and a traced count alike."""
    tight, wide = moe.topk_buffer_sizes(*shape)
    assert (tight, wide) == sizes
    held = np.array([0, tight // 2, tight, tight + 1, wide, 3 * wide])
    want = np.where(held <= tight, tight, wide)
    assert np.array_equal(moe.topk_buffer_rows(*shape, held), want)
    assert [moe.topk_buffer_rows(*shape, int(n)) for n in held] \
        == want.tolist()
    assert np.array_equal(jax.jit(lambda n: moe.topk_buffer_rows(
        *shape, n))(jnp.asarray(held, jnp.int32)), want)


def test_the_optimizer_never_touches_the_bias():
    """adamw would decay the leaf (its gradient is 0): what the step leaves
    is the bias before it plus the balance update, nothing else."""
    cfg, params = SMALL, _params()
    old = np.asarray(params["layers"]["router_bias"])
    opt = optax.adamw(1e-1, weight_decay=0.5)
    new, _, _, stats = tfm.make_train_step(_mesh(), cfg, opt)(
        params, opt.init(params), *_tokens())
    counts = np.asarray(stats["expert_counts"])
    want = old + np.float32(1e-3) * np.sign(
        counts.mean(axis=1, keepdims=True) - counts).astype(np.float32)
    assert np.array_equal(np.asarray(new["layers"]["router_bias"]), want)


@pytest.mark.parametrize("held, first, share", [
    (0, 0, False), (8, 0, False), (2, 0, True), (4, 4, True)],
    ids=["whole-by-default", "whole", "share-of-2", "share-of-4"])
def test_a_share_takes_no_gradient_through_its_routing_weights(
        reference, model, held, first, share):
    """On a share of the experts the gradient through the routing weights
    is one chip's term of a sum over chips, and no term is applied alone:
    the routers' gradient is zero there, the layers below get none of it
    either, and every leaf's gradient is the plain reference's, given the
    same share. The whole layer's routers take theirs."""
    cfg = dataclasses.replace(SMALL, experts_held=held, first_expert=first)
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(tfm.make_spmd_loss(_mesh(), cfg)))(
            params, inputs, targets)
        want = model.from_reference(jax.jit(
            lambda w: reference.grads(w, inputs, targets, _kinds(cfg),
                                      first, top_k=cfg.moe_top_k))(
                model.to_reference(params)), params)
    assert bool(jnp.any(got["layers"]["router"] != 0)) == (not share)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        _close(g, w, 2e-4)


@pytest.mark.parametrize("mesh", [(2, 1, 1), (1, 1, 2), (2, 1, 2)],
                         ids=["data2", "tensor2", "data2-tensor2"])
def test_a_mesh_takes_the_same_step(stepped, mesh):
    """Over data the counts are every shard's; over tensor every expert's
    hidden dim is split and the shared and routed parts summed once."""
    params, (inputs, targets) = _params(), _tokens()
    m = _mesh(*mesh)
    with jax.default_matmul_precision("highest"):
        new, loss, stats, _ = _sgd_step(
            SMALL, tfm.shard_params(params, m, SMALL), inputs, targets, m)
    assert abs(float(loss) - float(stepped[1])) <= TIGHT * float(stepped[1])
    assert np.array_equal(stats["expert_counts"],
                          stepped[2]["expert_counts"])
    for got, want in zip(jax.tree_util.tree_leaves(new),
                         jax.tree_util.tree_leaves(stepped[0])):
        _close(np.asarray(got), np.asarray(want), 2e-4)


# -- each piece alone -------------------------------------------------------

def _qkv(heads=4, kv_heads=2, t=32, d=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (2, t, heads, d)),
            jax.random.normal(ks[1], (2, t, kv_heads, d)),
            jax.random.normal(ks[2], (2, t, kv_heads, d)))


def _by_hand(q, k, v, window):
    """Materialized attention, K and V repeated, the band written out."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    t = q.shape[1]
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("window", [0, 1, 8, 32, 40])
@pytest.mark.parametrize("layout", ["bthk", "bhtk"])
def test_the_window_mask_against_the_materialised_band(window, layout):
    q, k, v = _qkv(kv_heads=4)
    swap = (lambda x: x.transpose(0, 2, 1, 3)) if layout == "bhtk" \
        else (lambda x: x)
    got = swap(fa.flash_attention_local(swap(q), swap(k), swap(v),
                                        layout=layout, window=window))
    _close(got, _by_hand(q, k, v, window), 1e-5)
    if window >= 32 or not window:      # a window no shorter than the row
        _close(got, local_attention(q, k, v), 1e-6)


@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_grouped_kv_against_repeated_k_and_v(window, kv_heads):
    q, k, v = _qkv(kv_heads=kv_heads)
    group = 4 // kv_heads
    want = local_attention(q, jnp.repeat(k, group, axis=2),
                           jnp.repeat(v, group, axis=2), window=window)
    _close(local_attention(q, k, v, window=window), want, 1e-6)
    _close(_by_hand(q, k, v, window), want, 1e-5)


def test_the_kernel_choice_for_a_window_and_grouped_heads(monkeypatch):
    """On the TPU splash takes the banded, grouped shape at the causal
    blocks (``tools/attn_sweep.py``'s choice); what splash refuses of a
    window or of grouped heads is materialized, never the stock flash
    kernel, which knows neither."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    found = fa.attention_kernel((1, 32, 8192, 128), (1, 4, 8192, 128),
                                under_remat=True, window=2048)
    assert found == {"kernel": "splash", "block_q": "1024",
                     "block_kv": "1024", "fused_bwd": "1", "window": "2048",
                     "head_size": "128", "v_head_size": "128"}
    assert fa.splash_geometry(8192, 128, True, True, 2048) \
        == fa.splash_geometry(8192, 128, True, True)
    assert fa._select_kernel((1, 32, 640, 128), (1, 4, 640, 128)) \
        == "materialized"
    assert fa._select_kernel((1, 4, 640, 128), (1, 4, 640, 128), 128) \
        == "materialized"
    assert fa._select_kernel((1, 4, 640, 128), (1, 4, 640, 128)) == "flash"


def _one_layer(**changes):
    """A one-layer configuration around ``_attn_mix`` and its leaves."""
    cfg = dataclasses.replace(SMALL, n_layers=1, layers=(), **changes)
    lp = {k: v[0] for k, v in tfm.init_params(
        jax.random.PRNGKey(3), cfg)["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    return cfg, lp, x


@pytest.mark.parametrize("window, rotate", [(8, True), (0, False),
                                            (0, True), (8, False)])
def test_the_attention_sublayer_against_the_reference(reference, window,
                                                      rotate):
    """q/k norm, the rotation or none, the band, the grouped heads and the
    gate in one call, against the reference's attention."""
    cfg, lp, x = _one_layer()
    lp["q_norm"], lp["k_norm"] = lp["q_norm"] * 1.3, lp["k_norm"] * 0.7
    rope = tfm._rope_tables(cfg, 32, None) if rotate else None
    with jax.default_matmul_precision("highest"):
        got = tfm._attn_mix(x, lp, cfg=cfg, rope=rope, window=window)
        want = reference.attention(x, lp, window, rotate)
    _close(got, want)


def test_qk_norm_takes_the_scale_of_the_projections_away():
    cfg, lp, x = _one_layer()
    big = {**lp, "wq": lp["wq"] * 3.0, "wk": lp["wk"] * 0.25}
    _close(tfm._attn_mix(x, big, cfg=cfg), tfm._attn_mix(x, lp, cfg=cfg),
           1e-4)
    plain = dataclasses.replace(cfg, qk_norm=False)
    assert float(jnp.max(jnp.abs(tfm._attn_mix(x, big, cfg=plain)
                                 - tfm._attn_mix(x, lp, cfg=plain)))) > 1e-2


def test_a_closed_gate_halves_the_attention():
    """sigmoid(0) = 1/2, head by head and channel by channel."""
    cfg, lp, x = _one_layer()
    shut = {**lp, "wgate": jnp.zeros_like(lp["wgate"])}
    ungated = dataclasses.replace(cfg, attn_gate=False)
    _close(tfm._attn_mix(x, shut, cfg=cfg),
           0.5 * tfm._attn_mix(x, lp, cfg=ungated), 1e-6)


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("norm, scale", [(True, 2.826), (True, 1.0),
                                         (False, 2.826)])
def test_sigmoid_top_k_by_hand(k, norm, scale):
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    route = moe.topk_route(x, router, jnp.zeros(8), k, scale, norm)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router)))
    want = np.argsort(-s, axis=1)[:, :k]
    assert np.array_equal(np.sort(route.expert, 1), np.sort(want, 1))
    picked = np.take_along_axis(s, np.asarray(route.expert), axis=1)
    weight = scale * picked / (picked.sum(1, keepdims=True) if norm else 1)
    np.testing.assert_allclose(route.weight, weight, rtol=1e-5)
    assert np.array_equal(route.counts, np.bincount(
        np.asarray(route.expert).ravel(), minlength=8))


def test_the_bias_enters_the_choice_and_not_the_weight_nor_the_gradient():
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros(8).at[5].set(10.0)
    route = moe.topk_route(x, router, bias, 2, 2.826, True)
    assert bool(jnp.all(jnp.any(route.expert == 5, axis=1)))
    s = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(s, route.expert, axis=1)
    np.testing.assert_allclose(
        route.weight, 2.826 * picked / picked.sum(1, keepdims=True),
        rtol=1e-5)

    def total(bias, router):
        return jnp.sum(moe.topk_route(x, router, bias, 2, 2.826,
                                      True).weight ** 2)
    g_bias, g_router = jax.grad(total, (0, 1))(bias, router)
    assert not np.asarray(g_bias).any() and np.asarray(g_router).any()


def _indexed(fn, *args, what=("gather", "scatter")):
    """The ``scatter`` and ``gather`` operations (``what``: either alone)
    of ``fn``'s lowered text, ``(rows, elements)``: those that move whole
    rows of a matrix and those that move single elements."""
    text = jax.jit(fn).lower(*args).as_text()
    rows = elements = 0
    for line in text.split("\n"):
        if '"stablehlo.%s"' % what[0] not in line and \
                '"stablehlo.%s"' % what[-1] not in line:
            continue
        if '"stablehlo.gather"' in line:
            width = re.search(r"slice_sizes = array<i64: ([\d, ]+)>",
                              line).group(1).split(", ")[-1]
            rows, elements = (rows + (width != "1"),
                              elements + (width == "1"))
        elif '"stablehlo.scatter"' in line:
            whole = "update_window_dims = [1]" in line
            rows, elements = rows + whole, elements + (not whole)
    return rows, elements


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("norm, scale", [(True, 2.826), (True, 1.0),
                                         (False, 2.826)])
def test_the_router_by_comparison_is_the_router_by_index(k, norm, scale):
    """The chosen scores and the counts as ``take_along_axis`` and a
    scatter-add of ones give them (``topk_route`` until ISSUE 33), to the
    bit; and neither the route nor its gradient with respect to the router
    indexes anything."""
    x = jax.random.normal(jax.random.PRNGKey(0), (24, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (8,))

    def by_index(x, router):
        s = jax.nn.sigmoid(jnp.dot(x, router,
                                   precision=jax.lax.Precision.HIGHEST))
        _, expert = jax.lax.top_k(s + bias, k)
        w = jnp.take_along_axis(s, expert, axis=-1)
        if norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return expert, w * scale, jnp.zeros((8,), jnp.int32).at[
            expert.reshape(-1)].add(1)

    def route(x, router):
        return moe.topk_route(x, router, bias, k, scale, norm)

    def total(fn):
        return lambda router: jnp.sum(fn(x, router)[1] ** 2)

    for got, want in zip(jax.jit(route)(x, router),
                         jax.jit(by_index)(x, router)):
        assert got.dtype == want.dtype and jnp.array_equal(got, want)
    assert jnp.array_equal(jax.grad(total(route))(router),
                           jax.grad(total(by_index))(router))
    assert _indexed(by_index, x, router) == (0, 2)
    assert _indexed(route, x, router) == (0, 0)
    assert _indexed(jax.grad(total(route)), router) == (0, 0)


@pytest.mark.parametrize("counts, want", [
    ([4, 4, 4, 4], [0, 0, 0, 0]), ([8, 4, 2, 2], [-1, 0, 1, 1]),
    ([0, 0, 0, 16], [1, 1, 1, -1])])
def test_the_bias_update(counts, want):
    got = moe.router_bias_update(jnp.full((4,), 0.25), jnp.array(counts),
                                 1e-3)
    np.testing.assert_allclose(got, 0.25 + 1e-3 * np.array(want), rtol=1e-6)


def _dense_experts(x, route, wg, wu, wd, first):
    """Every held expert over every token, weighted where chosen."""
    out = jnp.zeros_like(x)
    for e in range(wg.shape[0]):
        w_e = jnp.sum(jnp.where(route.expert == first + e, route.weight, 0.0),
                      axis=1)
        y = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
        out = out + w_e[:, None] * y
    return out


def _expert_weights(held, d=16, f=8, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (held, d, f)) * 0.3,
            jax.random.normal(ks[1], (held, d, f)) * 0.3,
            jax.random.normal(ks[2], (held, f, d)) * 0.3)


FORMS = ["gather", "chunks"]


@pytest.fixture
def form(request, monkeypatch):
    """The form of the row sums, whatever the shape would have it be."""
    monkeypatch.setattr(moe, "row_sum_form", lambda *shape: request.param)
    return request.param


# 8 experts, 2 a token: (the experts the bias forces every token onto, the
# first held, how many, and at 2,048 tokens the size of the buffer the
# forced routing runs and the buffers of it it fills): under the tight size,
# between the two, past the wide one
ROUTINGS = pytest.mark.parametrize("forced, first, held, size, buffers", [
    (None, 0, 8, "wide", 1), (None, 2, 4, "tight", 1),
    ((0, 1), 0, 2, "wide", 2), ((6, 7), 0, 2, "tight", 1),
    ((3, 5), 2, 2, "wide", 1), ((2, 3), 2, 2, "wide", 2),
    ((0, 1), 0, 1, "wide", 2), ((2, 7), 2, 4, "tight", 1),
    ((2, 3), 2, 4, "wide", 1)],
    ids=["even-all", "even-share", "all-to-held", "all-to-absent",
         "all-to-one-of-share", "all-to-share", "all-to-one-held",
         "half-to-one-of-four", "all-to-two-of-four"])
SIZES = pytest.mark.parametrize("t", [256, 2048],
                                ids=["one-buffer", "buffers"])


def _forced_bias(forced):
    return jnp.zeros(8) if forced is None else \
        jnp.zeros(8).at[jnp.array(forced)].set(10.0)


@SIZES
@ROUTINGS
def test_the_places_are_the_sorts(t, forced, first, held, size, buffers):
    """``topk_places`` against ``topk_order``'s own sort, to the row: the
    row a held choice is placed at holds its token and its weight, the
    places are the first rows of the sorted order, each once, and a choice
    of an absent expert has none."""
    route = moe.topk_route(
        jax.random.normal(jax.random.PRNGKey(0), (t, 16)),
        jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
        _forced_bias(forced), 2, 2.826, True)
    token, weight, sizes = moe.topk_order(route, first, held)
    places = moe.topk_places(route, first, held)
    assert int(places.live) == int(sizes.sum())
    place, mine = np.asarray(places.place).T, np.asarray(places.mine).T
    local = np.asarray(route.expert) - first
    assert np.array_equal(mine, (local >= 0) & (local < held))
    assert not place[~mine].any()
    assert np.array_equal(np.sort(place[mine]), np.arange(int(sizes.sum())))
    assert np.array_equal(np.asarray(token)[place[mine]],
                          np.nonzero(mine)[0])
    assert np.array_equal(np.asarray(weight)[place[mine]],
                          np.asarray(route.weight)[mine])


@pytest.mark.parametrize("form", FORMS, indirect=True)
@SIZES
@ROUTINGS
def test_no_assignment_is_dropped(t, forced, first, held, size, buffers,
                                  form):
    """Whatever the router does, with every token sent to the same two
    experts too, and in either form of the row sums: the held experts' part
    is the dense loop's, to the last token, and so is its gradient. At
    2,048 tokens a share runs the tight buffer where its held assignments
    fit 1.25 times their even part and the wide one where they do not, and
    one that draws more than 2.5 times its even part fills the wide buffer
    more than once and the further buffers run (``size``, ``buffers``: what
    the forced routing runs there); at 256 tokens the two sizes are one
    buffer that holds every assignment."""
    k = 2
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 16))
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = _forced_bias(forced)
    wg, wu, wd = _expert_weights(held)
    tight, wide = moe.topk_buffer_sizes(t, k, 8, held)
    assert all(rows % 512 == 0 or rows == t * k for rows in (tight, wide))
    assert (tight < wide) == (t == 2048 and held < 8)

    def run(layer, x, router, wg, wu, wd):
        route = moe.topk_route(x, router, bias, k, 2.826, True)
        return layer(x, route, wg, wu, wd), route

    def held_fn(x, route, wg, wu, wd):
        return moe.topk_moe_held(x, route, wg, wu, wd, first)

    def dense_fn(x, route, wg, wu, wd):
        return _dense_experts(x, route, wg, wu, wd, first)

    args = (x, router, wg, wu, wd)
    got, route = jax.jit(lambda *a: run(held_fn, *a))(*args)
    want, _ = run(dense_fn, *args)
    assert int(route.counts.sum()) == t * k
    if forced is not None:
        assert [int(route.counts[e]) for e in forced] == [t, t]
    n_held = int(route.counts[first:first + held].sum())
    rows = moe.topk_buffer_rows(t, k, 8, held, n_held)
    if t == 2048:
        assert rows == {"tight": tight, "wide": wide}[size]
    assert max(-(-n_held // rows), 1) == (buffers if t == 2048 else 1)
    _close(got, want, 1e-5)
    grads = [jax.jit(jax.grad(
        lambda *a: jnp.sum(run(fn, *a)[0] ** 2), (0, 1, 2, 3, 4)))(*args)
        for fn in (held_fn, dense_fn)]
    for g, w in zip(*grads):
        _close(g, w, 1e-4)


@pytest.mark.parametrize("form", FORMS, indirect=True)
@pytest.mark.parametrize("forced, size, buffers", [
    (None, "tight", 1), ((3, 5), "wide", 1), ((2, 3), "wide", 2)],
    ids=["under-tight", "between", "past-wide"])
def test_both_passes_run_the_buffer_the_loads_fit(monkeypatch, forced, size,
                                                  buffers, form):
    """Which buffers RUN, by the rows the grouped products are handed (a
    callback where they are called, so only a branch that is taken
    reports): the forward pass one tight buffer where the held assignments
    fit it, else the wide ones they fill; its gradient the same buffers
    twice, the forward's and the backward's own (it runs a buffer again):
    the backward pass takes the branch the forward took."""
    t, k, first, held = 2048, 2, 2, 2
    sizes = dict(zip(("tight", "wide"), moe.topk_buffer_sizes(t, k, 8, held)))
    assert sizes == {"tight": 1536, "wide": 2560}
    ran, swiglu = [], moe.grouped_swiglu

    def reporting(rows, *rest):
        jax.debug.callback(lambda: ran.append(rows.shape[0]))
        return swiglu(rows, *rest)

    monkeypatch.setattr(moe, "grouped_swiglu", reporting)
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 16))
    route = moe.topk_route(
        x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
        _forced_bias(forced), k, 2.826, True)
    args = (x,) + _expert_weights(held)
    layer = lambda *a: moe.topk_moe_held(  # noqa: E731
        a[0], route, *a[1:], first)
    jax.block_until_ready(jax.jit(layer)(*args))
    jax.effects_barrier()
    assert ran == [sizes[size]] * buffers
    del ran[:]
    jax.block_until_ready(jax.jit(jax.grad(
        lambda *a: jnp.sum(layer(*a) ** 2), (0, 1, 2, 3)))(*args))
    jax.effects_barrier()
    assert ran == [sizes[size]] * (2 * buffers)


@pytest.mark.parametrize("t, held, conditionals", [
    (2048, 8, 0), (2048, 4, 1), (256, 4, 0)],
    ids=["every-expert-held", "a-share", "a-share-in-one-buffer"])
def test_a_conditional_only_where_the_two_sizes_differ(t, held, conditionals):
    """The lowered text of the layer and of its gradient: one ``case`` a
    pass where a share's tight buffer is smaller than its wide one; none
    with every expert held, nor where both sizes are all ``T x k``
    assignments: the program is the one loop over buffers it was."""
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 16))
    route = moe.topk_route(
        x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)), jnp.zeros(8),
        2, 2.826, True)
    args = (x,) + _expert_weights(held)
    tight, wide = moe.topk_buffer_sizes(t, 2, 8, held)
    assert (tight < wide) == bool(conditionals)
    layer = lambda *a: moe.topk_moe_held(a[0], route, *a[1:], 0)  # noqa: E731
    for fn, passes in ((layer, 1), (jax.grad(
            lambda *a: jnp.sum(layer(*a) ** 2), (0, 1, 2, 3)), 2)):
        text = jax.jit(fn).lower(*args).as_text()
        assert text.count("stablehlo.case") == conditionals * passes
        assert "stablehlo.while" in text


def _routed_by_hand(t, n_rows):
    """A route of 2 choices a token over 8 experts, 0-2 held, with ``n_rows
    + 1`` held assignments, in which ONE token's two rows are the last of
    the first ``n_rows`` and the one after them: expert 0 takes the first
    ``n_rows - t`` tokens, expert 1 every token, expert 2 the last token
    alone (the others' second choice is the absent expert 5)."""
    assert t <= n_rows < 2 * t
    at = np.arange(t)
    expert = np.stack([np.where(at < n_rows - t, 0, 1),
                       np.where(at < n_rows - t, 1, 5)], axis=1)
    expert[-1] = (1, 2)
    weight = jax.random.uniform(jax.random.PRNGKey(3), (t, 2), jnp.float32,
                                0.2, 1.0)
    return moe.TopKRoute(jnp.asarray(expert, jnp.int32), weight, jnp.asarray(
        np.bincount(expert.ravel(), minlength=8), jnp.int32))


@pytest.mark.parametrize("form", FORMS, indirect=True)
@pytest.mark.parametrize("case", ["eight-rows-a-token",
                                  "a-token-on-the-boundary",
                                  "one-past-the-tight-buffer",
                                  "the-tight-buffer-full"])
def test_no_assignment_is_dropped_at_the_edges(case, form):
    """Every token with 8 live rows (top 8 of 8 experts, all held); a
    token whose rows are the last of one wide buffer and the first of the
    next, so that its sum is made of two buffers' (and two chunks') parts;
    one assignment more than the tight buffer holds (the wide one runs),
    and exactly as many as it holds (it runs, full to its last row): the
    dense loop's output and gradient, the routing weights' too, in either
    form of the row sums."""
    if case == "eight-rows-a-token":
        x = jax.random.normal(jax.random.PRNGKey(0), (256, 16))
        route = moe.topk_route(
            x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)),
            jnp.zeros(8), 8, 2.826, True)
        held = 8
        assert moe.topk_buffer_sizes(256, 8, 8, held) == (256 * 8,) * 2
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (4096, 16))
        held = 3
        tight, wide = moe.topk_buffer_sizes(4096, 2, 8, held)
        assert (tight, wide) == (4096, 7680)
        n_rows = wide if case == "a-token-on-the-boundary" else tight
        route = _routed_by_hand(4096, n_rows)
        if case == "the-tight-buffer-full":
            # (the last token's second choice absent too: one row fewer)
            route = route._replace(
                expert=route.expert.at[-1, 1].set(5),
                counts=route.counts.at[2].add(-1).at[5].add(1))
        token, _, sizes = moe.topk_order(route, 0, held)
        n_held = int(sizes.sum())
        assert n_held == n_rows + (case != "the-tight-buffer-full")
        assert moe.topk_buffer_rows(4096, 2, 8, held, n_held) == (
            tight if case == "the-tight-buffer-full" else wide)
        assert token[n_rows - 1] == 4095
        assert n_held == n_rows or token[n_rows] == 4095
    wg, wu, wd = _expert_weights(held)

    def total(layer):
        return lambda x, weight, *w: jnp.sum(layer(
            x, route._replace(weight=weight), *w, 0) ** 2)

    args = (x, route.weight, wg, wu, wd)
    _close(jax.jit(lambda *a: moe.topk_moe_held(a[0], route, *a[1:], 0))(
        x, wg, wu, wd), _dense_experts(x, route, wg, wu, wd, 0), 1e-5)
    for g, w in zip(*(jax.jit(jax.grad(total(layer), (0, 1, 2, 3, 4)))(*args)
                      for layer in (moe.topk_moe_held, _dense_experts))):
        _close(g, w, 1e-4)


@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_the_combine_accumulates_in_float32_and_rounds_once(form):
    """bfloat16 tokens and experts, every token with 8 live rows: what the
    dense loop gives when it adds its 8 terms in float32 and rounds once,
    to one bfloat16 ulp; added in bfloat16, a third of the sums differ."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 16))
    route = moe.topk_route(
        x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)), jnp.zeros(8),
        8, 2.826, True)
    x, wg, wu, wd = (a.astype(jnp.bfloat16)
                     for a in (x,) + _expert_weights(8))
    got = jax.jit(lambda *a: moe.topk_moe_held(a[0], route, *a[1:], 0))(
        x, wg, wu, wd)
    in_float32 = _dense_experts(x, route, wg, wu, wd, 0)
    assert got.dtype == jnp.bfloat16 and in_float32.dtype == jnp.float32
    want = np.asarray(in_float32.astype(jnp.bfloat16), np.float32)
    assert np.all(np.abs(np.asarray(got, np.float32) - want)
                  <= np.abs(want) * 2.0 ** -7)
    in_bfloat16 = jnp.zeros_like(x)
    for e in range(8):
        one = _dense_experts(x, route._replace(expert=jnp.where(
            route.expert == e, e, 8)), wg, wu, wd, 0)
        in_bfloat16 = in_bfloat16 + one.astype(jnp.bfloat16)
    assert np.mean(np.asarray(in_bfloat16, np.float32) != want) > 0.3


@pytest.mark.parametrize("form", FORMS, indirect=True)
def test_the_held_experts_index_rows_and_nothing_else(form):
    """The ``(row, element)`` scatters and gathers of ``topk_moe_held``'s
    lowered text; no single element is gathered or scattered in either
    form: the sort carries the tokens and the weights. As chunks, forward
    2: the dispatch's row gather and the combine's row sum. Its gradient as
    a share takes it (the route a constant) 6: the forward's two, the
    buffer run again in the backward pass (its sum is dead code there), and
    the two transposes, a gather for the sum and a sum for the gather. With
    a gradient through the routing weights (every expert held) ONE scatter
    of elements more, the sort's own transpose. As a gather NOTHING is
    scattered on either pass, rows or elements: every sum is a row gather a
    choice, two here (forward 3: the dispatch's and the combine's two; the
    gradient 11: the forward's three, the buffer run again with its
    combine, the combine's three, ``g[token]`` for the rows and ``y`` at
    the places for the weights (dead code where they are constants), and
    the dispatch's two), and the weights take their gradient where the
    choices are, not through the sort."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 16))
    route = moe.topk_route(
        x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)), jnp.zeros(8),
        2, 2.826, True)
    wg, wu, wd = _expert_weights(8)

    def held(x, weight, wg, wu, wd):
        return moe.topk_moe_held(x, route._replace(weight=weight), wg, wu,
                                 wd, 0)

    def total(*args):
        return jnp.sum(held(*args) ** 2)

    args = (x, route.weight, wg, wu, wd)
    programs = (held, jax.grad(total, (0, 2, 3, 4)),
                jax.grad(total, (0, 1, 2, 3, 4)))
    assert [_indexed(fn, *args) for fn in programs] == {
        "chunks": [(2, 0), (6, 0), (6, 1)],
        "gather": [(3, 0), (11, 0), (11, 0)]}[form]
    if form == "gather":
        assert [_indexed(fn, *args, what=("scatter",))
                for fn in programs] == [(0, 0)] * 3


@pytest.mark.parametrize("dtype, out, grad", [
    (jnp.float32, 1e-6, 1e-6), (jnp.bfloat16, 2.0 ** -7, 4.6e-3)],
    ids=["float32", "bfloat16"])
def test_the_gather_form_against_the_chunked_form(monkeypatch, dtype, out,
                                                  grad):
    """One layer, the same inputs, the two forms of its row sums: in
    float32 they differ in the order of a token's at most k additions
    (1e-6 of the largest value); in bfloat16 the combine is the same
    float32 sum rounded once, and the dispatch's backward pass, which the
    chunks add in bfloat16 and the gather in float32, is within the 4.6e-3
    the chip read between the forms of the sum (PERF.md section 6, PR 33)."""
    t, k, first, held = 2048, 4, 2, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 16))
    route = moe.topk_route(
        x, jax.random.normal(jax.random.PRNGKey(1), (16, 8)), jnp.zeros(8),
        k, 2.826, True)
    args = tuple(a.astype(dtype) for a in (x,) + _expert_weights(held))

    def both(form):
        monkeypatch.setattr(moe, "row_sum_form", lambda *shape: form)
        layer = lambda *a: moe.topk_moe_held(  # noqa: E731
            a[0], route, *a[1:], first)
        return jax.jit(layer)(*args), jax.jit(jax.grad(
            lambda *a: jnp.sum(layer(*a).astype(jnp.float32) ** 2),
            (0, 1, 2, 3)))(*args)

    (y_g, d_g), (y_c, d_c) = both("gather"), both("chunks")
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    _close(f32(y_g), f32(y_c), out)
    for g, c in zip(d_g, d_c):
        _close(f32(g), f32(c), grad)


@pytest.mark.parametrize("shape, form, rows_over_live, one_more", [
    # the conv/attention cell: 2 x 8,192 tokens, 4 of 32, 8 held: the tight
    # buffer of 20,480 rows is one slab of 80 MiB; past it, in the wide
    # buffer of 40,960 rows (two such slabs), every choice is gathered once
    # more, and again for a second buffer
    ((16384, 4, 32, 8), "gather", 4.0, {20480: 1, 20481: 2, 40961: 3}),
    # the sparse-expert cell: 8,192 tokens, 8 of 128, 8 held: 4 chunks; the
    # tight buffer of 5,120 rows is 5 chunks, the wide one goes on
    ((8192, 8, 128, 8), "chunks", 1.0, {4097: 5120 / 65536,
                                        5121: 6144 / 65536}),
    # a whole layer of either: 256 MiB of rows, three slabs
    ((16384, 4, 32, 32), "gather", 3.0, {}),
    ((8192, 8, 128, 128), "gather", 3.0, {}),
    # under a chunk of live rows (the rehearsals' programs): one chunk
    ((128, 2, 8, 4), "chunks", 2.0, {129: 1.0})],
    ids=["conv-attention-cell", "sparse-expert-cell", "whole-4-of-32",
         "whole-8-of-128", "under-a-chunk"])
def test_the_form_of_the_row_sums_follows_the_share_held(
        shape, form, rows_over_live, one_more):
    """One function of ``(T, k, E, held)``; and the rows a sum visits over
    the live ones at an even router's load, rows of 2,048 in bfloat16
    (``one_more``: live rows to the rows visited, in units of ``T x k``)."""
    t, k, n_experts, held = shape
    assert moe.row_sum_form(*shape) == form
    even = t * k * held // n_experts
    assert moe.row_sum_rows(*shape, even, 4096) / even == rows_over_live
    for live, visited in one_more.items():
        assert moe.row_sum_rows(*shape, live, 4096) == visited * t * k


# -- the share test (model-configs guide, section 4) -----------------------

def _expert_layer(cfg, lp, x):
    out, routes = tfm._expert_ffn(x, lp, cfg, None)
    return out, routes


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips, one expert of eight each: their routed parts, with the
    shared expert (which every chip computes alike) counted once, are the
    uncut layer's output; every share counts the same assignments."""
    whole = dataclasses.replace(SMALL, experts_held=8)
    lp = {k: v[1] for k, v in _params(whole)["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, routes = _expert_layer(whole, lp, x)
        shared = tfm._dense_ffn(x, lp, whole, None, "shared_")
        parts = []
        for i in range(8):
            share = dataclasses.replace(SMALL, experts_held=1, first_expert=i)
            mine = {**lp, **{k: lp[k][i:i + 1] for k in
                             ("ewg", "ewu", "ewd")}}
            out, r = _expert_layer(share, mine, x)
            assert jnp.array_equal(r.counts, routes.counts)
            assert jnp.array_equal(r.expert, routes.expert)
            parts.append(out - shared)
    _close(sum(parts) + shared, want)
    assert float(jnp.max(jnp.abs(parts[0]))) > 0


def test_a_share_draws_the_experts_the_whole_layer_draws():
    whole = tfm.init_params(jax.random.PRNGKey(0),
                            dataclasses.replace(SMALL, experts_held=8))
    share = tfm.init_params(jax.random.PRNGKey(0), dataclasses.replace(
        SMALL, experts_held=2, first_expert=4))
    for k in ("ewg", "ewu", "ewd"):
        assert jnp.array_equal(share["layers"][k],
                               whole["layers"][k][:, 4:6])
    assert jnp.array_equal(share["layers"]["router"],
                           whole["layers"]["router"])


def test_the_eight_vocabulary_slices_concatenate_to_the_whole_head():
    params, (inputs, _) = _params(), _tokens()
    logits, _ = tfm.forward_routes(params, inputs, SMALL)
    slices = []
    cfg = dataclasses.replace(SMALL, vocab_size=12)
    sliced = jax.jit(lambda p: tfm.forward_routes(p, inputs, cfg)[0])
    for i in range(8):
        # (the state before the head does not depend on the head's rows)
        slices.append(sliced({**params, "lm_head": params["lm_head"][
            12 * i:12 * (i + 1)]}))
    _close(jnp.concatenate(slices, axis=-1), logits, 1e-6)


# -- the two stacks, and what is refused by name ---------------------------

A, B = K(8, True, True), K(0, False, True)
DENSE = K(8, True, False)


@pytest.mark.parametrize("kinds, n_lead", [
    ((DENSE, A, A, B, A), 1),
    ((DENSE, DENSE) + (A, B, A, A) * 7 + (A, B), 2),    # the uncut list
    ((A, A, A, A), 0),
    ((DENSE, DENSE), 2),
])
def test_the_leading_dense_layers_and_the_expert_layers(kinds, n_lead):
    cfg = dataclasses.replace(SMALL, n_layers=len(kinds), layers=kinds)
    assert sum(not kind.experts for kind in kinds) == n_lead
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert ("dense_layers" in shapes) == bool(n_lead)
    assert ("layers" in shapes) == (n_lead < len(kinds))
    if n_lead < len(kinds):
        assert shapes["layers"]["router"].shape[0] == len(kinds) - n_lead


@pytest.mark.parametrize("kinds", [
    (DENSE, B, A, A, B), (A, B), (DENSE, K(0, True, False), B, B)],
    ids=["full-first", "no-dense", "two-kinds-of-dense"])
def test_any_order_of_kinds_runs_against_the_reference(reference, model,
                                                       kinds):
    """The switch on a layer's kind, with the kinds in another order, with
    no dense layer, and with a switch in the dense stack too."""
    cfg = dataclasses.replace(SMALL, n_layers=len(kinds), layers=kinds)
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda p: tfm.lean_lm_loss(p, inputs, targets, cfg)))(params)
        want = jax.jit(jax.value_and_grad(lambda w: reference.loss(
            w, inputs, targets, _kinds(cfg), top_k=cfg.moe_top_k)))(
            model.to_reference(params))
    assert abs(float(got[0]) - float(want[0])) <= TIGHT * float(want[0])
    for g, w in zip(jax.tree_util.tree_leaves(got[1]),
                    jax.tree_util.tree_leaves(
                        model.from_reference(want[1], params))):
        _close(g, w, 2e-4)


@pytest.mark.parametrize("changes, words", [
    ({"layers": (DENSE, A, K(mixer="none", experts=None)), "n_layers": 3},
     "has no stack to live in"),
    ({"n_loops": 2}, "not with n_loops > 1 or use_moe"),
    ({"moe_top_k": 0}, "an expert layer needs"),
    ({"n_kv_heads": 3}, "must divide"),
    ({"n_layers": 4}, "layers names 5 layers"),
])
def test_a_configuration_that_cannot_run_is_refused_by_name(changes, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(SMALL, **changes)


@pytest.mark.parametrize("builder", ["make_pp_train_step",
                                     "make_moe_ep_train_step"])
def test_the_other_builders_refuse_a_layer_pattern_by_name(builder):
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    tied = dataclasses.replace(SMALL, tie_embeddings=True)
    with pytest.raises(ValueError, match="per-layer pattern"):
        if builder == "make_pp_train_step":
            tfm.make_pp_train_step(mesh, tied, optax.sgd(1.0), n_micro=2)
        else:
            class Engine:       # refused before the engine is asked
                pass
            tfm.make_moe_ep_train_step(Engine(), tied, optax.sgd(1.0))


def test_sequence_parallel_attention_refuses_windows_and_grouped_heads():
    params, (inputs, targets) = _params(), _tokens()
    m = _mesh(1, 2, 1)
    with pytest.raises(ValueError, match="know no window and no grouped"):
        tfm.make_spmd_loss(m, SMALL)(tfm.shard_params(params, m, SMALL),
                                     inputs, targets)


# -- what the new fields leave alone ---------------------------------------

# sha256 of make_train_step's lowered text at the commit before the fields
# existed (e2bf786), rehearsal widths, adamw(3e-4): on a mesh of one, and
# over data=4 (a row a chip), where the gradients are summed inside the
# backward scan (_sum_in_backward) and after it: the four-chip cell's path
ACCEPTED = {
    "cerebras-gpt-1.3b": (
        dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=256,
             max_seq=128, dtype=jnp.bfloat16, attention="flash"),
        {1: "83678755a769c9ab10a82a6858e39d8d7312c731e4af0e7fa0255e4c9a501b6d",
         4: "baffd4b4a0c8b1b20c844a7700d8562cecdd80268212a1dfa870e76fbc9e02f9"}),
    "ouro-2.6b": (
        dict(vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=96,
             max_seq=128, dtype=jnp.bfloat16, attention="flash",
             remat="block", positions="rope", rope_theta=1e6, ffn="swiglu",
             norm="sandwich", norm_eps=1e-6, tie_embeddings=False,
             n_loops=4),
        {1: "f3636af92be62abcf7d5b9f3277ede9e83b4f7d6aa08dcdaebdafe2404624581",
         4: "da17fb7538b850cf1cb18c6508e46bc431d4c847360531c3df37837979285073"}),
}


@pytest.mark.parametrize("data", [1, 4])
@pytest.mark.parametrize("config", sorted(ACCEPTED))
def test_the_defaults_lower_the_accepted_cells_step_as_before(config, data):
    fields, digests = ACCEPTED[config]
    cfg = tfm.TransformerConfig(**fields)
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((data, cfg.max_seq), jnp.int32)
    text = tfm.make_train_step(_mesh(data), cfg, opt).lower(
        params, jax.eval_shape(opt.init, params), tok, tok).as_text()
    if data > 1:
        assert "all_reduce" in text or "all-reduce" in text
    assert hashlib.sha256(text.encode()).hexdigest() == digests[data]


# the sum of every weight's absolute value, at the same commit
WEIGHT_SUMS = {"cerebras-gpt-1.3b": 8773.095703125,
               "ouro-2.6b": 9234.390625}


@pytest.mark.parametrize("config", sorted(ACCEPTED))
def test_the_defaults_draw_the_accepted_cells_weights_as_before(config):
    """Every new leaf draws from keys of its own: a default configuration
    keeps its weights seed for seed (digests taken at e2bf786)."""
    cfg = tfm.TransformerConfig(**{**ACCEPTED[config][0],
                                   "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params["layers"]) <= {
        "ln1", "ln2", "wq", "wk", "wv", "wo", "w1", "w2", "wg", "wu", "wd",
        "ln1_post", "ln2_post"}
    got = float(sum(jnp.sum(jnp.abs(x)) for x in
                    jax.tree_util.tree_leaves(params)))
    assert got == pytest.approx(WEIGHT_SUMS[config], rel=1e-6)

