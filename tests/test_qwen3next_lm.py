"""The hybrid linear-attention sparse-expert decoder (``TransformerConfig``
with ``layers`` of ``LayerKind(mixer="gdn")`` and gated attention) through
``make_train_step`` against the plain float32 reference of the benchmark
(``benchmark/reference/qwen3-next-80b-a3b.py``, which runs the gated delta
rule token by token in the published layout and shares no code with the
program); the chunked rule alone against the recurrence; the share test of
the model-configs guide; the published layout both ways; the taps without a
bias; what refuses the new fields; the scopes and gauges; and what the new
fields leave alone.
"""

import dataclasses
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
from horovod_tpu.parallel import gated_delta, ssd

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "readers"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)

CONFIG, CELL = "qwen3-next-80b-a3b", "qwen3next-spmd-1chip-ep32share-8k"
K = tfm.LayerKind
GDN, ATTN = K(mixer="gdn", experts=True), K(experts=True)
# the cell's own period, G G G A, at tiny widths: 2 key heads over 4 value
# heads, attention 4 over 2 KV heads of 16 with a quarter rotated, 8
# experts (4 held by the share tests), top 2, a gated shared expert, two
# chunks of the rule a row
SMALL = tfm.TransformerConfig(
    vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_size=16,
    n_layers=4, max_seq=128, dtype=jnp.float32, attention="flash",
    positions="rope", rope_theta=1e7, rope_dim=4, ffn="swiglu", norm="pre",
    norm_eps=1e-6, zero_centred_norms=True, tie_embeddings=False,
    qk_norm=True, attn_gate=True, layers=(GDN, GDN, GDN, ATTN),
    conv_kernel=4, gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
    gdn_value_dim=8, n_experts=8, moe_top_k=2, d_ff_expert=16,
    n_shared_experts=1, d_ff_shared=16, shared_gate=True,
    route_score="softmax", route_eps=0.0, remat_barrier=True)
TIGHT = 2e-5        # float32 on both sides: the order of the sums differs
# A gradient through the chunked rule at the published decays: a head's
# log-decay reaches -40 a token (A_log up to log 16), so its cumulative sum
# over a chunk reaches -2,500 and ``exp(G_i - G_j)``, a difference of two
# float32 sums, carries 1e-4 of rounding that the token-by-token recurrence
# does not (at decays 20 times weaker every leaf agrees to 3e-5); read:
# 1.4e-3 of a leaf's length at most (the twelve numbers of the A_log leaf,
# one head's its worst), every other leaf under 5e-4
THROUGH_THE_RULE = 2e-3


@pytest.fixture(scope="module")
def reference():
    ref = files.reference_module(CONFIG)
    ref.KEY_HEADS = SMALL.gdn_key_heads
    ref.ROWS = 32           # four blocks of attention rows, through lax.map
    ref.SEGMENT = 32        # four recomputed pieces of the recurrence
    return ref


@pytest.fixture(scope="module")
def model():
    return files.config_module(CONFIG)


def _params(cfg=SMALL, seed=0):
    """Seeded weights with every norm's scale off its start (the zero-centred
    ones off 0, the gated norm's off 1) and the decays strong enough to
    show, so that a norm that is skipped or offset wrongly shows."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))

    def off(path, x):
        name = jax.tree_util.keystr(path)
        if re.search(r"ln1|ln2|ln_f|q_norm|k_norm", name):
            return 0.3 * jax.random.normal(next(keys), x.shape)
        if "gdn_norm" in name:
            return jax.random.uniform(next(keys), x.shape, x.dtype, 0.5, 1.5)
        return x
    return jax.tree_util.tree_map_with_path(off, params)


def _tokens(rows=2, seq=128, seed=0, vocab=96):
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


def _near(got, want):
    """What a gradient through the rule is held to (THROUGH_THE_RULE): its
    relative L2, and its worst element against the leaf's largest."""
    got, want = np.asarray(got), np.asarray(want)
    assert np.linalg.norm(got - want) <= THROUGH_THE_RULE * np.linalg.norm(
        want)
    _close(got, want, 4 * THROUGH_THE_RULE)


def _leaves(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


# -- the chunked rule against the recurrence --------------------------------

def _rule_operands(t, decay, seed=0, hk=2, hv=4, dk=16, dv=8, bsz=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(keys[0], (bsz, t, hk, dk)),
            jax.random.normal(keys[1], (bsz, t, hk, dk)),
            jax.random.normal(keys[2], (bsz, t, hv, dv)),
            -decay * jax.random.uniform(keys[3], (bsz, t, hv)),
            jax.random.uniform(keys[4], (bsz, t, hv)))


@pytest.mark.parametrize("t, decay", [
    (64, 0.05), (192, 0.05), (192, 4.0), (200, 0.5), (40, 1.0)],
    ids=["one-chunk", "three-chunks-weak-decay", "three-chunks-strong-decay",
         "padded-end", "shorter-than-a-chunk"])
def test_the_chunked_rule_against_the_recurrence(reference, t, decay):
    """Across chunk boundaries, with decays near 0 (the state carries over
    the whole row) and strong (a chunk forgets its start), and a row the
    chunk does not divide; the forward and every operand's gradient."""
    args = _rule_operands(t, decay)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, t, 4, 8))
    def both(rule):
        return jax.jit(lambda *a: (rule(*a), jax.grad(lambda *a: jnp.sum(
            rule(*a) * weights), range(5))(*a)))

    with jax.default_matmul_precision("highest"):
        got = both(gated_delta.gated_delta_chunked)(*args)
        want = both(reference.recurrence)(*args)
    _close(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        _close(g, w, 1e-4)


def test_the_inverse_is_exact_and_its_gradient_is_the_inverses():
    """64 rows, so all six factors count; the written backward against
    autodiff of a triangular solve."""
    strict = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 64, 64))
                      * 0.3, -1)
    eye = jnp.eye(64)
    with jax.default_matmul_precision("highest"):
        got = gated_delta.unit_lower_inverse(strict)
        _close(jnp.matmul(eye + strict, got), jnp.broadcast_to(eye, got.shape),
               1e-5)
        cot = jax.random.normal(jax.random.PRNGKey(1), strict.shape)
        g_got = jax.grad(lambda s: jnp.sum(
            gated_delta.unit_lower_inverse(s) * cot))(strict)
        g_want = jax.grad(lambda s: jnp.sum(jax.scipy.linalg.solve_triangular(
            eye + s, jnp.broadcast_to(eye, s.shape), lower=True,
            unit_diagonal=True) * cot))(strict)
    _close(jnp.tril(g_got, -1), jnp.tril(g_want, -1), 1e-4)


def test_the_rule_in_bfloat16_stays_near_the_float32_recurrence(reference):
    """The products' operands rounded to bfloat16, as in the training step:
    the chunked form at the rounded operands within a few thousandths of the
    float32 recurrence at the same operands (the cell's ``gdn_scan``)."""
    q, k, v, g, beta = _rule_operands(192, 0.5)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = gated_delta.gated_delta_chunked(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        want = reference.recurrence(*(x.astype(jnp.float32)
                                      for x in (q, k, v)), g, beta)
    err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
    assert 1e-4 < err < 2e-2, err


# -- the whole model against the reference ---------------------------------

@pytest.fixture(scope="module")
def wanted(reference, model):
    """The reference's logits, choices, loss and gradients for the seeded
    weights and tokens, in the program's tree."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)

    def loss(weights):
        logits, choices = reference.forward(weights, inputs, top_k=2,
                                            key_heads=2)
        return jnp.mean(reference.cross_entropy(logits, targets)), (
            logits, jnp.stack(choices))

    # (one program: op by op the float32 reference is slow)
    with jax.default_matmul_precision("highest"):
        (loss, (logits, choices)), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(weights)
    return {"logits": logits, "choices": choices, "loss": loss,
            "grads": model.from_reference(grads, SMALL)}


@pytest.fixture(scope="module")
def stepped():
    """One step of ``make_train_step`` under ``sgd(1.0)``: what it returns,
    and the gradient it applied (the parameters' change, negated)."""
    params, (inputs, targets) = _params(), _tokens()
    mesh = _mesh()
    with jax.default_matmul_precision("highest"):
        step = tfm.make_train_step(mesh, SMALL, optax.sgd(1.0))
        before = jax.tree_util.tree_map(np.asarray, params)
        new, _, loss, stats = step(tfm.shard_params(params, mesh, SMALL),
                                   optax.sgd(1.0).init(params), inputs,
                                   targets)
    return loss, stats, jax.tree_util.tree_map(
        lambda a, b: a - np.asarray(b), before, new)


def test_the_logits_and_the_choices_against_the_reference(wanted):
    params, (inputs, _) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        logits, routes = jax.jit(lambda p: tfm.forward_routes(
            p, inputs, SMALL))(params)
    # (each mixer alone agrees to 1e-6; through four layers, their norms and
    # the router's softmax a few tokens of the 256 read 1e-4 of the largest
    # logit: float32 sums in another order, amplified)
    _close(logits, wanted["logits"], 2e-4)
    assert np.array_equal(np.sort(routes.expert, -1),
                          np.sort(wanted["choices"], -1))


def test_the_loss_and_every_leafs_gradient_through_the_train_step(
        wanted, stepped):
    loss, stats, grads = stepped
    assert float(loss) == pytest.approx(float(wanted["loss"]), rel=1e-5)
    assert stats["expert_counts"].shape == (4, 8)
    got, want = _leaves(grads), _leaves(wanted["grads"])
    # the selection bias is no part of this model (rate 0): unmoved
    assert set(got) - set(want) == {
        "['gdn_layers']['router_bias']", "['layers']['router_bias']"}
    assert not any(np.any(got[k]) for k in set(got) - set(want))
    for leaf, g in want.items():
        assert float(jnp.max(jnp.abs(g))) > 0, leaf
        _near(got[leaf], g)


def test_in_bfloat16_each_mixer_stays_near_the_reference(
        reference, model):
    """The dtype of the cell, on the CPU: each mixer in bfloat16 (the
    rule's, the taps' and attention's products) lowers and runs here too,
    within a few hundredths of the float32 reference (the whole model is
    not: at these widths bfloat16 flips 6% of the tokens' choices of expert,
    and a delta-rule layer hands each on). The whole step in bfloat16 runs
    in ``tests/test_examples.py``."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.bfloat16)
    params = _params(cfg)
    weights = model.to_reference(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, 32))
    rope = tfm._rope_tables(cfg, 128, None)
    for (stack, row), lw in list(zip(tfm.layer_rows(cfg),
                                     weights["layers"]))[2:]:
        lp = {k: v[row] for k, v in params[stack].items()}
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda x, lw: reference.mixer(
                x, lw, key_heads=2))(x, lw)
        got = jax.jit(lambda x, lp, stack=stack: tfm._gdn_mix(
            x, lp, cfg=cfg) if stack == "gdn_layers" else tfm._attn_mix(
            x, lp, cfg=cfg, rope=rope))(x.astype(cfg.dtype), lp)
        err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                    / jnp.linalg.norm(want))
        assert 1e-4 < err < 3e-2, (stack, row, err)


# -- the share and the layout ------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer(reference, model):
    """Experts 0-3 and 4-7 of 8 (the cell: 0-15, ... 496-511 of 512), each
    program holding a half: the routed parts of the two shares, and the
    gated shared expert counted ONCE, add up to what the reference gives
    for the whole layer."""
    whole = dataclasses.replace(SMALL, layers=(GDN,), n_layers=1)
    full = tfm.init_params(jax.random.PRNGKey(4), whole)["gdn_layers"]
    lw = {k: v[0] for k, v in full.items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))
    with jax.default_matmul_precision("highest"):
        want, chosen = jax.jit(lambda x, lw: reference.expert_ffn(
            x, lw, 0, 2))(x, lw)
        shared = jax.jit(reference.shared)(x, lw)
        parts = []
        for first in (0, 4):
            cfg = dataclasses.replace(whole, experts_held=4,
                                      first_expert=first)
            share = tfm.init_params(jax.random.PRNGKey(4), cfg)["gdn_layers"]
            # a share draws the experts the whole layer draws
            assert np.array_equal(np.asarray(share["ewu"][0]),
                                  np.asarray(full["ewu"][0, first:first + 4]))
            out, routes = jax.jit(lambda x, lp, cfg=cfg: tfm._expert_ffn(
                x, lp, cfg, None))(x, {k: v[0] for k, v in share.items()})
            parts.append(out - shared)
            assert np.array_equal(np.sort(routes.expert, -1),
                                  np.sort(chosen, -1))
    assert float(jnp.max(jnp.abs(shared))) > 0.1 * float(
        jnp.max(jnp.abs(want)))
    _close(sum(parts) + shared, want, 1e-5)


def test_the_cells_share_against_the_reference_given_the_same_share(
        reference, model):
    """Two of eight experts held: program and reference leave out the same
    part, and on a share the routers take no gradient in either."""
    cfg = dataclasses.replace(SMALL, experts_held=2, max_seq=64,
                              layers=(GDN, ATTN), n_layers=2)
    params, (inputs, targets) = _params(cfg), _tokens(seq=64)
    with jax.default_matmul_precision("highest"):
        want = model.from_reference(jax.jit(lambda w: reference.grads(
            w, inputs, targets, 0, jax.checkpoint, 2, key_heads=2))(
            model.to_reference(params, cfg)), cfg)
        got = jax.jit(jax.grad(lambda p: tfm.lean_lm_loss(
            p, inputs, targets, cfg)))(params)
    for leaf, g in _leaves(want).items():
        if leaf.endswith("['router']"):
            assert float(jnp.max(jnp.abs(g))) == 0.0
            assert float(jnp.max(jnp.abs(_leaves(got)[leaf]))) == 0.0
        else:
            _near(_leaves(got)[leaf], g)


def test_the_published_layout_both_ways(model):
    """The permutations of ``gdn_qkvz``, ``gdn_ba`` and of q beside its gate
    are inverse to each other, and they put each column where the published
    per-head grouping has it."""
    params = _params()
    back = model.from_reference(model.to_reference(params, SMALL), SMALL)
    for leaf, x in _leaves(back).items():
        assert np.array_equal(np.asarray(x), np.asarray(_leaves(params)[leaf]))
    lw = model.to_reference(params, SMALL)["layers"][0]
    prog = params["gdn_layers"]
    # key head 1's q (8 columns at 8-15 of the program) opens its group of
    # 8 + 8 + 2 x 8 + 2 x 8 = 48 published columns; value head 3's z (the
    # program's last 8) closes it
    qkvz = np.asarray(lw["in_proj_qkvz"])
    assert np.array_equal(qkvz[:, 48:56], prog["gdn_qkvz"][0][:, 8:16])
    assert np.array_equal(qkvz[:, -8:], prog["gdn_qkvz"][0][:, -8:])
    # value head 2's v: the program's columns 32 + 16..24, key head 1's
    assert np.array_equal(qkvz[:, 64:72], prog["gdn_qkvz"][0][:, 48:56])
    ba = np.asarray(lw["in_proj_ba"])
    assert np.array_equal(ba[:, [4, 5, 6, 7]],
                          prog["gdn_ba"][0][:, [2, 3, 6, 7]])
    att = model.to_reference(params, SMALL)["layers"][3]["q_proj"]
    assert np.array_equal(np.asarray(att[:, 32:48]),
                          params["layers"]["wq"][0][:, 1])
    assert np.array_equal(np.asarray(att[:, 48:64]),
                          params["layers"]["wgate"][0][:, 1])


# -- the pieces the model shares with the accepted cells ---------------------

def test_the_taps_without_a_bias_in_both_forms():
    """``causal_conv_silu`` with the bias of zeros ``_gdn_mix`` gives it:
    the ``jax.numpy`` form against the convolution written out with no
    bias, and the kernels (through Pallas's interpreter) against the
    ``jax.numpy`` form, the backward too."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 3 * 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 3 * 128))
    parts = (128, 128, 128)

    def by_hand(u, w):
        pad = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))
        v = sum(w[j] * pad[:, j:j + 256] for j in range(4))
        return jnp.split(jax.nn.silu(v), 3, axis=-1)

    def loss(form):
        return lambda u, w: sum(jnp.sum(o * o) for o in form(u, w))

    zeros = jnp.zeros((384,), jnp.float32)
    jnp_form = lambda u, w: ssd.causal_conv_silu(u, w, zeros, parts, 0,
                                                 jnp.float32)
    kernels = lambda u, w: ssd.taps_kernels(
        u, w, zeros, parts, 0, jnp.float32, (128, 128), interpret=True)
    for got, want in zip(jnp_form(u, w), by_hand(u, w)):
        _close(got, want, 1e-6)
    for got, want in zip(kernels(u, w), jnp_form(u, w)):
        _close(got, want, 1e-6)
    for got, want in zip(jax.grad(loss(kernels), (0, 1))(u, w),
                         jax.grad(loss(jnp_form), (0, 1))(u, w)):
        _close(got, want, 1e-5)


def test_softmax_routing_and_the_gate_on_the_shared_expert():
    """``route_score="softmax"``: the weights are the softmax over ALL the
    router's outputs, renormalised over the chosen; the shared expert's
    output is scaled by ``sigmoid(x shared_gate)``."""
    from horovod_tpu.parallel.moe import topk_route
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    with jax.default_matmul_precision("highest"):
        route = topk_route(x, router, jnp.zeros(8), 2, eps=0.0,
                           score="softmax")
        p = jax.nn.softmax(x @ router, axis=-1)
    chosen = jnp.take_along_axis(p, route.expert, -1)
    assert np.array_equal(np.sort(route.expert, -1),
                          np.sort(jnp.argsort(-p, -1)[:, :2], -1))
    _close(route.weight, chosen / chosen.sum(-1, keepdims=True), 1e-6)
    cfg = dataclasses.replace(SMALL, layers=(GDN,), n_layers=1)
    lp = {k: v[0] for k, v in _params(cfg)["gdn_layers"].items()}
    xs = x.reshape(2, 8, 32)

    @jax.jit
    def both(xs, lp):
        gated, _ = tfm._expert_ffn(xs, lp, cfg, None)
        plain, _ = tfm._expert_ffn(xs, lp, dataclasses.replace(
            cfg, shared_gate=False), None)
        return gated, plain, tfm._dense_ffn(xs, lp, cfg, None, "shared_",
                                            reduce=False)

    gated, plain, shared = both(xs, lp)
    gate = jax.nn.sigmoid(xs @ lp["shared_gate"])[..., None]
    _close(gated - plain, (gate - 1.0) * shared, 1e-5)


# -- what refuses the new fields says so ------------------------------------

@pytest.mark.parametrize("changes, words", [
    ({"gdn_key_heads": 0}, "a gdn layer needs"),
    ({"gdn_value_heads": 3}, "a gdn layer needs"),
    ({"layers": (K(mixer="gdn", experts=False), GDN, GDN, ATTN)},
     "mixer 'gdn' and experts=False has no stack"),
    ({"layers": (K(mixer="gdn", experts=True, window=8), GDN, GDN, ATTN)},
     "has no window"),
    ({"rope_dim": 5}, "rope_dim 5: the rotated part"),
    ({"rope_dim": 32}, "rope_dim 32: the rotated part"),
    ({"route_score": "argmax"}, "unknown route_score"),
    ({"n_shared_experts": 0, "d_ff_shared": 0},
     "shared_gate gates the shared experts"),
])
def test_a_configuration_that_cannot_run_is_refused_by_name(changes, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(SMALL, **changes)


@pytest.mark.parametrize("mesh, words", [
    ((1, 2, 1), "gdn mixer under sequence parallelism"),
    ((1, 1, 2), "gdn mixer under tensor parallelism")],
    ids=["seq2", "tensor2"])
def test_meshes_the_rule_cannot_run_on_refuse_it_by_name(mesh, words):
    params, (inputs, targets) = _params(), _tokens()
    with pytest.raises(ValueError, match=words):
        tfm.make_train_step(_mesh(*mesh), SMALL, optax.sgd(1.0)).lower(
            params, optax.sgd(1.0).init(params), inputs, targets)


# -- scopes and gauges -----------------------------------------------------

def test_the_scopes_of_the_new_layers():
    params, (inputs, targets) = _params(), _tokens()
    text = tfm.make_train_step(_mesh(), SMALL, optax.sgd(1.0)).lower(
        params, optax.sgd(1.0).init(params), inputs, targets
    ).as_text(debug_info=True)
    for name in ("gdn_mixer/btd,de->bte/dot_general", "gdn_mixer/gdn_conv/",
                 "gdn_mixer/gdn_scan/", "gdn_mixer/gdn_gate_norm/",
                 "gdn_mixer/bte,ed->btd/dot_general", "attn/rope/",
                 "attn/attn_gate/", "attn/qk_norm/", "ffn/router/",
                 "ffn/shared_expert/", "/experts/",
                 "transpose(jvp(layers))"):
        assert name in text, name
    # the rule's scan over the chunks lies under gdn_scan
    assert re.search(r'gdn_scan/while', text)


def test_the_gauges_are_declared_and_set_by_the_example():
    from horovod_tpu.metrics import METRIC_SPECS
    example = os.path.join(os.path.dirname(BENCH), "examples",
                           "transformer_lm.py")
    with open(example) as fh:
        text = fh.read()
    for gauge in ("hvd_tpu_lm_layers", "hvd_tpu_lm_delta_rule",
                  "hvd_tpu_attn_kernel"):
        assert METRIC_SPECS[gauge][0] == "gauge"
        assert '"%s"' % gauge in text
    assert "gdn" in METRIC_SPECS["hvd_tpu_lm_layers"][1]
    assert gated_delta.delta_rule_form() == {"form": "chunked",
                                             "chunk": "64"}


def test_the_cells_own_configuration(model):
    """The benchmark's files give the published widths and one period G G G
    A as one scan of three and a run of one."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(files.cell(CELL)["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    assert [len(kinds) for _, _, kinds in tfm._segments(cfg)] == [3, 1]
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.conv_kernel) == (16, 32, 128, 128, 4)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.rope_dim,
            cfg.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_ff_expert,
            cfg.shared_width, cfg.vocab_size) == (512, 16, 10, 512, 512,
                                                  151936 // 8)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in
               jax.tree_util.tree_leaves(shapes)) == 424_342_592
    small = model.transformer_config(spec, traffic, True)
    assert small.layers == cfg.layers and small.max_seq == 128
