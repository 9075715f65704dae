"""The latent-attention sparse-expert decoder with its multi-token-prediction
module (``TransformerConfig`` with ``layers`` of ``LayerKind(mixer="mla")``
and ``mtp_depth=1``) through ``make_train_step`` against the plain float32
reference of the benchmark (``benchmark/reference/joyai-llm-flash.py``, which
rotates the published interleaved pairs and shares no code with the program);
latent attention alone, against attention over q, k and v built by hand and
against the absorbed form; the module's term alone; the share test of the
model-configs guide; what refuses the new mixer and the module; and what they
leave alone.
"""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu.models import transformer as tfm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "readers"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)

CONFIG, CELL = "joyai-llm-flash", "joyai-spmd-1chip-ep32share-8k"
K = tfm.LayerKind
DENSE, EXPERT = K(mixer="mla", experts=False), K(mixer="mla", experts=True)
# the cell's own pattern at the rehearsal's widths, one expert layer fewer:
# a dense layer, two expert layers (one scan), the module
SMALL = tfm.TransformerConfig(
    vocab_size=96, d_model=64, n_heads=4, n_layers=3, d_ff=96, max_seq=32,
    dtype=jnp.float32, attention="flash", positions="rope", rope_theta=32e6,
    ffn="swiglu", norm="pre", norm_eps=1e-6, tie_embeddings=False,
    layers=(DENSE, EXPERT, EXPERT), q_lora_rank=48, kv_lora_rank=32,
    qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, n_experts=8, moe_top_k=2,
    d_ff_expert=32, n_shared_experts=1, route_scale=2.5,
    router_bias_rate=1e-3, remat_barrier=True, mtp_depth=1, mtp_weight=0.1)
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
    0, so that a norm that is skipped or applied twice, or a bias that is
    ignored, shows."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))
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
    new, _, loss, *stats = step(tfm.shard_params(params, mesh, cfg),
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
    """The reference's logits of both heads, choices, loss terms and
    gradients for the seeded weights and tokens, in the program's tree."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)

    @jax.jit        # (one program: op by op the float32 reference is slow)
    def run(weights):
        logits, mtp_logits, choices = reference.forward(
            weights, inputs, targets, top_k=2)
        return (logits, mtp_logits, jnp.stack(choices),
                reference.loss_terms(weights, inputs, targets, top_k=2),
                reference.grads(weights, inputs, targets, 0, jax.checkpoint,
                                2))

    with jax.default_matmul_precision("highest"):
        logits, mtp_logits, choices, terms, grads = run(weights)
    return {"logits": logits, "mtp_logits": mtp_logits, "choices": choices,
            "terms": terms, "grads": model.from_reference(grads, SMALL)}


@pytest.fixture(scope="module")
def stepped():
    with jax.default_matmul_precision("highest"):
        return _sgd_step(SMALL, _params(), *_tokens())


def test_the_new_layers_leaves_and_the_stacks_they_live_in():
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, SMALL),
                            jax.random.PRNGKey(0))
    assert {k: next(iter(v.values())).shape[0] for k, v in shapes.items()
            if isinstance(v, dict)} == {
        "mla_dense_layers": 1, "mla_layers": 2, "mtp": 1}
    latent = {"wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b", "wo"}
    routed = {"router", "router_bias", "ewg", "ewu", "ewd", "shared_wg",
              "shared_wu", "shared_wd"}
    assert set(shapes["mla_dense_layers"]) == latent | {
        "ln1", "ln2", "wg", "wu", "wd"}
    assert set(shapes["mla_layers"]) == latent | routed | {"ln1", "ln2"}
    # the module: one more block of the expert kind and four leaves of its own
    assert set(shapes["mtp"]) == set(shapes["mla_layers"]) | {
        "enorm", "hnorm", "proj", "ln_f"}
    assert shapes["mtp"]["proj"].shape == (1, 128, 64)
    assert shapes["mla_layers"]["wq_b"].shape == (2, 48, 4, 16 + 8)
    assert shapes["mla_layers"]["wkv_a"].shape == (2, 64, 32 + 8)
    assert shapes["mla_layers"]["wkv_b"].shape == (2, 32, 4, 16 + 16)
    assert shapes["mla_layers"]["wo"].shape == (2, 4, 16, 64)
    assert tfm.layer_rows(SMALL) == [
        ("mla_dense_layers", 0), ("mla_layers", 0), ("mla_layers", 1)]
    # a run of one dense layer and ONE scan of the expert layers
    assert [len(kinds) for _, _, kinds in tfm._segments(SMALL)] == [1, 2]
    # the module's block lies in no stack; its counts are the last row
    assert tfm._expert_rows(SMALL) == {"mla_layers": [0, 1], "mtp": [2]}
    specs = tfm.param_specs(SMALL)
    assert set(specs["mtp"]) == set(shapes["mtp"])


def test_the_published_parameter_count(model):
    """491,697,408 parameters at the cell's cut: embedding and untied head
    33,095,680 each, the dense layer 70,391,808 (latent attention 26,347,520,
    the SwiGLU 44,040,192, the norms), an expert layer 69,343,488 (8 of 256
    held), the module 77,738,240 (proj 8,388,608, one expert layer, three
    norms)."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(files.cell(CELL)["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in  # noqa: E731
                            jax.tree_util.tree_leaves(tree))
    assert size(shapes) == 491_697_408
    assert size(shapes["embed"]) == size(shapes["lm_head"]) == 33_095_680
    assert size(shapes["mla_dense_layers"]) == 70_391_808
    assert size(shapes["mla_layers"]) == 4 * 69_343_488
    assert size(shapes["mtp"]) == 77_738_240
    assert model.latent_params(cfg) + 1536 + 512 == 26_347_520
    assert shapes["mla_layers"]["wq_b"].shape == (4, 1536, 32, 192)
    assert shapes["mla_layers"]["wkv_a"].shape == (4, 2048, 576)
    assert shapes["mla_layers"]["wkv_b"].shape == (4, 512, 32, 256)
    assert shapes["mla_layers"]["router"].shape == (4, 2048, 256)
    assert shapes["mla_layers"]["ewg"].shape == (4, 8, 2048, 768)
    assert cfg.mtp_weight == 0.1 and cfg.route_scale == 2.5


def test_no_width_of_the_cell_differs_from_the_catalogs_row(model):
    spec = files.load_json(files.config_path(CONFIG))
    assert spec["published"] == {"num_hidden_layers": 40,
                                 "n_routed_experts": 256,
                                 "vocab_size": 129280}
    assert set(spec["reduced"]) == set(spec["published"])
    assert (spec["hidden_size"], spec["q_lora_rank"], spec["kv_lora_rank"],
            spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
            spec["v_head_dim"], spec["num_attention_heads"],
            spec["moe_intermediate_size"], spec["intermediate_size"],
            spec["num_experts_per_tok"], spec["router_outputs"]) == (
        2048, 1536, 512, 128, 64, 128, 32, 768, 7168, 8, 256)
    assert spec["departures"] and "rope_interleave" in spec["departures"][0]
    for key in ("mtp_module", "mtp_hidden_state", "mtp_halves_order",
                "mtp_weight", "router_bias_rate", "route_eps",
                "norm_placement", "initialisation", "optimizer",
                "sequence_and_tokens", "settled_start",
                "routers_on_a_share"):
        assert key in spec["assumed"], key


@pytest.mark.parametrize("remat", ["none", "block"])
def test_both_heads_logits_and_the_choices_against_the_reference(wanted,
                                                                 remat):
    cfg = dataclasses.replace(SMALL, remat=remat)
    with jax.default_matmul_precision("highest"):
        (logits, mtp_logits), routes = jax.jit(
            lambda p, x, y: tfm.forward_heads(p, x, y, cfg))(
            _params(), *_tokens())
    _close(logits, wanted["logits"])
    _close(mtp_logits, wanted["mtp_logits"])
    assert np.array_equal(np.sort(routes.expert, -1),
                          np.sort(wanted["choices"], -1))
    assert routes.counts.shape == (3, 8)


def test_both_loss_terms_against_the_reference(wanted, stepped):
    main, mtp = (float(x) for x in wanted["terms"])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x, y: tfm.lm_loss_terms(p, x, y, SMALL))(
            _params(), *_tokens())
        whole = jax.jit(lambda p, x, y: tfm.lean_lm_loss(p, x, y, SMALL))(
            _params(), *_tokens())
        on_mesh = jax.jit(tfm.make_spmd_loss(_mesh(), SMALL))(
            _params(), *_tokens())
    assert float(got[0]) == pytest.approx(main, rel=TIGHT)
    assert float(got[1]) == pytest.approx(mtp, rel=TIGHT)
    # the two terms differ: the second is no copy of the first
    assert abs(main - mtp) > 1e-3
    for loss in (whole, on_mesh, stepped[1]):
        assert float(loss) == pytest.approx(main + 0.1 * mtp, rel=TIGHT)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_through_the_train_step(wanted, stepped, leaf):
    """Every leaf: the module's under ``mtp``, and the embedding's and the
    head's, which take both terms. The selection bias takes no gradient and
    is moved by the step's own rule instead; the routers' is 0 too, here as
    in the reference, only where a share is held (not here)."""
    got, want = _leaves(stepped[3])[leaf], _leaves(wanted["grads"])[leaf]
    if leaf.endswith("['router_bias']"):
        assert float(jnp.max(jnp.abs(want))) == 0.0
        assert np.all(np.isclose(np.abs(got), 1e-3, atol=1e-7) | (got == 0))
        assert np.mean(got != 0) > 0.7
    else:
        assert float(jnp.max(jnp.abs(want))) > 0.0
        _close(got, want, 2e-4)


def test_in_bfloat16_against_the_float32_reference(reference, model):
    """The step's own precision: bfloat16 products with float32
    accumulation, the norms and the rotation in float32. The reference is
    GIVEN the program's choices, as on the chip."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.bfloat16)
    params, (inputs, targets) = _params(), _tokens()
    (logits, mtp_logits), routes = jax.jit(
        lambda p, x, y: tfm.forward_heads(p, x, y, cfg))(params, inputs,
                                                         targets)
    with jax.default_matmul_precision("highest"):
        want, want_mtp, _ = jax.jit(lambda w, given: reference.forward(
            w, inputs, targets, top_k=2, given=given))(
            model.to_reference(params, SMALL), list(routes.expert))
    _close(logits.astype(jnp.float32), want, 1e-1)
    _close(mtp_logits.astype(jnp.float32), want_mtp, 1e-1)


def test_the_step_returns_the_counts_and_the_second_term_and_moves_the_bias(
        stepped, wanted):
    new, _, stats, _ = stepped
    assert set(stats[0]) == {"expert_counts", "mtp_loss"}
    assert float(stats[0]["mtp_loss"]) == pytest.approx(
        float(wanted["terms"][1]), rel=TIGHT)
    counts = np.asarray(stats[0]["expert_counts"])
    # the module's block's row is the last
    assert counts.shape == (3, 8) and (counts.sum(axis=1) == 2 * 32 * 2).all()
    before = _params()
    for stack, rows in tfm._expert_rows(SMALL).items():
        moved = np.asarray(new[stack]["router_bias"]) - np.asarray(
            before[stack]["router_bias"])
        want = 1e-3 * np.sign(counts[rows].mean(axis=1, keepdims=True)
                              - counts[rows])
        assert np.allclose(moved, want, atol=1e-7), stack
    stats_ = tfm.routing_stats(counts, SMALL, 64)
    assert len(stats_["held_share"]) == 3 and stats_["dropped"] == 0.0


def test_the_fourth_value_has_the_second_term_only_under_the_module():
    cfg = dataclasses.replace(SMALL, mtp_depth=0)
    params, (inputs, targets) = _params(cfg), _tokens()
    assert "mtp" not in params
    assert tfm.lm_loss_terms(params, inputs, targets, cfg)[1] is None
    *_, stats = tfm.make_train_step(_mesh(), cfg, optax.sgd(1.0))(
        params, optax.sgd(1.0).init(params), inputs, targets)
    assert set(stats) == {"expert_counts"}
    assert stats["expert_counts"].shape == (2, 8)
    # and a module after dense layers alone returns it without any counts
    dense = dataclasses.replace(SMALL, layers=(DENSE, DENSE), n_layers=2)
    params = _params(dense)
    *_, stats = tfm.make_train_step(_mesh(), dense, optax.sgd(1.0))(
        params, optax.sgd(1.0).init(params), inputs, targets)
    assert set(stats) == {"mtp_loss"}


@pytest.mark.parametrize("mesh", [(2, 1, 1), (4, 1, 1)],
                         ids=["data2", "data4"])
def test_a_data_mesh_takes_the_same_step(mesh):
    """The module's leaves and the head's are summed inside the backward
    pass, the head's from both of its uses."""
    inputs, targets = _tokens(rows=4)
    with jax.default_matmul_precision("highest"):
        one = _sgd_step(SMALL, _params(), inputs, targets)
        many = _sgd_step(SMALL, _params(), inputs, targets, _mesh(*mesh))
    assert float(many[1]) == pytest.approx(float(one[1]), rel=1e-5)
    assert float(many[2][0]["mtp_loss"]) == pytest.approx(
        float(one[2][0]["mtp_loss"]), rel=1e-5)
    assert np.array_equal(many[2][0]["expert_counts"],
                          one[2][0]["expert_counts"])
    for leaf, want in _leaves(one[3]).items():
        _close(_leaves(many[3])[leaf], want, 1e-4)
    axes = tfm.grad_reduce_axes(_mesh(*mesh), SMALL)
    assert set(tfm._in_backward(axes, SMALL)) == {"mla_layers", "mtp",
                                                  "lm_head"}
    assert 0.0 < tfm.grad_reduce_in_backward_share(_mesh(*mesh), SMALL) < 1.0


# -- latent attention alone ------------------------------------------------

def _one_layer():
    one = dataclasses.replace(SMALL, layers=(DENSE,), n_layers=1, mtp_depth=0)
    lp = {k: v[0] for k, v in _params(one)["mla_dense_layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    return one, lp, x


def _by_hand(x, lp, cfg):
    """q, k [B, H, T, dn + dr] and v [B, H, T, dv] built leaf by leaf, the
    rotation in the program's form."""
    rope = tfm._rope_tables(cfg, x.shape[1], None)
    dn, rkv = cfg.qk_nope_dim, cfg.kv_lora_rank
    c_q = tfm._rmsnorm(x @ lp["wq_a"], lp["q_a_norm"], cfg.norm_eps)
    q = jnp.einsum("btr,rhk->bhtk", c_q, lp["wq_b"])
    kv_a = x @ lp["wkv_a"]
    c_kv = tfm._rmsnorm(kv_a[..., :rkv], lp["kv_a_norm"], cfg.norm_eps)
    kv = jnp.einsum("btr,rhk->bhtk", c_kv, lp["wkv_b"])
    q = jnp.concatenate([q[..., :dn], tfm._rope(q[..., dn:], *rope)], -1)
    k_rope = tfm._rope(kv_a[..., rkv:], *rope)
    return q, kv[..., :dn], k_rope, kv[..., dn:], c_kv


def test_latent_attention_against_attention_over_q_k_v_built_by_hand():
    cfg, lp, x = _one_layer()
    with jax.default_matmul_precision("highest"):
        got = tfm._mla_mix(x, lp, cfg=cfg,
                           rope=tfm._rope_tables(cfg, 32, None))
        q, k_nope, k_rope, v, _ = _by_hand(x, lp, cfg)
        k = jnp.concatenate([k_nope, jnp.stack([k_rope] * 4, axis=1)], -1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(16 + 8)
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        want = jnp.einsum("bhtk,hkd->btd", out, lp["wo"])
    assert got.shape == (2, 32, 64)
    _close(got, want)


def test_latent_attention_against_the_absorbed_form():
    """The scores through the ``kv_lora_rank``-wide latent: ``q_nope_h .
    k_nope_h = (q_nope_h W_kb_h^T) . c_kv``, and the values read out of the
    latent after the softmax: what ties the low-rank algebra."""
    cfg, lp, x = _one_layer()
    dn = cfg.qk_nope_dim
    with jax.default_matmul_precision("highest"):
        got = tfm._mla_mix(x, lp, cfg=cfg,
                           rope=tfm._rope_tables(cfg, 32, None))
        q, _, k_rope, _, c_kv = _by_hand(x, lp, cfg)
        w_kb, w_vb = lp["wkv_b"][..., :dn], lp["wkv_b"][..., dn:]
        q_latent = jnp.einsum("bhtk,rhk->bhtr", q[..., :dn], w_kb)
        s = (jnp.einsum("bhqr,bkr->bhqk", q_latent, c_kv)
             + jnp.einsum("bhqd,bkd->bhqk", q[..., dn:], k_rope)) \
            / np.sqrt(16 + 8)
        s = jnp.where(jnp.tril(jnp.ones((32, 32), bool)), s, -jnp.inf)
        read = jnp.einsum("bhqk,bkr->bhqr", jax.nn.softmax(s, -1), c_kv)
        out = jnp.einsum("bhqr,rhk->bhqk", read, w_vb)
        want = jnp.einsum("bhtk,hkd->btd", out, lp["wo"])
    _close(got, want)


def test_latent_attention_against_the_references(reference, model):
    """The reference rotates the published interleaved pairs; the program
    its halves, on columns that the configuration's file permutes."""
    cfg, lp, x = _one_layer()
    weights = model.to_reference(
        {"mla_dense_layers": {k: v[None] for k, v in lp.items()},
         "mtp": {k: v[:1] for k, v in _params()["mtp"].items()}}, cfg)
    with jax.default_matmul_precision("highest"):
        got = tfm._mla_mix(x, lp, cfg=cfg,
                           rope=tfm._rope_tables(cfg, 32, None))
        want = reference.attention(x, weights["layers"][0])
        unpermuted = reference.attention(x, lp)
    _close(got, want)
    assert float(jnp.max(jnp.abs(got - unpermuted))) > 1e-2 * float(
        jnp.max(jnp.abs(want)))
    order = model.pairs_side_by_side(cfg)
    assert order.tolist() == [0, 4, 1, 5, 2, 6, 3, 7]
    assert np.array_equal(np.asarray(weights["layers"][0]["wq_b"][..., 16:]),
                          np.asarray(lp["wq_b"][..., 16:][..., order]))


def test_the_rotation_is_over_the_rotated_part_alone():
    cos, sin = tfm._rope_tables(SMALL, 32, None)
    assert cos.shape == sin.shape == (32, 8 // 2)
    assert float(cos[5, 0]) == pytest.approx(np.cos(5.0), abs=1e-6)
    assert float(sin[5, 3]) == pytest.approx(np.sin(5 * 32e6 ** -0.75),
                                             abs=1e-6)


# -- the module's term alone -----------------------------------------------

def test_the_modules_term_is_shifted_by_two_and_leaves_the_last_position_out():
    params, (inputs, targets) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        (_, mtp_logits), _ = tfm.forward_heads(params, inputs, targets, SMALL)
        got = tfm.lm_loss_terms(params, inputs, targets, SMALL)[1]
        nll = -jnp.take_along_axis(
            jax.nn.log_softmax(mtp_logits[:, :-1]), targets[:, 1:, None],
            axis=-1)[..., 0]
        # position i from the embedding of targets[i], against targets[i+1]:
        # T - 1 terms a row
        assert nll.shape == (2, 31)
        assert float(got) == pytest.approx(float(jnp.mean(nll)), rel=1e-5)
        # neither the mean over all T positions, the last against a token
        # that wrapped around, nor the main head's own targets
        wrapped = -jnp.take_along_axis(
            jax.nn.log_softmax(mtp_logits),
            jnp.roll(targets, -1, 1)[..., None], axis=-1)[..., 0]
        same = -jnp.take_along_axis(
            jax.nn.log_softmax(mtp_logits), targets[..., None],
            axis=-1)[..., 0]
        for other in (jnp.mean(wrapped), jnp.mean(same[:, :-1])):
            assert float(got) != pytest.approx(float(other), rel=1e-4)


def test_both_uses_of_the_embedding_and_the_head_are_in_their_gradients():
    params, (inputs, targets) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        main, mtp = (jax.grad(lambda p, i=i: tfm.lm_loss_terms(
            p, inputs, targets, SMALL)[i])(params) for i in (0, 1))
        both = jax.grad(lambda p: tfm.lean_lm_loss(p, inputs, targets,
                                                   SMALL))(params)
    for leaf in ("embed", "lm_head"):
        assert float(jnp.max(jnp.abs(main[leaf]))) > 0
        assert float(jnp.max(jnp.abs(mtp[leaf]))) > 0
        _close(both[leaf], main[leaf] + 0.1 * mtp[leaf], 1e-5)
    # the main term does not reach the module; the module's reaches the stack
    assert all(float(jnp.max(jnp.abs(g))) == 0.0
               for g in jax.tree_util.tree_leaves(main["mtp"]))
    assert float(jnp.max(jnp.abs(mtp["mla_dense_layers"]["wq_a"]))) > 0
    assert float(jnp.max(jnp.abs(mtp["ln_f"]))) == 0.0     # h BEFORE the norm
    assert float(jnp.max(jnp.abs(mtp["mtp"]["ln_f"]))) > 0
    # a token that is a row's LAST target alone is embedded at the last
    # position alone, which scores nothing: its row takes no gradient; the
    # rows the module looks up at the other positions do
    only = np.setdiff1d(np.asarray(targets), np.asarray(inputs))
    assert len(only) and float(jnp.max(jnp.abs(mtp["embed"][only]))) == 0
    seen = np.unique(np.asarray(targets[:, :-1]))
    assert float(jnp.min(jnp.max(jnp.abs(mtp["embed"][seen]), axis=-1))) > 0


# -- the share test of the model-configs guide -----------------------------

def test_the_four_shares_add_up_to_the_uncut_layer(reference):
    """Experts 0-1, 2-3, 4-5, 6-7 of 8 (the cell: 0-7, ... 248-255 of 256),
    each program holding a quarter: the routed parts of the four shares,
    and the shared expert counted ONCE, add up to what the reference gives
    for the whole layer."""
    whole = dataclasses.replace(SMALL, layers=(EXPERT,), n_layers=1,
                                mtp_depth=0)
    full = tfm.init_params(jax.random.PRNGKey(4), whole)["mla_layers"]
    lw = {k: v[0] for k, v in full.items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = reference.expert_ffn(x, lw, 0, 2)
        shared = reference.swiglu(x, lw["shared_wg"], lw["shared_wu"],
                                  lw["shared_wd"])
        parts = []
        for first in range(0, 8, 2):
            cfg = dataclasses.replace(whole, experts_held=2,
                                      first_expert=first)
            share = tfm.init_params(jax.random.PRNGKey(4), cfg)["mla_layers"]
            # a share draws the experts the whole layer draws
            assert np.array_equal(np.asarray(share["ewu"][0]),
                                  np.asarray(full["ewu"][0, first:first + 2]))
            out, routes = tfm._expert_ffn(
                x, {k: v[0] for k, v in share.items()}, cfg, None)
            parts.append(out - shared)
            assert np.array_equal(np.sort(routes.expert, -1),
                                  np.sort(chosen, -1))
    assert float(jnp.max(jnp.abs(shared))) > 0.1 * float(
        jnp.max(jnp.abs(want)))
    _close(sum(parts) + shared, want, 1e-5)


def test_the_cells_share_against_the_reference_given_the_same_share(
        reference, model):
    """Two of eight experts held, as the cell holds 8 of 256: program and
    reference leave out the same part, and on a share the routers take no
    gradient in either."""
    cfg = dataclasses.replace(SMALL, experts_held=2)
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        want = model.from_reference(jax.jit(lambda w: reference.grads(
            w, inputs, targets, 0, jax.checkpoint, 2))(
            model.to_reference(params, cfg)), cfg)
        _, _, _, grads = _sgd_step(cfg, params, inputs, targets)
    for leaf, g in _leaves(want).items():
        if leaf.endswith("['router']"):
            assert float(jnp.max(jnp.abs(g))) == 0.0
            assert float(np.max(np.abs(_leaves(grads)[leaf]))) == 0.0
        elif not leaf.endswith("['router_bias']"):
            _close(_leaves(grads)[leaf], g, 2e-4)


# -- scopes and gauges -----------------------------------------------------

def test_the_scopes_of_the_new_layers_and_of_the_module():
    params, (inputs, targets) = _params(), _tokens()
    text = tfm.make_train_step(_mesh(), SMALL, optax.sgd(1.0)).lower(
        params, optax.sgd(1.0).init(params), inputs, targets
    ).as_text(debug_info=True)
    for name in ("mla_mixer/mla_q/btd,dr->btr/dot_general",
                 "mla_mixer/mla_q/btr,rhk->bhtk/dot_general",
                 "mla_mixer/mla_q/concatenate",
                 "mla_mixer/mla_kv/btd,dr->btr/dot_general",
                 "mla_mixer/mla_kv/btr,rhk->bhtk/dot_general",
                 "mla_mixer/mla_kv/broadcast_in_dim",
                 "mla_mixer/mla_kv/concatenate", "mla_mixer/rope/",
                 "mla_mixer/attn_latent/",
                 "mla_mixer/bhtk,hkd->btd/dot_general",
                 "ffn/router/", "/experts/", "ffn/shared_expert/",
                 "(mtp)/mtp_proj/embed/",
                 "(mtp)/mtp_proj/bte,ed->btd/dot_general",
                 # its block: the one layer's body, a scan of one
                 "(mtp)/while/body/closed_call",
                 # the second use of the head and the second loss, inside
                 "(mtp)/head/btd,vd->btv", "(mtp)/loss/",
                 "jvp(head)/btd,vd->btv", "transpose(jvp(layers))",
                 "transpose(jvp(mtp))"):
        assert name in text, name
    assert "attn_full" not in text and "conv_mixer" not in text
    # the attention call alone is under attn_latent: no projection is
    assert "attn_latent/btd" not in text and "attn_latent/btr" not in text


def test_the_gauges_and_the_kernels_labels_are_declared():
    from horovod_tpu.metrics import METRIC_SPECS
    from horovod_tpu.parallel import flash_attention as fa
    example = os.path.join(os.path.dirname(BENCH), "examples",
                           "transformer_lm.py")
    with open(example) as fh:
        text = fh.read()
    for gauge in ("hvd_tpu_lm_mtp_loss", "hvd_tpu_lm_mtp_weight",
                  "hvd_tpu_lm_layers", "hvd_tpu_attn_kernel"):
        assert METRIC_SPECS[gauge][0] == "gauge"
        assert '"%s"' % gauge in text
    assert "mla" in METRIC_SPECS["hvd_tpu_lm_layers"][1]
    assert "v_head_size" in METRIC_SPECS["hvd_tpu_attn_kernel"][1]
    assert fa.attention_kernel((2, 32, 8192, 192), (2, 32, 8192, 192),
                               v_head_size=128)["v_head_size"] == "128"
    assert "resident_dq" in METRIC_SPECS["hvd_tpu_attn_kernel"][1]


# -- what refuses the new mixer and the module says so ---------------------

@pytest.mark.parametrize("mesh, words", [
    ((1, 2, 1), "mla mixer .latent attention. under sequence parallelism"),
    ((1, 1, 2), "mla mixer .latent attention. under tensor parallelism")],
    ids=["seq2", "tensor2"])
def test_meshes_latent_attention_cannot_run_on_refuse_it_by_name(mesh, words):
    cfg = dataclasses.replace(SMALL, mtp_depth=0)
    params, (inputs, targets) = _params(cfg), _tokens()
    m = _mesh(*mesh)
    with pytest.raises(ValueError, match=words):
        tfm.make_spmd_loss(m, cfg)(tfm.shard_params(params, m, cfg),
                                   inputs, targets)


@pytest.mark.parametrize("mesh", [(1, 2, 1), (1, 1, 2)],
                         ids=["seq2", "tensor2"])
def test_meshes_the_module_cannot_run_on_refuse_it_by_name(mesh):
    """After attention layers that run there, so that the module itself is
    what refuses."""
    cfg = dataclasses.replace(
        SMALL, layers=(K(experts=False),) * 2, n_layers=2, attention="ring",
        q_lora_rank=0)
    params, (inputs, targets) = _params(cfg), _tokens()
    m = _mesh(*mesh)
    with pytest.raises(ValueError, match="multi-token-prediction module "
                                         ".mtp_depth. under seq > 1 or "
                                         "tensor > 1"):
        tfm.make_spmd_loss(m, cfg)(tfm.shard_params(params, m, cfg),
                                   inputs, targets)


@pytest.mark.parametrize("builder", ["make_pp_train_step",
                                     "make_pp_engine_train_step",
                                     "make_moe_ep_train_step"])
def test_the_other_builders_refuse_the_new_layers_by_name(builder):
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    with pytest.raises(ValueError, match="mla mixer .*multi-token-prediction "
                                         "module .mtp_depth=1."):
        if builder == "make_moe_ep_train_step":
            class Engine:       # refused before the engine is asked
                pass
            tfm.make_moe_ep_train_step(Engine(), SMALL, optax.sgd(1.0))
        else:
            getattr(tfm, builder)(mesh, SMALL, optax.sgd(1.0), n_micro=2,
                                  schedule="1f1b", n_virtual=1)


def test_forward_routes_points_at_forward_heads():
    with pytest.raises(ValueError, match="forward_heads takes them"):
        tfm.forward_routes(_params(), _tokens()[0], SMALL)
    # the main head alone needs no next token
    logits = tfm.forward_block(_params(), _tokens()[0], SMALL)
    assert logits.shape == (2, 32, 96)


@pytest.mark.parametrize("changes, words", [
    ({"q_lora_rank": 0}, "an mla layer needs"),
    ({"v_head_dim": 0}, "an mla layer needs"),
    ({"qk_rope_dim": 7}, "an mla layer needs"),
    ({"layers": (K(window=8, mixer="mla"), EXPERT, EXPERT)},
     "has no window"),
    ({"layers": (K(mixer="mla", experts=None), EXPERT, EXPERT)},
     "mixer 'mla' and experts=None has no stack"),
    ({"layers": (K(), EXPERT, EXPERT)}, "one rotation table"),
    ({"mtp_depth": 2}, "mtp_depth 2: one multi-token-prediction module"),
    ({"layers": (), "n_layers": 3}, "follows a per-layer pattern"),
    ({"layers": (DENSE, EXPERT, K(mixer="none", experts=True)),
      "mtp_depth": 1}, "a mixer with its FFN"),
])
def test_a_configuration_that_cannot_run_is_refused_by_name(changes, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(SMALL, **changes)


# -- what the new fields leave alone ---------------------------------------

# sha256 of make_train_step's lowered text, the state-space cell's program
# at its rehearsal's widths in bfloat16, adamw(3e-4), on a mesh of one and
# over data=4, as it lowers since the mixer's taps take the in-projection's
# output whole (ssd.causal_conv_silu; the text was 6272b10's, from before
# the new mixer and the module existed, until then) (the other accepted
# cells': tests/test_nemotron_lm.py, tests/test_trinity_lm.py,
# tests/test_lfm2_lm.py, untouched)
ACCEPTED = {
    ("nemotron-3-nano-30b-a3b", "nemotron3-spmd-1chip-ep16share-8k"): (
        16756.6875, {
            1: "577c4ae033380ca75f984135b3bbf3fe82739ce0277640e5d68c5b7f8e69"
               "26cd",
            4: "5ea49a7579a44c2c07e0420e49da3d79f0565fabdaf9e9ff3eb19aa2a549"
               "0a2a"}),
}


def _accepted(config, cell):
    module = files.load_module(os.path.join(
        BENCH, "configs", config + ".py"), "bench_config_accepted")
    spec = files.load_json(files.config_path(config))
    traffic = files.load_json(files.traffic_path(files.cell(cell)["traffic"]))
    return module.transformer_config(spec, traffic, True)


@pytest.mark.parametrize("data", [1, 4])
@pytest.mark.parametrize("config, cell", sorted(ACCEPTED))
def test_the_accepted_patterns_lower_as_before(config, cell, data):
    cfg = dataclasses.replace(_accepted(config, cell), dtype=jnp.bfloat16)
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((data, cfg.max_seq), jnp.int32)
    text = tfm.make_train_step(_mesh(data), cfg, opt).lower(
        params, jax.eval_shape(opt.init, params), tok, tok).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ACCEPTED[config, cell][1][data]


@pytest.mark.parametrize("config, cell", sorted(ACCEPTED))
def test_the_accepted_patterns_draw_their_weights_as_before(config, cell):
    params = tfm.init_params(jax.random.PRNGKey(0), _accepted(config, cell))
    got = float(sum(jnp.sum(jnp.abs(x)) for x in
                    jax.tree_util.tree_leaves(params)))
    assert got == pytest.approx(ACCEPTED[config, cell][0], rel=1e-6)


def test_the_cells_own_configuration_at_the_rehearsals_widths(model):
    """The benchmark's files give the pattern the tests above run: a dense
    layer, four expert layers as one scan, the module; two rows through the
    step over data=2."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(files.cell(CELL)["traffic"]))
    cfg = model.transformer_config(spec, traffic, True)
    assert [len(kinds) for _, _, kinds in tfm._segments(cfg)] == [1, 4]
    assert (cfg.mtp_depth, cfg.held, cfg.n_experts) == (1, 4, 8)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    inputs, targets = _tokens(rows=2, seq=cfg.max_seq, vocab=cfg.vocab_size)
    opt = model.optimizer()
    *_, loss, stats = tfm.make_train_step(_mesh(2), cfg, opt)(
        tfm.shard_params(params, _mesh(2), cfg), opt.init(params), inputs,
        targets)
    assert np.isfinite(float(loss))
    assert stats["expert_counts"].shape == (5, 8)
    assert model.expert_rows(cfg)[-1] == ("mtp", 0)
    assert model.flops_per_sample(cfg) > 0
