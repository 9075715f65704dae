"""The looped decoder (``TransformerConfig`` with RoPE, SwiGLU, sandwich
norms, an untied head, ``n_loops`` passes and the exit gate) through
``make_train_step`` against the plain float32 reference of the benchmark
(``benchmark/reference/ouro-2.6b.py``, which shares no code with the
program), and what the new fields leave alone.

Float32 comparisons are tight (the two sides differ in the order of sums
only). The bfloat16 ones go through the cell's own checks at the
rehearsal's widths, with the bands of ``benchmark/configs/ouro-2.6b*.py``;
the counts quoted there are the ones these tests print.
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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common import scopes
from horovod_tpu.models import transformer as tfm

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "tests"), os.path.join(BENCH, "readers"),
           BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)
import ouro_defects         # noqa: E402  (benchmark/tests/)

CONFIG = ouro_defects.CONFIG
LOOPED = tfm.TransformerConfig(
    vocab_size=96, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_seq=32,
    dtype=jnp.float32, attention="ring", positions="rope", rope_theta=1e6,
    ffn="swiglu", norm="sandwich", tie_embeddings=False, n_loops=4)
TIGHT = 2e-5        # float32 on both sides: the order of the sums differs


@pytest.fixture(scope="module")
def reference():
    return files.reference_module(CONFIG)


@pytest.fixture(scope="module")
def model():
    return files.config_module(CONFIG)


def _params(cfg=LOOPED, seed=0):
    """Seeded weights with the norms' scales off 1, so that a norm that is
    skipped or misplaced shows."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    return jax.tree_util.tree_map(
        lambda x: x * jax.random.uniform(next(keys), x.shape, x.dtype,
                                         0.5, 1.5)
        if x.ndim and bool(jnp.all(x == 1.0)) else x, params)


def _tokens(rows=4, seq=32, seed=0, vocab=96):
    tok = np.random.RandomState(seed).randint(
        0, vocab, size=(rows, seq + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def _mesh(data=1, seq=1, tensor=1):
    devs = np.array(jax.devices()[:data * seq * tensor]).reshape(
        data, seq, tensor)
    return Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))


def _worst(got, want):
    """Largest difference over the leaves, each as a share of its leaf's
    largest reference value."""
    return max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
               for a, b in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))


@pytest.fixture(scope="module")
def wanted(reference, model):
    """The reference's logits, exit distribution, loss and gradients for
    the seeded weights and tokens."""
    params, (x, y) = _params(), _tokens()
    with jax.default_matmul_precision("highest"):
        weights = model.to_reference(params)
        logits, p = reference.forward(weights, x)
        return {"logits": jnp.stack(logits), "p": p,
                "loss": reference.loss(weights, x, y),
                "grads": model.from_reference(reference.grads(weights, x, y))}


def test_every_exits_logits_and_the_exit_distribution(wanted):
    logits, p = tfm.forward_exits(_params(), _tokens()[0], LOOPED)
    assert logits.shape == (4, 4, 32, 96) and p.shape == (4, 4, 32)
    assert _worst(logits, wanted["logits"]) < TIGHT
    assert float(jnp.max(jnp.abs(p - wanted["p"]))) < TIGHT
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    # the last pass's logits are what the plain forward returns
    np.testing.assert_array_equal(
        tfm.forward_block(_params(), _tokens()[0], LOOPED), logits[-1])


MESHES = {"one": ({}, "ring"), "data4": ({"data": 4}, "ring"),
          "tensor2": ({"tensor": 2}, "ring"), "seq2-ring": ({"seq": 2}, "ring"),
          "seq2-ulysses": ({"seq": 2}, "ulysses"),
          "data2-seq2-tensor2": ({"data": 2, "seq": 2, "tensor": 2}, "ring")}


@pytest.mark.parametrize("name", list(MESHES))
def test_loss_and_every_gradient_on_a_mesh(wanted, name):
    axes, attention = MESHES[name]
    cfg = dataclasses.replace(LOOPED, attention=attention)
    mesh = _mesh(**axes)
    params = tfm.shard_params(_params(), mesh, cfg)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    x, y = jax.device_put(_tokens(), tok_sh)
    loss, grads = jax.jit(jax.value_and_grad(tfm.make_spmd_loss(mesh, cfg)))(
        params, x, y)
    assert abs(float(loss) - float(wanted["loss"])) < TIGHT * float(loss)
    assert set(grads) == set(wanted["grads"])
    assert _worst(grads, wanted["grads"]) < 10 * TIGHT


def test_train_step_trains_the_looped_model():
    mesh = _mesh(data=2, seq=2, tensor=2)
    opt = optax.adamw(1e-2)
    step = tfm.make_train_step(mesh, LOOPED, opt)
    params = tfm.shard_params(_params(), mesh, LOOPED)
    state = opt.init(params)
    x, y = jax.device_put(_tokens(), NamedSharding(
        mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS)))
    losses = []
    for _ in range(4):
        params, state, loss = step(params, state, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and all(np.isfinite(losses)), losses


def test_zigzag_maps_the_positions(wanted):
    """Under ``sp_layout="zigzag"`` every shard rotates by the global
    positions of the stripes it holds: the permuted row gives the
    single-shard loss."""
    from horovod_tpu.parallel.ring_attention import zigzag_indices
    cfg = dataclasses.replace(LOOPED, sp_layout="zigzag")
    mesh = _mesh(seq=4)
    idx, _ = zigzag_indices(32, 4)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    x, y = (jax.device_put(jnp.take(t, idx, axis=1), tok_sh)
            for t in _tokens())
    loss = jax.jit(tfm.make_spmd_loss(mesh, cfg))(
        tfm.shard_params(_params(), mesh, cfg), x, y)
    assert abs(float(loss) - float(wanted["loss"])) < TIGHT * float(loss)


def test_ulysses_refuses_zigzag_by_name():
    cfg = dataclasses.replace(LOOPED, attention="ulysses",
                              sp_layout="zigzag")
    mesh = _mesh(seq=2)
    x, y = _tokens()
    with pytest.raises(ValueError, match="zigzag"):
        jax.jit(tfm.make_spmd_loss(mesh, cfg))(_params(), x, y)


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_rope_tables_hold_global_positions(layout):
    cfg = dataclasses.replace(LOOPED, sp_layout=layout)
    whole = tfm._rope_tables(cfg, 32, None)
    mesh = _mesh(seq=4)
    local = jax.jit(jax.shard_map(
        lambda: tfm._rope_tables(cfg, 8, 4), mesh=mesh, in_specs=(),
        out_specs=P(tfm.SEQ_AXIS), check_vma=False))()
    order = np.arange(32)
    if layout == "zigzag":
        from horovod_tpu.parallel.ring_attention import zigzag_indices
        order = np.asarray(zigzag_indices(32, 4)[0])
    for got, want in zip(local, whole):
        np.testing.assert_allclose(got, want[order], atol=1e-6)


def test_the_shared_stacks_gradient_is_the_sum_over_the_passes(
        wanted, reference, model):
    """An unrolled copy with a stack of its own for every pass: the shared
    stack's gradient is the sum of the four."""
    params, (x, y) = _params(), _tokens()
    weights = model.to_reference(params)

    def unrolled(stacks):
        h = weights["embed"][x].astype(jnp.float32)
        nll, lam = [], []
        for stack in stacks:
            for lw in stack:
                h = reference.layer(h, lw)
            h = reference.rmsnorm(h, weights["ln_f"])
            one = reference.exit_of(weights, h, y)
            nll.append(one[0])
            lam.append(one[1])
        return reference.objective(jnp.stack(nll),
                                   reference.exit_probs(lam))

    with jax.default_matmul_precision("highest"):
        per_pass = jax.grad(unrolled)([weights["layers"]] * 4)
    summed = model.from_reference({"layers": jax.tree_util.tree_map(
        lambda *g: sum(g), *per_pass)})["layers"]
    got = jax.grad(lambda p: tfm.lean_lm_loss(p, x, y, LOOPED))(params)
    assert _worst(got["layers"], summed) < 10 * TIGHT
    # and one pass's share alone is not it
    alone = model.from_reference({"layers": per_pass[-1]})["layers"]
    assert _worst(got["layers"], alone) > 0.1


def test_the_entropy_term_reaches_the_gate_and_what_feeds_it():
    params, (x, y) = _params(), _tokens()
    with_entropy, without = (
        jax.grad(lambda p: tfm.lean_lm_loss(p, x, y, dataclasses.replace(
            LOOPED, exit_entropy_weight=beta)))(params)
        for beta in (0.1, 0.0))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), with_entropy, without)
    # the head is behind the gate: the entropy of p does not see it
    assert moved["lm_head"] == 0.0
    assert moved["exit_gate"]["w"] > 1e-6 and moved["exit_gate"]["b"] > 1e-6
    assert moved["ln_f"] > 1e-7 and moved["embed"] > 1e-9
    assert all(v > 1e-8 for v in moved["layers"].values())


def test_exit_distribution_is_the_mean_share_of_every_pass(wanted):
    share = tfm.exit_distribution(_params(), _tokens()[0], LOOPED)
    np.testing.assert_allclose(share, jnp.mean(wanted["p"], axis=(1, 2)),
                               atol=1e-6)
    assert abs(float(jnp.sum(share)) - 1.0) < 1e-6


@pytest.mark.parametrize("remat", ["block", "attention"])
def test_remat_changes_no_number_under_the_loop(remat):
    params, (x, y) = _params(), _tokens()
    want = jax.value_and_grad(
        lambda p: tfm.lean_lm_loss(p, x, y, LOOPED))(params)
    got = jax.value_and_grad(lambda p: tfm.lean_lm_loss(
        p, x, y, dataclasses.replace(LOOPED, remat=remat)))(params)
    assert abs(float(got[0]) - float(want[0])) < 1e-6
    assert _worst(got[1], want[1]) < 1e-5


# -- what the new fields leave alone ---------------------------------------

PLAIN = tfm.TransformerConfig(vocab_size=96, d_model=32, n_heads=4,
                              n_layers=2, d_ff=48, max_seq=32,
                              dtype=jnp.float32)


def test_defaults_keep_the_parameter_tree_and_the_loss():
    """With every new field at its default the model is the one the other
    LM configuration's reference states: pre-norm, GELU, no positions, the
    head tied to the embedding, one pass."""
    params, (x, y) = _params(PLAIN), _tokens()
    assert set(params) == {"embed", "layers", "ln_f"}
    assert set(params["layers"]) == {"ln1", "wq", "wk", "wv", "wo", "ln2",
                                     "w1", "w2"}
    old = files.reference_module("cerebras-gpt-1.3b")
    weights = {**params, "layers": [
        {k: v[i] for k, v in params["layers"].items()} for i in range(2)]}
    with jax.default_matmul_precision("highest"):
        want = old.loss(old.forward(weights, x), y)
    got = tfm.lean_lm_loss(params, x, y, PLAIN)
    assert abs(float(got) - float(want)) < TIGHT * float(want)
    # seed for seed: the new fields draw from keys the defaults never used
    both = tfm.init_params(jax.random.PRNGKey(3), LOOPED)
    plain = tfm.init_params(jax.random.PRNGKey(3), PLAIN)
    for k in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(both["layers"][k], plain["layers"][k])
    np.testing.assert_array_equal(both["embed"], plain["embed"])


@pytest.mark.parametrize("field,value", [
    ("positions", "alibi"), ("ffn", "relu"), ("norm", "post"),
    ("n_loops", 0)])
def test_an_unknown_value_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        dataclasses.replace(PLAIN, **{field: value})


def test_moe_refuses_the_gated_ffn_by_name():
    with pytest.raises(ValueError, match="ffn"):
        dataclasses.replace(PLAIN, use_moe=True, ffn="swiglu")


NEW_FIELDS = {"positions": "rope", "ffn": "swiglu", "norm": "sandwich",
              "norm_eps": 1e-5, "tie_embeddings": False, "n_loops": 4}
STILL_REFUSED = ("tie_embeddings", "n_loops")
BAND = dict(rtol=1e-4, atol=1e-5)   # test_pp_flagship_matches_single_device's


def _pp_step(builder, cfg, params, x, y):
    """(loss, gradients) of one pipeline step over two stages: the builder's
    own sgd(1.0) update read back as a difference."""
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    placed = jax.tree_util.tree_map(
        lambda a, s: jax.device_put(np.asarray(a), NamedSharding(mesh, s)),
        params, tfm.pp_param_specs(cfg))
    if builder == "make_pp_train_step":
        opt = optax.sgd(1.0)
        step = tfm.make_pp_train_step(mesh, cfg, opt, n_micro=2)
    else:
        import horovod_tpu as hvd
        from horovod_tpu.optimizer import DistributedEagerOptimizer
        hvd.init()
        hvd._engine().replay.invalidate_all("test isolation")
        opt = DistributedEagerOptimizer(optax.sgd(1.0), op=hvd.Sum)
        step = tfm.make_pp_engine_train_step(mesh, cfg, opt, n_micro=2,
                                             schedule="1f1b")
    after, _, loss = step(placed, opt.init(placed), x, y)
    return loss, jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), params, after)


def _moe_ep_step(cfg, params, x, y):
    """(loss, gradients) of one MoE-EP step in the engine's world of one,
    in the layout of ``params``."""
    import horovod_tpu as hvd
    hvd.init()
    eng = hvd._engine()
    eng.replay.invalidate_all("test isolation")
    shared, expert = tfm.moe_ep_partition(params, 0, 1, cfg)
    opt = optax.sgd(1.0)
    step = tfm.make_moe_ep_train_step(eng, cfg, opt)
    shared2, expert2, _, loss = step(
        shared, expert, opt.init({"shared": shared, "expert": expert}), x, y)
    after = {**shared2, "layers": {**shared2["layers"], **expert2}}
    return loss, jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), params, after)


@pytest.mark.parametrize("field", list(NEW_FIELDS))
@pytest.mark.parametrize("builder", ["make_pp_train_step",
                                     "make_pp_engine_train_step",
                                     "make_moe_ep_train_step"])
def test_every_builder_runs_the_one_block(builder, field):
    """The pipeline and MoE-EP builders run the block ``make_train_step``
    runs: with one of its fields set they give the single-device loss and
    gradients of that configuration. What their first and last stage (and
    MoE-EP's loss segment) cannot run, more than one pass and an untied
    head, they refuse by name."""
    moe = builder == "make_moe_ep_train_step"
    if moe and field == "ffn":
        # the configuration's own refusal: use_moe replaces the dense FFN
        with pytest.raises(ValueError, match="ffn"):
            dataclasses.replace(PLAIN, use_moe=True, ffn="swiglu")
        return
    cfg = dataclasses.replace(PLAIN, **{field: NEW_FIELDS[field]})
    if moe:
        cfg = dataclasses.replace(cfg, use_moe=True, n_experts=4)
    if field in STILL_REFUSED:
        args = (None, cfg, optax.sgd(0.1)) if moe else (
            Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,)), cfg,
            optax.sgd(0.1), 2)
        with pytest.raises(ValueError, match=f"{builder}.*{field}="):
            getattr(tfm, builder)(*args)
        return
    params, (x, y) = _params(cfg), _tokens()
    want_loss, want = jax.value_and_grad(
        lambda p: tfm.lean_lm_loss(p, x, y, cfg))(params)
    loss, got = (_moe_ep_step(cfg, params, x, y) if moe
                 else _pp_step(builder, cfg, params, x, y))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert set(got) == set(want) and set(got["layers"]) == set(want["layers"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, **BAND), got, want)


# -- the names inside the program ------------------------------------------

@pytest.fixture(scope="module")
def looped_step_hlo():
    mesh = _mesh()
    cfg = dataclasses.replace(LOOPED, attention="flash", remat="block")
    opt = optax.adamw(1e-3)
    params = _params(cfg)
    x, y = _tokens(rows=1)
    return tfm.make_train_step(mesh, cfg, opt).lower(
        params, opt.init(params), x, y).compile().as_text()


def _under(op_name, scope):
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


@pytest.mark.parametrize("scope", [scopes.LOOP, scopes.ROPE,
                                   scopes.EXIT_GATE, scopes.HEAD,
                                   scopes.LOSS, scopes.ATTN, scopes.FFN,
                                   scopes.LAYERS])
@pytest.mark.parametrize("phase", ["forward", "backward"])
def test_scope_in_each_pass_of_the_looped_step(looped_step_hlo, scope,
                                               phase):
    found = [n for n in set(re.findall(r'op_name="([^"]*)"',
                                       looped_step_hlo))
             if _under(n, scope)]
    if phase == "forward":
        found = [n for n in found if "jvp(" in n and "transpose(" not in n]
    else:
        found = [n for n in found if "transpose(jvp(" in n]
    assert found, f"no {phase} operation under scope {scope!r}"


def test_what_block_remat_runs_again_is_named(looped_step_hlo):
    """``recompute_ms_per_step`` reads ``rematted_computation``: layers and,
    under the loop, the exits."""
    again = [n for n in set(re.findall(r'op_name="([^"]*)"',
                                       looped_step_hlo))
             if "rematted_computation" in n]
    assert [n for n in again if _under(n, scopes.FFN)]
    assert [n for n in again if _under(n, scopes.HEAD)]


# -- the cell's own checks, at the rehearsal's widths ----------------------

def test_the_program_in_bfloat16_is_inside_every_band():
    job, state, checks = ouro_defects.cell_checks(seed=1)
    ouro_defects.say("program", ouro_defects.readings(checks))
    assert checks["reference"]["ok"] and checks["loop_grad"]["ok"], checks
    # the step that loop_grad took left a state the loop can go on from
    state, loss = job.step(state, job.batch(0))
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("name", list(ouro_defects.DEFECTS))
def test_a_wrong_model_misses_a_band_by_far(name):
    _, _, checks = ouro_defects.cell_checks(
        seed=1, defect=ouro_defects.DEFECTS[name])
    found = ouro_defects.readings(checks)
    ouro_defects.say(name, found)
    assert not (checks["reference"]["ok"] and checks["loop_grad"]["ok"])
    assert max(v / b for v, b in found.values()) > 3
