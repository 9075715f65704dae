"""The state-space/attention sparse-expert decoder (``TransformerConfig``
with ``layers`` whose every layer is ONE sublayer: a Mamba-2 mixer alone,
attention alone, or the routed-expert FFN alone, its experts ``relu(.)^2``
with no gate and a shared expert of another width) through
``make_train_step`` against the plain float32 reference of the benchmark
(``benchmark/reference/nemotron-3-nano-30b-a3b.py``, which runs the
recurrence token by token and shares no code with the program); the chunked
scan alone; the share test of the model-configs guide; patterns that mix
layers of one and of two sublayers; what refuses the scan; and what the new
kinds leave alone.
"""

import dataclasses
import functools
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
from horovod_tpu.parallel import moe
from horovod_tpu.parallel import ssd
from horovod_tpu.parallel.ssd import ssd_chunked

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
for _p in (os.path.join(BENCH, "readers"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import files                # noqa: E402  (benchmark/files.py)

CONFIG = "nemotron-3-nano-30b-a3b"
K = tfm.LayerKind
M = K(mixer="mamba2", experts=None, rope=False)
E = K(mixer="none", experts=True, rope=False)
A = K(mixer="attention", experts=None, rope=False)
# the cell's own pattern: the first seven letters of the published one
KINDS = (M, E, M, E, M, A, E)
SMALL = tfm.TransformerConfig(
    vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, head_size=16,
    n_layers=7, max_seq=32, dtype=jnp.float32, attention="flash",
    positions="none", ffn="swiglu", norm="pre", norm_eps=1e-5,
    tie_embeddings=False, layers=KINDS, conv_kernel=4, ssm_heads=8,
    ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=8, n_experts=8,
    moe_top_k=2, d_ff_expert=24, expert_ffn="relu2", n_shared_experts=1,
    d_ff_shared=48, route_scale=2.5, router_bias_rate=1e-3,
    remat_barrier=True)
TIGHT = 2e-5        # float32 on both sides: the order of the sums differs


@pytest.fixture(scope="module")
def reference():
    ref = files.reference_module(CONFIG)
    ref.ROWS = 8        # four blocks of attention rows, through lax.map
    ref.SEGMENT = 8     # four recomputed pieces of the recurrence
    return ref


@pytest.fixture(scope="module")
def model():
    return files.config_module(CONFIG)


def _params(cfg=SMALL, seed=0):
    """Seeded weights with the norms' scales and ``ssm_D`` off 1 and a
    selection bias off 0, so that a norm that is skipped, a skip that is
    left out or a bias that is ignored shows."""
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
    """The reference's logits, choices, loss and gradients for the seeded
    weights and tokens, in the program's tree."""
    params, (inputs, targets) = _params(), _tokens()
    weights = model.to_reference(params, SMALL)

    @jax.jit        # (one program: op by op the float32 reference is slow)
    def run(weights):
        logits, choices = reference.forward(weights, inputs, top_k=2,
                                            groups=2)
        return (logits, jnp.stack(choices),
                reference.loss(weights, inputs, targets, top_k=2, groups=2),
                reference.grads(weights, inputs, targets, 0, jax.checkpoint,
                                2, None, 2))

    with jax.default_matmul_precision("highest"):
        logits, choices, loss, grads = run(weights)
    return {"logits": logits, "choices": choices, "loss": loss,
            "grads": model.from_reference(grads, SMALL)}


@pytest.fixture(scope="module")
def stepped():
    with jax.default_matmul_precision("highest"):
        return _sgd_step(SMALL, _params(), *_tokens())


def test_a_layer_of_one_sublayer_has_one_norm_and_a_stack_of_its_kind():
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, SMALL),
                            jax.random.PRNGKey(0))
    assert {k: next(iter(v.values())).shape[0] for k, v in shapes.items()
            if isinstance(v, dict)} == {
        "mamba_mixers": 3, "expert_ffns": 3, "attn_mixers": 1}
    assert set(shapes["mamba_mixers"]) == {
        "ln1", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_A_log", "ssm_D", "ssm_norm", "ssm_out"}
    assert set(shapes["attn_mixers"]) == {"ln1", "wq", "wk", "wv", "wo"}
    # relu2: two matrices an expert, no gate; the shared one of its own width
    assert set(shapes["expert_ffns"]) == {
        "ln2", "router", "router_bias", "ewu", "ewd", "shared_wu",
        "shared_wd"}
    assert shapes["mamba_mixers"]["ssm_in"].shape == (3, 64, 2 * 64 + 64 + 8)
    assert shapes["mamba_mixers"]["ssm_conv_w"].shape == (3, 4, 64 + 64)
    assert shapes["expert_ffns"]["ewu"].shape == (3, 8, 64, 24)
    assert shapes["expert_ffns"]["shared_wu"].shape == (3, 64, 48)
    assert tfm.layer_rows(SMALL) == [
        ("mamba_mixers", 0), ("expert_ffns", 0), ("mamba_mixers", 1),
        ("expert_ffns", 1), ("mamba_mixers", 2), ("attn_mixers", 0),
        ("expert_ffns", 2)]
    # seven runs of one layer each
    assert [len(kinds) for _, _, kinds in tfm._segments(SMALL)] == [1] * 7


def test_the_mixers_initial_values_are_the_models():
    p = tfm.init_params(jax.random.PRNGKey(3), SMALL)["mamba_mixers"]
    a = np.exp(np.asarray(p["ssm_A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(p["ssm_dt_bias"]))
    assert dt.min() >= 1e-3 * (1 - 1e-5) and dt.max() <= 0.1 * (1 + 1e-5)
    assert np.all(np.asarray(p["ssm_D"]) == 1.0)
    assert np.abs(np.asarray(p["ssm_conv_b"])).max() <= 0.5
    assert np.abs(np.asarray(p["ssm_conv_b"])).max() > 0.25


def test_the_published_parameter_count(model):
    """528,093,120 parameters at the cell's cut: a state-space layer
    38,744,896, the attention layer 23,399,040, an expert layer (8 of 128
    held, the shared expert of 3712) 100,125,440, embedding and untied head
    44,040,192 each, the final norm 2,688."""
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(files.cell(
        "nemotron3-spmd-1chip-ep16share-8k")["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    size = lambda tree: sum(int(np.prod(x.shape)) for x in  # noqa: E731
                            jax.tree_util.tree_leaves(tree))
    assert size(shapes) == 528_093_120
    assert size(shapes["mamba_mixers"]) == 3 * 38_744_896
    assert size(shapes["attn_mixers"]) == 23_399_040
    assert size(shapes["expert_ffns"]) == 3 * 100_125_440
    assert shapes["mamba_mixers"]["ssm_in"].shape == (3, 2688, 10304)
    assert shapes["expert_ffns"]["shared_wd"].shape == (3, 3712, 2688)


@pytest.mark.parametrize("remat", ["none", "block"])
def test_logits_and_choices_against_the_reference(wanted, remat):
    cfg = dataclasses.replace(SMALL, remat=remat)
    with jax.default_matmul_precision("highest"):
        logits, routes = jax.jit(lambda p, x: tfm.forward_routes(p, x, cfg))(
            _params(), _tokens()[0])
    _close(logits, wanted["logits"])
    assert np.array_equal(np.sort(routes.expert, -1),
                          np.sort(wanted["choices"], -1))
    assert routes.counts.shape == (3, 8)


def test_loss_against_the_reference(wanted, stepped):
    assert float(stepped[1]) == pytest.approx(float(wanted["loss"]),
                                              rel=TIGHT)


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_through_the_train_step(wanted, stepped, leaf):
    """Every leaf: ``ssm_A_log``, ``ssm_dt_bias``, ``ssm_D`` and the
    convolution's bias among them. The selection bias takes no gradient and
    is moved by the step's own rule instead."""
    got, want = _leaves(stepped[3])[leaf], _leaves(wanted["grads"])[leaf]
    if leaf.endswith("['router_bias']"):
        assert float(jnp.max(jnp.abs(want))) == 0.0
        # the bias step: 0 for an expert whose count is the mean
        assert np.all(np.isclose(np.abs(got), 1e-3, atol=1e-7) | (got == 0))
        assert np.mean(got != 0) > 0.8
    else:
        assert float(jnp.max(jnp.abs(want))) > 0.0
        _close(got, want, 2e-4)


def test_in_bfloat16_against_the_float32_reference(reference, model, wanted):
    """The step's own precision: bfloat16 products with float32
    accumulation, the decays and norms in float32. The reference is GIVEN
    the program's choices, as on the chip."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.bfloat16)
    params, (inputs, _) = _params(), _tokens()
    logits, routes = jax.jit(lambda p, x: tfm.forward_routes(p, x, cfg))(
        params, inputs)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda w, given: reference.forward(
            w, inputs, top_k=2, given=given, groups=2))(
            model.to_reference(params, SMALL), list(routes.expert))
    _close(logits.astype(jnp.float32), want, 1e-1)


def test_the_step_returns_the_counts_and_moves_the_bias(stepped):
    new, _, stats, _ = stepped
    counts = np.asarray(stats[0]["expert_counts"])
    assert counts.shape == (3, 8) and (counts.sum(axis=1) == 2 * 32 * 2).all()
    before = _params()["expert_ffns"]["router_bias"]
    moved = np.asarray(new["expert_ffns"]["router_bias"]) - np.asarray(before)
    want = 1e-3 * np.sign(counts.mean(axis=1, keepdims=True) - counts)
    assert np.allclose(moved, want, atol=1e-7)


@pytest.mark.parametrize("mesh", [(2, 1, 1), (4, 1, 1)],
                         ids=["data2", "data4"])
def test_a_data_mesh_takes_the_same_step(stepped, mesh):
    """Every new stack's gradient is summed inside the backward scan."""
    inputs, targets = _tokens(rows=4)
    with jax.default_matmul_precision("highest"):
        one = _sgd_step(SMALL, _params(), inputs, targets)
        many = _sgd_step(SMALL, _params(), inputs, targets, _mesh(*mesh))
    assert float(many[1]) == pytest.approx(float(one[1]), rel=1e-5)
    for leaf, want in _leaves(one[3]).items():
        _close(_leaves(many[3])[leaf], want, 1e-4)
    axes = tfm.grad_reduce_axes(_mesh(*mesh), SMALL)
    assert set(tfm._in_backward(axes, SMALL)) == {
        "mamba_mixers", "expert_ffns", "attn_mixers", "lm_head"}


# -- the chunked scan alone ------------------------------------------------

def _scan_inputs(t, dt_value=None, seed=0, b=2, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = (jnp.full((b, t, h), dt_value) if dt_value is not None else
          jnp.exp(jax.random.uniform(ks[1], (b, t, h), jnp.float32,
                                     np.log(1e-3), np.log(1e-1))))
    return (jax.random.normal(ks[0], (b, t, h, p)), dt,
            -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0),
            jax.random.normal(ks[3], (b, t, g, n)),
            jax.random.normal(ks[4], (b, t, g, n)),
            jax.random.normal(ks[5], (h,)))


def _recurrence(reference, x, dt, a, b, c, d):
    reads = reference.group_of(jnp.arange(x.shape[2]), x.shape[2],
                               b.shape[2])
    return reference.recurrence(x, dt, a, b[:, :, reads], c[:, :, reads]) \
        + d[:, None] * x


@pytest.mark.parametrize("t, chunk", [(16, 16), (32, 16), (64, 8),
                                      (128, 128), (96, 32)],
                         ids=["one-chunk", "two", "eight", "one-of-128",
                              "three"])
@pytest.mark.parametrize("dt_value", [None, 1e-3, 1e-1],
                         ids=["drawn", "smallest", "largest"])
def test_the_chunked_scan_against_the_recurrence(reference, t, chunk,
                                                 dt_value):
    args = _scan_inputs(t, dt_value)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: ssd_chunked(*a, chunk))(*args)
        want = jax.jit(lambda *a: _recurrence(reference, *a))(*args)
    assert got.dtype == jnp.float32
    _close(got, want, 1e-5)


def test_the_chunked_scans_gradients_against_the_recurrences(reference):
    args = _scan_inputs(48, seed=2)
    g = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_chunked(*a, 16) * g),
                               range(6)))(*args)
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(_recurrence(reference, *a) * g),
            range(6)))(*args)
    for got_, want_ in zip(got, want):
        _close(got_, want_, 1e-4)


def test_the_scans_products_take_the_inputs_dtype_and_sum_in_float32():
    """bfloat16 operands, float32 results of every product: the lowered
    text holds no product that returns bfloat16."""
    x, dt, a, b, c, d = _scan_inputs(32)
    text = jax.jit(lambda *a: ssd_chunked(*a, 16)).lower(
        x.astype(jnp.bfloat16), dt, a, b, c, d).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert len(dots) == 4
    for line in dots:
        assert "bf16" in line.split("->")[0], line
        assert "f32" in line.split("->")[1] and "bf16" not in \
            line.split("->")[1], line


def test_the_scan_is_causal_to_the_bit():
    x, dt, a, b, c, d = _scan_inputs(64)
    run = jax.jit(lambda *a: ssd_chunked(*a, 16))
    whole = run(x, dt, a, b, c, d)
    later = run(x.at[:, 40:].set(7.0), dt, a, b.at[:, 40:].set(-3.0),
                c.at[:, 40:].set(5.0), d)
    assert np.array_equal(np.asarray(whole[:, :40]),
                          np.asarray(later[:, :40]))


# -- the scan's kernels, interpreted (the CPU has no Mosaic): the smallest
# shapes that tile, against the recurrence and against the chunked form ------

# rows, tokens, heads, head, groups, state, chunk
TILED = {"a-pair-of-64": (1, 256, 2, 64, 1, 128, 128),
         "two-groups": (2, 384, 4, 64, 2, 128, 128),
         "a-head-a-tile": (1, 256, 2, 128, 2, 128, 128),
         "a-chunk-of-256": (1, 512, 2, 64, 1, 128, 256)}


def _tiled_inputs(shape, dt_value=None, seed=0):
    rows, t, h, p, g, n, chunk = TILED[shape]
    return _scan_inputs(t, dt_value, seed, rows, h, p, g, n), chunk


def _interpreted(chunk):
    return jax.jit(lambda *a: ssd.ssd_kernels(*a, chunk, interpret=True))


@pytest.mark.parametrize("shape", sorted(TILED))
@pytest.mark.parametrize("dt_value", [None, 1e-3, 1e-1],
                         ids=["drawn", "smallest", "largest"])
def test_the_scans_kernels_against_the_recurrence(reference, shape, dt_value):
    args, chunk = _tiled_inputs(shape, dt_value)
    with jax.default_matmul_precision("highest"):
        got = _interpreted(chunk)(*args)
        want = jax.jit(lambda *a: _recurrence(reference, *a))(*args)
        spec = jax.jit(lambda *a: ssd._ssd_numpy(*a, chunk))(*args)
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    _close(got, want, 1e-5)
    # the same sums in the same order as the chunked form's, but for the
    # order inside a product
    _close(got, spec, 2e-6)


@pytest.mark.parametrize("shape", ["two-groups", "a-head-a-tile"])
def test_the_scans_kernels_gradients_against_the_recurrences(reference,
                                                             shape):
    """The written backward, all six arguments."""
    args, chunk = _tiled_inputs(shape, seed=2)
    g = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.grad(lambda *a: jnp.sum(ssd.ssd_kernels(
            *a, chunk, interpret=True) * g), range(6)))(*args)
        want = jax.jit(jax.grad(
            lambda *a: jnp.sum(_recurrence(reference, *a) * g),
            range(6)))(*args)
    for got_, want_ in zip(got, want):
        assert got_.shape == want_.shape and got_.dtype == want_.dtype
        _close(got_, want_, 1e-4)


def test_the_scans_kernels_under_checkpoint_keep_no_states_the_first_time():
    """``jax.checkpoint`` around the scan: the forward pass runs the kernel
    that writes ``y`` alone, the recomputation the one that keeps the
    chunks' incoming states, and the gradients are the same."""
    args, chunk = _tiled_inputs("a-pair-of-64", seed=3)

    def loss(*a):
        return jnp.sum(jnp.square(ssd.ssd_kernels(*a, chunk,
                                                  interpret=True)))

    def kernels(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for inner in jax.core.jaxprs_in_params(eqn.params):
                found += kernels(inner)
        return found

    again = jax.grad(jax.checkpoint(loss), range(6))
    assert kernels(jax.make_jaxpr(again)(*args).jaxpr) == [
        "ssd_scan_fwd", "ssd_scan_fwd_states", "ssd_scan_bwd"]
    assert kernels(jax.make_jaxpr(jax.grad(loss, range(6)))(
        *args).jaxpr) == ["ssd_scan_fwd_states", "ssd_scan_bwd"]
    assert kernels(jax.make_jaxpr(loss)(*args).jaxpr) == ["ssd_scan_fwd"]
    again = jax.jit(again)
    for got_, want_ in zip(again(*args), jax.jit(jax.grad(loss, range(6)))(
            *args)):
        assert np.array_equal(np.asarray(got_), np.asarray(want_))


@pytest.mark.parametrize("shape", ["a-pair-of-64", "two-groups"])
def test_the_scans_kernels_in_bfloat16_are_no_further_off_than_the_chunked_form(
        reference, shape):
    """bfloat16 X, B and C: against the float32 recurrence ON THE SAME
    operands the kernels read what the chunked form reads (they round where
    it rounds), the result and every gradient."""
    (x, dt, a, b, c, d), chunk = _tiled_inputs(shape, seed=4)
    args = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
            c.astype(jnp.bfloat16), d)
    exact = tuple(v.astype(jnp.float32) for v in args)
    g = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def both(form):
        return jax.jit(lambda *a: (form(*a), *jax.grad(
            lambda *a_: jnp.sum(form(*a_) * g), range(6))(*a)))

    def off(got, want):
        return float(jnp.sqrt(jnp.sum(jnp.square(
            got.astype(jnp.float32) - want)) / jnp.sum(jnp.square(want))))

    with jax.default_matmul_precision("highest"):
        want = both(lambda *a: _recurrence(reference, *a))(*exact)
    kernels = both(lambda *a: ssd.ssd_kernels(*a, chunk, interpret=True))(
        *args)
    chunked = both(lambda *a: ssd._ssd_numpy(*a, chunk))(*args)
    assert kernels[0].dtype == jnp.float32
    assert off(kernels[0], want[0]) <= 1.03 * off(chunked[0], want[0])
    assert off(kernels[0], want[0]) < 2.5e-3    # the cell's own limit
    for got_, spec_, want_ in zip(kernels[1:], chunked[1:], want[1:]):
        assert got_.dtype == spec_.dtype
        assert off(got_, want_) <= 1.25 * off(spec_, want_) + 1e-5


def test_the_scans_kernels_are_causal_to_the_bit():
    (x, dt, a, b, c, d), chunk = _tiled_inputs("two-groups")
    run = _interpreted(chunk)
    whole = run(x, dt, a, b, c, d)
    later = run(x.at[:, 200:].set(7.0), dt, a, b.at[:, 200:].set(-3.0),
                c.at[:, 200:].set(5.0), d)
    assert np.array_equal(np.asarray(whole[:, :200]),
                          np.asarray(later[:, :200]))


# the cell's own shapes: 2 rows of 8,192, 64 heads of 64, 8 groups of 128
CELL = ((2, 8192, 64, 64), (2, 8192, 8, 128), 128)


@pytest.mark.parametrize("backend, x, bc, chunk, form, heads", [
    ("cpu", *CELL, "chunked", 0),
    ("tpu", *CELL, "kernel", 8),
    ("tpu", (1, 8192, 64, 64), (1, 8192, 8, 128), 128, "kernel", 8),
    ("tpu", (2, 128, 8, 8), (2, 128, 2, 16), 16, "chunked", 0),
    ("tpu", (2, 8192, 64, 64), (2, 8192, 8, 128), 64, "chunked", 0),
    ("tpu", (2, 8064, 64, 64), (2, 8064, 8, 128), 96, "chunked", 0),
    ("tpu", (2, 8192, 64, 64), (2, 8192, 8, 64), 128, "chunked", 0),
    ("tpu", (2, 8192, 64, 64), (2, 8192, 64, 128), 128, "chunked", 0),
    ("tpu", (2, 8192, 64, 96), (2, 8192, 16, 128), 128, "chunked", 0),
    ("tpu", (2, 8192, 16, 128), (2, 8192, 8, 128), 256, "kernel", 2),
    ("tpu", (1, 2048, 16, 64), (1, 2048, 1, 128), 512, "chunked", 0),
    ("gpu", *CELL, "chunked", 0),
], ids=["the-cpu", "the-cell", "the-cells-checks-one-row",
        "the-rehearsal", "a-chunk-of-64", "a-chunk-of-96", "a-state-of-64",
        "a-head-a-group", "a-head-of-96", "heads-of-128",
        "blocks-past-the-vmem", "a-gpu"])
def test_the_scans_form_is_a_function_of_the_backend_and_the_shapes(
        monkeypatch, backend, x, bc, chunk, form, heads):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssd.scan_form(x, bc, chunk) == {
        "form": form, "chunk": str(chunk), "heads_per_block": str(heads)}


def test_the_scan_runs_the_form_its_shapes_give(monkeypatch):
    """``ssd_chunked`` asks ``scan_form`` and nothing else: here, on the
    CPU, the chunked form at shapes that would tile; told "kernel", the
    kernels (which the CPU cannot lower uninterpreted: the call is seen,
    not run)."""
    (x, dt, a, b, c, d), chunk = _tiled_inputs("a-pair-of-64")
    text = jax.jit(lambda *a_: ssd_chunked(*a_, chunk)).lower(
        x, dt, a, b, c, d).as_text(debug_info=True)
    assert "ssm_scan/while" in text and "pallas_call" not in text
    seen = []
    monkeypatch.setattr(ssd, "scan_form", lambda *shapes: {"form": "kernel"})
    monkeypatch.setattr(ssd, "ssd_kernels", lambda *a_: seen.append(a_[-1])
                        or a_[0].astype(jnp.float32))
    ssd_chunked(x, dt, a, b, c, d, chunk)
    assert seen == [chunk]


# -- the taps' kernels, interpreted: 2 rows, 3 blocks of T, X, B and C in 4
# blocks of channels read from the middle of a wider operand ----------------

TAPS_PARTS, TAPS_START = (256, 128, 128), 128


def _taps_inputs(dtype, rows=2, t=768, seed=0):
    """The operand ``[rows, T, 128 + 512 + 64]`` (X, B, C from column 128,
    the last 64 columns nobody reads), the taps and the bias drawn as
    ``init_params`` draws them, and a cotangent for each part."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    width = sum(TAPS_PARTS)
    u = jax.random.normal(ks[0], (rows, t, TAPS_START + width + 64))
    w = jax.random.normal(ks[1], (4, width)) * 0.5
    b = jax.random.uniform(ks[2], (width,), jnp.float32, -0.5, 0.5)
    gs = [jax.random.normal(k, (rows, t, p)).astype(dtype)
          for k, p in zip(jax.random.split(ks[3], 3), TAPS_PARTS)]
    return (u.astype(dtype), w, b), gs


def _taps_interpreted(dtype, blocks):
    return lambda u, w, b: ssd.taps_kernels(
        u, w, b, TAPS_PARTS, TAPS_START, dtype, blocks, interpret=True)


def _taps_numpy(dtype):
    return lambda u, w, b: ssd._taps_numpy(u, w, b, TAPS_PARTS, TAPS_START,
                                           dtype)


@pytest.mark.parametrize("blocks", [(256, 128), (128, 128)],
                         ids=["3-blocks-of-t", "6-blocks-of-t"])
@pytest.mark.parametrize("dtype, du_tol", [(jnp.float32, 1e-6),
                                           (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
def test_the_taps_kernels_against_the_jnp_form(blocks, dtype, du_tol):
    """The parts, and the cotangents of the operand, the taps and the bias.
    The sums are the ``"jnp"`` form's in float32 with one rounding of each
    result: the parts to the bit, the cotangents to their order of sums (in
    bfloat16 ``du`` rounds once on each side: an element may land a unit of
    the last place apart)."""
    args, gs = _taps_inputs(dtype)

    def both(form):
        def weighed(*a):
            return sum(jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))
                       for o, g in zip(form(*a), gs))
        return jax.jit(lambda *a: (form(*a), jax.grad(weighed, range(3))(
            *a)))

    (got, got_d), (want, want_d) = (both(f)(*args) for f in (
        _taps_interpreted(dtype, blocks), _taps_numpy(dtype)))
    for got_, want_ in zip(got, want):
        assert got_.dtype == want_.dtype == dtype
        assert np.array_equal(np.asarray(got_), np.asarray(want_))
    for got_, want_, tol in zip(got_d, want_d, (du_tol, 1e-6, 1e-6)):
        assert got_.shape == want_.shape and got_.dtype == want_.dtype
        _close(got_.astype(jnp.float32), want_.astype(jnp.float32), tol)
    # nothing reaches the columns the taps do not read
    du = np.asarray(got_d[0])
    assert not du[..., :TAPS_START].any() and not du[..., -64:].any()


def test_the_taps_kernels_are_causal_and_each_row_starts_from_zeros():
    """A row's outputs before a position do not see the row from there on;
    a row's first positions see nothing of the row before it (its first
    block starts from zeros, not from the last rows the kernel carried)."""
    (u, w, b), _ = _taps_inputs(jnp.float32)
    run = jax.jit(_taps_interpreted(jnp.float32, (256, 128)))
    whole = np.concatenate([np.asarray(p) for p in run(u, w, b)], axis=-1)
    later = np.concatenate([np.asarray(p) for p in run(
        u.at[:, 300:].set(7.0), w, b)], axis=-1)
    assert np.array_equal(whole[:, :300], later[:, :300])
    assert not np.array_equal(whole[:, 300:], later[:, 300:])
    other_row = np.concatenate([np.asarray(p) for p in run(
        u.at[0].set(-3.0), w, b)], axis=-1)
    assert np.array_equal(whole[1], other_row[1])
    # the first positions of a row are the taps over zeros before it
    first = jax.nn.silu(w[3] * u[:, 0, TAPS_START:TAPS_START + 512] + b)
    _close(jnp.asarray(whole[:, 0]), first, 1e-6)


def test_the_taps_kernels_under_checkpoint_run_the_forward_twice():
    """``jax.checkpoint`` around the taps and what reads their outputs: the
    forward kernel in the forward pass and again in the backward (which
    reads the outputs), then the written backward, which keeps nothing but
    the operands; the gradients are the same bits."""
    args, gs = _taps_inputs(jnp.bfloat16)

    def loss(*a):
        return sum(jnp.sum(jnp.square(o.astype(jnp.float32))
                           * g.astype(jnp.float32))
                   for o, g in zip(_taps_interpreted(jnp.bfloat16, (256, 128))(
                       *a), gs))

    def kernels(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for inner in jax.core.jaxprs_in_params(eqn.params):
                found += kernels(inner)
        return found

    again = jax.value_and_grad(jax.checkpoint(loss), range(3))
    assert kernels(jax.make_jaxpr(again)(*args).jaxpr) == [
        "ssm_taps_fwd", "ssm_taps_fwd", "ssm_taps_bwd"]
    for got_, want_ in zip(jax.tree_util.tree_leaves(jax.jit(again)(*args)),
                           jax.tree_util.tree_leaves(jax.jit(
                               jax.value_and_grad(loss, range(3)))(*args))):
        assert np.array_equal(np.asarray(got_), np.asarray(want_))


# the in-projection's output, taps, X, B and C's widths, where X starts
TAPS_CELL = ((2, 8192, 10304), 4, (4096, 1024, 1024), 4096)


@pytest.mark.parametrize("backend, shape, taps, parts, start, form, blocks", [
    ("cpu", *TAPS_CELL, "jnp", (0, 0)),
    ("tpu", *TAPS_CELL, "kernel", ssd.TAPS_BLOCKS),
    ("tpu", (1, 8192, 10304), 4, (4096, 1024, 1024), 4096, "kernel",
     ssd.TAPS_BLOCKS),
    ("tpu", (2, 32, 192), 4, (64, 32, 32), 64, "jnp", (0, 0)),
    ("tpu", (2, 8192, 10304), 4, (4096, 1024, 1024), 4032, "jnp", (0, 0)),
    ("tpu", (2, 8064, 10304), 4, (4096, 1024, 1024), 4096, "kernel",
     (128, ssd.TAPS_BLOCKS[1])),
    ("tpu", (2, 8000, 10304), 4, (4096, 1024, 1024), 4096, "jnp", (0, 0)),
    ("tpu", (2, 8192, 10304), 9, (4096, 1024, 1024), 4096, "jnp", (0, 0)),
    ("tpu", (2, 8192, 2688), 4, (1024, 128, 128), 1024, "kernel",
     (ssd.TAPS_BLOCKS[0], 128)),
    ("gpu", *TAPS_CELL, "jnp", (0, 0)),
], ids=["the-cpu", "the-cell", "the-cells-checks-one-row", "the-rehearsal",
        "a-part-off-the-lanes", "t-of-8064", "t-of-8000", "nine-taps", "parts-of-128",
        "a-gpu"])
def test_the_taps_form_is_a_function_of_the_backend_and_the_shapes(
        monkeypatch, backend, shape, taps, parts, start, form, blocks):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssd.taps_form(shape, taps, parts, start) == {
        "form": form, "block_t": str(blocks[0]), "block_c": str(blocks[1])}


def test_the_mixer_runs_the_taps_form_its_shapes_give(monkeypatch):
    """``mamba_mix`` asks ``taps_form`` and nothing else: on the CPU and at
    the rehearsal's widths the ``"jnp"`` form; told "kernel", the kernels
    with the projection's output as it is, X, B, C from column ``H P``."""
    one = dataclasses.replace(SMALL, layers=(M,), n_layers=1)
    lp = {k: v[0] for k, v in _params(one)["mamba_mixers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    text = jax.jit(lambda x_: tfm.mamba_mix(x_, lp, cfg=one)).lower(
        x).as_text(debug_info=True)
    assert "ssm_conv/split" in text and "pallas_call" not in text
    seen = []

    def kernels(u, w, b, parts, start, dtype, blocks):
        seen.append((u.shape, parts, start, dtype, blocks))
        return ssd._taps_numpy(u, w, b, parts, start, dtype)

    monkeypatch.setattr(ssd, "taps_form", lambda *shapes: {
        "form": "kernel", "block_t": "16", "block_c": "32"})
    monkeypatch.setattr(ssd, "taps_kernels", kernels)
    tfm.mamba_mix(x, lp, cfg=one)
    inner, gn = 64, 32
    assert seen == [((2, 32, 2 * inner + 2 * gn + 8), (inner, gn, gn), inner,
                     jnp.dtype(jnp.float32), (16, 32))]


def test_the_mixer_with_the_taps_kernels_against_the_jnp_form(monkeypatch):
    """A mixer whose X, B and C tile (2 heads of 64, one group of state 128:
    X 128, B and C 128 each from column 128), its output and the gradients
    of its leaves with the taps' kernels interpreted against the ``"jnp"``
    form."""
    cfg = dataclasses.replace(
        SMALL, layers=(M,), n_layers=1, ssm_heads=2, ssm_head_dim=64,
        ssm_groups=1, ssm_state=128, ssm_chunk=64, max_seq=256)
    lp = {k: v[0] for k, v in _params(cfg)["mamba_mixers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 256, 64))
    g = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def run():
        return jax.jit(lambda x_, lp_: (
            tfm.mamba_mix(x_, lp_, cfg=cfg), jax.grad(
                lambda x__, lp__: jnp.sum(tfm.mamba_mix(x__, lp__, cfg=cfg)
                                          * g), (0, 1))(x_, lp_)))(x, lp)

    with jax.default_matmul_precision("highest"):
        want = run()
        monkeypatch.setattr(ssd, "taps_form", lambda *shapes: {
            "form": "kernel", "block_t": "128", "block_c": "128"})
        monkeypatch.setattr(ssd, "taps_kernels", functools.partial(
            ssd.taps_kernels, interpret=True))
        got = run()
    for got_, want_ in zip(jax.tree_util.tree_leaves(got),
                           jax.tree_util.tree_leaves(want)):
        _close(got_, want_, 1e-5)


def test_the_mixer_against_the_reference(reference):
    one = dataclasses.replace(SMALL, layers=(M,), n_layers=1)
    lp = {k: v[0] for k, v in _params(one)["mamba_mixers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        got = tfm.mamba_mix(x, lp, cfg=one)
        want = reference.mamba(x, lp, groups=2)
    _close(got, want)


def test_the_scopes_of_the_new_layers():
    """``mamba_mixer`` where an attention layer has ``attn``, and inside it
    ``ssm_conv``, ``ssm_scan``, ``ssm_gate_norm``; an expert-only layer
    keeps ``ffn`` and its inner scopes, an attention-only layer ``attn`` and
    ``attn_full``."""
    params, (inputs, targets) = _params(), _tokens()
    text = tfm.make_train_step(_mesh(), SMALL, optax.sgd(1.0)).lower(
        params, optax.sgd(1.0).init(params), inputs, targets
    ).as_text(debug_info=True)
    for name in ("mamba_mixer/btd,de->bte/dot_general",
                 "mamba_mixer/bte,ed->btd/dot_general",
                 "mamba_mixer/ssm_conv/pad",
                 "mamba_mixer/ssm_conv/add", "mamba_mixer/jit(softplus)",
                 "mamba_mixer/ssm_scan/jit(cumsum)",
                 "mamba_mixer/ssm_scan/bclgn,bcsgn->bcgls/dot_general",
                 "mamba_mixer/ssm_scan/while", "mamba_mixer/ssm_gate_norm/",
                 "ffn/router/", "/experts/", "ffn/shared_expert/",
                 "/moe_combine/", "attn/attn_full/",
                 "transpose(jvp(layers))"):
        assert name in text, name
    # the projections are outside the inner scopes; nothing rotates
    assert "ssm_scan/btd" not in text and "ssm_conv/btd" not in text
    assert "conv_mixer" not in text and "rope" not in text


# -- what refuses the scan says so -----------------------------------------

def test_tokens_that_are_no_multiple_of_the_chunk_are_refused_by_name():
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        ssd_chunked(*_scan_inputs(24), 16)
    cfg = dataclasses.replace(SMALL, ssm_chunk=24)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        jax.eval_shape(lambda p: tfm.forward_block(p, _tokens()[0], cfg),
                       _params(cfg))


@pytest.mark.parametrize("mesh, words", [
    ((1, 2, 1), "mamba2 mixer under sequence parallelism"),
    ((1, 1, 2), "mamba2 mixer under tensor parallelism")],
    ids=["seq2", "tensor2"])
def test_meshes_the_scan_cannot_run_on_refuse_it_by_name(mesh, words):
    params, (inputs, targets) = _params(), _tokens()
    m = _mesh(*mesh)
    with pytest.raises(ValueError, match=words):
        tfm.make_spmd_loss(m, SMALL)(tfm.shard_params(params, m, SMALL),
                                     inputs, targets)


@pytest.mark.parametrize("builder", ["make_pp_train_step",
                                     "make_moe_ep_train_step"])
def test_the_other_builders_refuse_the_new_layers_by_name(builder):
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    with pytest.raises(ValueError, match="mamba2 mixer .*one sublayer"):
        if builder == "make_pp_train_step":
            tfm.make_pp_train_step(mesh, SMALL, optax.sgd(1.0), n_micro=2)
        else:
            class Engine:       # refused before the engine is asked
                pass
            tfm.make_moe_ep_train_step(Engine(), SMALL, optax.sgd(1.0))


@pytest.mark.parametrize("changes, words", [
    ({"layers": (K(mixer="none", experts=None),) + KINDS[1:]},
     "has no stack to live in"),
    # kinds of layer that no model has brought yet
    ({"layers": (K(mixer="mamba2", rope=False),) + KINDS[1:]},
     "mixer 'mamba2' and experts=False has no stack"),
    ({"layers": (K(mixer="mamba2", experts=True, rope=False),) + KINDS[1:]},
     "mixer 'mamba2' and experts=True has no stack"),
    ({"layers": (K(mixer="conv", experts=None, rope=False),) + KINDS[1:]},
     "mixer 'conv' and experts=None has no stack"),
    ({"layers": (K(mixer="none"),) + KINDS[1:]},
     "mixer 'none' and experts=False has no stack"),
    ({"layers": (K(window=8, mixer="mamba2", experts=None),) + KINDS[1:]},
     "has no window"),
    ({"ssm_heads": 0}, "a mamba2 layer needs"),
    ({"ssm_groups": 3}, "a mamba2 layer needs"),
    ({"expert_ffn": "gelu"}, "unknown expert_ffn"),
])
def test_a_pattern_that_cannot_run_is_refused_by_name(changes, words):
    with pytest.raises(ValueError, match=words):
        dataclasses.replace(SMALL, **changes)


# -- the share test of the model-configs guide -----------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer(reference):
    """Experts 0-1, 2-3, ... 30-31 of 32 (the cell: 0-7, ... 120-127 of
    128), each program holding a sixteenth: the routed parts of the sixteen
    shares, and the shared expert counted ONCE, add up to what the
    reference gives for the whole layer."""
    whole = dataclasses.replace(SMALL, layers=(E,), n_layers=1, n_experts=32,
                                moe_top_k=6)
    full = tfm.init_params(jax.random.PRNGKey(4), whole)["expert_ffns"]
    lw = {k: v[0] for k, v in full.items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    with jax.default_matmul_precision("highest"):
        want, chosen = reference.expert_ffn(x, lw, 0, 6)
        shared = reference.shared(x, lw)
        parts = []
        for first in range(0, 32, 2):
            cfg = dataclasses.replace(whole, experts_held=2,
                                      first_expert=first)
            share = tfm.init_params(jax.random.PRNGKey(4),
                                    cfg)["expert_ffns"]
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


def test_the_two_products_of_relu2_against_the_loop_over_experts(reference):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(ks[0], (64, 32))
    wu = jax.random.normal(ks[1], (4, 32, 24)) / 6
    wd = jax.random.normal(ks[2], (4, 24, 32)) / 5
    sizes = jnp.array([20, 0, 30, 10], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = moe.grouped_relu2(rows, sizes, wu, wd)
        want = jnp.concatenate([
            reference.relu2(rows[lo:lo + n], wu[e], wd[e]) for e, (lo, n)
            in enumerate(zip((0, 20, 20, 50), (20, 0, 30, 10)))])
    _close(got[:60], want, 1e-5)


@pytest.mark.parametrize("m, k, n, want", [
    # 1856 = 14.5 x 128 under a hidden size of 2688 = 3 x 896
    (7680, 2688, 1856, (256, 896, moe.gmm_tiles(7680, 2688, 1856)[2])),
    (7680, 1856, 2688, (256, moe.gmm_tiles(7680, 1856, 2688)[1], 896)),
])
def test_the_tile_rule_at_a_width_no_multiple_of_128_divides(m, k, n, want):
    assert moe.gmm_tiles(m, k, n) == want
    for tile, size in zip(want[1:], (k, n)):
        assert tile % 128 == 0 or tile == size


# -- layers of one sublayer beside layers of two ---------------------------

TWO = K(rope=False)                                 # attention + dense FFN
TWO_E = K(experts=True, rope=False)                 # attention + routed experts
CONV_E = K(mixer="conv", experts=True, rope=False)  # conv + routed experts
MIXED = dataclasses.replace(
    SMALL, d_ff=40, layers=(M, TWO, E, CONV_E, A, TWO_E, M, TWO), n_layers=8)


def test_a_mixed_pattern_initialises_shards_and_steps():
    """One-sublayer layers between two-sublayer layers, a dense layer after
    the expert layers: every layer has a stack, and that is the one rule."""
    stacks = {"mamba_mixers", "dense_layers", "expert_ffns", "conv_layers",
              "attn_mixers", "layers"}
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, MIXED),
                            jax.random.PRNGKey(0))
    assert {k for k, v in shapes.items() if isinstance(v, dict)} == stacks
    assert shapes["dense_layers"]["wq"].shape[0] == 2
    specs = tfm.param_specs(MIXED)
    assert jax.tree_util.tree_structure(specs, is_leaf=lambda s: isinstance(
        s, jax.sharding.PartitionSpec)) == jax.tree_util.tree_structure(
        shapes)
    inputs, targets = _tokens(rows=4)
    with jax.default_matmul_precision("highest"):
        one = _sgd_step(MIXED, _params(MIXED), inputs, targets)
        two = _sgd_step(MIXED, _params(MIXED), inputs, targets, _mesh(2))
    assert np.isfinite(float(one[1]))
    assert np.asarray(one[2][0]["expert_counts"]).shape == (3, 8)
    assert set(tfm._in_backward(tfm.grad_reduce_axes(_mesh(2), MIXED),
                                MIXED)) == stacks - {
        "dense_layers", "conv_dense_layers"} | {"lm_head"}
    for leaf, want in _leaves(one[3]).items():
        if not leaf.endswith("['router_bias']"):
            assert float(jnp.max(jnp.abs(want))) > 0.0, leaf
        _close(_leaves(two[3])[leaf], want, 1e-4)


def test_a_pattern_without_the_scan_runs_over_tensor_too():
    cfg = dataclasses.replace(
        SMALL, d_ff=40, layers=(A, E, TWO, CONV_E), n_layers=4)
    inputs, targets = _tokens(rows=4)
    with jax.default_matmul_precision("highest"):
        one = _sgd_step(cfg, _params(cfg), inputs, targets)
        many = _sgd_step(cfg, _params(cfg), inputs, targets, _mesh(2, 1, 2))
    assert float(many[1]) == pytest.approx(float(one[1]), rel=1e-5)
    for leaf, want in _leaves(one[3]).items():
        _close(_leaves(many[3])[leaf], want, 1e-4)


def test_a_layer_of_two_sublayers_is_its_two_layers_of_one():
    """``attention + routed experts`` in one layer is an attention-only
    layer followed by an experts-only layer of the same leaves: the same
    loss and the same gradients, in other stacks."""
    both = dataclasses.replace(SMALL, layers=(M, TWO_E, M, TWO_E), n_layers=4)
    apart = dataclasses.replace(both, layers=(M, A, E, M, A, E), n_layers=6)
    params = _params(both)
    whole = params.pop("layers")
    mixer = ("ln1", "wq", "wk", "wv", "wo")
    split = {**params,
             "attn_mixers": {k: whole[k] for k in mixer},
             "expert_ffns": {k: v for k, v in whole.items()
                             if k not in mixer}}
    inputs, targets = _tokens()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(lambda p: tfm.lean_lm_loss(
            p, inputs, targets, both)))({**params, "layers": whole})
        got = jax.jit(jax.value_and_grad(lambda p: tfm.lean_lm_loss(
            p, inputs, targets, apart)))(split)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for k in whole:
        _close(got[1]["attn_mixers" if k in mixer else "expert_ffns"][k],
               want[1]["layers"][k], 1e-5)
    _close(got[1]["mamba_mixers"]["ssm_in"], want[1]["mamba_mixers"]["ssm_in"],
           1e-5)


def test_a_dense_layer_after_the_expert_layers_against_the_reference():
    """What ``dense layers lead`` refused until PR 39, against the
    conv/attention cell's plain reference, which takes layers in any
    order."""
    ref = files.reference_module("lfm2-8b-a1b")
    ref.ROWS, ref.TOP_K = 8, 2
    model = files.load_module(os.path.join(
        BENCH, "configs", "lfm2-8b-a1b.py"), "bench_config_lfm2_for_order")
    cfg = tfm.TransformerConfig(
        vocab_size=96, d_model=64, n_heads=4, n_kv_heads=2, n_layers=4,
        d_ff=96, max_seq=32, dtype=jnp.float32, attention="flash",
        positions="rope", rope_theta=1e6, ffn="swiglu", norm="pre",
        norm_eps=1e-5, qk_norm=True, conv_kernel=3, n_experts=8, moe_top_k=2,
        d_ff_expert=32, route_eps=1e-6,
        layers=(K(experts=True), K(mixer="conv"),
                K(experts=True, mixer="conv"), K()))
    params, (inputs, targets) = _params(cfg), _tokens()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(lambda p: tfm.lean_lm_loss(
            p, inputs, targets, cfg)))(params)
        want = jax.jit(jax.value_and_grad(lambda w: ref.loss(
            w, inputs, targets, top_k=2)))(model.to_reference(params, cfg))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=TIGHT)
    for leaf, w in _leaves(model.from_reference(want[1], cfg)).items():
        _close(_leaves(got[1])[leaf], w, 2e-4)


# -- what the new kinds leave alone ----------------------------------------

# sha256 of make_train_step's lowered text at the commit before the kinds of
# one sublayer existed (f994a16), the two sparse-expert cells' programs at
# their rehearsal's widths in bfloat16, adamw(3e-4): on a mesh of one and
# over data=4 (the plain and the looped decoder's: tests/test_trinity_lm.py)
ACCEPTED = {
    ("lfm2-8b-a1b", "lfm2-spmd-1chip-ep4share-8k"): (22233.8984375, {
        1: "8096100351538caefd9a7292bd2fee56ad5e67e59146556091c908e67b48817f",
        4: "85442ec159f9a9b79122f197326c091c272753da73e93810fbcf6914323a4538"}),
    ("trinity-mini", "trinity-spmd-1chip-ep8share-8k"): (27451.498046875, {
        1: "02b2de41c2f0b4171d991f5ebee6ce72fa1ef4d71d383b7b00309a3e3228e095",
        4: "1aeddf3e6d0a8fb4237da9e5d720a792e263969d9b5a43255aed5dc15470a212"}),
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


def test_the_examples_gauges_are_declared():
    from horovod_tpu.metrics import METRIC_SPECS
    example = os.path.join(os.path.dirname(BENCH), "examples",
                           "transformer_lm.py")
    with open(example) as fh:
        text = fh.read()
    for gauge in ("hvd_tpu_lm_layers", "hvd_tpu_lm_scan_chunks",
                  "hvd_tpu_lm_scan_kernel", "hvd_tpu_lm_taps_kernel"):
        assert METRIC_SPECS[gauge][0] == "gauge"
        assert '"%s"' % gauge in text
    assert "mamba2" in METRIC_SPECS["hvd_tpu_lm_layers"][1]
