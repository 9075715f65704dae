"""Flagship transformer: dp/sp/tp-sharded loss and train step must match the
single-device computation — the SPMD analog of the reference's rule that
distributed training reproduce serial numerics."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm


CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=32, dtype=jnp.float32)


def _data(bsz=4, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    inputs = rng.randint(0, CFG.vocab_size, size=(bsz, seq)).astype(np.int32)
    targets = rng.randint(0, CFG.vocab_size, size=(bsz, seq)).astype(np.int32)
    return inputs, targets


def _single_device_loss(params, inputs, targets):
    total, count, _aux = tfm._local_loss(params, jnp.asarray(inputs),
                                         jnp.asarray(targets), CFG)
    return total / count


@pytest.mark.parametrize("shape", [(2, 2, 2), (8, 1, 1), (1, 4, 2)])
def test_spmd_loss_matches_single_device(shape):
    d, s, t = shape
    devs = np.array(jax.devices()[:d * s * t]).reshape(d, s, t)
    mesh = Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    inputs, targets = _data(bsz=8)

    ref = float(_single_device_loss(params, inputs, targets))

    loss_fn = tfm.make_spmd_loss(mesh, CFG)
    sharded_params = tfm.shard_params(params, mesh, CFG)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    out = float(jax.jit(loss_fn)(sharded_params, jax.device_put(inputs, tok_sh),
                                 jax.device_put(targets, tok_sh)))
    assert abs(out - ref) / abs(ref) < 1e-4, (out, ref)


def test_spmd_loss_zigzag_layout_matches():
    """sp_layout='zigzag' (causally load-balanced ring): feeding the
    zigzag-permuted tokens/targets must give the SAME loss — the per-token
    loss mean is permutation-invariant, and the lean LM has no positional
    encoding, so only the ring schedule changes."""
    from horovod_tpu.parallel.ring_attention import zigzag_indices
    d, s, t = 1, 4, 1
    devs = np.array(jax.devices()[:d * s * t]).reshape(d, s, t)
    mesh = Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    cfg = dataclasses.replace(CFG, sp_layout="zigzag")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    inputs, targets = _data(bsz=4, seq=16)
    ref = float(_single_device_loss(params, inputs, targets))

    idx, _ = zigzag_indices(16, s)
    loss_fn = tfm.make_spmd_loss(mesh, cfg)
    sharded_params = tfm.shard_params(params, mesh, cfg)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    zi = jnp.take(jnp.asarray(inputs), idx, axis=1)
    zt = jnp.take(jnp.asarray(targets), idx, axis=1)
    out = float(jax.jit(loss_fn)(sharded_params,
                                 jax.device_put(zi, tok_sh),
                                 jax.device_put(zt, tok_sh)))
    assert abs(out - ref) / abs(ref) < 1e-4, (out, ref)


def test_spmd_train_step_decreases_loss_and_matches_dp1():
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    params = tfm.init_params(jax.random.PRNGKey(1), CFG)
    opt = optax.sgd(0.1)
    inputs, targets = _data(bsz=4, seq=16, seed=2)

    # Single-device reference: 2 full-batch SGD steps.
    ref_params = params
    ref_state = opt.init(ref_params)
    losses_ref = []
    for _ in range(2):
        loss, grads = jax.value_and_grad(
            lambda p: _single_device_loss(p, inputs, targets))(ref_params)
        updates, ref_state = opt.update(grads, ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, updates)
        losses_ref.append(float(loss))

    # SPMD: same total batch split over the mesh.
    step = tfm.make_train_step(mesh, CFG, opt)
    sp = tfm.shard_params(params, mesh, CFG)
    st = opt.init(sp)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    gi, gt = jax.device_put(inputs, tok_sh), jax.device_put(targets, tok_sh)
    losses = []
    for _ in range(2):
        sp, st, loss = step(sp, st, gi, gt)
        losses.append(float(loss))

    assert losses[1] < losses[0], losses
    np.testing.assert_allclose(losses, losses_ref, rtol=1e-3)


def test_ulysses_attention_variant_matches_ring():
    """attention='ulysses' computes the same exact attention as 'ring': the
    SPMD loss must be identical for identical params/data."""
    devs = np.array(jax.devices()).reshape(2, 2, 2)
    mesh = jax.sharding.Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS,
                                    tfm.TENSOR_AXIS))
    losses = {}
    for attn in ("ring", "ulysses"):
        cfg = dataclasses.replace(CFG, attention=attn)
        params = tfm.shard_params(
            tfm.init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
        inputs, targets = _data(4, 16)
        loss_fn = jax.jit(tfm.make_spmd_loss(mesh, cfg))
        tok_sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(tfm.DATA_AXIS, tfm.SEQ_AXIS))
        losses[attn] = float(loss_fn(
            params, jax.device_put(jnp.asarray(inputs), tok_sh),
            jax.device_put(jnp.asarray(targets), tok_sh)))
    np.testing.assert_allclose(losses["ring"], losses["ulysses"], rtol=2e-5)


def test_moe_variant_trains_and_matches_across_meshes():
    """use_moe=True: the train step runs on a (data, seq, tensor=expert)
    mesh; the SPMD loss equals the single-device loss for the same params
    (expert sharding must not change routing results)."""
    import optax
    cfg = dataclasses.replace(CFG, use_moe=True, n_experts=4,
                              moe_capacity_factor=4.0)
    params_full = tfm.init_params(jax.random.PRNGKey(1), cfg)
    inputs, targets = _data(4, 16, seed=5)
    # single-device reference (no shard_map)
    total, count, aux = tfm._local_loss(params_full, jnp.asarray(inputs),
                                        jnp.asarray(targets), cfg)
    ref = float(total / count + cfg.moe_aux_weight * aux)

    devs = np.array(jax.devices()).reshape(2, 2, 2)
    mesh = jax.sharding.Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS,
                                    tfm.TENSOR_AXIS))
    params = tfm.shard_params(params_full, mesh, cfg)
    tok_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    ti = jax.device_put(jnp.asarray(inputs), tok_sh)
    tt = jax.device_put(jnp.asarray(targets), tok_sh)
    loss_fn = jax.jit(tfm.make_spmd_loss(mesh, cfg))
    np.testing.assert_allclose(float(loss_fn(params, ti, tt)), ref,
                               rtol=5e-4)
    # and a full train step updates the routers/experts with finite values
    # (snapshot before the step: donate_argnums consumes the input buffers)
    router_before = np.array(np.asarray(params["layers"]["router"]))
    opt = optax.adam(1e-3)
    step = tfm.make_train_step(mesh, cfg, opt)
    p2, _, loss = step(params, opt.init(params), ti, tt)
    assert np.isfinite(float(loss))
    delta = np.abs(np.asarray(p2["layers"]["router"]) - router_before)
    assert delta.sum() > 0  # router learned


def test_moe_pad_tokens_do_not_skew_results():
    """Per-shard token count not divisible by tensor_size: pad rows must not
    route, consume capacity, or skew the aux loss — loss still matches the
    single-device reference (review r2 scenario)."""
    cfg = dataclasses.replace(CFG, use_moe=True, n_experts=4,
                              moe_capacity_factor=8.0)
    params_full = tfm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.RandomState(7)
    inputs = rng.randint(0, CFG.vocab_size, size=(3, 6)).astype(np.int32)
    targets = rng.randint(0, CFG.vocab_size, size=(3, 6)).astype(np.int32)
    total, count, aux = tfm._local_loss(params_full, jnp.asarray(inputs),
                                        jnp.asarray(targets), cfg)
    ref = float(total / count + cfg.moe_aux_weight * aux)

    # 18 tokens per shard over tensor=4 -> pad of 2
    devs = np.array(jax.devices()[:4]).reshape(1, 1, 4)
    mesh = jax.sharding.Mesh(devs, (tfm.DATA_AXIS, tfm.SEQ_AXIS,
                                    tfm.TENSOR_AXIS))
    params = tfm.shard_params(params_full, mesh, cfg)
    tok_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    loss = float(jax.jit(tfm.make_spmd_loss(mesh, cfg))(
        params, jax.device_put(jnp.asarray(inputs), tok_sh),
        jax.device_put(jnp.asarray(targets), tok_sh)))
    np.testing.assert_allclose(loss, ref, rtol=5e-4)


def test_flash_attention_fallback_and_lean_loss():
    """attention="flash" falls back to the materialized kernel off-TPU, and
    lean_lm_loss matches the log_softmax formulation written out here (fp32
    config)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                forward_block, init_params,
                                                _local_loss, lean_lm_loss)

    cfg = TransformerConfig(vocab_size=128, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=16, dtype=jnp.float32,
                            attention="flash")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 16)))
    tgt = jnp.asarray(np.random.RandomState(1).randint(0, 128, (2, 16)))
    lean = float(lean_lm_loss(params, tok, tgt, cfg))
    ref = float(jnp.mean(_log_softmax_nll(forward_block(params, tok, cfg),
                                          tgt)))
    assert abs(lean - ref) < 1e-4, (lean, ref)
    total, count, _ = _local_loss(params, tok, tgt, cfg)
    assert abs(float(total) / count - ref) < 1e-4

    # flash config == default config numerics on the fallback path
    cfg_ref = TransformerConfig(vocab_size=128, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32)
    total2, _, _ = _local_loss(params, tok, tgt, cfg_ref)
    assert abs(float(total) - float(total2)) < 1e-5


def test_multislice_mesh_flagship_step():
    """The flagship train step compiles and runs over a DCN-aware
    (data@DCN, seq+tensor@ICI) multislice_mesh — the multi-slice pod layout
    (single-slice fallback path on the CPU world; real pods use
    create_hybrid_device_mesh with the same axis semantics)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel.mesh import multislice_mesh
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params, make_train_step,
                                                shard_params)

    mesh = multislice_mesh({"data": 2}, {"seq": 2, "tensor": 2})
    assert mesh.axis_names == ("data", "seq", "tensor")
    assert mesh.devices.shape == (2, 2, 2)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=16, dtype=jnp.float32)
    params = shard_params(init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
    opt = optax.sgd(0.01)
    step = make_train_step(mesh, cfg, opt)
    tok = jax.device_put(jnp.zeros((4, 16), jnp.int32),
                         NamedSharding(mesh, P("data", "seq")))
    p2, o2, loss = step(params, opt.init(params), tok, tok)
    assert np.isfinite(float(loss))


def test_splash_gating_and_kernel_construction():
    """The splash gating/block-size logic is pure Python (mask + BlockSizes
    validation run in numpy) and must handle every T the gate admits —
    including odd multiples of 1024 where kv-block 2048 doesn't divide T
    (review finding: T=3072 crashed make_splash_mha)."""
    import pytest
    pytest.importorskip(
        "jax.experimental.pallas.ops.tpu.splash_attention")
    from horovod_tpu.parallel.flash_attention import (_splash_kernel,
                                                      _splash_ok)
    sq = (1, 4, 1024, 128)
    assert _splash_ok(sq, sq)
    assert _splash_ok((1, 4, 3072, 128), (1, 4, 3072, 128))
    assert not _splash_ok((1, 4, 512, 128), (1, 4, 512, 128))   # too short
    assert not _splash_ok((1, 4, 1536, 128), (1, 4, 1536, 128))  # not /1024
    assert not _splash_ok((1, 4, 2048, 64), (1, 4, 2048, 64))   # d not 128
    assert not _splash_ok(sq, (1, 4, 2048, 128))  # rectangular q/kv
    for t in (1024, 2048, 3072):
        for causal in (True, False):
            for under_remat in (True, False):
                # construction validates the blocks against T
                assert _splash_kernel(2, t, 128, causal, under_remat)
    _splash_kernel.cache_clear()


def test_remat_matches_no_remat():
    """VERDICT r3 item 4: remat changes memory, never numerics — loss and
    grads under remat='block'/'attention' match remat='none' exactly (same
    program modulo recompute), on the single-shard AND the SPMD path."""
    params = tfm.init_params(jax.random.PRNGKey(3), CFG)
    inputs, targets = _data(bsz=2, seq=16, seed=4)

    def loss_of(cfg):
        def f(p):
            total, count, _aux = tfm._local_loss(
                p, jnp.asarray(inputs), jnp.asarray(targets), cfg)
            return total / count
        return jax.jit(jax.value_and_grad(f))

    base_l, base_g = loss_of(CFG)(params)
    for mode in ("block", "attention"):
        cfg = dataclasses.replace(CFG, remat=mode)
        l, g = loss_of(cfg)(params)
        np.testing.assert_allclose(float(l), float(base_l), rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(base_g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    # SPMD path with remat compiles and matches too (ring attention's custom
    # VJP must survive jax.checkpoint's recompute)
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    sp = tfm.shard_params(params, mesh, CFG)
    gi = jax.device_put(inputs, tok_sh)
    gt = jax.device_put(targets, tok_sh)
    ref = float(jax.jit(tfm.make_spmd_loss(mesh, CFG))(sp, gi, gt))
    cfg = dataclasses.replace(CFG, remat="block")
    out = float(jax.jit(tfm.make_spmd_loss(mesh, cfg))(sp, gi, gt))
    assert abs(out - ref) / abs(ref) < 1e-5, (out, ref)


def test_remat_unknown_mode_raises():
    cfg = dataclasses.replace(CFG, remat="everything")
    params = tfm.init_params(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError, match="remat"):
        tfm.forward_block(params, jnp.zeros((1, 8), jnp.int32), cfg)


# ---------------------------------------------------------------------------
# The one cross-entropy (_lean_xent: fp32 log-sum-exp over the logits as the
# head wrote them, backward written by hand) against log_softmax written out
# here on the same _forward logits. The benchmark's mesh_step check runs the
# new code on both of its sides and cannot see a wrong backward: these do.
# ---------------------------------------------------------------------------

def _log_softmax_nll(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _written_out_spmd_loss(mesh, cfg):
    """make_spmd_loss with the cross-entropy written out: the same _forward
    on the same shards, so the two differ by the cross-entropy alone."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = (tfm.DATA_AXIS, tfm.SEQ_AXIS)

    def body(params, inputs, targets):
        logits, _ = tfm._forward(params, inputs, cfg, sizes[tfm.SEQ_AXIS],
                                 sizes[tfm.TENSOR_AXIS])
        nll = _log_softmax_nll(logits, targets)
        n = nll.size * sizes[tfm.DATA_AXIS] * sizes[tfm.SEQ_AXIS]
        return jax.lax.psum(jnp.sum(nll), axes) / n

    tok_spec = P(*axes)
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(tfm.param_specs(cfg), tok_spec, tok_spec),
                         out_specs=P(), check_vma=False)


def _xent_cfg(dtype):
    # a vocabulary no other dimension equals, so a shape names the logits
    return dataclasses.replace(CFG, vocab_size=80, dtype=dtype)


def _xent_errors(dtype, n_data):
    """(relative loss error, {leaf: gradient error over its band}) of
    make_spmd_loss against the written-out form over ``data=n_data``.
    Bands: float32, 1e-6 of the leaf's largest entry; bfloat16, one bf16
    rounding of the cotangent, 2^-8 of the leaf's norm."""
    cfg = _xent_cfg(dtype)
    mesh = Mesh(np.array(jax.devices()[:n_data]).reshape(n_data, 1, 1),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    params = tfm.shard_params(tfm.init_params(jax.random.PRNGKey(5), cfg),
                              mesh, cfg)
    rng = np.random.RandomState(6)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    inputs, targets = (
        jax.device_put(rng.randint(0, cfg.vocab_size, (4, 16), np.int32),
                       tok_sh) for _ in range(2))
    (loss, grads), (ref_loss, ref_grads) = (
        jax.jit(jax.value_and_grad(f))(params, inputs, targets)
        for f in (tfm.make_spmd_loss(mesh, cfg),
                  _written_out_spmd_loss(mesh, cfg)))

    def over_band(path, g, ref):
        g, ref = np.asarray(g, np.float64), np.asarray(ref, np.float64)
        assert np.abs(ref).max() > 0, path
        if dtype == jnp.float32:
            return np.abs(g - ref).max() / (1e-6 * np.abs(ref).max())
        return np.linalg.norm(g - ref) / (2.0 ** -8 * np.linalg.norm(ref))

    errors = jax.tree_util.tree_map_with_path(over_band, grads, ref_grads)
    return (abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
            {jax.tree_util.keystr(k): v for k, v
             in jax.tree_util.tree_leaves_with_path(errors)})


@pytest.mark.parametrize("n_data", [1, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xent_loss_and_every_gradient_match_written_out(dtype, n_data):
    loss_err, grad_errs = _xent_errors(dtype, n_data)
    assert loss_err < 1e-6, loss_err
    assert len(grad_errs) == 10
    assert max(grad_errs.values()) < 1.0, grad_errs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("left_out", ["onehot", "g"])
def test_xent_wrong_backward_is_refused(monkeypatch, left_out, dtype):
    """What the bands above can see: the hand-written backward without its
    one-hot, or without the incoming cotangent, misses every leaf's band by
    orders of magnitude while the loss stays right."""
    def bwd(res, g):
        logits, targets, lse = res
        p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
        if left_out == "onehot":
            d = g[..., None] * p
        else:
            d = p - jax.nn.one_hot(targets, logits.shape[-1])
        return d.astype(logits.dtype), None

    wrong = jax.custom_vjp(lambda logits, targets:
                           tfm._lean_xent_fwd(logits, targets)[0])
    wrong.defvjp(tfm._lean_xent_fwd, bwd)
    monkeypatch.setattr(tfm, "_lean_xent", wrong)
    loss_err, grad_errs = _xent_errors(dtype, 4)
    assert loss_err < 1e-6, loss_err
    assert min(grad_errs.values()) > 100.0, grad_errs


def test_xent_saves_no_fp32_array_of_vocabulary_width():
    """At bfloat16 what _local_loss keeps for its backward holds the logits
    as the head's matmul wrote them and the fp32 log-sum-exp [B, T], and no
    float32 array whose last dimension is the vocabulary."""
    cfg = _xent_cfg(jnp.bfloat16)
    params = tfm.init_params(jax.random.PRNGKey(5), cfg)
    tok = jnp.zeros((2, 16), jnp.int32)
    _, vjp = jax.vjp(
        lambda p: tfm._local_loss(p, tok, tok, cfg)[0], params)
    saved = {(x.dtype.name, x.shape) for x in jax.tree_util.tree_leaves(vjp)
             if hasattr(x, "dtype")}
    assert ("bfloat16", (2, 16, cfg.vocab_size)) in saved, saved
    assert ("float32", (2, 16)) in saved, saved
    wide = [s for s in saved
            if s[0] == "float32" and s[1][-1:] == (cfg.vocab_size,)]
    assert not wide, wide


# ---------------------------------------------------------------------------
# make_train_step takes the gradient inside the shard_map and sums it itself
# (each layer's inside the backward scan): the update must be the one the
# gradient of make_spmd_loss, taken from outside, gives.

_STEP_MESHES = {"data=4": (4, 1, 1), "data=2,seq=2": (2, 2, 1),
                "data=2,tensor=2": (2, 1, 2)}


def _step_case(mesh_name, cfg, to_dtype=None):
    """(mesh, sharded parameters, inputs, targets) of one case."""
    d, s, t = _STEP_MESHES[mesh_name]
    mesh = Mesh(np.array(jax.devices()[:d * s * t]).reshape(d, s, t),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    if to_dtype is not None:
        params = jax.tree_util.tree_map(lambda x: x.astype(to_dtype), params)
    tok_sh = NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS))
    inputs, targets = (jax.device_put(x, tok_sh)
                       for x in _data(bsz=4, seq=16, seed=7))
    return mesh, tfm.shard_params(params, mesh, cfg), inputs, targets


def _assert_same_bits(got, want, dtype):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.dtype == dtype
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            jax.tree_util.keystr(path))


def _keep_gradient():
    """An optax link that hands the gradient on and keeps it as its state:
    what the step gave the optimizer, beside what sgd made of it."""
    return optax.GradientTransformation(
        lambda params: jax.tree_util.tree_map(jnp.zeros_like, params),
        lambda grads, state, params=None: (grads, grads))


@pytest.mark.parametrize("ffn", ["dense", "moe"])
@pytest.mark.parametrize("n_loops", [1, 2])
@pytest.mark.parametrize("head", ["tied", "untied"])
@pytest.mark.parametrize("remat", ["none", "block"])
@pytest.mark.parametrize("mesh_name", list(_STEP_MESHES))
def test_train_step_moves_params_by_the_outside_gradient(
        mesh_name, remat, head, n_loops, ffn):
    """One ``make_train_step`` step with ``optax.sgd(1.0)`` against
    ``jax.value_and_grad`` of ``make_spmd_loss`` from outside the shard_map,
    where the transpose of the replicated inputs sums every leaf over the
    axes its spec does not name (the step before ISSUE 29): the same loss
    scalar, and with one pass over the stack the same gradient and the same
    parameters bit for bit, whichever leaf is summed inside the backward
    scan. Under ``n_loops=2`` XLA's CPU backend orders a float32 sum of the
    exit loss's backward differently in the two programs: over the 24 such
    cases every leaf reads at most 9.6e-7 of its norm off (held to 2e-6),
    but for the gate's scalar bias, whose terms cancel: up to 7.5e-6 (held
    to 2e-5; it starts at 0, so its parameter is its gradient). In float64
    the two are bit-equal there too (the test below); a sum over the wrong
    chips reads 0.5 and more."""
    cfg = dataclasses.replace(
        CFG, remat=remat, tie_embeddings=head == "tied", n_loops=n_loops,
        **({"use_moe": True, "n_experts": 4, "moe_capacity_factor": 4.0}
           if ffn == "moe" else {}))
    mesh, params, inputs, targets = _step_case(mesh_name, cfg)
    opt = optax.chain(_keep_gradient(), optax.sgd(1.0))

    loss_fn = tfm.make_spmd_loss(mesh, cfg)

    @jax.jit
    def outside(params):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, inputs, targets))(params)
        updates, _ = opt.update(grads, opt.init(params), params)
        return loss, grads, optax.apply_updates(params, updates)

    want_loss, want_grads, want_params = outside(params)
    got_params, got_state, got_loss = tfm.make_train_step(mesh, cfg, opt)(
        params, opt.init(params), inputs, targets)
    got_grads = got_state[0]

    assert float(got_loss) == float(want_loss)
    for kind, got, want in (("gradient", got_grads, want_grads),
                            ("parameter", got_params, want_params)):
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                                jax.tree_util.tree_leaves(want)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == np.float32
            if n_loops == 1:
                assert np.array_equal(a, b), (
                    kind, jax.tree_util.keystr(path))
            else:
                name = jax.tree_util.keystr(path)
                err = np.linalg.norm(a - b) / np.linalg.norm(b)
                bound = 2e-5 if name == "['exit_gate']['b']" else 2e-6
                assert err <= bound, (kind, name, err)


@pytest.mark.parametrize("mesh_name", list(_STEP_MESHES))
def test_train_step_bf16_compute_sums_in_float32(mesh_name):
    """The cell's own form (bfloat16 compute, fp32 parameters): a sum taken
    on the bfloat16 side of the weight casts, or a wire narrower than the
    gradient, does not give the outside gradient's bits."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    mesh, params, inputs, targets = _step_case(mesh_name, cfg)
    loss_fn = tfm.make_spmd_loss(mesh, cfg)
    want = jax.jit(jax.grad(lambda p: loss_fn(p, inputs, targets)))(params)
    opt = _keep_gradient()
    _, got, _ = tfm.make_train_step(mesh, cfg, opt)(
        params, opt.init(params), inputs, targets)
    _assert_same_bits(got, want, jnp.float32)


@pytest.mark.parametrize("mesh_name", list(_STEP_MESHES))
def test_train_step_looped_gradient_is_exact_in_float64(mesh_name):
    """What ``n_loops=2`` is off by in float32 above is the order of a sum:
    with float64 parameters and compute the two gradients are bit-equal."""
    with jax.enable_x64():
        cfg = dataclasses.replace(CFG, dtype=jnp.float64, n_loops=2,
                                  tie_embeddings=False)
        mesh, params, inputs, targets = _step_case(mesh_name, cfg,
                                                   jnp.float64)
        loss_fn = tfm.make_spmd_loss(mesh, cfg)
        want = jax.jit(jax.grad(
            lambda p: loss_fn(p, inputs, targets)))(params)
        opt = _keep_gradient()
        _, got, _ = tfm.make_train_step(mesh, cfg, opt)(
            params, opt.init(params), inputs, targets)
        _assert_same_bits(got, want, jnp.float64)
