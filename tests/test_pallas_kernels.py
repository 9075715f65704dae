"""Pallas kernel correctness vs the lax reference implementations (interpret
mode on the CPU world; the same code compiles via Mosaic on real TPU, where
``chip_smoke.py``'s kernels phase holds each against its lax twin)."""

import numpy as np
import pytest
import jax.numpy as jnp

from horovod_tpu.ops.adasum import adasum_combine
from horovod_tpu.ops.pallas_kernels import (adasum_combine_pallas,
                                            pack_pallas, pallas_supported)

pytestmark = pytest.mark.skipif(not pallas_supported(),
                                reason="pallas unavailable")


@pytest.mark.parametrize("shape,dtype", [
    ((1000,), np.float32),
    ((70000,), np.float32),
    ((3, 5, 7), np.float32),
    ((65536,), "bfloat16"),
])
def test_adasum_combine_matches_lax(shape, dtype):
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(*shape), dtype)
    b = jnp.asarray(rng.randn(*shape), dtype)
    got = np.asarray(adasum_combine_pallas(a, b), np.float32)
    want = np.asarray(adasum_combine(a, b), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2 if dtype == "bfloat16"
                               else 2e-5, atol=1e-5)


def test_adasum_combine_zero_operand():
    a = jnp.zeros((512,), jnp.float32)
    b = jnp.asarray(np.random.RandomState(1).randn(512), jnp.float32)
    got = np.asarray(adasum_combine_pallas(a, b))
    want = np.asarray(adasum_combine(a, b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_env_knob_switches_impl(monkeypatch):
    monkeypatch.setenv("HOROVOD_ADASUM_PALLAS", "1")
    a = jnp.asarray(np.random.RandomState(2).randn(256), jnp.float32)
    out = np.asarray(adasum_combine(a, a))
    np.testing.assert_allclose(out, np.asarray(a), rtol=1e-5)


def test_pack_pallas_matches_concat():
    from horovod_tpu.ops.pallas_kernels import pack_pallas_supported
    rng = np.random.RandomState(3)
    # whole 1-D tiles only: Mosaic refuses ragged tensors on the chip, and
    # the engine does not offer them to the kernel
    shapes = [(1024,), (8, 128), (2, 4, 128), (2048,)]
    assert pack_pallas_supported(shapes, jnp.float32)
    assert not pack_pallas_supported([(5,), (3, 4), (1024,)], jnp.float32)
    assert not pack_pallas_supported([(1024,)], jnp.bfloat16)
    ts = [jnp.asarray(rng.randn(*s), jnp.float32) for s in shapes]
    got = np.asarray(pack_pallas(ts))
    want = np.concatenate([np.asarray(t).ravel() for t in ts])
    np.testing.assert_array_equal(got, want)


# -- fused BatchNorm kernels + module (docs/roofline.md) --------------------


@pytest.mark.parametrize("m,c", [(1000, 256), (1000, 64), (512, 128),
                                 (777, 384)])
def test_bn_stats_matches_numpy(m, c):
    from horovod_tpu.ops.pallas_kernels import bn_stats_pallas
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, c), "bfloat16")
    s, q = bn_stats_pallas(x)
    xf = np.asarray(x, np.float32)
    np.testing.assert_allclose(np.asarray(s), xf.sum(0), rtol=2e-2, atol=1e-2)
    np.testing.assert_allclose(np.asarray(q), (xf * xf).sum(0), rtol=2e-2,
                               atol=1e-2)


def test_bn_bwd_stats_matches_numpy():
    from horovod_tpu.ops.pallas_kernels import bn_bwd_stats_pallas
    rng = np.random.RandomState(1)
    m, c = 900, 256
    x = jnp.asarray(rng.randn(m, c), "bfloat16")
    dy = jnp.asarray(rng.randn(m, c), "bfloat16")
    xf, dyf = np.asarray(x, np.float32), np.asarray(dy, np.float32)
    mean = jnp.asarray(xf.mean(0))
    invstd = jnp.asarray(1.0 / (xf.std(0) + 1e-5))
    s1, s2 = bn_bwd_stats_pallas(dy, x, mean, invstd)
    xh = (xf - np.asarray(mean)) * np.asarray(invstd)
    np.testing.assert_allclose(np.asarray(s1), dyf.sum(0), rtol=2e-2,
                               atol=1e-1)
    np.testing.assert_allclose(np.asarray(s2), (dyf * xh).sum(0), rtol=3e-2,
                               atol=2e-1)


def test_fused_batch_norm_matches_flax():
    """FusedBatchNorm must match nn.BatchNorm: outputs, all three gradients,
    running-stat EMA, and eval mode (fp32 so the comparison is tight)."""
    import jax
    import flax.linen as nn
    from horovod_tpu.ops.fused_batch_norm import FusedBatchNorm

    x = jnp.asarray(np.random.RandomState(0).randn(8, 5, 5, 12), jnp.float32)
    ref = nn.BatchNorm(use_running_average=False, momentum=0.9, epsilon=1e-5,
                       dtype=jnp.float32, param_dtype=jnp.float32)
    fus = FusedBatchNorm(use_running_average=False, momentum=0.9,
                         epsilon=1e-5, dtype=jnp.float32)
    vr = ref.init(jax.random.PRNGKey(0), x)
    vf = fus.init(jax.random.PRNGKey(0), x)

    def run(mod, p, bs, x):
        y, mut = mod.apply({"params": p, "batch_stats": bs}, x,
                           mutable=["batch_stats"])
        return y, mut["batch_stats"]

    yr, bsr = run(ref, vr["params"], vr["batch_stats"], x)
    yf, bsf = run(fus, vr["params"], vf["batch_stats"], x)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yf), atol=1e-5)
    np.testing.assert_allclose(np.asarray(bsr["mean"]),
                               np.asarray(bsf["mean"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(bsr["var"]),
                               np.asarray(bsf["var"]), atol=1e-5)

    def loss(mod, v0, p, x):
        return jnp.sum(jnp.sin(run(mod, p, v0["batch_stats"], x)[0]))

    gr = jax.grad(lambda p: loss(ref, vr, p, x))(vr["params"])
    gf = jax.grad(lambda p: loss(fus, vf, p, x))(vr["params"])
    np.testing.assert_allclose(np.asarray(gr["scale"]),
                               np.asarray(gf["scale"]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gr["bias"]),
                               np.asarray(gf["bias"]), atol=1e-4)
    gxr = jax.grad(lambda x: loss(ref, vr, vr["params"], x))(x)
    gxf = jax.grad(lambda x: loss(fus, vf, vr["params"], x))(x)
    np.testing.assert_allclose(np.asarray(gxr), np.asarray(gxf), atol=1e-4)

    refe = nn.BatchNorm(use_running_average=True, momentum=0.9, epsilon=1e-5,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    fuse = FusedBatchNorm(use_running_average=True, momentum=0.9,
                          epsilon=1e-5, dtype=jnp.float32)
    ye = refe.apply({"params": vr["params"], "batch_stats": bsr}, x)
    yfe = fuse.apply({"params": vr["params"], "batch_stats": bsf}, x)
    np.testing.assert_allclose(np.asarray(ye), np.asarray(yfe), atol=1e-5)


def test_resnet_fused_bn_variant_trains():
    """ResNet(fused_bn=True) runs fwd+bwd on the CPU world (XLA fallback of
    the same custom_vjp path the TPU kernels use)."""
    import jax
    import optax
    from horovod_tpu.models.resnet import ResNet18ish

    m = ResNet18ish(num_classes=10, dtype=jnp.float32, fused_bn=True)
    x = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3), jnp.float32)
    v = m.init(jax.random.PRNGKey(0), x, train=True)

    def loss(p):
        logits, _ = m.apply({"params": p, "batch_stats": v["batch_stats"]},
                            x, train=True, mutable=["batch_stats"])
        return jnp.mean(logits ** 2)

    g = jax.grad(loss)(v["params"])
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree_util.tree_leaves(g))


@pytest.fixture
def on_tpu(monkeypatch):
    """The selection asks ``jax.default_backend()``; its arithmetic is the
    same on any backend."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("HOROVOD_SPLASH", raising=False)


@pytest.mark.parametrize("under_remat", [False, True])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("d", [64, 128, 256])
@pytest.mark.parametrize("t", [1024, 2048, 3072, 4096, 8192])
def test_splash_geometry_is_buildable_and_skips_what_the_mask_drops(
        t, d, causal, under_remat):
    """The blocks ``splash_geometry`` answers for every shape ``_splash_ok``
    admits: the stock kernel can be built with them, and a causal call of
    2048 positions or more never gets one kv block as long as the sequence
    (the kernel skips by block: one block is the whole square)."""
    from horovod_tpu.parallel import flash_attention as fa
    g = fa.splash_geometry(t, d, causal, under_remat)
    assert all(b % 128 == 0 and t % b == 0 for b in g), g
    assert g.block_kv % g.block_kv_compute == 0
    assert g.block_kv_dkv % g.block_kv_dkv_compute == 0
    if causal and t >= 2048:
        assert g.block_kv < t and g.block_kv_dkv < t
    # the stock BlockSizes refuses dq blocks beside the fused backward
    # kernel: the geometry has none, and the kernel is built with it
    kernel = fa._splash_kernel(2, t, d, causal, under_remat)
    sizes = kernel.kwargs["block_sizes"]
    assert sizes.use_fused_bwd_kernel
    assert (sizes.block_q_dq, sizes.block_kv_dq) == (None, None)
    assert kernel.dq_mask_info is None
    fa._splash_kernel.cache_clear()


@pytest.mark.parametrize("q, kv, mode, want", [
    ((4, 16, 2048, 128), None, None, "splash"),
    ((1, 16, 4096, 128), None, "force", "splash"),
    ((1, 16, 4096, 128), None, "off", "flash"),
    ((4, 16, 1536, 128), None, None, "flash"),          # T not / 1024
    ((4, 16, 512, 128), None, None, "flash"),           # too short
    ((4, 16, 2048, 64), None, None, "flash"),   # head of 64, as many KV heads
    ((2, 32, 8192, 64), (2, 8, 8192, 64), None, "splash"),  # half the lanes
    ((4, 16, 2048, 96), None, None, "flash"),       # head not 64, not / 128
    ((4, 16, 1024, 128), (4, 16, 2048, 128), None, "flash"),   # rectangular
    ((8, 12, 197, 64), None, None, "materialized"),     # ViT-B/16
    ((8, 12, 256, 64), (8, 12, 17, 64), None, "materialized"),
])
def test_which_kernel_a_shape_reaches(on_tpu, monkeypatch, q, kv, mode, want):
    """Splash for what ``_splash_ok`` admits, with and without
    recomputation; the stock flash kernel for the rest and with
    ``HOROVOD_SPLASH`` off; materialized attention for unaligned lengths."""
    from horovod_tpu.parallel import flash_attention as fa
    if mode:
        monkeypatch.setenv("HOROVOD_SPLASH", mode)
    kv = kv or q
    assert fa._select_kernel(q, kv) == want
    for under_remat in (False, True):
        labels = fa.attention_kernel(q, kv, True, under_remat)
        assert labels["kernel"] == want
        assert set(labels) == {"kernel", "block_q", "block_kv", "fused_bwd",
                               "window", "head_size", "v_head_size"}
        assert labels["head_size"] == labels["v_head_size"] == str(q[3])
        if want == "flash":
            block = "1024" if q[2] % 1024 == 0 == kv[2] % 1024 else "128"
            assert (labels["block_q"], labels["fused_bwd"]) == (block, "0")


def test_off_the_tpu_attention_is_materialized_and_the_gauge_is_declared():
    from horovod_tpu.metrics import METRIC_SPECS
    from horovod_tpu.parallel import flash_attention as fa
    sq = (4, 16, 2048, 128)
    assert fa.attention_kernel(sq, sq) == {
        "kernel": "materialized", "block_q": "0", "block_kv": "0",
        "fused_bwd": "0", "window": "0", "head_size": "128",
        "v_head_size": "128"}
    assert METRIC_SPECS["hvd_tpu_attn_kernel"][0] == "gauge"


@pytest.mark.parametrize("q, kv, v_head, want", [
    # latent attention: q/k heads of 192 = 128 + 64 rotated, v heads of 128
    ((2, 32, 8192, 192), None, 128, "splash"),
    ((1, 2, 2048, 192), None, 128, "splash"),
    # another unequal pair was never built or timed: no kernel takes it
    ((2, 32, 8192, 256), None, 128, "materialized"),
    ((2, 32, 8192, 192), None, 64, "materialized"),
    # the pair under fewer KV heads: the MQA form at it was never built
    ((2, 32, 8192, 192), (2, 4, 8192, 192), 128, "materialized"),
    # splash's other conditions hold for it too
    ((2, 32, 1536, 192), None, 128, "materialized"),
    # equal heads of 192 are no multiple of 128: the stock flash kernel
    ((2, 32, 8192, 192), None, 192, "flash"),
])
def test_which_kernel_unequal_head_sizes_reach(on_tpu, q, kv, v_head, want):
    from horovod_tpu.parallel import flash_attention as fa
    kv = kv or q
    assert fa._select_kernel(q, kv, 0, v_head) == want
    labels = fa.attention_kernel(q, kv, True, True, v_head_size=v_head)
    assert (labels["kernel"], labels["head_size"], labels["v_head_size"]) \
        == (want, str(q[3]), str(v_head))
    if want == "materialized":
        assert "192" in fa._kernel_and_why(q, kv, 0, v_head)[1] or \
            "256" in fa._kernel_and_why(q, kv, 0, v_head)[1]


def test_materialized_attention_takes_v_heads_of_another_size():
    """Off the TPU, and for what no kernel takes: the result's heads are
    v's, plainly and under grouped KV heads."""
    import jax
    from horovod_tpu.parallel.ring_attention import local_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 24))
    for kv_heads in (4, 2):
        k = jax.random.normal(ks[1], (2, 64, kv_heads, 24))
        v = jax.random.normal(ks[2], (2, 64, kv_heads, 16))
        out = local_attention(q, k, v)
        assert out.shape == (2, 64, 4, 16)
        wide = local_attention(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 8),)))
        np.testing.assert_allclose(out, wide[..., :16], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("causal", [True, False])
def test_chosen_splash_geometry_against_float32(on_tpu, monkeypatch, causal):
    """dq, dk, dv of ``flash_attention_local`` with the stock splash kernel
    at the chosen blocks (interpreted here) against a float32 materialized
    attention: relative L2. On the v5e at the cells' shapes the causal
    geometry reads dq 3.8e-3, dk 3.7e-3, dv 3.1e-3 at worst over 8 seeds
    (``tools/attn_sweep.py errors``, PERF.md section 4); the band is 1.5
    times that; a backward through scores rounded to 8 bits reads 3e-2."""
    import functools
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from horovod_tpu.parallel import flash_attention as fa
    monkeypatch.setattr(sk, "make_splash_mha", functools.partial(
        sk.make_splash_mha, interpret=True))
    fa._splash_kernel.cache_clear()
    t, d = 2048, 128
    q, k, v, w = (jax.random.normal(key, (1, 2, t, d), jnp.float32)
                  .astype(jnp.bfloat16)
                  for key in jax.random.split(jax.random.PRNGKey(5), 4))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def kernel(q, k, v):
        return fa.flash_attention_local(q, k, v, causal=causal,
                                        layout="bhtk")

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    assert fa.attention_kernel(q.shape, k.shape, causal)["kernel"] == "splash"
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    fa._splash_kernel.cache_clear()
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        err = np.linalg.norm(g - r) / np.linalg.norm(r)
        assert err < 6e-3, (name, err)


def test_chosen_splash_geometry_at_unequal_heads_against_float32(
        on_tpu, monkeypatch):
    """The latent-attention call, q and k heads of 192 beside v heads of
    128, causal, at the chosen blocks (interpreted here, 1 x 2 x 2048): dq,
    dk, dv against a float32 materialized attention, relative L2. On the
    v5e at 2 x 32 x 8192 the chosen geometry reads what
    ``tools/attn_sweep.py errors --shapes mla`` gives (PERF.md section 6, PR
    41); the band is the equal-heads test's."""
    import functools
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    from horovod_tpu.parallel import flash_attention as fa
    monkeypatch.setattr(sk, "make_splash_mha", functools.partial(
        sk.make_splash_mha, interpret=True))
    fa._splash_kernel.cache_clear()
    t, d, dv = 2048, 192, 128
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q, k, v, w = (jax.random.normal(key, (1, 2, t, width), jnp.float32)
                  .astype(jnp.bfloat16)
                  for key, width in zip(keys, (d, d, dv, dv)))

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def kernel(q, k, v):
        return fa.flash_attention_local(q, k, v, causal=True, layout="bhtk",
                                        under_remat=True)

    def reference(q, k, v):
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    assert fa.attention_kernel(q.shape, k.shape, True, True,
                               v_head_size=dv)["kernel"] == "splash"
    out = kernel(q, k, v)
    assert out.shape == (1, 2, t, dv)
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(reference(q, k, v)),
            atol=2e-2)
    fa._splash_kernel.cache_clear()
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert g.shape == r.shape
        err = np.linalg.norm(g - r) / np.linalg.norm(r)
        assert err < 6e-3, (name, err)


def test_engine_offers_the_pack_kernel_aligned_buckets_only(monkeypatch):
    """HOROVOD_PALLAS_PACK=1 in the engine: a bucket of whole tiles goes to
    pack_pallas, a ragged one (which Mosaic refuses on the chip) to the
    jitted concat, and both reduce to what the default path gives."""
    import horovod_tpu as hvd
    from horovod_tpu.ops import pallas_kernels as pk
    monkeypatch.delenv("HOROVOD_PALLAS_PACK", raising=False)
    hvd.init()
    eng = hvd._engine()
    rng = np.random.RandomState(4)
    # two fusion buckets (one per dtype): whole f32 tiles, ragged bf16
    tensors = [jnp.asarray(rng.randn(*s), jnp.float32)
               for s in [(1024,), (8, 128)]]
    tensors += [jnp.asarray(rng.randn(*s), jnp.bfloat16)
                for s in [(5,), (3, 4)]]
    kernel_buckets = []
    real = pk.pack_pallas

    def spy(bucket):
        kernel_buckets.append([tuple(t.shape) for t in bucket])
        return real(bucket)

    monkeypatch.setattr(pk, "pack_pallas", spy)

    def reduce(name):
        return [np.asarray(h.synchronize(), np.float32)
                for h in eng.grouped_allreduce(tensors, name=name)]

    monkeypatch.setattr(eng, "_pack_pallas_base", False)
    default = reduce("pack.default")
    assert kernel_buckets == []
    monkeypatch.setattr(eng, "_pack_pallas_base", True)
    with_kernel = reduce("pack.kernel")
    assert kernel_buckets == [[(1024,), (8, 128)]]
    for got, want, t in zip(with_kernel, default, tensors):
        assert got.shape == t.shape
        np.testing.assert_array_equal(got, want)
