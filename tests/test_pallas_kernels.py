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


class TestSplashRematSelection:
    """VERDICT r4 item 7: splash must auto-degrade to flash when a remat'd
    block would recompute its residual-saving forward with a VMEM
    residency above the chip scope — the env knobs are overrides, not the
    mechanism. The selection arithmetic is backend-independent."""

    def test_flagship_remat_shape_degrades_to_flash(self, monkeypatch):
        from horovod_tpu.parallel import flash_attention as fa
        monkeypatch.delenv("HOROVOD_SPLASH", raising=False)
        monkeypatch.delenv("HOROVOD_SPLASH_BLOCK_KV", raising=False)
        # T=2048 D=128 (flagship): bkv=2048 recompute bound > 16 MiB scope
        assert fa._splash_remat_vmem_bytes(2048, 128, 2048) > \
            fa._scoped_vmem_bytes()
        assert fa._select_kernel(2048, 128, under_remat=True) == "flash"
        # ...but without remat splash stays
        assert fa._select_kernel(2048, 128, under_remat=False) == "splash"

    def test_small_block_fits_and_keeps_splash(self, monkeypatch):
        from horovod_tpu.parallel import flash_attention as fa
        # the other empirical anchor: bkv=1024 fits under the scope
        assert fa._splash_remat_vmem_bytes(2048, 128, 1024) < \
            fa._scoped_vmem_bytes()
        monkeypatch.setenv("HOROVOD_SPLASH_BLOCK_KV", "1024")
        assert fa._select_kernel(2048, 128, under_remat=True) == "splash"

    def test_force_overrides_degrade(self, monkeypatch):
        from horovod_tpu.parallel import flash_attention as fa
        monkeypatch.setenv("HOROVOD_SPLASH", "force")
        monkeypatch.delenv("HOROVOD_SPLASH_BLOCK_KV", raising=False)
        assert fa._select_kernel(2048, 128, under_remat=True) == "splash"


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
