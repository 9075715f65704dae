"""Kernel micro-benchmarks: Pallas vs lax for the Adasum combine and the
fusion packer (VERDICT r1 #3). Prints one JSON line per comparison.

Timing uses dependent chaining, each timed span ending in a host fetch.
Runs on a TPU only: a kernel time taken anywhere else is not a device
number."""

from __future__ import annotations

import json
import time

import numpy as np


def _time(fn, args, iters=20):
    import jax
    out = fn(*args)
    float(np.asarray(out).ravel()[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    float(np.asarray(out).ravel()[0])
    return (time.perf_counter() - t0) / iters


def main():
    import jax
    import jax.numpy as jnp
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    platforms = sorted({d.platform for d in jax.devices()})
    if platforms != ["tpu"]:
        raise SystemExit(f"bench_kernels.py measures TPU kernels; visible "
                         f"platforms are {platforms}")
    from horovod_tpu.ops.adasum import adasum_combine
    from horovod_tpu.ops.pallas_kernels import (adasum_combine_pallas,
                                                pack_pallas)
    from horovod_tpu.ops.collectives import build_pack

    rng = np.random.RandomState(0)
    for n, dtype in [(1 << 20, jnp.float32), (1 << 24, jnp.float32),
                     (1 << 24, jnp.bfloat16)]:
        a = jnp.asarray(rng.randn(n), dtype)
        b = jnp.asarray(rng.randn(n), dtype)
        lax_fn = jax.jit(adasum_combine)
        t_lax = _time(lax_fn, (a, b))
        try:
            t_pl = _time(adasum_combine_pallas, (a, b))
        except Exception as e:
            t_pl = None
            err = f"{type(e).__name__}: {str(e)[:120]}"
        print(json.dumps({
            "bench": "adasum_combine", "n": n, "dtype": str(dtype.__name__),
            "lax_ms": round(t_lax * 1e3, 3),
            "pallas_ms": round(t_pl * 1e3, 3) if t_pl else None,
            "winner": ("pallas" if t_pl and t_pl < t_lax else "lax"),
            **({} if t_pl else {"pallas_error": err}),
        }))

    for count, size in [(100, 1024), (200, 1024), (160, 4096)]:
        ts = [jnp.asarray(rng.randn(size), jnp.float32)
              for _ in range(count)]
        shapes = tuple(tuple(t.shape) for t in ts)
        concat_fn = build_pack(shapes, jnp.float32)
        t_concat = _time(concat_fn, ts)
        try:
            t_pl = _time(lambda *xs: pack_pallas(xs), ts)
        except Exception as e:
            t_pl = None
            err = f"{type(e).__name__}: {str(e)[:120]}"
        print(json.dumps({
            "bench": "fusion_pack", "tensors": count, "each": size,
            "concat_ms": round(t_concat * 1e3, 3),
            "pallas_ms": round(t_pl * 1e3, 3) if t_pl else None,
            "winner": ("pallas" if t_pl and t_pl < t_concat else "concat"),
            **({} if t_pl else {"pallas_error": err}),
        }))

    _bench_attention()
    _bench_ring_segment()


def _bench_attention():
    """Attention kernel comparison (fwd+bwd, marginal scan timing) — the
    measurement behind flash_attention_local's splash-first default."""
    import math
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.parallel.flash_attention import (flash_attention_local,
                                                      splash_available)
    from horovod_tpu.parallel.ring_attention import local_attention

    B, H, T, D = 4, 16, 2048, 128
    fl = 4 * B * H * T * T * D // 2 * 3  # causal fwd + 2x bwd

    def marginal(att):
        # distinct q/k/v: identical operands would let XLA exploit the
        # symmetry of q·qᵀ in the materialized path
        q0, k0, v0 = (jax.random.normal(jax.random.PRNGKey(i), (B, T, H, D),
                                        jnp.bfloat16) for i in range(3))

        def loss(q, k, v):
            return jnp.sum(att(q, k, v).astype(jnp.float32) ** 2)

        @partial(jax.jit, static_argnums=0)
        def run(iters, q, k, v):
            def body(c, _):
                q, k, v, acc = c
                # full backward (dq, dk, dv) so every kernel pays the same
                # work — argnums=0 alone lets XLA dead-code-eliminate the
                # dK/dV matmuls of the materialized path
                l, (gq, gk, gv) = jax.value_and_grad(
                    loss, argnums=(0, 1, 2))(q, k, v)
                eps = jnp.bfloat16(1e-9)
                return (q + gq * eps, k + gk * eps, v + gv * eps,
                        acc + l), 0.
            (q, k, v, acc), _ = lax.scan(
                body, (q, k, v, jnp.zeros((), jnp.float32)), None,
                length=iters)
            return acc
        for it in (4, 24):
            float(np.asarray(run(it, q0, k0, v0)))
        t0 = time.perf_counter()
        float(np.asarray(run(4, q0, k0, v0)))
        d1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(np.asarray(run(24, q0, k0, v0)))
        d2 = time.perf_counter() - t0
        return (d2 - d1) / 20

    import os
    results = {}
    saved = os.environ.get("HOROVOD_SPLASH")
    try:
        results["materialized"] = marginal(
            lambda q, k, v: local_attention(q, k, v, causal=True))
        os.environ["HOROVOD_SPLASH"] = "0"
        results["flash_tuned"] = marginal(
            lambda q, k, v: flash_attention_local(q, k, v, causal=True))
        os.environ["HOROVOD_SPLASH"] = "1"
        if splash_available():
            results["splash"] = marginal(
                lambda q, k, v: flash_attention_local(q, k, v,
                                                      causal=True))
    finally:
        if saved is None:
            os.environ.pop("HOROVOD_SPLASH", None)
        else:
            os.environ["HOROVOD_SPLASH"] = saved
    print(json.dumps({
        "bench": "attention_fwd_bwd", "shape": f"B{B} H{H} T{T} D{D} causal",
        **{f"{k}_ms": round(v * 1e3, 2) for k, v in results.items()},
        **{f"{k}_tflops": round(fl / v / 1e12, 1)
           for k, v in results.items()},
        "winner": min(results, key=results.get),
    }))


def _bench_ring_segment():
    """Ring per-segment kernel comparison: the Pallas segment path
    (stock flash fwd-with-residuals + global-lse dq/dkv backward) vs the
    chunked pure-JAX inner that CPU and 128-unaligned blocks use — the
    number that justifies routing multi-chip rings through Pallas
    (r4 measured the old chunked inner ~3x slower; the r5 whole-ring
    design makes the Pallas path the default)."""
    from functools import partial
    import jax
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.parallel import ring_attention as ra

    B, H, D = 1, 16, 128

    def marginal(S, seg_fwd, seg_bwd):
        q0, k0, v0 = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D),
                                        jnp.bfloat16) for i in range(3))

        @partial(jax.jit, static_argnums=0)
        def run(iters, q, k, v):
            def body(c, _):
                q, k, v, acc = c
                o, lse = seg_fwd(q, k, v, True)
                do = o.astype(jnp.bfloat16)
                di = jnp.sum(o * o, axis=-1)
                dq, dk, dv = seg_bwd(q, k, v, lse, do, di, True)
                eps = jnp.bfloat16(1e-9)
                return (q + dq.astype(q.dtype) * eps,
                        k + dk.astype(q.dtype) * eps,
                        v + dv.astype(q.dtype) * eps,
                        acc + jnp.sum(lse)), 0.
            (q, k, v, acc), _ = lax.scan(
                body, (q, k, v, jnp.zeros((), jnp.float32)), None,
                length=iters)
            return acc
        # sub-2ms kernels need a 100-step span to clear the per-fetch
        # noise; median of 3 marginals (bench.py convention)
        i1, i2 = 8, 108
        for it in (i1, i2):
            float(np.asarray(run(it, q0, k0, v0)))
        marg = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(np.asarray(run(i1, q0, k0, v0)))
            d1 = time.perf_counter() - t0
            t0 = time.perf_counter()
            float(np.asarray(run(i2, q0, k0, v0)))
            d2 = time.perf_counter() - t0
            marg.append((d2 - d1) / (i2 - i1))
        marg = [m for m in marg if m > 0]
        if len(marg) < 2:
            raise RuntimeError("non-positive marginals; noise swamped the "
                               "measurement — rerun on a quieter chip")
        import statistics
        return statistics.median(marg)

    # Two segment scales: near-parity at S=2048 (the chunked inner's
    # working set is still cache-friendly), Pallas ~3.75x ahead at the
    # ring-realistic S=4096 (the f32 [B,H,S,chunk] slabs leave VMEM) —
    # the measurement behind routing TPU rings through the Pallas path.
    for S in (2048, 4096):
        fl = 4 * B * H * S * S * D // 2 * 3  # causal diag fwd + 2x bwd
        res = {"pallas": marginal(S, ra._seg_fwd_pallas, ra._seg_bwd_pallas),
               "jax_chunked": marginal(S, ra._seg_fwd_jax, ra._seg_bwd_jax)}
        print(json.dumps({
            "bench": "ring_segment_fwd_bwd",
            "shape": f"B{B} H{H} S{S} D{D} diag",
            **{f"{k}_ms": round(v * 1e3, 2) for k, v in res.items()},
            **{f"{k}_tflops": round(fl / v / 1e12, 1)
               for k, v in res.items()},
            "pallas_speedup": round(res["jax_chunked"] / res["pallas"], 2),
        }))


if __name__ == "__main__":
    main()
