"""Pallas TPU kernels for the framework's hot inner loops.

Native-kernel layer for the compute path (the reference implements these in
SIMD C++: the Adasum combine — fused dot/|a|²/|b|² + scaled add — at
adasum.h:194-336 and its AVX/F16C fp16 specializations at adasum.h:426-546;
the fusion-buffer pack/unpack memcpys at collective_operations.cc:38-82).

Each kernel has a lax fallback; selection is by :func:`pallas_supported` +
env knob (HOROVOD_ADASUM_PALLAS / HOROVOD_PALLAS_PACK). Kernels run in
interpret mode off-TPU so the same code path is testable on the CPU world.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_LANES = 128
_ROW_BLOCK = 512  # rows per grid step: 512*128*4B = 256 KB/operand in VMEM


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def pallas_supported() -> bool:
    """Pallas path availability: real TPU (Mosaic) or anywhere via the
    interpreter (tests)."""
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except Exception:
        return False


def _pad_to_grid(v: jax.Array):
    n = v.shape[0]
    per_block = _ROW_BLOCK * _LANES
    pad = (-n) % per_block
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    rows = v.shape[0] // _LANES
    return v.reshape(rows, _LANES), n


def _triple_kernel(a_ref, b_ref, acc_ref):
    """Grid-accumulated [dot(a,b), |a|², |b|²] in fp32 — one read of each
    operand for all three reductions (adasum.h:338-398 computes the same
    3-vector; the fp16 SIMD kernels at :426-546 accumulate in fp32 too)."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[0, 0] = 0.0
        acc_ref[0, 1] = 0.0
        acc_ref[0, 2] = 0.0

    af = a_ref[...].astype(jnp.float32)
    bf = b_ref[...].astype(jnp.float32)
    acc_ref[0, 0] += jnp.sum(af * bf)
    acc_ref[0, 1] += jnp.sum(af * af)
    acc_ref[0, 2] += jnp.sum(bf * bf)


def _scale_kernel(coef_ref, a_ref, b_ref, o_ref):
    ca = coef_ref[0, 0]
    cb = coef_ref[0, 1]
    o_ref[...] = (ca * a_ref[...].astype(jnp.float32) +
                  cb * b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=())
def adasum_combine_pallas(a: jax.Array, b: jax.Array) -> jax.Array:
    """Pairwise Adasum combine via two Pallas passes: a fused triple
    reduction, then the coefficient scaled-add. Semantically identical to
    :func:`horovod_tpu.ops.adasum.adasum_combine`."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    orig_shape, orig_dtype = a.shape, a.dtype
    av, n = _pad_to_grid(a.reshape(-1))
    bv, _ = _pad_to_grid(b.reshape(-1))
    rows = av.shape[0]
    grid = rows // _ROW_BLOCK

    triple = pl.pallas_call(
        _triple_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((_ROW_BLOCK, _LANES), lambda i: (i, 0)),
                  pl.BlockSpec((_ROW_BLOCK, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 3), jnp.float32),
        interpret=_interpret(),
    )(av, bv)

    dot, na, nb = triple[0, 0], triple[0, 1], triple[0, 2]
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(na == 0, 1.0, na)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(nb == 0, 1.0, nb)))
    coef = jnp.stack([ca, cb]).reshape(1, 2)

    out = pl.pallas_call(
        _scale_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((_ROW_BLOCK, _LANES), lambda i: (i, 0)),
                  pl.BlockSpec((_ROW_BLOCK, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_ROW_BLOCK, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(av.shape, orig_dtype),
        interpret=_interpret(),
    )(coef, av, bv)

    return out.reshape(-1)[:n].reshape(orig_shape)


def adasum_pallas_enabled() -> bool:
    # divcheck: ignore[opt-in kernel A/B knob read per combine by design (bench flips it live); the launcher env contract keeps it rank-uniform and both lowerings are numerically matched]
    v = os.environ.get("HOROVOD_ADASUM_PALLAS", "").strip().lower()
    return v in ("1", "true", "yes", "on") and pallas_supported()


# ---------------------------------------------------------------------------
# Fusion packer (collective_operations.cc:38-82 MemcpyInFusionBuffer role)
# ---------------------------------------------------------------------------


def pack_pallas_supported(shapes, dtype) -> bool:
    """Whether :func:`pack_pallas` compiles for a bucket of these shapes.
    Mosaic copies whole 1-D tiles (128 lanes x 8 sublanes of 32 bits), so
    every tensor must hold a whole number of them; it refuses a ragged
    tensor outright ("infer-vector-layout: unsupported shape cast",
    v5e / libtpu 0.0.34, PR 21's chip run). The engine offers the kernel
    for aligned buckets only and packs the others with XLA's concat."""
    tile = _LANES * 8 * 4 // np.dtype(dtype).itemsize
    return all(int(np.prod(s)) % tile == 0 for s in shapes)


def pack_pallas(tensors):
    """Pallas fusion packer: one kernel, one DMA-style copy per tensor into
    the flat buffer. Against XLA's fused concat pack on the chip: not
    measured (no benchmark cell makes the engine pack), so this stays
    opt-in via HOROVOD_PALLAS_PACK. Tensors are flattened
    before the kernel (free in XLA; Mosaic has no general in-kernel shape
    cast). Shapes must satisfy :func:`pack_pallas_supported`."""
    from jax.experimental import pallas as pl

    flat = [jnp.ravel(jnp.asarray(t)) for t in tensors]
    sizes = [int(f.shape[0]) for f in flat]
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    dtype = flat[0].dtype

    def kernel(*refs):
        o_ref = refs[-1]
        for i, (off, sz) in enumerate(zip(offsets, sizes)):
            o_ref[pl.dslice(int(off), sz)] = refs[i][...]

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((int(sum(sizes)),), dtype),
        interpret=_interpret(),
    )(*flat)


def pack_pallas_enabled() -> bool:
    v = os.environ.get("HOROVOD_PALLAS_PACK", "").strip().lower()
    return v in ("1", "true", "yes", "on") and pallas_supported()


# ---------------------------------------------------------------------------
# Fused BatchNorm statistics (the ResNet hot op: the BN stat reductions are
# the largest operations of the traced step — docs/roofline.md section 1).
# One bf16 read of the activation per pass, fp32 accumulation in VMEM.
# ---------------------------------------------------------------------------

_BN_BLOCK_BYTES = 512 * 1024  # per-operand VMEM budget per grid step


def _bn_rows(c: int, itemsize: int) -> int:
    """Rows per grid step: full-width (all-lanes) contiguous blocks of about
    _BN_BLOCK_BYTES, so HBM reads are sequential bursts — a (rows, 128)
    column slice of a wider array reads 256-byte strided chunks and lands at
    a fraction of HBM bandwidth (measured 2x regression on ResNet-50)."""
    rows = max(_BN_BLOCK_BYTES // (c * itemsize), 8)
    return (rows // 8) * 8


def _bn_rows_pad(x2d: jax.Array, rows: int) -> jax.Array:
    m = x2d.shape[0]
    pad = (-m) % rows
    if pad:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad, x2d.shape[1]), x2d.dtype)])
    return x2d


def _fold_lanes(x2d: jax.Array):
    """(M, C) with C < 128 -> (M/k, 128) so reductions use full lanes; the
    caller folds the k per-channel copies back with _unfold_stats."""
    m, c = x2d.shape
    if c >= _LANES or _LANES % c or m % (_LANES // c):
        return x2d, 1
    k = _LANES // c
    return x2d.reshape(m // k, _LANES), k


def _unfold_stats(s: jax.Array, c: int, k: int) -> jax.Array:
    if k == 1:
        return s
    return s.reshape(k, c).sum(axis=0)


def _bn_stats_kernel(x_ref, s_ref, q_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)
        q_ref[...] = jnp.zeros_like(q_ref)

    x = x_ref[...].astype(jnp.float32)
    s_ref[...] += jnp.sum(x, axis=0, keepdims=True)
    q_ref[...] += jnp.sum(x * x, axis=0, keepdims=True)


def bn_stats_pallas(x2d: jax.Array):
    """Per-channel (sum, sum-of-squares) of a (M, C) activation in one read
    pass: bf16 in, fp32 accumulators, full-width blocks (1-D grid over
    rows). C must be a multiple of 128, or a divisor of 128 with M divisible
    by 128/C (lane folding)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c_orig = x2d.shape[1]
    x2d, k = _fold_lanes(x2d)
    rows = _bn_rows(x2d.shape[1], x2d.dtype.itemsize)
    x2d = _bn_rows_pad(x2d, rows)
    m, c = x2d.shape
    s, q = pl.pallas_call(
        _bn_stats_kernel,
        grid=(m // rows,),
        in_specs=[pl.BlockSpec((rows, c), lambda mi: (mi, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda mi: (0, 0)),
                   pl.BlockSpec((1, c), lambda mi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(x2d)
    return (_unfold_stats(s[0], c_orig, k), _unfold_stats(q[0], c_orig, k))


def _bn_bwd_kernel(mu_ref, isd_ref, dy_ref, x_ref, s1_ref, s2_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    dy = dy_ref[...].astype(jnp.float32)
    xh = (x_ref[...].astype(jnp.float32) - mu_ref[...]) * isd_ref[...]
    s1_ref[...] += jnp.sum(dy, axis=0, keepdims=True)
    s2_ref[...] += jnp.sum(dy * xh, axis=0, keepdims=True)


def bn_bwd_stats_pallas(dy2d: jax.Array, x2d: jax.Array,
                        mean: jax.Array, invstd: jax.Array):
    """Per-channel (sum(dy), sum(dy * xhat)) in one read pass of dy and x —
    the two reductions of the BatchNorm backward. mean/invstd are (C,) fp32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c_orig = x2d.shape[1]
    x2d, k = _fold_lanes(x2d)
    dy2d, _ = _fold_lanes(dy2d)
    if k > 1:
        mean = jnp.tile(mean, k)
        invstd = jnp.tile(invstd, k)
    rows = _bn_rows(x2d.shape[1], x2d.dtype.itemsize)
    x2d = _bn_rows_pad(x2d, rows)
    dy2d = _bn_rows_pad(dy2d, rows)
    m, c = x2d.shape
    s1, s2 = pl.pallas_call(
        _bn_bwd_kernel,
        grid=(m // rows,),
        in_specs=[pl.BlockSpec((1, c), lambda mi: (0, 0)),
                  pl.BlockSpec((1, c), lambda mi: (0, 0)),
                  pl.BlockSpec((rows, c), lambda mi: (mi, 0)),
                  pl.BlockSpec((rows, c), lambda mi: (mi, 0))],
        out_specs=[pl.BlockSpec((1, c), lambda mi: (0, 0)),
                   pl.BlockSpec((1, c), lambda mi: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(mean.reshape(1, c).astype(jnp.float32),
      invstd.reshape(1, c).astype(jnp.float32), dy2d, x2d)
    return (_unfold_stats(s1[0], c_orig, k), _unfold_stats(s2[0], c_orig, k))


def bn_stats_supported(c: int, m: int) -> bool:
    """Shapes the fused BN kernels handle: full lane tiles or cleanly
    foldable narrow channel counts."""
    if c % _LANES == 0:
        return True
    return _LANES % c == 0 and m % (_LANES // c) == 0
