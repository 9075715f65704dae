"""Ring attention: sequence/context parallelism over an ICI mesh axis.

The reference has no sequence parallelism (SURVEY.md §5 long-context:
absent), but its primitive set — point-to-point neighbor exchange
(adasum.h:294-305 PointToPointSendRecv) and alltoall — is exactly what SP
needs. Here we build blockwise ring attention natively: the sequence
dimension is sharded across the ``seq`` mesh axis; K/V blocks rotate around
the ring via ``lax.ppermute`` (one ICI neighbor hop per step) while each
device merges per-block flash-attention results into a running
(out, logsumexp) pair with the residual recurrence

    lse' = logaddexp(lse, lse_b)
    out' = out·exp(lse − lse') + out_b·exp(lse_b − lse')

Whole-ring ``custom_vjp`` (the r4 "staged design", now built): the ring is
ONE differentiable unit whose backward is hand-scheduled. With the global
``lse`` saved from the forward, each block's backward is the *standard*
flash backward under residuals ``(m = lse, l = 1)`` — i.e. the stock Pallas
dq/dkv kernels apply per block with no lse-cotangent term — while dk/dv
accumulators rotate around the ring with their K/V blocks and land on the
owning rank after n hops. Compared to differentiating the ring scan with
AD (the r3/r4 design), this removes the per-block dlse VJP entirely and
shrinks residual memory from O(n) rotated K/V copies (the scan's per-step
carries) to the local q/k/v/out/lse only.

Per-block kinds, not positions: under either layout every (q block,
kv block) interaction is FULL (all visible), DIAG (aligned causal), or
EMPTY (skipped via ``lax.switch`` — a real runtime branch, no masked-out
matmuls). On TPU the FULL/DIAG branches call the Pallas flash kernels
(forward with ``save_residuals`` for the block lse; backward the stock
dq/dkv kernels); elsewhere (and for 128-unaligned block lengths) a chunked
pure-JAX flash with identical semantics keeps the path portable and the
8-virtual-device CPU tests meaningful. Peak per-step temp stays
O(T_local·chunk) — never the [T_local, T_local] score block.

Causal load balance — zig-zag layout (``layout="zigzag"``): with contiguous
blocks, late ranks own mostly-visible history while early ranks skip most
ring steps (~2× straggler imbalance). Striping the sequence so rank r holds
stripes (r, 2n−1−r) makes every rank's per-step work IDENTICAL: each
off-diagonal ring step is exactly two FULL half-blocks, the diagonal step
is one FULL + two DIAG half-blocks ((lo,hi) pairs are statically empty and
never computed). See :func:`zigzag_indices` for the layout permutation and
:func:`zigzag_pair_kinds` for the (testable) schedule.

Use inside shard_map with the sequence axis manual; see
``horovod_tpu.models.transformer`` for the full integration. Ring size 1
dispatches to the tuned single-shard Pallas kernels
(``parallel/flash_attention.py``); ``force_ring=True`` drives the generic
ring path even at n=1 (identity ppermute) so a single chip can measure the
multi-chip code path honestly.
"""

from __future__ import annotations

import functools
import math
import os as _os

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
# K/V chunk length of the pure-JAX flash inner kernel. 512 keeps the
# per-chunk score slab [B,H,S,512] comfortably inside VMEM-friendly tiling
# while giving the MXU full-width contractions. Tunable per chip generation
# via HOROVOD_RING_CHUNK.
_KV_CHUNK = int(_os.environ.get("HOROVOD_RING_CHUNK", "512"))

# Per-block segment kinds (lax.switch branch order).
KIND_EMPTY, KIND_DIAG, KIND_FULL = 0, 1, 2


def _vary_like(x, ref):
    """Mark ``x`` varying over ``ref``'s manual axes (shard_map VMA typing)
    so scan carries / switch branches initialized from constants match the
    data-derived branches' types; a no-op outside manual regions and
    under ``check_vma=False``, where nothing is tracked."""
    vma = tuple(jax.typeof(ref).vma)
    return lax.pcast(x, vma, to="varying") if vma else x


def _chunk_len(tk: int) -> int:
    if tk % _KV_CHUNK == 0:
        return _KV_CHUNK
    # largest power-of-two divisor; below 64 lanes a chunked scan would
    # degenerate into thousands of sliver matmuls, so fall back to the
    # whole block — correctness and MXU width first
    c = 1
    while tk % (c * 2) == 0 and c * 2 <= _KV_CHUNK:
        c *= 2
    return c if c >= 64 else tk


# ---------------------------------------------------------------------------
# Segment kernels: one (q block, kv block) interaction, [B, H, S, D] layout.
# fwd -> (o f32 normalized-within-block, lse f32); bwd under the GLOBAL lse
# -> (dq, dk, dv) f32. TPU takes the stock Pallas flash kernels; the chunked
# pure-JAX implementation is bit-compatible in semantics and portable.
# ---------------------------------------------------------------------------


# The ring's per-block kernels reach into PRIVATE names of the stock Pallas
# flash module (_flash_attention, _flash_attention_bwd_dkv/_dq, BlockSizes,
# DEFAULT_MASK_VALUE). They are imported at the call sites below with no
# guard: a jax bump that renames one is an ImportError/AttributeError at
# the first TPU trace of the ring, never a silent switch to the chunked
# pure-JAX kernels (which remain for CPU and for 128-unaligned blocks).


def _pallas_seg_ok(s: int) -> bool:
    if _os.environ.get("HOROVOD_RING_PALLAS", "1").strip().lower() not in (
            "1", "true", "yes", "on"):
        return False
    from .flash_attention import flash_available
    return flash_available() and s >= 128 and s % 128 == 0


# Preferred Pallas block size for the ring's per-segment kernels; 1024 is
# the measured winner at T=8192 on v5e (512 probed: 12.0 vs 11.6 ms).
# Read once at import like HOROVOD_RING_CHUNK (the lru_cache below keys on
# segment length only); invalid values (non-positive / not a multiple of
# the 128 TPU tile) are ignored with the default kept.
_SEG_BLOCK_PREF = int(_os.environ.get("HOROVOD_RING_SEG_BLOCK", "1024"))
if _SEG_BLOCK_PREF <= 0 or _SEG_BLOCK_PREF % 128:
    _SEG_BLOCK_PREF = 1024


@functools.lru_cache(maxsize=16)
def _seg_blocksizes(s: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    b = next(bb for bb in (_SEG_BLOCK_PREF, 1024, 512, 256, 128)
             if s % bb == 0)
    return BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                      block_q_major_dkv=b, block_k_major_dkv=b,
                      block_k_dkv=b, block_q_dkv=b,
                      block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


def _seg_fwd_pallas(q, kb, vb, causal: bool):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        _flash_attention)
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, l, m = _flash_attention(q, kb, vb, None, None, True, causal, scale,
                               _seg_blocksizes(q.shape[2]), False)
    lse = m + jnp.log(l)
    return o.astype(jnp.float32), lse.astype(jnp.float32)


def _seg_bwd_pallas(q, kb, vb, lse, do, di, causal: bool):
    """Standard flash backward of one block under residuals (m=global lse,
    l=1): p = exp(s·scale − lse) is the block's slice of the GLOBAL
    softmax, so ds = p∘(dp − di) needs no lse-cotangent term — the stock
    kernels apply unchanged."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    scale = 1.0 / math.sqrt(q.shape[-1])
    bs = _seg_blocksizes(q.shape[2])
    ones = jnp.ones_like(lse)
    dk, dv = fa._flash_attention_bwd_dkv(
        q, kb, vb, None, None, ones, lse, do, di,
        block_q_major=bs.block_q_major_dkv, block_q=bs.block_q_dkv,
        block_k_major=bs.block_k_major_dkv, block_k=bs.block_k_dkv,
        sm_scale=scale, causal=causal, mask_value=fa.DEFAULT_MASK_VALUE,
        debug=False)
    dq, _ = fa._flash_attention_bwd_dq(
        q, kb, vb, None, None, ones, lse, do, di,
        block_q_major=bs.block_q_dq, block_k_major=bs.block_k_major_dq,
        block_k=bs.block_k_dq,
        sm_scale=scale, causal=causal, mask_value=fa.DEFAULT_MASK_VALUE,
        debug=False)
    return (dq.astype(jnp.float32), dk.astype(jnp.float32),
            dv.astype(jnp.float32))


def _kv_chunks(x, c):
    b, h, s, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, s // c, c, d), 2, 0)


def _seg_fwd_jax(q, kb, vb, causal: bool):
    b, h, s, d = q.shape
    sk = kb.shape[2]
    c = _chunk_len(sk)
    scale = 1.0 / math.sqrt(d)
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(c)[None, :]

    o0 = _vary_like(jnp.zeros((b, h, s, d), jnp.float32), q)
    m0 = _vary_like(jnp.full((b, h, s), _NEG_INF, jnp.float32), q)
    l0 = _vary_like(jnp.zeros((b, h, s), jnp.float32), q)

    def body(carry, xs):
        o, m, l = carry
        kc, vc, c0 = xs
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                        preferred_element_type=jnp.float32) * scale
        if causal:
            sc = jnp.where((c0 + cols <= rows)[None, None], sc, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        p = jnp.where(sc <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        corr = jnp.where(m <= _NEG_INF / 2, 0.0, corr)
        l = l * corr + jnp.sum(p, axis=-1)
        o = (o * corr[..., None]
             + jnp.einsum("bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
                          preferred_element_type=jnp.float32))
        return (o, m_new, l), None

    (o, m, l), _ = lax.scan(
        body, (o0, m0, l0),
        (_kv_chunks(kb, c), _kv_chunks(vb, c), jnp.arange(sk // c) * c))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = o / l_safe[..., None]
    lse = jnp.where(l > 0.0, m + jnp.log(l_safe), _NEG_INF)
    return out, lse


def _seg_bwd_jax(q, kb, vb, lse, do, di, causal: bool):
    b, h, s, d = q.shape
    sk = kb.shape[2]
    c = _chunk_len(sk)
    scale = 1.0 / math.sqrt(d)
    rows = jnp.arange(s)[:, None]
    cols = jnp.arange(c)[None, :]
    do32 = do.astype(jnp.float32)
    lse_row = lse[..., None]
    di_row = di[..., None]

    def body(dq_acc, xs):
        kc, vc, c0 = xs
        sc = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                        preferred_element_type=jnp.float32) * scale
        if causal:
            sc = jnp.where((c0 + cols <= rows)[None, None], sc, _NEG_INF)
        # p = exp(s − lse): this block's slice of the GLOBAL softmax (lse
        # is the whole ring's); for visible entries s ≤ lse so exp never
        # overflows; masked entries zero through the sentinel
        p = jnp.where(sc <= _NEG_INF / 2, 0.0, jnp.exp(sc - lse_row))
        dp = jnp.einsum("bhqd,bhkd->bhqk", do32, vc,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - di_row)
        dq_acc += jnp.einsum("bhqk,bhkd->bhqd", ds,
                             kc.astype(jnp.float32),
                             preferred_element_type=jnp.float32) * scale
        dkc = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32),
                         preferred_element_type=jnp.float32) * scale
        dvc = jnp.einsum("bhqk,bhqd->bhkd", p, do32,
                         preferred_element_type=jnp.float32)
        return dq_acc, (dkc, dvc)

    dq, (dks, dvs) = lax.scan(
        body, _vary_like(jnp.zeros((b, h, s, d), jnp.float32), q),
        (_kv_chunks(kb, c), _kv_chunks(vb, c), jnp.arange(sk // c) * c))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, sk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, sk, d)
    return dq, dk, dv


def _seg_fwd(q, kb, vb, causal: bool):
    if _pallas_seg_ok(q.shape[2]) and _pallas_seg_ok(kb.shape[2]):
        return _seg_fwd_pallas(q, kb, vb, causal)
    return _seg_fwd_jax(q, kb, vb, causal)


def _seg_bwd(q, kb, vb, lse, do, di, causal: bool):
    if _pallas_seg_ok(q.shape[2]) and _pallas_seg_ok(kb.shape[2]):
        return _seg_bwd_pallas(q, kb, vb, lse, do, di, causal)
    return _seg_bwd_jax(q, kb, vb, lse, do, di, causal)


def _seg_fwd_switch(kind, q, kb, vb):
    """(o, lse) of one block interaction under a runtime kind: EMPTY skips
    the matmuls entirely (real branch, merge-identity result)."""
    def empty(q, kb, vb):
        return (_vary_like(jnp.zeros(q.shape, jnp.float32), q),
                _vary_like(jnp.full(q.shape[:3], _NEG_INF, jnp.float32), q))

    return lax.switch(kind, (empty,
                             lambda q, kb, vb: _seg_fwd(q, kb, vb, True),
                             lambda q, kb, vb: _seg_fwd(q, kb, vb, False)),
                      q, kb, vb)


def _seg_bwd_switch(kind, q, kb, vb, lse, do, di):
    def empty(q, kb, vb, lse, do, di):
        z = functools.partial(jnp.zeros, dtype=jnp.float32)
        return (_vary_like(z(q.shape), q), _vary_like(z(kb.shape), q),
                _vary_like(z(vb.shape), q))

    return lax.switch(
        kind,
        (empty,
         lambda *a: _seg_bwd(*a, causal=True),
         lambda *a: _seg_bwd(*a, causal=False)),
        q, kb, vb, lse, do, di)


# ---------------------------------------------------------------------------
# The whole-ring custom_vjp
# ---------------------------------------------------------------------------


def _merge(o, lse, o_b, lse_b):
    lse_n = jnp.logaddexp(lse, lse_b)
    w = jnp.exp(lse - lse_n)[..., None]
    w_b = jnp.exp(lse_b - lse_n)[..., None]
    return o * w + o_b * w_b, lse_n


def _kind(a, b):
    """Segment kind of q-stripe ``a`` attending kv-stripe ``b`` under the
    global causal order: FULL below the diagonal, DIAG on it, EMPTY above."""
    return (jnp.sign(a - b) + 1).astype(jnp.int32)


def _ring_fwd_impl(causal, layout, axis_name, n, q, k, v):
    """q, k, v local blocks in [B, H, T, D]; returns (out f32, lse f32)."""
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    b, h, t, d = q.shape
    o0 = _vary_like(jnp.zeros((b, h, t, d), jnp.float32), q)
    lse0 = _vary_like(jnp.full((b, h, t), _NEG_INF, jnp.float32), q)
    s_half = t // 2

    def one_step(step, k_cur, v_cur, o, lse):
        s_owner = jnp.mod(my - step, n)
        if not causal:
            o_b, lse_b = _seg_fwd(q, k_cur, v_cur, False)
            return _merge(o, lse, o_b, lse_b)
        if layout == "contiguous":
            o_b, lse_b = _seg_fwd_switch(_kind(my, s_owner), q, k_cur, v_cur)
            return _merge(o, lse, o_b, lse_b)
        # zigzag: halves are stripes (my, 2n-1-my) vs (s, 2n-1-s); the
        # (lo,hi) pair is statically empty, (hi,lo) statically full
        q_lo, q_hi = q[:, :, :s_half], q[:, :, s_half:]
        k_lo, k_hi = k_cur[:, :, :s_half], k_cur[:, :, s_half:]
        v_lo, v_hi = v_cur[:, :, :s_half], v_cur[:, :, s_half:]
        o_lo, o_hi = o[:, :, :s_half], o[:, :, s_half:]
        l_lo, l_hi = lse[:, :, :s_half], lse[:, :, s_half:]
        o_ll, lse_ll = _seg_fwd_switch(_kind(my, s_owner), q_lo, k_lo, v_lo)
        o_hl, lse_hl = _seg_fwd(q_hi, k_lo, v_lo, False)
        o_hh, lse_hh = _seg_fwd_switch(_kind(s_owner, my), q_hi, k_hi, v_hi)
        o_lo, l_lo = _merge(o_lo, l_lo, o_ll, lse_ll)
        o_hi, l_hi = _merge(o_hi, l_hi, o_hl, lse_hl)
        o_hi, l_hi = _merge(o_hi, l_hi, o_hh, lse_hh)
        return (jnp.concatenate([o_lo, o_hi], axis=2),
                jnp.concatenate([l_lo, l_hi], axis=2))

    def step_fn(carry, step):
        k_cur, v_cur, o, lse = carry
        o, lse = one_step(step, k_cur, v_cur, o, lse)
        return (lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm), o, lse), None

    if n > 1:
        (k_last, v_last, o, lse), _ = lax.scan(
            step_fn, (k, v, o0, lse0), jnp.arange(n - 1))
    else:
        k_last, v_last, o, lse = k, v, o0, lse0
    o, lse = one_step(jnp.int32(n - 1), k_last, v_last, o, lse)
    return o, lse


def _ring_bwd_impl(causal, layout, axis_name, n, q, k, v, out, lse, dout):
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    b, h, t, d = q.shape
    s_half = t // 2
    do = dout.astype(q.dtype)
    di = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def zeros(shape):
        return _vary_like(jnp.zeros(shape, jnp.float32), q)

    def one_step(step, k_cur, v_cur):
        """-> (dq_part, dk_part, dv_part) for the currently-held block."""
        s_owner = jnp.mod(my - step, n)
        if not causal:
            return _seg_bwd(q, k_cur, v_cur, lse, do, di, False)
        if layout == "contiguous":
            return _seg_bwd_switch(_kind(my, s_owner), q, k_cur, v_cur,
                                   lse, do, di)
        q_lo, q_hi = q[:, :, :s_half], q[:, :, s_half:]
        k_lo, k_hi = k_cur[:, :, :s_half], k_cur[:, :, s_half:]
        v_lo, v_hi = v_cur[:, :, :s_half], v_cur[:, :, s_half:]
        l_lo, l_hi = lse[:, :, :s_half], lse[:, :, s_half:]
        do_lo, do_hi = do[:, :, :s_half], do[:, :, s_half:]
        di_lo, di_hi = di[:, :, :s_half], di[:, :, s_half:]
        dq_ll, dk_ll, dv_ll = _seg_bwd_switch(
            _kind(my, s_owner), q_lo, k_lo, v_lo, l_lo, do_lo, di_lo)
        dq_hl, dk_hl, dv_hl = _seg_bwd(q_hi, k_lo, v_lo, l_hi, do_hi,
                                       di_hi, False)
        dq_hh, dk_hh, dv_hh = _seg_bwd_switch(
            _kind(s_owner, my), q_hi, k_hi, v_hi, l_hi, do_hi, di_hi)
        dq_part = jnp.concatenate([dq_ll, dq_hl + dq_hh], axis=2)
        dk_part = jnp.concatenate([dk_ll + dk_hl, dk_hh], axis=2)
        dv_part = jnp.concatenate([dv_ll + dv_hl, dv_hh], axis=2)
        return dq_part, dk_part, dv_part

    def step_fn(carry, step):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        dq_p, dk_p, dv_p = one_step(step, k_cur, v_cur)
        # dk/dv accumulators travel WITH their K/V block; after n total
        # hops each block's full gradient lands back on its owner
        return (lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm),
                lax.ppermute(dk_cur + dk_p, axis_name, perm),
                lax.ppermute(dv_cur + dv_p, axis_name, perm),
                dq + dq_p), None

    shape = (b, h, t, d)
    if n > 1:
        (k_last, v_last, dk_cur, dv_cur, dq), _ = lax.scan(
            step_fn, (k, v, zeros(shape), zeros(shape), zeros(shape)),
            jnp.arange(n - 1))
    else:
        k_last, v_last = k, v
        dk_cur, dv_cur, dq = zeros(shape), zeros(shape), zeros(shape)
    dq_p, dk_p, dv_p = one_step(jnp.int32(n - 1), k_last, v_last)
    dq = dq + dq_p
    # final hop sends each block's accumulated dk/dv home (n-1 scan hops
    # + this one = n): rank r processed block (r+1)%n last, so one more
    # rotation lands block s's gradients on rank s. k/v themselves need
    # no final hop — they're residuals, not outputs.
    dk = lax.ppermute(dk_cur + dk_p, axis_name, perm)
    dv = lax.ppermute(dv_cur + dv_p, axis_name, perm)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ring(causal, layout, axis_name, n, q, k, v):
    out, _ = _ring_fwd_impl(causal, layout, axis_name, n, q, k, v)
    return out


def _ring_fwd(causal, layout, axis_name, n, q, k, v):
    out, lse = _ring_fwd_impl(causal, layout, axis_name, n, q, k, v)
    # residuals: local blocks only — O(B·H·T_local·D), no per-step copies
    return out, (q, k, v, out.astype(q.dtype), lse)


def _ring_bwd(causal, layout, axis_name, n, res, dout):
    q, k, v, out, lse = res
    return _ring_bwd_impl(causal, layout, axis_name, n, q, k, v, out, lse,
                          dout)


_ring.defvjp(_ring_fwd, _ring_bwd)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def ring_attention_p(q, k, v, axis_name: str, axis_size: int,
                     causal: bool = True, layout: str = "contiguous",
                     force_ring: bool = False, under_remat: bool = False):
    """Blockwise ring attention over mesh axis ``axis_name``.

    Args:
      q, k, v: local blocks [B, T_local, H, D]. Under ``layout=
        "contiguous"`` the global sequence is the concatenation of blocks
        in axis order; under ``"zigzag"`` rank r holds stripes
        (r, 2n−1−r) of the 2n-striped sequence (see
        :func:`zigzag_indices`) — causally load-balanced: every rank
        executes identical per-step work instead of late ranks doing ~2×.
      causal: apply a causal mask over *global* positions. (Non-causal
        attention is permutation-invariant over keys, so layout does not
        matter and the contiguous schedule is used.)
      force_ring: drive the generic ring path even at axis_size 1 (the
        ppermute is an identity hop) — lets a single chip measure the
        multi-chip kernels honestly.

    Returns the local attention output [B, T_local, H, D].
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring layout {layout!r}")
    n = axis_size
    if n == 1 and not force_ring:
        # degenerate ring: route to the tuned single-shard kernel (Pallas
        # flash/splash on TPU, materialized elsewhere)
        from .flash_attention import flash_attention_local
        return flash_attention_local(q, k, v, causal=causal,
                                     under_remat=under_remat)
    if layout == "zigzag" and q.shape[1] % 2:
        raise ValueError("zigzag layout needs an even local block length")
    eff_layout = layout if causal else "contiguous"
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = _ring(causal, eff_layout, axis_name, n, qh, kh, vh)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def zigzag_indices(t_global: int, n: int):
    """Permutation mapping the natural sequence order to zig-zag layout.

    The sequence is cut into 2n stripes; rank r owns stripes
    (r, 2n−1−r). ``idx`` is ordered so a *contiguous* shard of
    ``x[..., idx, ...]`` over the seq axis hands each rank its stripe
    pair: ``x_zig = jnp.take(x, idx, axis=seq_axis)``. Returns
    (idx, inverse) — apply ``inverse`` to outputs to restore natural
    order."""
    if t_global % (2 * n):
        raise ValueError(f"sequence length {t_global} not divisible into "
                         f"{2 * n} zigzag stripes")
    s = t_global // (2 * n)
    import numpy as np
    idx = np.concatenate([
        np.concatenate([np.arange(r * s, (r + 1) * s),
                        np.arange((2 * n - 1 - r) * s, (2 * n - r) * s)])
        for r in range(n)])
    inv = np.empty_like(idx)
    inv[idx] = np.arange(t_global)
    return jnp.asarray(idx), jnp.asarray(inv)


def zigzag_pair_kinds(rank: int, owner: int, n: int):
    """The (testable) zig-zag schedule: kinds of the four stripe-pair
    interactions when ``rank`` attends the block owned by ``owner``.
    Returns {(qs, ks): kind} with qs/ks in {"lo","hi"} and kind in
    {KIND_EMPTY, KIND_DIAG, KIND_FULL}. The compiled program drives its
    ``lax.switch`` branches from exactly this arithmetic."""
    def k3(a, b):
        return KIND_FULL if a > b else (KIND_DIAG if a == b else KIND_EMPTY)
    a_lo, a_hi = rank, 2 * n - 1 - rank
    b_lo, b_hi = owner, 2 * n - 1 - owner
    return {("lo", "lo"): k3(a_lo, b_lo), ("lo", "hi"): k3(a_lo, b_hi),
            ("hi", "lo"): k3(a_hi, b_lo), ("hi", "hi"): k3(a_hi, b_hi)}


def local_attention(q, k, v, causal: bool = True, window: int = 0):
    """Single-device reference attention (same layout), for tests and the
    non-SP path: [B, T, H, D] -> [B, T, H, D]. ``window`` > 0: key ``j`` is
    visible from query ``i`` iff ``0 <= i - j < window``. ``k`` and ``v``
    may have fewer heads than ``q``: query head ``n`` reads KV head ``n //
    (H / H_kv)``, and K and V are not repeated. ``v``'s heads may be of
    another size than ``q``'s and ``k``'s: the result's are ``v``'s."""
    B, T, H, D = q.shape
    grouped = k.shape[2] != H
    if grouped:     # [B, T, H_kv, G, D]: a KV head's group of query heads
        q = q.reshape(B, T, k.shape[2], H // k.shape[2], D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk" if grouped else "bqhd,bkhd->bhqk",
                   q, k, preferred_element_type=jnp.float32) / math.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        if window:
            mask = jnp.logical_and(mask, ~jnp.tril(mask, -window))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    elif window:
        raise ValueError("a window is causal")
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd" if grouped else "bhqk,bkhd->bqhd",
                     p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    if grouped:
        out = out.reshape(B, T, H, v.shape[-1])
    return out.astype(q.dtype)
