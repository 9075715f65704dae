"""Flash attention for the single-device (non-sequence-parallel) path.

The sequence-parallel kernels (ring/Ulysses, parallel/{ring_attention,
ulysses}.py) own the *distributed* attention surface; this module is the
single-shard compute kernel: on TPU it calls the Pallas attention kernels
shipped with JAX (blockwise online-softmax: O(T) memory, wholly masked
blocks skipped): splash at the blocks :func:`splash_geometry` chose for
square attention over a multiple of 1024 positions with heads a multiple of
128 (or of 64 under fewer KV heads than query heads), causal or banded (a
sliding ``window``), with as many KV heads as query heads or fewer (then the kernel's MQA form over each KV head's group of
query heads: K and V are never repeated in HBM); the older flash kernel for
the other aligned shapes; off TPU it computes the materialized reference
attention so CPU tests exercise the same call sites.
:func:`attention_kernel` says which of them a shape reaches. On TPU
the stock kernel modules are imported unguarded: a jax that moved them is an
ImportError at the first trace, never a silent change of kernel.

Motivation: materialized attention keeps a [B, H, T, T] score matrix a
layer (0.5 GB at B4 H16 T2048 in bf16); the kernels never write it. What
they take on the chip is ``attn_kernel_ms_per_step`` of the LM cells
(``python3 benchmark/run.py --workload lm-spmd-1chip --trace 1``; PERF.md
section 5); materialized attention at those shapes: not measured. The
reference has no attention kernels at all (it is model-agnostic); this is
part of the beyond-parity compute layer the TPU build owns (SURVEY §7 maps
the reference's SIMD C++ to Pallas).
"""

from __future__ import annotations

import functools
import logging
import math
from typing import NamedTuple, Optional

import jax

from ..common.env import _get_choice
from .ring_attention import local_attention


def _splash_mode() -> str:
    """The HOROVOD_SPLASH choice, normalized to "0" / "1" / "force",
    through the registry parser (ISSUE 11 knobcheck: declared-choice
    knobs must not be re-parsed ad hoc — the two raw reads here had
    already drifted to different defaults and accepted-token sets).
    The declared choices keep every historically-working token: the
    boolean aliases stay valid in BOTH directions, so a deliberate
    ``HOROVOD_SPLASH=off`` keeps disabling the kernel. Two edges
    deliberately follow the framework-wide ``_get_choice`` discipline
    instead of the old ad-hoc parse: genuinely unknown tokens warn
    loudly and take the default (instead of silently disabling), and a
    set-but-EMPTY value means "unset" (default, enabled) like every
    other knob in the registry — not a silent disable."""
    from ..common.knobs import KNOB_SPECS
    spec = KNOB_SPECS["HOROVOD_SPLASH"]
    v = _get_choice("HOROVOD_SPLASH", spec["default"], spec["choices"])
    if v == "force":
        return "force"
    return "1" if v in ("1", "true", "yes", "on") else "0"


def flash_available() -> bool:
    """Whether attention takes the Pallas TPU kernels: on a TPU, always."""
    return jax.default_backend() == "tpu"


def splash_available() -> bool:
    """The stock splash-attention kernel: on a TPU unless ``HOROVOD_SPLASH``
    is off. Since PR 31 it takes every shape :func:`_splash_ok` admits,
    under recomputation too (``force`` has nothing left to override and
    reads as on)."""
    return _splash_mode() != "0" and flash_available()


class SplashBlocks(NamedTuple):
    """The geometry the stock splash kernel is built with: the forward's and
    the backward's blocks as its ``BlockSizes`` names them. The backward is
    always the ONE fused kernel (``use_fused_bwd_kernel``): dq comes out of
    the dkv kernel, a partial per kv block summed outside, so there are no
    dq blocks."""
    block_q: int
    block_kv: int
    block_kv_compute: int
    block_q_dkv: int
    block_kv_dkv: int
    block_kv_dkv_compute: int


def splash_geometry(t: int, d: int, causal: bool, under_remat: bool,
                    window: int = 0) -> SplashBlocks:
    """The blocks for square attention over ``t`` positions (a multiple of
    1024: :func:`_splash_ok`) with heads of ``d``. Chosen on the v5e by
    ``tools/attn_sweep.py`` (PERF.md section 6, PR 31: every block in 512,
    1024, 2048 at 4 x 16 x 2048 x 128 and at 1 x 16 x 4096 x 128 under
    ``jax.checkpoint``, ms a call forward / forward + backward):

    - the kernel skips a (block_q, block_kv) block of the mask that is
      wholly masked and runs every ``block_kv_compute`` slice of one that is
      not, so a causal call wants a kv block below ``t``: 1024, in compute
      slices of 512 (0.93 / 0.70 ms against 1.24 / 0.89 for one kv block of
      2048; q 512 or kv 512 skip more and lose it again to the extra grid
      steps: 0.96-1.05 / 0.71-0.78);
    - the backward is one fused kernel at 1024 blocks (5 matmuls for the
      7 of dkv and dq apart): 2.65 / 2.01 ms forward + backward against
      3.26 / 2.48 for the best split backward and 3.57 / 2.65 for the
      blocks before PR 31. It writes dq a kv block at a time in q's dtype
      and sums the partials outside; against float32 its dq reads 3.79e-3 /
      3.83e-3 (relative L2, worst of 8 seeds) where the split backward
      reads 3.72e-3 / 3.73e-3, dk and dv the same;
    - 2048 anywhere in the backward, or q 2048 with kv 2048 forward, does
      not fit the 16 MiB of scoped VMEM; what is chosen here compiles under
      recomputation too (``tests/test_tpu_compile.py`` holds that), so
      ``under_remat`` changes nothing on this backend: both shapes chose
      the same blocks;
    - heads of 256 (in no model of this repo; fit by the same compile test,
      times not measured): the fused backward at q 1024 needs 17.4 MB and a
      forward kv block of 2048 does not fit either, so the backward's q
      block is 512 and every kv block 1024.

    Under a band (``window``: key ``j`` visible from query ``i`` iff ``0 <=
    i - j < window``; PR 32, same tool, 1 x 32/4 x 8192 x 128 under
    ``jax.checkpoint``, 32 query heads over 4 KV heads through the kernel's
    MQA form, window 2048 / causal; ms forward, forward + forward +
    backward): the causal blocks again, 2.61, 9.51 / 4.45, 14.55, against
    2.60, 11.11 / 5.38, 17.59 for 512 blocks everywhere (a band of 2048
    runs 5 kv blocks of 512 a q block where 3 of 1024 do, and skips no more
    by it than the grid steps cost), 2.94, 9.85 / 4.91, 15.02 for compute
    slices of 1024, 3.20, 10.85 / 4.54, 14.82 for kv blocks of 2048, and
    10.48 / 17.76 with dq in a kernel of its own: the fused backward's dq
    partials (8 copies of dq at T = 8192) still cost less than a second pass
    over the scores. So ``window`` changes nothing here either.

    Heads of 64 (PR 34, same tool, 2 x 32/8 x 8192 x 64 causal under
    ``jax.checkpoint``, 52 geometries that fit; ms forward, forward + forward
    + backward): the causal blocks of heads of 128 again, 9.46, 31.25,
    against 9.54 for a kv block of 2048 forward, 9.95-12.0 for q or kv 512,
    32.5-37.3 for the other fused backwards, 37.05 with dq in a kernel of its
    own and 11.29, 38.95 for the blocks before PR 31. A pair of (query, key)
    costs a head of 64 what it costs a head of 128 (twice the sparse-expert
    cell's full layer for twice the pairs: 4.45, 14.55 there): the kernel
    pads the head to its 128 lanes, so at the published 64 it reads about
    half the share of its roofline.

    Q and k heads of 192 beside v heads of 128 (latent attention; PR 41, same
    tool, 2 x 32 x 8192 x (192, 128) causal under ``jax.checkpoint``, 36
    geometries that fit; ms forward, forward + forward + backward): the
    causal blocks of heads of 128 once more, 15.11, 50.21, against 15.30 for
    a kv block of 2048 forward, 15.65-16.43 for the other forwards, 50.96
    with the backward's q block at 512 (what ``d > 128`` gave before),
    51.1-57.9 for the other fused backwards and 57.91 with dq in a kernel of
    its own. The kernel lays a head of 192 out on 256 lanes: the same call
    with q and k zero-padded to 256 OUTSIDE the kernel (exact: the scores do
    not change) has the same temporaries to the byte (2.95 GB) and reads
    15.96, 51.06, the two pad copies slower; so a pair costs 384 lanes for
    320 in the two products over the head, and the kernel reads at most
    five sixths of what a kernel that multiplied the 128 and the 64 apart
    would. The fused backward at q 1024 fits the 16 MiB at 192 where it does
    not at 256, so only heads ABOVE 192 take the smaller block.

    Not causal (in no cell: ViT's lengths never reach the kernel; 4 x 16 x
    2048 x 128 in the same sweep): no block is masked, a smaller kv block
    skips nothing, so the kv block is 2048 where it divides ``t``, in
    compute slices of 1024, and the backward is fused there too: 1.06 ms
    forward, 3.12 forward + backward, against 1.14 and 4.03 for the blocks
    before PR 31 and 1.18 and 3.32 for the causal blocks."""
    del under_remat, window     # one answer on this backend: see above
    wide = d > 192
    kv = 1024 if causal or wide or t % 2048 else 2048
    return SplashBlocks(block_q=1024, block_kv=kv,
                        block_kv_compute=512 if causal else 1024,
                        block_q_dkv=512 if wide else 1024, block_kv_dkv=kv,
                        block_kv_dkv_compute=1024)


def _kernel_and_why(q_shape, kv_shape, window: int = 0,
                    v_head_size: int = 0) -> tuple:
    """``(kernel, why)`` for q and k/v of [B, H, T, D] on this backend
    (``v_head_size``: the width of a V head where it is not K's):
    "splash", "flash" or "materialized", and for materialized attention on
    a TPU the reason in words (``""`` off the TPU, where it is the only
    choice). Materialized attention off the TPU and for sequence lengths the
    kernels' 128-row blocks do not divide (ViT's 197 and 17 tokens); splash
    for what :func:`_splash_refusal` admits; the stock flash kernel for the
    rest (rectangular q/kv, T not a multiple of 1024, other head sizes) and
    with ``HOROVOD_SPLASH`` off. The stock flash kernel knows no window, no
    grouped KV heads and no V head of another size than q's and k's: what
    splash refuses of those is materialized."""
    if not flash_available():
        return "materialized", ""
    if q_shape[2] % 128 or kv_shape[2] % 128:
        return "materialized", (
            f"sequence lengths q={q_shape[2]} kv={kv_shape[2]} are not "
            f"multiples of 128")
    v_head_size = v_head_size or kv_shape[3]
    refused = _splash_refusal(q_shape, kv_shape, v_head_size) \
        if splash_available() else "HOROVOD_SPLASH is off"
    if not refused:
        return "splash", ""
    if window or q_shape[1] != kv_shape[1] or v_head_size != q_shape[3]:
        return "materialized", (
            f"the stock flash kernel knows no window (here {window}), no "
            f"grouped KV heads (here {kv_shape[1]} under {q_shape[1]} query "
            f"heads) and no V head of another size than q's and k's (here "
            f"{v_head_size} beside {q_shape[3]}), and splash does not take "
            f"the shape: {refused}")
    return "flash", ""


def _select_kernel(q_shape, kv_shape, window: int = 0,
                   v_head_size: int = 0) -> str:
    return _kernel_and_why(q_shape, kv_shape, window, v_head_size)[0]


def attention_kernel(q_shape, kv_shape, causal: bool = True,
                     under_remat: bool = False, window: int = 0,
                     v_head_size: int = 0) -> dict:
    """What :func:`flash_attention_local` runs for q and k/v of [B, H, T, D]
    on this backend, as the labels of the gauge ``hvd_tpu_attn_kernel``:
    ``kernel`` ("splash", "flash", "materialized"), the forward's
    ``block_q`` and ``block_kv``, whether the backward is one fused kernel,
    the ``window`` (0: none), the ``head_size`` of q and k and the
    ``v_head_size`` (``v_head_size`` 0: as wide as k's). A function of the
    shapes, ``causal``, ``under_remat`` and ``window`` alone; the call
    itself dispatches on it."""
    kernel = _select_kernel(q_shape, kv_shape, window, v_head_size)
    if kernel == "splash":
        g = splash_geometry(q_shape[2], q_shape[3], causal, under_remat,
                            window)
        blocks = (g.block_q, g.block_kv, True)
    elif kernel == "flash":
        blocks = (_flash_block(q_shape[2], kv_shape[2]),) * 2 + (False,)
    else:
        blocks = (0, 0, False)
    return {"kernel": kernel, "block_q": str(blocks[0]),
            "block_kv": str(blocks[1]), "fused_bwd": str(int(blocks[2])),
            "window": str(window), "head_size": str(q_shape[3]),
            "v_head_size": str(v_head_size or kv_shape[3])}


@functools.lru_cache(maxsize=32)
def _splash_kernel(h: int, t: int, d: int, causal: bool, under_remat: bool,
                   window: int = 0, grouped: bool = False):
    """The stock splash kernel over ``h`` query heads: one K and V a head
    (``make_splash_mha``), or with ``grouped`` ONE K and V for all ``h`` of
    them (``make_splash_mqa``: a KV head and its group of query heads)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    # Kernel construction may run inside a jit trace (shapes are only known
    # there); its mask-processing arrays must be compile-time constants, not
    # tracers — the lru_cache would otherwise leak a tracer into later
    # traces (observed as UnexpectedTracerError on the second trace).
    with jax.ensure_compile_time_eval():
        if window:
            if not causal:
                raise ValueError("a window is causal: key j is visible "
                                 "from query i iff 0 <= i - j < window")
            one = sm.LocalMask((t, t), (window - 1, 0), 0)
        else:
            one = (sm.CausalMask if causal else sm.FullMask)((t, t))
        mask = sm.MultiHeadMask([one for _ in range(h)])
        bs = sk.BlockSizes(
            use_fused_bwd_kernel=True,
            **splash_geometry(t, d, causal, under_remat, window)._asdict())
        make = sk.make_splash_mqa if grouped else sk.make_splash_mha
        return make(mask, head_shards=1, q_seq_shards=1, block_sizes=bs)


# (q/k head, v head) pairs of unequal sizes that splash takes: each was
# built, fitted and timed on the v5e before it came here (:func:`splash_geometry`
# has the readings). 192 = 128 + 64 rotated beside 128: latent attention.
_UNEQUAL_HEADS = ((192, 128),)


def _splash_refusal(q_shape, kv_shape, v_head_size: int = 0) -> str:
    """Why splash does not take q and k of [B, H, T, D] with v heads of
    ``v_head_size`` (0: ``D``); ``""`` where it does."""
    _, h, t, d = q_shape
    v_head_size = v_head_size or d
    # square attention only: the mask is built (t, t); rectangular q/kv
    # (cross-attention, chunked decode) falls back to the flash kernel
    if kv_shape[2] != t or kv_shape[3] != d:
        return f"q {tuple(q_shape)} and k/v {tuple(kv_shape)} are not square"
    if t < 1024 or t % 1024:
        return f"{t} positions are not a multiple of 1024"
    if h % kv_shape[1]:
        return f"{kv_shape[1]} KV heads do not divide {h} query heads"
    if v_head_size != d:
        # the stock kernels keep ``head_dim_qk`` and ``head_dim_v`` apart;
        # what is admitted is what was measured
        if (d, v_head_size) in _UNEQUAL_HEADS and kv_shape[1] == h:
            return ""
        return (f"q/k heads of {d} beside v heads of {v_head_size}: only "
                f"{_UNEQUAL_HEADS} with as many KV heads as query heads "
                f"were built and timed; another pair needs its blocks "
                f"fitted (tests/test_tpu_compile.py) and swept "
                f"(tools/attn_sweep.py) before splash_geometry answers it")
    # a head of 64 fills half of the kernel's 128 lanes (its
    # ``head_dim_v_repeats`` slices to the head's width). Admitted where it
    # was measured on the v5e (PERF.md section 6, PR 34): the MQA form,
    # fewer KV heads than query heads, for which the stock flash kernel has
    # no form and the other choice is materialized attention. With as many
    # KV heads as query heads a head of 64 stays on the stock flash kernel,
    # which takes it and was never timed against splash.
    if d % 128 and not (d == 64 and kv_shape[1] < h):
        return (f"a head of {d} is no multiple of 128 (64 only under "
                f"fewer KV heads than query heads)")
    return ""


def _splash_ok(q_shape, kv_shape, v_head_size: int = 0) -> bool:
    return not _splash_refusal(q_shape, kv_shape, v_head_size)


def _flash_block(q_t: int, kv_t: int) -> int:
    """Every block of the stock flash kernel: 1024 where it divides both
    sequence lengths, else the kernel's own default of 128. 1024 against
    512 on this backend: PERF.md section 6 (PR 31's sweep, T = 4096)."""
    return 128 if q_t % 1024 or kv_t % 1024 else 1024


def _block_sizes(q_t: int, kv_t: int):
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    b = _flash_block(q_t, kv_t)
    return BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                      block_q_major_dkv=b, block_k_major_dkv=b,
                      block_k_dkv=b, block_q_dkv=b,
                      block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


@functools.lru_cache(maxsize=None)
def _warn_materialized_once(why: str) -> None:
    logging.getLogger("horovod_tpu").warning(
        "flash_attention_local: %s; this shape runs the materialized "
        "O(T^2) attention on TPU, not a Pallas kernel", why)


def _device_bytes() -> Optional[int]:
    """What the first device holds, where the backend says."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def _refuse_what_no_chip_holds(q_shape, kv_shape, why: str) -> None:
    """Materialized attention keeps a row's scores and their softmax, two
    fp32 arrays of [H, T_q, T_kv]: where they exceed the device's memory the
    allocator would fail deep inside the program, so say it here."""
    need = 2 * 4 * q_shape[1] * q_shape[2] * kv_shape[2]
    have = _device_bytes()
    if have is not None and need > have:
        raise ValueError(
            f"flash_attention_local: {why}; materialized, one row's scores "
            f"and their softmax ({q_shape[1]} heads x {q_shape[2]} x "
            f"{kv_shape[2]} in fp32, {need / 1e9:.1f} GB) exceed the "
            f"device's {have / 1e9:.1f} GB")


def flash_attention_local(q, k, v, causal: bool = True,
                          layout: str = "bthk",
                          under_remat: bool = False, window: int = 0):
    """Attention via the stock Pallas TPU kernels (:func:`attention_kernel`
    says which); materialized attention off-TPU and (with a one-time
    warning that gives the reason) for what no kernel takes: sequence
    lengths the kernels' 128-row blocks do not divide, a window or grouped KV
    heads at a shape splash refuses (a ``ValueError`` where one row's scores
    would not fit the device).
    ``layout`` is the layout of q/k/v (and the result): "bthk" ([B, T, H,
    D], the framework's default) or "bhtk" ([B, H, T, D], the kernels'
    native layout — callers that can project straight into it skip the
    transposes). ``under_remat=True`` says this call sits inside a
    jax.checkpoint region whose backward runs it again; the geometry may
    depend on it (:func:`splash_geometry`: on this backend it does not).
    ``window`` > 0: key ``j`` is visible from query ``i`` iff ``0 <= i - j
    < window``. ``k`` and ``v`` may have fewer heads than ``q``, a divisor
    of its number: query head ``n`` reads KV head ``n // (H / H_kv)``. ``v``'s
    heads may be of another size than ``q``'s and ``k``'s (the result's are
    ``v``'s; the scores are scaled by ``q``'s)."""
    if layout not in ("bthk", "bhtk"):
        raise ValueError(f"unknown attention layout {layout!r}")
    bhtk = layout == "bhtk"

    def swap(x):    # [B, T, H, D] <-> [B, H, T, D]
        return x.transpose(0, 2, 1, 3)

    def as_bhtk(shape):
        return shape if bhtk else (shape[0], shape[2], shape[1], shape[3])

    kernel, why = _kernel_and_why(as_bhtk(q.shape), as_bhtk(k.shape),
                                  window, v.shape[-1])
    if kernel == "materialized":
        # The Pallas kernels want both sequence lengths divisible by their
        # blocks (128 at least); unaligned lengths (ViT-B/16 at 224px -> 197
        # tokens, ViT_Tiny/32 -> 17) take the materialized attention
        # instead of crashing on TPU, and so does a window or grouped KV
        # heads at a shape splash refuses.
        if why:
            _refuse_what_no_chip_holds(as_bhtk(q.shape), as_bhtk(k.shape),
                                       why)
            _warn_materialized_once(why)
        if bhtk:
            return swap(local_attention(swap(q), swap(k), swap(v),
                                        causal=causal, window=window))
        return local_attention(q, k, v, causal=causal, window=window)
    if not bhtk:
        q, k, v = swap(q), swap(k), swap(v)
    scale = 1.0 / math.sqrt(q.shape[-1])
    if kernel == "splash":
        b, h, t, d = q.shape
        q = (q * scale).astype(q.dtype)
        if k.shape[1] == h:
            splash = _splash_kernel(h, t, d, causal, under_remat, window)
            out = jax.vmap(splash)(q, k, v)
        else:
            # a KV head and its group of query heads a call: [B, H_kv, G, T,
            # D] against [B, H_kv, T, D]
            group = h // k.shape[1]
            splash = _splash_kernel(group, t, d, causal, under_remat, window,
                                    grouped=True)
            out = jax.vmap(jax.vmap(splash))(
                q.reshape(b, k.shape[1], group, t, d), k, v
            ).reshape(b, h, t, v.shape[-1])
    else:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _fa)
        out = _fa(q, k, v, causal=causal, sm_scale=scale,
                  block_sizes=_block_sizes(q.shape[2], k.shape[2]))
    return out if bhtk else swap(out)
