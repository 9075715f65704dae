"""Flash attention for the single-device (non-sequence-parallel) path.

The sequence-parallel kernels (ring/Ulysses, parallel/{ring_attention,
ulysses}.py) own the *distributed* attention surface; this module is the
single-shard compute kernel: on TPU it calls the Pallas flash-attention
kernel shipped with JAX (blockwise online-softmax — O(T) memory, causal
blocks skipped); off TPU it computes the materialized reference attention
so CPU tests exercise the same call sites. On TPU the stock kernel modules
are imported unguarded: a jax that moved them is an ImportError at the
first trace, never a silent change of kernel.

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
import os

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
    """The newer splash-attention TPU kernel, the default wherever
    :func:`_select_kernel` does not degrade to flash. On the v5e it is what
    ``lm-spmd-1chip`` runs (B4 H16 T2048 D128 causal: ``attn_kernel_ms_per_step``
    13.14 ms over 4 layers, 31.9% of roofline; ledger, PR 22) and flash is
    what ``ouro-spmd-1chip-loop4`` runs under remat (ledger, PR 28). The two
    kernels at ONE shape against each other: not measured (the one-chip
    sweep of ROADMAP queue 1)."""
    # default-on choice knob ("force" additionally overrides the
    # automatic under-remat degrade — see _select_kernel)
    return _splash_mode() != "0" and jax.default_backend() == "tpu"


def _scoped_vmem_bytes() -> int:
    """v5e scoped VMEM budget the splash kernel compiles against;
    overridable per chip generation (read per call, like the sibling
    HOROVOD_SPLASH* knobs)."""
    return int(os.environ.get("HOROVOD_SPLASH_VMEM_LIMIT",
                              str(16 * 1024 * 1024)))


def _splash_bkv(t: int) -> int:
    """The kv block size the splash kernel will actually be built with
    (single source of truth for _build_splash_kernel and the VMEM
    estimator): 2048 is the measured winner but must divide t; odd
    multiples of 1024 take the 1024 block. HOROVOD_SPLASH_BLOCK_KV
    overrides (e.g. to fit under remat recompute)."""
    bkv_pref = int(os.environ.get("HOROVOD_SPLASH_BLOCK_KV", "2048"))
    return bkv_pref if t % bkv_pref == 0 else 1024


def _splash_remat_vmem_bytes(t: int, d: int, bkv: int,
                             itemsize: int = 2) -> int:
    """Engineering estimate of splash's peak scoped-VMEM residency when a
    remat'd block RECOMPUTES the residual-saving forward inside the
    backward pass (so forward slabs co-reside with the dq/dkv kernel's).
    Counted: the f32 score slab (block_q x block_kv), double-buffered
    streamed K/V and q blocks, and the f32 output accumulator — for both
    the recomputed forward (at block_kv = ``bkv``) and the backward
    kernels (at their 1024 blocks). Anchored on the two v5e measurements
    (VERDICT r4 weak #4): bkv=2048 at the flagship shape overflows the
    16 MiB scope (estimate 17.0 MiB), bkv=1024 fits (12.0 MiB)."""
    bq = min(1024, t)
    bkv = min(bkv, t)

    def slab(block_q, block_k):
        return (block_q * block_k * 4            # f32 scores
                + 2 * (2 * block_k * d * itemsize)  # double-buffered K,V
                + 2 * (block_q * d * itemsize)      # double-buffered q
                + block_q * d * 4)                  # f32 out accumulator

    bd = min(1024, t)
    return slab(bq, bkv) + slab(bd, bd)


def _select_kernel(t: int, d: int, under_remat: bool,
                   itemsize: int = 2) -> str:
    """'splash' or 'flash' for a splash-eligible shape. Under remat the
    residual-saving splash forward is recomputed inside the backward and
    its VMEM residency can overflow the scope (an XLA compile error, not
    an OOM a user can act on) — degrade to flash automatically unless
    HOROVOD_SPLASH=force insists (VERDICT r4 item 7: knobs are overrides,
    not the mechanism). ``itemsize`` is the q/k/v element size (fp32
    inputs double the streamed-slab residency).

    What the estimate does with bf16 heads of 128 under remat: T = 1024
    reads 12.6 MB and stays with splash; every T that 2048 divides (2048,
    4096, 8192, ...) reads 17.8 MB against the 16 MiB scope and goes to the
    stock flash kernel at 1024 blocks; odd multiples of 1024 (3072, ...)
    take the 1024 kv block, read 12.6 MB and stay with splash. It is an
    estimate anchored on two readings of an older backend, and whether
    splash at T = 4096 would in fact overflow under recomputation has not
    been tried on the chip. What the chip showed of the flash side
    (PERF.md PR 28, v5e, 1 x 16 x 4096 x 128, remat="block", 24 layer
    applications a step, by scope): forward 15.1 ms, the forward run again
    15.8, dkv 27.6, dq 20.0; 77.7 ms of kernel time a step, 32.3% of the
    compute roofline of the attention the objective needs (25.1 ms),
    against 31.8% for splash at 4 x 2048 without remat."""
    if not under_remat:
        return "splash"
    if _splash_mode() == "force":
        return "splash"
    if _splash_remat_vmem_bytes(t, d, _splash_bkv(t),
                                itemsize) > _scoped_vmem_bytes():
        return "flash"
    return "splash"


@functools.lru_cache(maxsize=32)
def _splash_kernel(h: int, t: int, causal: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    # Kernel construction may run inside a jit trace (shapes are only known
    # there); its mask-processing arrays must be compile-time constants, not
    # tracers — the lru_cache would otherwise leak a tracer into later
    # traces (observed as UnexpectedTracerError on the second trace).
    with jax.ensure_compile_time_eval():
        return _build_splash_kernel(sk, sm, h, t, causal)


def _build_splash_kernel(sk, sm, h: int, t: int, causal: bool):
    mk = sm.CausalMask if causal else (lambda s: sm.FullMask(s))
    mask = sm.MultiHeadMask([mk((t, t)) for _ in range(h)])
    bq = min(1024, t)
    bkv = _splash_bkv(t)  # shared with the remat VMEM estimator
    bd = min(1024, t)
    bs = sk.BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv,
                       block_q_dkv=bd, block_kv_dkv=bd,
                       block_kv_dkv_compute=bd, block_q_dq=bd,
                       block_kv_dq=bd)
    return sk.make_splash_mha(mask, head_shards=1, q_seq_shards=1,
                              block_sizes=bs)


def _splash_ok(q_shape, kv_shape) -> bool:
    _, _, t, d = q_shape
    # square attention only: the mask is built (t, t); rectangular q/kv
    # (cross-attention, chunked decode) falls back to the flash kernel
    return (t >= 1024 and t % 1024 == 0 and d % 128 == 0
            and kv_shape[2] == t and kv_shape[3] == d)


def _block_sizes(t: int):
    """Measured on v5e (T=2048, D=128): 1024/1024 blocks beat the kernel's
    512-default by ~20% fwd; fall back to defaults for short sequences."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    if t < 1024 or t % 1024:
        return None
    b = 1024
    return BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                      block_q_major_dkv=b, block_k_major_dkv=b,
                      block_k_dkv=b, block_q_dkv=b,
                      block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


@functools.lru_cache(maxsize=None)
def _warn_unaligned_once(q_t: int, kv_t: int) -> None:
    logging.getLogger("horovod_tpu").warning(
        "flash_attention_local: sequence lengths q=%d kv=%d are not "
        "multiples of 128; this shape runs the materialized O(T^2) "
        "attention on TPU, not the Pallas flash kernel", q_t, kv_t)


def flash_attention_local(q, k, v, causal: bool = True,
                          layout: str = "bthk",
                          under_remat: bool = False):
    """Attention via the Pallas TPU flash kernel; materialized attention
    off-TPU and (with a one-time warning) for sequence lengths the kernel's
    128-row blocks do not divide. ``layout``
    is the layout of q/k/v (and the result):
    "bthk" ([B, T, H, D], the framework's default) or "bhtk" ([B, H, T, D],
    the kernel's native layout — callers that can project straight into it
    skip the transposes). ``under_remat=True`` tells the kernel selector
    this call sits inside a jax.checkpoint region whose backward recomputes
    it — splash auto-degrades to flash when its recompute VMEM bound
    exceeds the chip scope (see :func:`_select_kernel`)."""
    if layout not in ("bthk", "bhtk"):
        raise ValueError(f"unknown attention layout {layout!r}")
    # The Pallas flash kernel's _verify_block requires both sequence lengths
    # divisible by its block sizes (128 minimum); unaligned lengths
    # (ViT-B/16 at 224px -> 197 tokens, ViT_Tiny/32 -> 17) take the
    # materialized attention instead of crashing on TPU (ADVICE r3 medium).
    kernel_t = q.shape[1] if layout == "bthk" else q.shape[2]
    kv_t = k.shape[1] if layout == "bthk" else k.shape[2]
    unaligned = kernel_t % 128 or kv_t % 128
    if flash_available() and unaligned:
        _warn_unaligned_once(kernel_t, kv_t)
    if not flash_available() or unaligned:
        if layout == "bhtk":
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
        out = local_attention(q, k, v, causal=causal)
        return out.transpose(0, 2, 1, 3) if layout == "bhtk" else out
    scale = 1.0 / math.sqrt(q.shape[-1])
    if layout == "bthk":
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    if (splash_available() and _splash_ok(q.shape, k.shape)
            and _select_kernel(q.shape[2], q.shape[3], under_remat,
                               q.dtype.itemsize) == "splash"):
        kernel = _splash_kernel(q.shape[1], q.shape[2], causal)
        out = jax.vmap(kernel)((q * scale).astype(q.dtype), k, v)
    else:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as _fa)
        bs = _block_sizes(q.shape[2])
        out = _fa(q, k, v, causal=causal, sm_scale=scale,
                  **({"block_sizes": bs} if bs is not None else {}))
    return out.transpose(0, 2, 1, 3) if layout == "bthk" else out
