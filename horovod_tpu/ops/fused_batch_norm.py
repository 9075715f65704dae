"""Fused BatchNorm for TPU: Pallas one-pass statistics + custom_vjp backward.

Why this exists: the largest operations of the ResNet-50 train step on a
v5e chip are BatchNorm statistics reductions (XLA ``convert_reduce_fusion``
ops; docs/roofline.md section 1), and the step is bound by HBM there. The
Pallas kernels in :mod:`horovod_tpu.ops.pallas_kernels` read the activation
once in bf16 and accumulate in fp32 VMEM. Whether they beat XLA's own
fusions on today's chip: not measured (default off; ROADMAP queue 3).

Reference parity: the reference has SyncBatchNorm frontends
(torch/sync_batch_norm.py:17-199, tensorflow/sync_batch_norm.py) whose math
this matches (count/mean/var aggregation); the cross-rank part lives in
:mod:`horovod_tpu.ops.sync_batch_norm`. This module is the *single-chip
compute path*: a drop-in for flax ``nn.BatchNorm`` (training mode uses batch
statistics, eval mode running statistics) with identical use_fast_variance
numerics (var = E[x²] − E[x]²).

Backward math (standard BatchNorm vjp):
    xh = (x − μ)·invstd
    dβ = Σ dy            dγ = Σ dy·xh
    dx = γ·invstd · (dy − dβ/M − xh·dγ/M)
The two reductions are one fused Pallas pass over (dy, x).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
import flax.linen as nn

from .pallas_kernels import (bn_bwd_stats_pallas, bn_stats_pallas,
                             bn_stats_supported, pallas_supported)


def _use_pallas(m: int, c: int) -> bool:
    if not pallas_supported() or not bn_stats_supported(c, m):
        return False
    # interpret mode is only for correctness; off-TPU the XLA path is faster
    return jax.default_backend() == "tpu"


def _stats(x2d: jax.Array):
    m, c = x2d.shape
    if _use_pallas(m, c):
        return bn_stats_pallas(x2d)
    xf = x2d.astype(jnp.float32)
    return jnp.sum(xf, axis=0), jnp.sum(xf * xf, axis=0)


def _bwd_stats(dy2d, x2d, mean, invstd):
    m, c = x2d.shape
    if _use_pallas(m, c):
        return bn_bwd_stats_pallas(dy2d, x2d, mean, invstd)
    dyf = dy2d.astype(jnp.float32)
    xh = (x2d.astype(jnp.float32) - mean) * invstd
    return jnp.sum(dyf, axis=0), jnp.sum(dyf * xh, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def batch_norm_train(x, scale, bias, eps: float):
    """Training-mode batch norm over all axes but the last.

    Returns ``(y, mean, var)`` with mean/var in fp32 for the running-stat
    EMA. Gradients flow through ``y`` only (mean/var feed stop-gradient EMA
    state, matching flax BatchNorm)."""
    y, mean, var, _ = _fwd_impl(x, scale, bias, eps)
    return y, mean, var


def _fwd_impl(x, scale, bias, eps):
    c = x.shape[-1]
    x2d = x.reshape(-1, c)
    m = x2d.shape[0]
    s, q = _stats(x2d)
    mean = s / m
    var = jnp.maximum(q / m - mean * mean, 0.0)
    invstd = lax.rsqrt(var + eps)
    a = scale.astype(jnp.float32) * invstd
    b = bias.astype(jnp.float32) - mean * a
    y = (x.astype(jnp.float32) * a + b).astype(x.dtype)
    return y, mean, var, invstd


def _bn_fwd(x, scale, bias, eps):
    y, mean, var, invstd = _fwd_impl(x, scale, bias, eps)
    return (y, mean, var), (x, scale, mean, invstd)


def _bn_bwd(eps, res, cotangents):
    dy, _dmean, _dvar = cotangents  # stats feed stop-gradient EMA only
    x, scale, mean, invstd = res
    c = x.shape[-1]
    x2d = x.reshape(-1, c)
    dy2d = dy.reshape(-1, c)
    m = x2d.shape[0]
    s1, s2 = _bwd_stats(dy2d, x2d, mean, invstd)
    k1 = s1 / m
    k2 = s2 / m
    a = scale.astype(jnp.float32) * invstd
    xh = (x.astype(jnp.float32) - mean) * invstd
    dx = (a * (dy.astype(jnp.float32) - k1 - xh * k2)).astype(x.dtype)
    return dx, s2.astype(scale.dtype), s1.astype(scale.dtype)


batch_norm_train.defvjp(_bn_fwd, _bn_bwd)


class FusedBatchNorm(nn.Module):
    """Drop-in for ``nn.BatchNorm`` (axis=-1) with the fused TPU stat path.

    Supports the subset of the flax API the framework's models use:
    use_running_average / momentum / epsilon / dtype / param_dtype /
    scale_init / bias_init. Statistics use use_fast_variance numerics.
    """
    use_running_average: bool | None = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None
    param_dtype: Any = jnp.float32
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros

    @nn.compact
    def __call__(self, x, use_running_average: bool | None = None):
        if self.use_running_average is None and use_running_average is None:
            use_ra = False
        else:
            use_ra = nn.merge_param(
                "use_running_average", self.use_running_average,
                use_running_average)
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,), self.param_dtype)
        bias = self.param("bias", self.bias_init, (c,), self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        if use_ra:
            mean, var = ra_mean.value, ra_var.value
            invstd = lax.rsqrt(var + self.epsilon)
            a = scale.astype(jnp.float32) * invstd
            b = bias.astype(jnp.float32) - mean * a
            dtype = self.dtype or x.dtype
            return (x.astype(jnp.float32) * a + b).astype(dtype)
        dtype = self.dtype or x.dtype
        y, mean, var = batch_norm_train(x.astype(dtype), scale, bias,
                                        self.epsilon)
        if not self.is_initializing():
            mom = self.momentum
            ra_mean.value = mom * ra_mean.value + (1 - mom) * \
                lax.stop_gradient(mean)
            ra_var.value = mom * ra_var.value + (1 - mom) * \
                lax.stop_gradient(var)
        return y
