"""ResNet-v1.5 family (ResNet-50 flagship for the benchmark suite).

The reference's headline numbers are ResNet-50/101 synthetic-benchmark
throughput and scaling (docs/benchmarks.rst:7-46; scripts
examples/*_synthetic_benchmark.py). This is a from-scratch flax.linen
implementation designed for the MXU: NHWC layouts, bfloat16 compute with
fp32 params/batch-stats, channel counts in multiples of 128.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import jax.numpy as jnp
import flax.linen as nn

from ..ops.fused_batch_norm import FusedBatchNorm

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides,
                                 name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # Opt-in Pallas fused-BN path. XLA already fuses the BN stat reductions
    # into adjacent elementwise passes, so extracting them adds a memory
    # pass; before PR 1 that lost, on today's chip it is not measured
    # (docs/roofline.md section 1; ROADMAP queue 3: one traced run, then
    # delete what does not win).
    fused_bn: bool = False

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32)
        if self.fused_bn:
            norm = partial(FusedBatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        else:
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           param_dtype=jnp.float32)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                 name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i, strides, conv, norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
# Tiny variant for tests / dryruns
ResNet18ish = partial(ResNet, stage_sizes=[1, 1, 1, 1], num_filters=16)
