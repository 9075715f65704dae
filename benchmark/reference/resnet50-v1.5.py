"""Plain reference of ResNet v1.5 in training mode: forward and loss in
float32 ``jax.numpy``/``lax``, no flax module, no code of the program.

He et al. 2015 (arXiv:1512.03385) with the stride of a down-sampling
bottleneck on its 3x3 convolution (v1.5, as torchvision). As the program
runs it (configs/resnet50-v1.5.json, ``departures``): NHWC, "SAME" padding
on the 3x3 and 1x1 convolutions (a strided 3x3 on an even input pads
(0, 1), where torchvision pads (1, 1)), no convolution bias, batch
normalization over the batch with eps 1e-5 and the variance taken as
E[x^2] - E[x]^2.

``weights``::

    {"conv_init": [7, 7, 3, C], "bn_init": (scale, bias),
     "blocks": [{"stride": 1 | 2,
                 "conv1": [1, 1, Cin, F], "bn1": (scale, bias),
                 "conv2": [3, 3, F, F], "bn2": ..., "conv3": [1, 1, F, 4F],
                 "bn3": ..., and where the shape changes
                 "conv_proj": [1, 1, Cin, 4F], "bn_proj": ...}, ...],
     "dense": (kernel [4F, classes], bias [classes])}

Call under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def conv(x, w, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, scale_bias, eps=1e-5):
    scale, bias = scale_bias
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.maximum(jnp.mean(x * x, axis=(0, 1, 2)) - mean * mean, 0.0)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def relu(x):
    return jnp.maximum(x, 0.0)


def bottleneck(x, b):
    y = relu(batch_norm(conv(x, b["conv1"]), b["bn1"]))
    y = relu(batch_norm(conv(y, b["conv2"], b["stride"]), b["bn2"]))
    y = batch_norm(conv(y, b["conv3"]), b["bn3"])
    if "conv_proj" in b:
        x = batch_norm(conv(x, b["conv_proj"], b["stride"]), b["bn_proj"])
    return relu(x + y)


def forward(weights, images, between=None):
    """images [B, H, W, 3] -> logits [B, classes] float32. A list given as
    ``between`` receives what goes into each bottleneck and what comes out
    of the last."""
    x = images.astype(jnp.float32)
    x = conv(x, weights["conv_init"], 2, [(3, 3), (3, 3)])
    x = relu(batch_norm(x, weights["bn_init"]))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for b in weights["blocks"]:
        if between is not None:
            between.append(x)
        x = bottleneck(x, b)
    if between is not None:
        between.append(x)
    x = jnp.mean(x, axis=(1, 2))
    kernel, bias = weights["dense"]
    return x @ kernel + bias


def loss(logits, labels):
    """Mean negative log-likelihood of the labels."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - hit)
