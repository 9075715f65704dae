"""Plain reference of the decoder block that ``horovod_tpu.models.transformer``
runs for the ``cerebras-gpt-1.3b`` configuration: forward and loss in
float32 ``jax.numpy``, no kernel, no code of the program.

It is the block the program runs, not GPT-2's: RMSNorm without bias (eps
1e-6) where GPT-2 has LayerNorm with bias, no learned position table, no
biases on the linear layers, the tanh form of GELU, the output head tied
to the embedding (configs/cerebras-gpt-1.3b.json, ``departures``).
Attention is dense and causal in every layer, materialized.

``weights``::

    {"embed": [V, D], "ln_f": [D],
     "layers": [{"ln1": [D], "wq": [D, H, K], "wk": ..., "wv": ...,
                 "wo": [H, K, D], "ln2": [D], "w1": [D, F], "w2": [F, D]}]}

Call under ``jax.default_matmul_precision("highest")``: on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + 1e-6)) * scale


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, lw):
    """x [B, T, D] -> [B, T, D]; every query sees itself and the past."""
    q = jnp.einsum("btd,dhk->bhtk", x, lw["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, lw["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, lw["wv"])
    t = x.shape[1]
    scores = jnp.einsum("bhqk,bhsk->bhqs", q, k) / math.sqrt(q.shape[-1])
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqs,bhsk->bhqk", p, v)
    return jnp.einsum("bhtk,hkd->btd", out, lw["wo"])


def forward(weights, tokens):
    """tokens [B, T] int -> logits [B, T, V] float32."""
    h = weights["embed"][tokens].astype(jnp.float32)
    for lw in weights["layers"]:
        h = h + attention(rmsnorm(h, lw["ln1"]), lw)
        u = gelu_tanh(jnp.einsum("btd,df->btf", rmsnorm(h, lw["ln2"]),
                                 lw["w1"]))
        h = h + jnp.einsum("btf,fd->btd", u, lw["w2"])
    h = rmsnorm(h, weights["ln_f"])
    return jnp.einsum("btd,vd->btv", h, weights["embed"])


def loss(logits, targets):
    """Mean next-token negative log-likelihood."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - hit)
