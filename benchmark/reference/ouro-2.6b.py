"""Plain reference of Ouro-2.6B, a looped decoder (ByteDance Seed, "Scaling
Latent Reasoning via Looped Language Models", 2025-10; HF ``config.json`` of
``ByteDance/Ouro-2.6B``, ``model_type`` ``ouro``, and its ``modeling_ouro.py``):
forward, exit distribution, loss and gradients in float32 ``jax.numpy``,
python loops over passes and layers, no kernel, no scan, no recomputation,
nothing of the program's code.

One stack of layers is applied ``PASSES`` times with the same weights::

    h_0 = E[tokens]
    h_t = Nf(Layers(h_{t-1}))                     t = 1 .. PASSES
    layer:  h = h + N2(Attn(N1(h)));  h = h + N4(SwiGLU(N3(h)))
    Attn:   causal MHA, rotate-half RoPE (THETA, the whole head) on q and k,
            scores scaled by head ** -0.5
    SwiGLU: (silu(x Wg) * (x Wu)) Wd
    logits_t = h_t W_head;   lambda_t = sigmoid(h_t . w_g + b_g)
    p_1 = lambda_1,  p_t = lambda_t prod_{j<t} (1 - lambda_j),
    p_PASSES = prod_{j<PASSES} (1 - lambda_j)
    loss = mean over tokens of  sum_t p_t CE(logits_t, target) - BETA H(p)

``config.json`` gives the widths, ``rms_norm_eps``, ``rope_theta`` and
``total_ut_steps``. From the report and ``modeling_ouro.py`` as recalled (no
network here; configs/ouro-2.6b.json lists them under ``assumed``): the
sandwich placement of the four norms, the final norm inside the loop, the
gate as a ``Linear(hidden, 1)`` on the normed state, the stage-I objective
and ``BETA``. Departures from the source: none known; cos and sin are taken
in float32 here and in the program, where the HF code rounds them to the
activations' dtype first.

Attention is materialized, in blocks of ``ROWS`` query rows so that 4,096
positions fit a chip: each block sees every key and masks the future.

``weights``::

    {"embed": [V, D], "lm_head": [V, D], "ln_f": [D],
     "exit_gate": {"w": [D], "b": []},
     "layers": [{"ln1", "ln1_post", "ln2", "ln2_post": [D],
                 "wq", "wk", "wv": [D, H, K], "wo": [H, K, D],
                 "wg", "wu": [D, F], "wd": [F, D]}]}

Call under ``jax.default_matmul_precision("highest")``: on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

PASSES = 4          # total_ut_steps
EPS = 1e-6          # rms_norm_eps
THETA = 1e6         # rope_theta
BETA = 0.1          # weight of the exit distribution's entropy (assumed)
ROWS = 1024         # query rows of one block of materialized attention


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + EPS)) * scale


def rope(x):
    """x [B, H, T, K]: pairs (i, i + K/2) rotated by position * THETA **
    (-2i / K), the rotate-half form."""
    t, half = x.shape[2], x.shape[3] // 2
    inv_freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, lw):
    """x [B, T, D] -> [B, T, D]; every query sees itself and the past."""
    q = rope(jnp.einsum("btd,dhk->bhtk", x, lw["wq"]))
    k = rope(jnp.einsum("btd,dhk->bhtk", x, lw["wk"]))
    v = jnp.einsum("btd,dhk->bhtk", x, lw["wv"])
    t = x.shape[1]
    blocks = []
    for lo in range(0, t, ROWS):
        rows = jnp.arange(lo, min(lo + ROWS, t))
        scores = jnp.einsum("bhqk,bhsk->bhqs", q[:, :, lo:lo + ROWS], k) \
            / math.sqrt(q.shape[-1])
        scores = jnp.where(rows[:, None] >= jnp.arange(t)[None, :], scores,
                           -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores)
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        blocks.append(jnp.einsum("bhqs,bhsk->bhqk", p, v))
    return jnp.einsum("bhtk,hkd->btd", jnp.concatenate(blocks, axis=2),
                      lw["wo"])


def swiglu(x, lw):
    gate = jnp.einsum("btd,df->btf", x, lw["wg"])
    up = jnp.einsum("btd,df->btf", x, lw["wu"])
    return jnp.einsum("btf,fd->btd", gate / (1.0 + jnp.exp(-gate)) * up,
                      lw["wd"])


def layer(h, lw):
    h = h + rmsnorm(attention(rmsnorm(h, lw["ln1"]), lw), lw["ln1_post"])
    return h + rmsnorm(swiglu(rmsnorm(h, lw["ln2"]), lw), lw["ln2_post"])


def states(weights, tokens, passes: int = PASSES, run_layer=layer):
    """tokens [B, T] int -> [h_1 .. h_passes], each [B, T, D]: the normed
    state after every pass."""
    h = weights["embed"][tokens].astype(jnp.float32)
    out = []
    for _ in range(passes):
        for lw in weights["layers"]:
            h = run_layer(h, lw)
        h = rmsnorm(h, weights["ln_f"])
        out.append(h)
    return out


def head(weights, h):
    return jnp.einsum("btd,vd->btv", h, weights["lm_head"])


def gate(weights, h):
    """lambda [B, T]: the probability of leaving here, having come so far."""
    z = jnp.sum(h * weights["exit_gate"]["w"], axis=-1) \
        + weights["exit_gate"]["b"]
    return 1.0 / (1.0 + jnp.exp(-z))


def exit_probs(lam):
    """[passes, B, T] from every pass's lambda: the probability of leaving
    after each pass; the last takes what is left."""
    stayed, p = jnp.ones_like(lam[0]), []
    for lam_t in lam[:-1]:
        p.append(lam_t * stayed)
        stayed = stayed * (1.0 - lam_t)
    return jnp.stack(p + [stayed])


def forward(weights, tokens, passes: int = PASSES):
    """-> ([logits_1 .. logits_passes], each [B, T, V]; p [passes, B, T])."""
    hs = states(weights, tokens, passes)
    return ([head(weights, h) for h in hs],
            exit_probs([gate(weights, h) for h in hs]))


def cross_entropy(logits, targets):
    """Next-token negative log-likelihood of every token, [B, T]."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - hit


def exit_of(weights, h, targets):
    """What one pass's exit gives the loss: its cross-entropy and lambda."""
    return cross_entropy(head(weights, h), targets), gate(weights, h)


def objective(nll, p, beta: float = BETA):
    """The stage-I objective from every exit's cross-entropy and the exit
    distribution, both [passes, B, T]: the expected cross-entropy less
    ``beta`` times the distribution's entropy, the mean over tokens."""
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0) - beta * entropy)


def loss(weights, tokens, targets, passes: int = PASSES, beta: float = BETA,
         wrap=lambda f: f):
    """:func:`objective` of the model's exits on ``tokens``. ``wrap`` goes around :func:`layer` and :func:`exit_of`; it is the
    identity here. The chip's gradient check hands in ``jax.checkpoint``,
    which changes no number and lets the float32 backward of 4,096
    positions fit beside the program's state."""
    run_exit = wrap(exit_of)
    nll, lam = zip(*[run_exit(weights, h, targets) for h in states(
        weights, tokens, passes, wrap(layer))])
    return objective(jnp.stack(nll), exit_probs(lam), beta)


grads = jax.grad(loss)      # (weights, tokens, targets, ...) -> like weights
