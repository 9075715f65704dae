"""Plain reference of Trinity-Mini (Arcee, 2025-12; HF ``config.json`` of
``arcee-ai/Trinity-Mini``, ``model_type`` ``afmoe``; ``modeling_afmoe.py`` as
recalled): forward, routing, loss and gradients in float32 ``jax.numpy``,
python loops over layers and over experts, materialized masks, no kernel, no
scan, no sort, nothing of the program's code.

With ``N`` an RMSNorm (``EPS``) with a learned scale::

    h_0 = E[tokens] * sqrt(D)                                   (mup_enabled)
    layer:  a = h + N2(Attn(N1(h)));   h' = a + N4(FFN(N3(a)))
    logits = Nf(h_L) W_head^T                                   (untied)
    Attn:   q = x Wq [H heads of K], k = x Wk, v = x Wv [H_kv heads of K],
            g = x Wg [H x K]; q, k RMSNormed over K with scales of K; in a
            window layer q and k are rotated (rotate-half over all K, THETA)
            and key j is visible from query i iff 0 <= i - j < window; in a
            full layer there is no rotation and j <= i; query head n reads
            KV head n // (H / H_kv); scores scaled by K ** -0.5;
            Attn = (softmax(q k^T) v * sigmoid(g)) Wo
    dense FFN (leading layers):  (silu(x Wg) * (x Wu)) Wd
    expert FFN:  s = sigmoid(x Wr) over all E experts; S = the TOP_K largest
            of s + b (b: the selection bias, a buffer: it enters the choice
            only and takes no gradient); w_e = ROUTE_SCALE * s_e / (sum over
            S of s + 1e-20) for e in S;
            FFN(x) = Shared(x) + sum over e in S AND HELD of w_e Expert_e(x),
            Shared and every Expert_e a SwiGLU
    after a step, per expert layer:  b_e += RATE * sign(mean(c) - c_e), c_e
            the tokens the step routed to expert e (all E)

The reference is given the same share of a layer as the program: the experts
``first_expert .. first_expert + held - 1`` (the leading axis of a layer's
``ewg``), the chosen experts that are absent add nothing, and that partial
result goes on to the next layer. On such a share (fewer experts held than
the router has outputs) the weights ``w_e`` are constants of the backward
pass: the gradient through them is one chip's term of a sum the deployment
takes over every chip that holds experts, and the program applies no term
alone (``models/transformer.py _expert_ffn``). The vocabulary is the slice
the weights hold.

``config.json`` gives the widths, heads, window, ``layer_types``,
``rms_norm_eps``, ``rope_theta``, ``score_func``, ``route_norm``,
``route_scale``, ``num_experts_per_tok``, ``mup_enabled``. From
``modeling_afmoe.py`` as recalled (no network here;
configs/trinity-mini.json lists them under ``assumed``): the sandwich
placement of the four norms, q/k norm before the rotation, the gate on the
attention's output before ``Wo``, no rotation on the full-attention layers,
the embedding's multiplier, the 1e-20, ``load_balance_coeff`` as the rate of
the bias update. Departures from the source: none known; cos and sin are
taken in float32 here and in the program.

Attention is materialized in blocks of ``ROWS`` query rows so that 8,192
positions fit a chip: each block sees every key and masks what it may not.
A row longer than one block goes through ``jax.lax.map`` over its blocks,
one after the other: the compiler would otherwise hold every block's T x
ROWS scores of every head at once (14.7 GB in the backward at 8,192
positions; v5e compiler, PR 32).

``weights``::

    {"embed": [V, D], "lm_head": [V, D], "ln_f": [D],
     "layers": [{"ln1", "ln1_post", "ln2", "ln2_post": [D],
                 "wq", "wgate": [D, H, K], "wk", "wv": [D, H_kv, K],
                 "wo": [H, K, D], "q_norm", "k_norm": [K],
                 and either "wg", "wu": [D, F], "wd": [F, D]
                 or "router": [D, E], "router_bias": [E],
                    "ewg", "ewu": [held, D, Fe], "ewd": [held, Fe, D],
                    "shared_wg", "shared_wu": [D, Fs], "shared_wd": [Fs, D]}]}

``kinds``: one ``(window, rotate)`` a layer; window 0 is full attention.

Call under ``jax.default_matmul_precision("highest")``: on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS = 1e-5              # rms_norm_eps
THETA = 1e4             # rope_theta
TOP_K = 8               # num_experts_per_tok
ROUTE_SCALE = 2.826     # route_scale
RATE = 1e-3             # load_balance_coeff, read as the bias update's rate
ROWS = 512              # query rows of one block of materialized attention


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


def attend(q, k, v, lo, *, window):
    """The block of ``ROWS`` query rows from ``lo`` on against every key:
    [B, H, ROWS, K]."""
    t = k.shape[2]
    q_rows = jax.lax.dynamic_slice_in_dim(q, lo, min(ROWS, t), axis=2)
    rows = lo + jnp.arange(q_rows.shape[2])
    dist = rows[:, None] - jnp.arange(t)[None, :]
    seen = dist >= 0 if not window else (dist >= 0) & (dist < window)
    scores = jnp.einsum("bhqk,bhsk->bhqs", q_rows, k) \
        / math.sqrt(q_rows.shape[-1])
    scores = jnp.where(seen, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    return jnp.einsum("bhqs,bhsk->bhqk",
                      p / jnp.sum(p, axis=-1, keepdims=True), v)


def attention(x, lw, window, rotate, wrap=lambda f: f):
    """x [B, T, D] -> [B, T, D]. ``wrap`` goes around :func:`attend` (the
    chip's gradient check hands in ``jax.checkpoint``)."""
    q = rmsnorm(jnp.einsum("btd,dhk->bhtk", x, lw["wq"]), lw["q_norm"])
    k = rmsnorm(jnp.einsum("btd,dhk->bhtk", x, lw["wk"]), lw["k_norm"])
    v = jnp.einsum("btd,dhk->bhtk", x, lw["wv"])
    if rotate:
        q, k = rope(q), rope(k)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = wrap(functools.partial(attend, window=window))
    t = x.shape[1]
    if t <= ROWS:
        out = block(q, k, v, 0)
    else:       # [blocks, B, H, ROWS, K] -> [B, H, T, K]
        assert t % ROWS == 0, (t, ROWS)
        out = jax.lax.map(lambda lo: block(q, k, v, lo),
                          jnp.arange(0, t, ROWS))
        out = jnp.moveaxis(out, 0, 2).reshape(q.shape)
    gate = jnp.einsum("btd,dhk->bhtk", x, lw["wgate"])
    return jnp.einsum("bhtk,hkd->btd", out / (1.0 + jnp.exp(-gate)),
                      lw["wo"])


def swiglu(x, wg, wu, wd):
    gate = x @ wg
    return (gate / (1.0 + jnp.exp(-gate)) * (x @ wu)) @ wd


def route(x, lw, top_k=TOP_K):
    """x [B, T, D] -> (the chosen experts [B, T, top_k], their weights)."""
    s = 1.0 / (1.0 + jnp.exp(-(x @ lw["router"])))
    biased = s + jax.lax.stop_gradient(lw["router_bias"])
    chosen = jnp.argsort(-biased, axis=-1)[..., :top_k]
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, ROUTE_SCALE * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def expert_ffn(x, lw, first_expert, top_k=TOP_K):
    """-> (FFN(x), chosen): the shared expert, and every held expert over
    every token, weighted by the token's weight for it (0: not chosen)."""
    chosen, weight = route(x, lw, top_k)
    if lw["ewg"].shape[0] < lw["router"].shape[1]:
        # a share of the experts: the routing weights are constants of the
        # backward pass (see the module's first words)
        weight = jax.lax.stop_gradient(weight)
    out = swiglu(x, lw["shared_wg"], lw["shared_wu"], lw["shared_wd"])
    for e in range(lw["ewg"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first_expert + e, weight, 0.0),
                      axis=-1)
        out = out + w_e[..., None] * swiglu(x, lw["ewg"][e], lw["ewu"][e],
                                            lw["ewd"][e])
    return out, chosen


def layer(h, lw, window, rotate, first_expert, wrap=lambda f: f,
          top_k=TOP_K):
    """-> (h', the layer's choices of expert, None for a dense layer)."""
    h = h + rmsnorm(attention(rmsnorm(h, lw["ln1"]), lw, window, rotate,
                              wrap), lw["ln1_post"])
    x = rmsnorm(h, lw["ln2"])
    if "router" in lw:
        out, chosen = expert_ffn(x, lw, first_expert, top_k)
    else:
        out, chosen = swiglu(x, lw["wg"], lw["wu"], lw["wd"]), None
    return h + rmsnorm(out, lw["ln2_post"]), chosen


def head(weights, h):
    return jnp.einsum("btd,vd->btv", h, weights["lm_head"])


def forward(weights, tokens, kinds, first_expert=0, wrap=lambda f: f,
            top_k=TOP_K):
    """tokens [B, T] int -> (logits [B, T, V], [the choices [B, T, top_k] of
    every expert layer])."""
    d = weights["embed"].shape[1]
    h = weights["embed"][tokens].astype(jnp.float32) * math.sqrt(d)
    choices = []
    for lw, (window, rotate) in zip(weights["layers"], kinds):
        run = wrap(lambda h, lw, window=window, rotate=rotate: layer(
            h, lw, window, rotate, first_expert, wrap, top_k))
        h, chosen = run(h, lw)
        if chosen is not None:
            choices.append(chosen)
    return head(weights, rmsnorm(h, weights["ln_f"])), choices


def cross_entropy(logits, targets):
    """Next-token negative log-likelihood of every token, [B, T]."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - hit


def loss(weights, tokens, targets, kinds, first_expert=0, wrap=lambda f: f,
         top_k=TOP_K):
    """Mean cross-entropy over the tokens: no auxiliary term (the balance is
    the bias update's). ``wrap``: see :func:`attention`; it also goes
    around every layer and the exit."""
    logits, _ = forward(weights, tokens, kinds, first_expert, wrap, top_k)
    return jnp.mean(wrap(cross_entropy)(logits, targets))


grads = jax.grad(loss)      # (weights, tokens, targets, ...) -> like weights


def counts(chosen, n_experts):
    """Tokens routed to each of the ``n_experts``, [E]."""
    return jnp.sum(chosen[..., None] == jnp.arange(n_experts),
                   axis=tuple(range(chosen.ndim)))


def bias_update(bias, chosen, rate=RATE):
    """The selection bias after a step that routed ``chosen``."""
    c = counts(chosen, bias.shape[-1]).astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)
