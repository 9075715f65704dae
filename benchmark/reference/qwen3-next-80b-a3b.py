"""Plain reference of Qwen3-Next-80B-A3B (Qwen, 2025-09; HF ``config.json``
of ``Qwen/Qwen3-Next-80B-A3B-Instruct``, ``model_type`` ``qwen3_next``;
``modeling_qwen3_next.py`` as recalled): forward, routing, loss and
gradients in float32 ``jax.numpy``, python loops over layers, over experts
and over the convolution's taps, the gated delta rule as the RECURRENCE
itself (a ``lax.scan`` over the tokens that carries the state),
materialized masks, no kernel, no chunked form, no sort, nothing of the
program's code. The weights are in the PUBLISHED layout (the projections'
columns grouped per head as the checkpoint has them).

With ``N`` the zero-centred RMSNorm ``x / rms(x) * (1 + w)`` (``EPS``)::

    h_0 = E[tokens];   layer:  a = h + F(N1(h));  h' = a + MoE(N2(a))
    logits = Nf(h_L) W_head^T

    F of a Gated DeltaNet layer, x [T, D], Hk key heads of dk, Hv value
    heads of dv (r = Hv / Hk):
        x W_qkvz, per key head h: [q_h | k_h | v_{rh} .. v_{rh+r-1} |
              z_{rh} .. z_{rh+r-1}];  x W_ba, per key head h:
              [b_{rh} .. b_{rh+r-1} | a_{rh} .. a_{rh+r-1}]
        [q | k | v] = silu(conv([q | k | v])): depthwise, causal, TAPS taps,
              NO bias, v_t = sum_j w[j] u_{t - (TAPS - 1) + j}
        q = l2norm(q) dk ** -0.5,  k = l2norm(k)   (L2_EPS);  value head j
              reads key head j // r
        beta = sigmoid(b),  g = -exp(A_log) softplus(a + dt_bias)
        S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - (e^{g_t} S_{t-1})^T k_t)^T,
              S in [dk, dv], S_0 = 0;   o_t = S_t^T q_t
        y = RMSNorm(o) * w_norm * silu(z)  over each head's dv, the norm
              (plain scale) BEFORE the gate;   F = y W_out
    F of a gated attention layer: x W_q, per head [query (Dh) | gate (Dh)];
        k = x W_k, v = x W_v (H_kv heads of Dh); q and k zero-centred
        RMSNormed over the head; rotate-half RoPE (THETA) over the FIRST
        ``ROTARY * Dh`` of each head, the rest unrotated; key j visible from
        query i iff j <= i; query head n reads KV head n // (H / H_kv);
        scores scaled by Dh ** -0.5;  F = (softmax(q k^T) v * sigmoid(gate))
        W_o
    MoE: p = softmax(x Wr) over ALL E experts; S = the TOP_K largest;
        w_e = p_e / sum over S of p  (norm_topk_prob, no eps, no scale, no
        selection bias);  MoE(x) = sigmoid(x w_sg) Shared(x) + sum over e
        in S AND HELD of w_e Expert_e(x), every expert a SwiGLU
        (silu(x Wg) * (x Wu)) Wd

The reference is given the same share of a layer as the program: the experts
``first_expert .. first_expert + held - 1`` (the leading axis of a layer's
``ewu``), the chosen experts that are absent add nothing, and that partial
result goes on to the next layer. On such a share the weights ``w_e`` are
constants of the backward pass (``models/transformer.py _expert_ffn`` has
why). The vocabulary is the slice the weights hold.

The gradient of the recurrence over 8,192 tokens would keep 8,192 states of
2 MB a row and layer. Under ``wrap=jax.checkpoint`` the scan over the tokens
runs in ``SEGMENT``-token pieces whose inner states are recomputed; every
step is still the one-token recurrence above, and no number changes.

Attention is materialized in blocks of ``ROWS`` query rows so that 8,192
positions fit a chip.

``weights``::

    {"embed": [V, D], "lm_head": [V, D], "ln_f": [D],
     "layers": [one of
        {"ln1": [D], "in_proj_qkvz": [D, Hk (2 dk + 2 r dv)],
         "in_proj_ba": [D, 2 Hv], "conv_w": [TAPS, 2 Hk dk + Hv dv],
         "A_log", "dt_bias": [Hv], "norm": [dv], "out_proj": [Hv dv, D],
         + the MoE's}
        {"ln1": [D], "q_proj": [D, 2 H Dh], "k_proj", "v_proj": [D, H_kv
         Dh], "q_norm", "k_norm": [Dh], "o_proj": [H Dh, D], + the MoE's}
     the MoE's: {"ln2": [D], "router": [D, E], "ewg", "ewu": [held, D, Fe],
         "ewd": [held, Fe, D], "shared_wg", "shared_wu": [D, Fs],
         "shared_wd": [Fs, D], "shared_gate": [D]}]}

A layer is what its leaves say; the key heads of a delta-rule layer, which
they do not say, are an argument (``KEY_HEADS``).

Call under ``jax.default_matmul_precision("highest")``: on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6              # rms_norm_eps
L2_EPS = 1e-6           # the unit-length norm of q and k (the kernel's)
KEY_HEADS = 16          # linear_num_key_heads
TOP_K = 10              # num_experts_per_tok
THETA = 1e7             # rope_theta
ROTARY = 0.25           # partial_rotary_factor: the rotated share of a head
ROWS = 512              # query rows of one block of materialized attention
SEGMENT = 128           # tokens of one recomputed piece of the recurrence


def rmsnorm(x, w):
    """The zero-centred norm: the scale is ``1 + w``."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS) \
        * (1.0 + w)


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def silu(x):
    return x * sigmoid(x)


def softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delayed(u, by):
    """u [B, T, C] moved ``by`` positions towards the end of the row, zeros
    coming in at its start."""
    if by == 0:
        return u
    return jnp.concatenate([jnp.zeros_like(u[:, :by]), u[:, :-by]], axis=1)


def causal_conv(u, taps):
    """u [B, T, C] -> silu of the depthwise causal convolution, no bias,
    the taps as shifted products."""
    v = sum(taps[j] * delayed(u, taps.shape[0] - 1 - j)
            for j in range(taps.shape[0]))
    return silu(v)


def widths(lw, key_heads=KEY_HEADS):
    """(Hk, dk, Hv, dv) of a delta-rule layer."""
    hv, dv = lw["A_log"].shape[0], lw["norm"].shape[0]
    return key_heads, (lw["conv_w"].shape[1] - hv * dv) // (2 * key_heads), \
        hv, dv


def gdn_operands(x, lw, key_heads=KEY_HEADS):
    """x [B, T, D] -> (q, k [B, T, Hk, dk] as the taps leave them, v, z [B,
    T, Hv, dv], g, beta [B, T, Hv]): what the rule runs on, and its gate."""
    hk, dk, hv, dv = widths(lw, key_heads)
    r, shape = hv // hk, x.shape[:2]
    grouped = (x @ lw["in_proj_qkvz"]).reshape(shape + (hk, -1))
    q, k = grouped[..., :dk], grouped[..., dk:2 * dk]
    v = grouped[..., 2 * dk:2 * dk + r * dv].reshape(shape + (hv, dv))
    z = grouped[..., 2 * dk + r * dv:].reshape(shape + (hv, dv))
    ba = (x @ lw["in_proj_ba"]).reshape(shape + (hk, 2 * r))
    b, a = ba[..., :r].reshape(shape + (hv,)), ba[..., r:].reshape(
        shape + (hv,))
    mixed = causal_conv(jnp.concatenate(
        [q.reshape(shape + (-1,)), k.reshape(shape + (-1,)),
         v.reshape(shape + (-1,))], axis=-1), lw["conv_w"])
    q = mixed[..., :hk * dk].reshape(shape + (hk, dk))
    k = mixed[..., hk * dk:2 * hk * dk].reshape(shape + (hk, dk))
    v = mixed[..., 2 * hk * dk:].reshape(shape + (hv, dv))
    g = -jnp.exp(lw["A_log"]) * softplus(a + lw["dt_bias"])
    return q, k, v, z, g, sigmoid(b)


def key_head_of(value_head, value_heads, key_heads):
    """The key head (of q and k) that ``value_head`` reads."""
    return value_head // (value_heads // key_heads)


def recur(state, q_t, k_t, v_t, g_t, beta_t):
    """One token: state [B, Hv, dk, dv], q_t, k_t [B, Hv, dk] (each value
    head's own key head's, normed), v_t [B, Hv, dv], g_t, beta_t [B, Hv] ->
    (state, o_t [B, Hv, dv])."""
    state = jnp.exp(g_t)[..., None, None] * state
    read = jnp.sum(state * k_t[..., :, None], axis=-2)
    state = state + k_t[..., :, None] * (
        beta_t[..., None] * (v_t - read))[..., None, :]
    return state, jnp.sum(state * q_t[..., :, None], axis=-2)


def recurrence(q, k, v, g, beta, wrap=lambda f: f):
    """q, k [B, T, Hk, dk] as the taps leave them, v [B, T, Hv, dv], g,
    beta [B, T, Hv] -> o [B, T, Hv, dv]: the norms of q and k, then the
    state carried token by token from zero. ``wrap``: the module's first
    words."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    reads = key_head_of(jnp.arange(hv), hv, hk)
    q = (l2norm(q) * dk ** -0.5)[:, :, reads]
    k = l2norm(k)[:, :, reads]

    def one(state, at):
        return recur(state, *at)

    def piece(state, ats):
        return jax.lax.scan(one, state, ats)

    by_token = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state = jnp.zeros((bsz, hv, dk, dv), v.dtype)
    if t > SEGMENT and t % SEGMENT == 0:
        pieces = tuple(x.reshape((t // SEGMENT, SEGMENT) + x.shape[1:])
                       for x in by_token)
        _, o = jax.lax.scan(wrap(piece), state, pieces)
        o = o.reshape((t,) + o.shape[2:])
    else:
        _, o = piece(state, by_token)
    return jnp.moveaxis(o, 0, 1)


def gated_norm(o, z, lw):
    """o, z [B, T, Hv, dv] -> the norm over each head's dv, its plain
    scale, THEN the gate: [B, T, Hv dv]."""
    normed = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS) \
        * lw["norm"]
    return (normed * silu(z)).reshape(o.shape[:2] + (-1,))


def gdn(x, lw, wrap=lambda f: f, key_heads=KEY_HEADS):
    """x [B, T, D] -> [B, T, D]: the delta-rule mixer."""
    q, k, v, z, g, beta = gdn_operands(x, lw, key_heads)
    o = recurrence(q, k, v, g, beta, wrap)
    return gated_norm(o, z, lw) @ lw["out_proj"]


def rope(x, rotated):
    """x [B, H, T, K] with its first ``rotated`` columns rotated
    (rotate-half within them, THETA) and the rest as they are."""
    t, half = x.shape[2], rotated // 2
    inv_freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attend(q, k, v, lo):
    """The block of ``ROWS`` query rows from ``lo`` on against every key:
    [B, H, ROWS, K]."""
    t = k.shape[2]
    q_rows = jax.lax.dynamic_slice_in_dim(q, lo, min(ROWS, t), axis=2)
    rows = lo + jnp.arange(q_rows.shape[2])
    seen = rows[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.einsum("bhqk,bhsk->bhqs", q_rows, k) \
        / math.sqrt(q_rows.shape[-1])
    scores = jnp.where(seen, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores)
    return jnp.einsum("bhqs,bhsk->bhqk",
                      p / jnp.sum(p, axis=-1, keepdims=True), v)


def qkv(x, lw):
    """x [B, T, D] -> (q, gate [B, H, T, Dh], k, v [B, H_kv, T, Dh]), q and
    k normed and rotated."""
    dh = lw["q_norm"].shape[0]
    shape = x.shape[:2]
    both = (x @ lw["q_proj"]).reshape(shape + (-1, 2 * dh))
    q, gate = both[..., :dh], both[..., dh:]
    k = (x @ lw["k_proj"]).reshape(shape + (-1, dh))
    v = (x @ lw["v_proj"]).reshape(shape + (-1, dh))
    q, k = rmsnorm(q, lw["q_norm"]), rmsnorm(k, lw["k_norm"])
    q, gate, k, v = (jnp.moveaxis(a, 2, 1) for a in (q, gate, k, v))
    rotated = int(dh * ROTARY)
    return rope(q, rotated), gate, rope(k, rotated), v


def attention(x, lw, wrap=lambda f: f):
    """x [B, T, D] -> [B, T, D]. ``wrap`` goes around :func:`attend`."""
    q, gate, k, v = qkv(x, lw)
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    block = wrap(attend)
    t = x.shape[1]
    if t <= ROWS:
        out = block(q, k, v, 0)
    else:       # [blocks, B, H, ROWS, K] -> [B, H, T, K]
        assert t % ROWS == 0, (t, ROWS)
        out = jax.lax.map(lambda lo: block(q, k, v, lo),
                          jnp.arange(0, t, ROWS))
        out = jnp.moveaxis(out, 0, 2).reshape(q.shape)
    out = jnp.moveaxis(out * sigmoid(gate), 1, 2)
    return out.reshape(out.shape[:2] + (-1,)) @ lw["o_proj"]


def swiglu(x, wg, wu, wd):
    return (silu(x @ wg) * (x @ wu)) @ wd


def shared(x, lw):
    """The shared expert, times its gate."""
    return sigmoid(x @ lw["shared_gate"])[..., None] * swiglu(
        x, lw["shared_wg"], lw["shared_wu"], lw["shared_wd"])


def scores(x, lw):
    """The router's softmax over ALL experts."""
    logits = x @ lw["router"]
    p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    return p / jnp.sum(p, axis=-1, keepdims=True)


def weights_of(x, lw, chosen):
    """The weights the router gives the experts ``chosen`` [B, T, k]."""
    picked = jnp.take_along_axis(scores(x, lw), chosen, axis=-1)
    return picked / jnp.sum(picked, axis=-1, keepdims=True)


def route(x, lw, top_k=TOP_K):
    """x [B, T, D] -> (the chosen experts [B, T, top_k], their weights)."""
    chosen = jnp.argsort(-scores(x, lw), axis=-1)[..., :top_k]
    return chosen, weights_of(x, lw, chosen)


def expert_ffn(x, lw, first_expert, top_k=TOP_K, given=None):
    """-> (MoE(x), chosen): the gated shared expert, and every held expert
    over every token weighted by the token's weight for it (0: not
    chosen). ``given`` [B, T, top_k]: the experts to take in place of the
    ``top_k`` largest (see :func:`forward`); ``chosen`` is the reference's
    own choice either way."""
    chosen, weight = route(x, lw, top_k)
    own = chosen
    if given is not None:
        chosen, weight = given, weights_of(x, lw, given)
    if lw["ewu"].shape[0] < lw["router"].shape[1]:
        # a share of the experts: the routing weights are constants of the
        # backward pass (see the module's first words)
        weight = jax.lax.stop_gradient(weight)
    out = shared(x, lw)
    for e in range(lw["ewu"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == first_expert + e, weight, 0.0),
                      axis=-1)
        out = out + w_e[..., None] * swiglu(x, lw["ewg"][e], lw["ewu"][e],
                                            lw["ewd"][e])
    return out, own


def mixer(x, lw, wrap=lambda f: f, key_heads=KEY_HEADS):
    """F of the layer ``lw``: the delta rule's or attention's."""
    if "in_proj_qkvz" in lw:
        return gdn(x, lw, wrap, key_heads)
    return attention(x, lw, wrap)


def layer(h, lw, first_expert, wrap=lambda f: f, top_k=TOP_K, given=None,
          probes=None, key_heads=KEY_HEADS):
    """-> (h', the layer's choices of expert). ``probes``: a list that takes
    ``(x, F(x))`` of every delta-rule layer's mixer."""
    x = rmsnorm(h, lw["ln1"])
    out = mixer(x, lw, wrap, key_heads)
    if probes is not None and "in_proj_qkvz" in lw:
        probes.append((x, out))
    h = h + out
    out, chosen = expert_ffn(rmsnorm(h, lw["ln2"]), lw, first_expert, top_k,
                             given)
    return h + out, chosen


def head(weights, h):
    return jnp.einsum("btd,vd->btv", h, weights["lm_head"])


def forward(weights, tokens, first_expert=0, wrap=lambda f: f, top_k=TOP_K,
            given=None, probes=None, key_heads=KEY_HEADS):
    """tokens [B, T] int -> (logits [B, T, V], [the choices [B, T, top_k] of
    every layer]).

    ``given``: one [B, T, top_k] a layer, the experts every token TAKES, in
    place of the ``top_k`` largest of ``p``; their weights are the
    reference's own scores of them. The choices returned are still the
    reference's own, each layer's made on the activations the given choices
    led to. ``probes``: :func:`layer`'s. It is how the chip's comparison
    tells rounding from routing: a choice near a tie falls the other way
    under bfloat16 activations, a token that chose otherwise differs by a
    whole expert's output, and a delta-rule layer hands that difference on
    to every later token of the row."""
    h = weights["embed"][tokens].astype(jnp.float32)
    choices = []
    taken = iter(given if given is not None else ())
    for lw in weights["layers"]:
        take = next(taken) if given is not None else None

        def run(h, lw, take=take):
            return layer(h, lw, first_expert, wrap, top_k, take, probes,
                         key_heads)

        # (a probe leaves the layer: then no wrap around it)
        h, chosen = (run if probes is not None else wrap(run))(h, lw)
        choices.append(chosen)
    return head(weights, rmsnorm(h, weights["ln_f"])), choices


def cross_entropy(logits, targets):
    """Next-token negative log-likelihood of every token, [B, T]."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - hit


def loss(weights, tokens, targets, first_expert=0, wrap=lambda f: f,
         top_k=TOP_K, given=None, key_heads=KEY_HEADS):
    """Mean cross-entropy over the tokens: no auxiliary term
    (``router_aux_loss_coef`` is not in the configuration, and on a share
    the routing weights take no gradient). ``wrap`` goes around every
    layer, every block of attention rows, every piece of the recurrence
    and the exit. ``given``: see :func:`forward`."""
    logits, _ = forward(weights, tokens, first_expert, wrap, top_k, given,
                        None, key_heads)
    return jnp.mean(wrap(cross_entropy)(logits, targets))


grads = jax.grad(loss)      # (weights, tokens, targets, ...) -> like weights


def counts(chosen, n_experts):
    """Tokens routed to each of the ``n_experts``, [E]."""
    return jnp.sum(chosen[..., None] == jnp.arange(n_experts),
                   axis=tuple(range(chosen.ndim)))


def bias_update(bias, chosen, rate=0.0):
    """The selection bias after a step that routed ``chosen``: the model
    has none to move (rate 0), and this is the bias as it was."""
    c = counts(chosen, bias.shape[-1]).astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)
