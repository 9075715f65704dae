"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (NVIDIA, 2025-12; HF
``config.json`` of ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``,
``model_type`` ``nemotron_h``; ``modeling_nemotron_h.py`` as recalled):
forward, routing, loss and gradients in float32 ``jax.numpy``, python loops
over layers, over experts and over the convolution's taps, the state-space
layer as the RECURRENCE itself (a ``lax.scan`` over the tokens that carries
the state), materialized masks, no kernel, no chunked form, no sort, nothing
of the program's code.

With ``N`` an RMSNorm (``EPS``) with a learned scale, every layer ONE
sublayer::

    h_0 = E[tokens];    layer:  h' = h + F(N(h));    logits = Nf(h_L) W^T

    F of a state-space layer ("M"), x [T, D], H heads of P, G groups, a
    state of N:
        (z, xBC, dt) = split(x W_in) at H P, H P + 2 G N, H
        xBC = silu(conv(xBC)): depthwise, causal, TAPS taps and a bias,
              v_t = b + sum_j w[j] u_{t - (TAPS - 1) + j}
        (X, B, C) = split(xBC) at H P, G N, G N;  head h reads group
              h // (H / G)
        dt = softplus(dt + dt_bias);   A = -exp(A_log)        (a head each)
        S_t = exp(dt_t A) S_{t-1} + dt_t X_t B_t^T,   S in [P, N], S_0 = 0
        y_t = S_t C_t + D X_t
        y = RMSNorm over each of the G groups of channels of (y * silu(z)),
            the gate BEFORE the norm, times a learned scale of H P
        F = y W_out
    F of an attention layer ("*"): q = x Wq [H_q heads of K], k = x Wk, v =
        x Wv [H_kv heads of K]; no bias, no norm on q or k, NO rotation and
        no other position signal (``ROTATE`` False); key j visible from
        query i iff j <= i; query head n reads KV head n // (H_q / H_kv);
        scores scaled by K ** -0.5;  F = softmax(q k^T) v Wo
    F of an expert layer ("E"): s = sigmoid(x Wr) over all E experts; S =
        the TOP_K largest of s + b (b: the selection bias, a buffer: it
        enters the choice only and takes no gradient); w_e = ROUTE_SCALE *
        s_e / (sum over S of s + ROUTE_EPS) for e in S;
        F = Shared(x) + sum over e in S AND HELD of w_e Expert_e(x),
        Expert(x) = relu(x W_up)^2 W_down: two matrices and no gate; the
        shared expert of the same form at its own width
    after a step, per expert layer:  b_e += RATE * sign(mean(c) - c_e), c_e
        the tokens the step routed to expert e (all E)

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
        {"ln1": [D], "ssm_in": [D, 2 H P + 2 G N + H],
         "ssm_conv_w": [TAPS, H P + 2 G N], "ssm_conv_b": [H P + 2 G N],
         "ssm_dt_bias", "ssm_A_log", "ssm_D": [H], "ssm_norm": [H P],
         "ssm_out": [H P, D]}
        {"ln1": [D], "wq": [D, H_q, K], "wk", "wv": [D, H_kv, K],
         "wo": [H_q, K, D]}
        {"ln2": [D], "router": [D, E], "router_bias": [E],
         "ewu": [held, D, Fe], "ewd": [held, Fe, D],
         "shared_wu": [D, Fs], "shared_wd": [Fs, D]}]}

A layer is what its leaves say; the groups of a state-space layer, which
they do not say, are an argument (``SSM_GROUPS``).

Call under ``jax.default_matmul_precision("highest")``: on a TPU a float32
matrix multiplication otherwise runs in bfloat16 passes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-5              # norm_eps, layer_norm_epsilon
SSM_GROUPS = 8          # n_groups
TOP_K = 6               # num_experts_per_tok
ROUTE_SCALE = 2.5       # routed_scaling_factor
ROUTE_EPS = 1e-20       # added to the sum of the chosen scores
RATE = 1e-3             # the bias update's rate (assumed: arXiv:2408.15664)
ROTATE = False          # the attention layers apply no rotary embedding
THETA = 1e4             # rope_theta, unused while ROTATE is False
ROWS = 512              # query rows of one block of materialized attention
SEGMENT = 128           # tokens of one recomputed piece of the recurrence


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + EPS)) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(x)))


def delayed(u, by):
    """u [B, T, C] moved ``by`` positions towards the end of the row, zeros
    coming in at its start."""
    if by == 0:
        return u
    return jnp.concatenate([jnp.zeros_like(u[:, :by]), u[:, :-by]], axis=1)


def causal_conv(u, lw):
    """u [B, T, C] -> silu of the depthwise causal convolution with its
    bias, the taps as shifted products."""
    taps = lw["ssm_conv_w"]
    v = sum(taps[j] * delayed(u, taps.shape[0] - 1 - j)
            for j in range(taps.shape[0]))
    return silu(v + lw["ssm_conv_b"])


def group_of(head, heads, groups):
    """The group of B and C that ``head`` reads."""
    return head // (heads // groups)


def step_size(dt, lw):
    """dt [B, T, H] as projected -> the step sizes."""
    return softplus(dt + lw["ssm_dt_bias"])


def recur(state, x_t, dt_t, a, b_t, c_t):
    """One token: state [B, H, P, N], x_t [B, H, P], dt_t [B, H], a [H],
    b_t, c_t [B, H, N] (each head's own group's) -> (state, y_t [B, H, P])
    without the ``D`` term."""
    state = jnp.exp(dt_t * a)[..., None, None] * state \
        + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
    return state, jnp.sum(state * c_t[..., None, :], axis=-1)


def recurrence(x, dt, a, b, c, wrap=lambda f: f):
    """x [B, T, H, P], dt [B, T, H], a [H], b, c [B, T, H, N] -> y [B, T, H,
    P]: the state carried token by token from zero. ``wrap``: the module's
    first words."""
    bsz, t, h, p = x.shape

    def one(state, at):
        return recur(state, at[0], at[1], a, at[2], at[3])

    def piece(state, ats):
        return jax.lax.scan(one, state, ats)

    by_token = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state = jnp.zeros((bsz, h, p, b.shape[-1]), x.dtype)
    if t > SEGMENT and t % SEGMENT == 0:
        pieces = tuple(v.reshape((t // SEGMENT, SEGMENT) + v.shape[1:])
                       for v in by_token)
        _, y = jax.lax.scan(wrap(piece), state, pieces)
        y = y.reshape((t,) + y.shape[2:])
    else:
        _, y = piece(state, by_token)
    return jnp.moveaxis(y, 0, 1)


def gated_norm(y, z, lw, groups):
    """y, z [B, T, H P] -> the norm over each group's channels of the
    gated y, times the learned scale."""
    gated = (y * silu(z)).reshape(y.shape[:-1] + (groups, -1))
    normed = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1, keepdims=True)
                              + EPS)
    return normed.reshape(y.shape) * lw["ssm_norm"]


def scan_operands(x, lw, groups=SSM_GROUPS):
    """x [B, T, D] -> (z [B, T, H P], X [B, T, H, P], dt [B, T, H], the
    step sizes, B and C [B, T, G, N]): what the recurrence is run on."""
    heads = lw["ssm_A_log"].shape[0]
    inner = lw["ssm_out"].shape[0]
    gn = (lw["ssm_conv_w"].shape[1] - inner) // 2
    proj = x @ lw["ssm_in"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * gn],
                  proj[..., 2 * inner + 2 * gn:])
    xbc = causal_conv(xbc, lw)
    shape = x.shape[:2]
    return (z, xbc[..., :inner].reshape(shape + (heads, inner // heads)),
            step_size(dt, lw),
            xbc[..., inner:inner + gn].reshape(shape + (groups, gn // groups)),
            xbc[..., inner + gn:].reshape(shape + (groups, gn // groups)))


def scan(xs, dt, b, c, lw, wrap=lambda f: f):
    """X [B, T, H, P], dt [B, T, H], B and C [B, T, G, N] -> y [B, T, H, P]:
    the recurrence, every head on its group of B and C, and ``D X``."""
    heads, groups = xs.shape[2], b.shape[2]
    reads = group_of(jnp.arange(heads), heads, groups)
    return recurrence(xs, dt, -jnp.exp(lw["ssm_A_log"]), b[:, :, reads],
                      c[:, :, reads], wrap) + skip(xs, lw)


def mamba(x, lw, wrap=lambda f: f, groups=SSM_GROUPS):
    """x [B, T, D] -> [B, T, D]: the state-space mixer."""
    z, xs, dt, b, c = scan_operands(x, lw, groups)
    y = scan(xs, dt, b, c, lw, wrap)
    return gated_norm(y.reshape(z.shape), z, lw, groups) @ lw["ssm_out"]


def skip(xs, lw):
    """The ``D X`` term, xs [B, T, H, P]."""
    return lw["ssm_D"][:, None] * xs


def rope(x):
    """x [B, H, T, K] rotated (rotate-half, THETA): what the attention
    layers do NOT apply; here for the defect that switches it on."""
    t, half = x.shape[2], x.shape[3] // 2
    inv_freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def attention(x, lw, wrap=lambda f: f):
    """x [B, T, D] -> [B, T, D]. ``wrap`` goes around :func:`attend`."""
    q = jnp.einsum("btd,dhk->bhtk", x, lw["wq"])
    k = jnp.einsum("btd,dhk->bhtk", x, lw["wk"])
    v = jnp.einsum("btd,dhk->bhtk", x, lw["wv"])
    if ROTATE:
        q, k = rope(q), rope(k)
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
    return jnp.einsum("bhtk,hkd->btd", out, lw["wo"])


def relu2(x, wu, wd):
    return jnp.square(jnp.maximum(x @ wu, 0.0)) @ wd


def shared(x, lw):
    """The shared expert, at its own width."""
    return relu2(x, lw["shared_wu"], lw["shared_wd"])


def scores(x, lw):
    return 1.0 / (1.0 + jnp.exp(-(x @ lw["router"])))


def weights_of(x, lw, chosen):
    """The weights the router gives the experts ``chosen`` [B, T, k]."""
    picked = jnp.take_along_axis(scores(x, lw), chosen, axis=-1)
    return ROUTE_SCALE * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + ROUTE_EPS)


def route(x, lw, top_k=TOP_K):
    """x [B, T, D] -> (the chosen experts [B, T, top_k], their weights)."""
    biased = scores(x, lw) + jax.lax.stop_gradient(lw["router_bias"])
    chosen = jnp.argsort(-biased, axis=-1)[..., :top_k]
    return chosen, weights_of(x, lw, chosen)


def expert_ffn(x, lw, first_expert, top_k=TOP_K, given=None):
    """-> (F(x), chosen): the shared expert, and every held expert over
    every token weighted by the token's weight for it (0: not chosen).
    ``given`` [B, T, top_k]: the experts to take in place of the ``top_k``
    largest (see :func:`forward`); ``chosen`` is the reference's own choice
    either way."""
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
        out = out + w_e[..., None] * relu2(x, lw["ewu"][e], lw["ewd"][e])
    return out, own


def layer(h, lw, first_expert, wrap=lambda f: f, top_k=TOP_K, given=None,
          probes=None, groups=SSM_GROUPS):
    """-> (h', the layer's choices of expert, None where it routes none).
    ``probes``: a list that takes ``(x, F(x))`` of every state-space
    layer."""
    if "router" in lw:
        out, chosen = expert_ffn(rmsnorm(h, lw["ln2"]), lw, first_expert,
                                 top_k, given)
        return h + out, chosen
    x = rmsnorm(h, lw["ln1"])
    if "ssm_in" in lw:
        out = mamba(x, lw, wrap, groups)
        if probes is not None:
            probes.append((x, out))
    else:
        out = attention(x, lw, wrap)
    return h + out, None


def head(weights, h):
    return jnp.einsum("btd,vd->btv", h, weights["lm_head"])


def forward(weights, tokens, first_expert=0, wrap=lambda f: f, top_k=TOP_K,
            given=None, probes=None, groups=SSM_GROUPS):
    """tokens [B, T] int -> (logits [B, T, V], [the choices [B, T, top_k] of
    every expert layer]).

    ``given``: one [B, T, top_k] an expert layer, the experts every token
    TAKES, in place of the ``top_k`` largest of ``s + b``; their weights are
    the reference's own scores of them. The choices returned are still the
    reference's own, each layer's made on the activations the given choices
    led to. ``probes``: :func:`layer`'s. ``groups``: the groups of B and C
    of the state-space layers (their heads are ``ssm_A_log``'s length). It
    is how the chip's comparison tells rounding from routing: a
    choice near a tie falls the other way under bfloat16 activations, a
    token that chose otherwise differs by a whole expert's output, and a
    state-space layer hands that difference on to every later token."""
    h = weights["embed"][tokens].astype(jnp.float32)
    choices = []
    taken = iter(given if given is not None else ())
    for lw in weights["layers"]:
        take = next(taken) if given is not None and "router" in lw else None
        def run(h, lw, take=take):
            return layer(h, lw, first_expert, wrap, top_k, take, probes,
                         groups)

        # (a probe leaves the layer: then no wrap around it)
        h, chosen = (run if probes is not None else wrap(run))(h, lw)
        if chosen is not None:
            choices.append(chosen)
    return head(weights, rmsnorm(h, weights["ln_f"])), choices


def cross_entropy(logits, targets):
    """Next-token negative log-likelihood of every token, [B, T]."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - hit


def loss(weights, tokens, targets, first_expert=0, wrap=lambda f: f,
         top_k=TOP_K, given=None, groups=SSM_GROUPS):
    """Mean cross-entropy over the tokens: no auxiliary term (the balance is
    the bias update's). ``wrap`` goes around every layer, every block of
    attention rows, every piece of the recurrence and the exit. ``given``:
    see :func:`forward`."""
    logits, _ = forward(weights, tokens, first_expert, wrap, top_k, given,
                        None, groups)
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
