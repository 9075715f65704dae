"""Plain reference of JoyAI-LLM-Flash (JD, 2026-04; HF ``config.json`` of
``jdopensource/JoyAI-LLM-Flash``, ``model_type`` ``joyai_llm_flash``; its
keys are DeepSeek-V3's, arXiv:2412.19437 sections 2.1.1, 2.1.2 and 2.2):
forward, routing, both loss terms and gradients in float32 ``jax.numpy``,
python loops over layers and over experts, materialized masks, no kernel, no
scan over layers, no sort, nothing of the program's code.

With ``N`` an RMSNorm (``EPS``) with a learned scale::

    h_0 = E[tokens]
    layer:  a = h + Attn(N1(h));   h' = a + FFN(N2(a))
    logits = Nf(h_L) W_head^T                                  (untied)
    latent attention, x [T, D], H heads, sizes read off the leaves:
            c_q = Nq(x W_qa)                    (q_lora_rank wide)
            q_h = [q_nope_h | q_rope_h] = (c_q W_qb)_h     (dn + dr)
            x W_kva is kv_lora_rank + dr wide:
            c_kv = Nkv(its first kv_lora_rank),  k_rope = its last dr:
            ONE key part shared by all H heads
            [k_nope_h | v_h] = (c_kv W_kvb)_h              (dn + dv)
            q_rope_h, k_rope rotated: pairs (2i, 2i + 1) by position *
            THETA ** (-2i / dr)   (``rope_interleave`` true)
            k_h = [k_nope_h | k_rope];  key j visible from query i iff j
            <= i; scores q_h . k_h (dn + dr) ** -0.5 (``rope_scaling``
            null: no further factor);  o_h = softmax(.) v_h  (dv)
            Attn = concat(o_h) W_o.   No bias anywhere.
    dense FFN (layer 0):  (silu(x W1) * (x W3)) W2
    expert FFN:  s = sigmoid(x Wr) over all E experts; S = the TOP_K largest
            of s + b (``noaux_tc``; n_group = topk_group = 1: no group
            limit; b enters the choice only and takes no gradient);
            w_e = ROUTE_SCALE * s_e / (sum over S of s + ROUTE_EPS);
            FFN(x) = Shared(x) + sum over e in S AND HELD of w_e Expert_e(x),
            every expert and the shared one a SwiGLU
    MTP module (depth 1), with h_i the stack's output at position i BEFORE
            Nf and t_{i+1} the next token:
            u_i = [Ne(E[t_{i+1}]) ; Nh(h_i)] M          (M: 2 D x D)
            one more expert layer over u (own leaves, own router and bias),
            Nm, the SAME head;  position i scores t_{i+2}
    loss = CE + MTP_WEIGHT * CE_mtp;  CE_mtp the mean over the T - 1
            positions a row that have a t_{i+2}
    after a step, per expert layer (the module's too):
            b_e += RATE * sign(mean(c) - c_e)

``forward`` takes ``tokens`` and ``targets`` (``tokens`` shifted by one, as
a training step has them): ``E[t_{i+1}]`` is the embedding of ``targets[i]``
and the module's target ``targets[i + 1]``; the row's last position scores
nothing.

The reference is given the same share of a layer as the program: the experts
``first_expert .. first_expert + held - 1`` (the leading axis of a layer's
``ewg``), the chosen experts that are absent add nothing, and that partial
result goes on to the next layer. On such a share the weights ``w_e`` are
constants of the backward pass (``models/transformer.py _expert_ffn``;
reference/lfm2-8b-a1b.py has why). The vocabulary is the slice the weights
hold.

``config.json`` gives the widths, ranks, heads, head sizes, ``rms_norm_eps``,
``rope_theta``, ``rope_interleave``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``scoring_func``,
``topk_method``, ``n_shared_experts``, ``first_k_dense_replace``,
``num_nextn_predict_layers``. Assumed (configs/joyai-llm-flash.json lists
them under ``assumed``): that the module is DeepSeek-V3's, that ``h`` is
taken before the final norm, the order of the two halves of ``M``'s input,
``MTP_WEIGHT``, ``RATE``, ``ROUTE_EPS``, the pre-norm placement.

Attention is materialized in blocks of ``ROWS`` query rows so that 8,192
positions fit a chip, through ``jax.lax.map`` over the blocks.

``weights``::

    {"embed", "lm_head": [V, D], "ln_f": [D],
     "layers": [{"ln1", "ln2": [D],
                 "wq_a": [D, rq], "q_a_norm": [rq], "wq_b": [rq, H, dn + dr],
                 "wkv_a": [D, rkv + dr], "kv_a_norm": [rkv],
                 "wkv_b": [rkv, H, dn + dv], "wo": [H, dv, D],
                 and either "wg", "wu": [D, F], "wd": [F, D]
                 or "router": [D, E], "router_bias": [E],
                    "ewg", "ewu": [held, D, Fe], "ewd": [held, Fe, D],
                    "shared_wg", "shared_wu": [D, Fs], "shared_wd": [Fs, D]}],
     "mtp": {"enorm", "hnorm", "ln_f": [D], "proj": [2 D, D],
             "block": one expert layer's dict}}

The last ``dr`` columns of a head of ``wq_b`` and of ``wkv_a`` are in the
PUBLISHED order, pairs side by side. Call under
``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6              # rms_norm_eps
THETA = 32e6            # rope_theta
TOP_K = 8               # num_experts_per_tok
ROUTE_SCALE = 2.5       # routed_scaling_factor
ROUTE_EPS = 1e-20       # added to the sum of the chosen scores
RATE = 1e-3             # the bias update's rate (assumed: arXiv:2408.15664)
MTP_WEIGHT = 0.1        # assumed: arXiv:2412.19437 section 4.2's last value
ROWS = 512              # query rows of one block of materialized attention


def rmsnorm(x, scale):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                               + EPS)) * scale


def rope(x):
    """x [..., T, dr]: pairs (2i, 2i + 1) rotated by position * THETA **
    (-2i / dr), the interleaved form."""
    t, half = x.shape[-2], x.shape[-1] // 2
    inv_freq = THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def attend(q, k, v, lo):
    """The block of ``ROWS`` query rows from ``lo`` on against every key:
    [B, H, ROWS, dv]."""
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


def q_latent(x, lw):
    """``c_q``: the normed latent q is made from."""
    return rmsnorm(x @ lw["wq_a"], lw["q_a_norm"])


def kv_latent(x, lw):
    """``(c_kv, the key part before its rotation [B, T, dr])``."""
    rkv = lw["kv_a_norm"].shape[0]
    kv_a = x @ lw["wkv_a"]
    return rmsnorm(kv_a[..., :rkv], lw["kv_a_norm"]), kv_a[..., rkv:]


def shared_key(k_rope, heads):
    """The ONE rotated key part, the same for every head: [B, H, T, dr]."""
    return jnp.broadcast_to(k_rope[:, None], k_rope.shape[:1] + (heads,)
                            + k_rope.shape[1:])


def rotated(q, k_nope, k_rope):
    """q [B, H, T, dn + dr], k_nope [B, H, T, dn], k_rope [B, T, dr] -> (q,
    k) with the last ``dr`` of each rotated, the key's shared."""
    dn = k_nope.shape[-1]
    return (jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1),
            jnp.concatenate([k_nope, shared_key(rope(k_rope), q.shape[1])],
                            axis=-1))


def qkv(x, lw):
    """x [B, T, D] -> q, k [B, H, T, dn + dr], v [B, H, T, dv]."""
    c_kv, k_rope = kv_latent(x, lw)
    dn = lw["wq_b"].shape[-1] - k_rope.shape[-1]
    q = jnp.einsum("btr,rhk->bhtk", q_latent(x, lw), lw["wq_b"])
    kv = jnp.einsum("btr,rhk->bhtk", c_kv, lw["wkv_b"])
    q, k = rotated(q, kv[..., :dn], k_rope)
    return q, k, kv[..., dn:]


def attention(x, lw, wrap=lambda f: f):
    """x [B, T, D] -> [B, T, D]. ``wrap`` goes around :func:`attend` (the
    chip's gradient check hands in ``jax.checkpoint``)."""
    q, k, v = qkv(x, lw)
    block = wrap(attend)
    t = x.shape[1]
    if t <= ROWS:
        out = block(q, k, v, 0)
    else:       # [blocks, B, H, ROWS, dv] -> [B, H, T, dv]
        assert t % ROWS == 0, (t, ROWS)
        out = jax.lax.map(lambda lo: block(q, k, v, lo),
                          jnp.arange(0, t, ROWS))
        out = jnp.moveaxis(out, 0, 2).reshape(v.shape)
    return jnp.einsum("bhtk,hkd->btd", out, lw["wo"])


def swiglu(x, wg, wu, wd):
    gate = x @ wg
    return (gate / (1.0 + jnp.exp(-gate)) * (x @ wu)) @ wd


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
    """-> (FFN(x), chosen): the shared expert, and every held expert over
    every token weighted by the token's weight for it (0: not chosen).
    ``given`` [B, T, top_k]: the experts to take in place of the ``top_k``
    largest (see :func:`forward`); ``chosen`` is the reference's own choice
    either way."""
    chosen, weight = route(x, lw, top_k)
    own = chosen
    if given is not None:
        chosen, weight = given, weights_of(x, lw, given)
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
    return out, own


def layer(h, lw, first_expert, wrap=lambda f: f, top_k=TOP_K, given=None,
          probes=None):
    """-> (h', the layer's choices of expert, None for a dense layer).
    ``probes``: a list that gains ``(the mixer's normed input, its
    output)``."""
    x = rmsnorm(h, lw["ln1"])
    mixed = attention(x, lw, wrap)
    if probes is not None:
        probes.append((x, mixed))
    h = h + mixed
    x = rmsnorm(h, lw["ln2"])
    if "router" in lw:
        out, chosen = expert_ffn(x, lw, first_expert, top_k, given)
    else:
        out, chosen = swiglu(x, lw["wg"], lw["wu"], lw["wd"]), None
    return h + out, chosen


def head(weights, h):
    return jnp.einsum("btd,vd->btv", h, weights["lm_head"])


def mtp_input(weights, h, tokens, targets):
    """``[Ne(E[next]) ; Nh(h)] M``: what the module's block takes; the next
    token of position i is ``targets[i]``."""
    del tokens
    mw = weights["mtp"]
    e = weights["embed"][targets].astype(jnp.float32)
    return jnp.concatenate([rmsnorm(e, mw["enorm"]),
                            rmsnorm(h, mw["hnorm"])], axis=-1) @ mw["proj"]


def forward(weights, tokens, targets, first_expert=0, wrap=lambda f: f,
            top_k=TOP_K, given=None, probes=None):
    """tokens, targets [B, T] int -> (logits [B, T, V], the module's logits
    [B, T, V], [the choices [B, T, top_k] of every expert layer, the
    module's block's last]).

    ``given``: one [B, T, top_k] an expert layer (the module's last), the
    experts every token TAKES, in place of the ``top_k`` largest of ``s +
    b``; their weights are the reference's own scores of them, and the
    choices returned are still the reference's own
    (reference/lfm2-8b-a1b.py ``forward`` has why). ``probes``: see
    :func:`layer`; one entry a layer, the module's block's last."""
    h = weights["embed"][tokens].astype(jnp.float32)
    choices = []
    taken = iter(given if given is not None else ())

    def run_layer(h, lw):
        take = next(taken) if given is not None and "router" in lw else None
        run = wrap(lambda h, lw: layer(h, lw, first_expert, wrap, top_k,
                                       take))
        if probes is not None:      # (a probe leaves no checkpoint)
            run = lambda h, lw: layer(h, lw, first_expert, wrap, top_k,
                                      take, probes)
        h, chosen = run(h, lw)
        if chosen is not None:
            choices.append(chosen)
        return h

    for lw in weights["layers"]:
        h = run_layer(h, lw)
    logits = head(weights, rmsnorm(h, weights["ln_f"]))
    u = run_layer(mtp_input(weights, h, tokens, targets),
                  weights["mtp"]["block"])
    return logits, head(weights, rmsnorm(u, weights["mtp"]["ln_f"])), choices


def cross_entropy(logits, targets):
    """Negative log-likelihood of ``targets`` under ``logits``, [B, T]."""
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1))
    hit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - hit


def mtp_cross_entropy(mtp_logits, targets):
    """The module's negative log-likelihood, [B, T - 1]: position i scores
    ``targets[i + 1]``, the token after the next; the last scores none."""
    return cross_entropy(mtp_logits[:, :-1], targets[:, 1:])


def loss_terms(weights, tokens, targets, first_expert=0, wrap=lambda f: f,
               top_k=TOP_K, given=None):
    """(mean cross-entropy over the tokens, the module's mean over the T - 1
    positions a row that score something). ``wrap`` also goes around every
    layer and both exits."""
    logits, mtp_logits, _ = forward(weights, tokens, targets, first_expert,
                                    wrap, top_k, given)
    return (jnp.mean(wrap(cross_entropy)(logits, targets)),
            jnp.mean(wrap(mtp_cross_entropy)(mtp_logits, targets)))


def loss(weights, tokens, targets, first_expert=0, wrap=lambda f: f,
         top_k=TOP_K, given=None):
    """``CE + MTP_WEIGHT * CE_mtp``: no auxiliary term (the balance is the
    bias update's)."""
    main, mtp = loss_terms(weights, tokens, targets, first_expert, wrap,
                           top_k, given)
    return main + MTP_WEIGHT * mtp


grads = jax.grad(loss)      # (weights, tokens, targets, ...) -> like weights


def counts(chosen, n_experts):
    """Tokens routed to each of the ``n_experts``, [E]."""
    return jnp.sum(chosen[..., None] == jnp.arange(n_experts),
                   axis=tuple(range(chosen.ndim)))


def bias_update(bias, chosen, rate=RATE):
    """The selection bias after a step that routed ``chosen``."""
    c = counts(chosen, bias.shape[-1]).astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c) - c)
