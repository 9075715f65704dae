"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464; the linear
attention of Qwen3-Next's three layers in four) in its chunked form.

Per value head, with the key head it reads, ``q`` and ``k`` normed to unit
length (``q`` then scaled by ``d_k ** -0.5``), a state ``S`` in ``[d_k,
d_v]`` that starts at zero, a decay ``g_t <= 0`` and a write strength
``beta_t`` in (0, 1)::

    S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - (e^{g_t} S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

The recurrence is never run token by token. Over chunks of ``CHUNK``
tokens, with ``G_t`` the cumulative sum of ``g`` since the chunk began and
``S_0`` the state the chunk takes in (the UT / WY form):

1. within a chunk, ``L = tril(diag(beta) K K^T o exp(G_i - G_j), -1)``
   (the decays masked BEFORE ``exp``), and ``T = (I + L)^{-1}``, exactly:
   ``L`` is strictly lower over ``CHUNK`` = 64 rows, so ``L^64 = 0`` and
   ``(I + L)^{-1} = prod_{i < 6} (I + (-L)^{2^i})``;
2. ``u = T (beta V)`` and ``w = T (beta K o e^G)``: what a token writes,
   before and after the incoming state's part;
3. the states carried across the chunks, ONE ``lax.scan``: the values
   each token really writes, ``v' = u - w S_0``, and ``S_out = e^{G_last}
   S_0 + (K o e^{G_last - G})^T v'``;
4. the output, ``o = (Q o e^G) S_0 + (tril(Q K^T o exp(G_i - G_j))) v'``.

The products take their operands in ``v``'s dtype (bfloat16 in a training
step; ``T``, ``u``, ``w`` and the carried state rounded once for them, as
the published kernel stores them) and accumulate in
float32; the norms, the decays and their cumulative sums, the solve and the
carried state are float32 (the solve's products at the highest matmul
precision). ``K K^T`` and ``Q K^T`` are a key head's, shared by the value
heads that read it. ``benchmark/reference/qwen3-next-80b-a3b.py`` runs the
recurrence itself, and ``tests/test_qwen3next_lm.py`` holds this form to
it.

One form, ``jax.numpy`` with autodiff's backward: every ``[CHUNK, CHUNK]``
array of a chunk and head and every chunk's incoming state reach HBM. A
Pallas kernel that keeps them in VMEM is the next step (as
``parallel/ssd.py``'s scan took one after its chunked form).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..common import scopes

# Tokens of a chunk: the published kernel's (fla's chunk_gated_delta_rule)
CHUNK = 64
# what the unit-length norm of q and k adds under its square root
L2_EPS = 1e-6


def delta_rule_form() -> dict:
    """What :func:`gated_delta_chunked` runs, as the labels of the gauge
    ``hvd_tpu_lm_delta_rule``: the form (``"chunked"``, ``jax.numpy``, on
    every backend) and the chunk's tokens."""
    return {"form": "chunked", "chunk": str(CHUNK)}


def _unit_length(x):
    """``x`` over its last axis divided by its length, in float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _mul(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(strict):
    """``(I + strict)^{-1}`` for a strictly lower ``strict [..., C, C]``,
    C at most 64, in float32: ``prod_{i < 6} (I + M^{2^i})`` with ``M =
    -strict``, which is the whole series ``sum_k M^k`` because ``M^64 =
    0``. The backward is written: ``d strict = -tril(T^T dT T^T, -1)``,
    and the inverse ``T`` is all it keeps."""
    c = strict.shape[-1]
    if c > 64:
        raise ValueError(f"unit_lower_inverse: {c} rows need more than six "
                         f"factors")
    eye = jnp.eye(c, dtype=jnp.float32)
    power = -strict
    inverse = eye + power
    for _ in range(5):
        power = _mul(power, power)
        inverse = _mul(inverse, eye + power)
    return inverse


def _inverse_fwd(strict):
    inverse = unit_lower_inverse(strict)
    return inverse, inverse


def _inverse_bwd(inverse, g):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-jnp.tril(_mul(_mul(t, g), t), -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_chunked(q, k, v, g, beta):
    """``o [B, T, Hv, dv]`` in float32 of the rule above.

    ``q``, ``k`` ``[B, T, Hk, dk]`` as they come out of the taps (this
    function norms them); ``v [B, T, Hv, dv]``, its dtype the products';
    ``g``, ``beta`` ``[B, T, Hv]``, the log-decay (at most 0) and the write
    strength, float32. Value head ``j`` reads key head ``j // (Hv / Hk)``.
    A row that is no multiple of ``CHUNK`` is padded at its end with
    tokens that write nothing (``beta = 0``), which no earlier output
    sees."""
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    if hv % hk:
        raise ValueError(f"gated_delta_chunked: {hk} key heads do not "
                         f"divide {hv} value heads")
    with jax.named_scope(scopes.GDN_SCAN):
        pad = -t % CHUNK
        if pad:
            def padded(x):
                return jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            q, k, v, g, beta = map(padded, (q, k, v, g, beta))
        return _chunked(q, k, v, g, beta)[:, :t]


def _chunked(q, k, v, g, beta):
    bsz, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n, r, f32, dtype = t // CHUNK, hv // hk, jnp.float32, v.dtype

    def chunks(x, per_value_head):     # -> [B, n, Hk, (r,) C, ...]
        x = x.reshape((bsz, n, CHUNK) + x.shape[2:])
        if per_value_head:
            x = x.reshape(x.shape[:3] + (hk, r) + x.shape[4:])
            return jnp.moveaxis(x, 2, 4)
        return jnp.moveaxis(x, 2, 3)

    qc = chunks((_unit_length(q) * dk ** -0.5).astype(dtype), False)
    kc = chunks(_unit_length(k).astype(dtype), False)   # [B, n, Hk, C, dk]
    vc = chunks(v, True)                            # [B, n, Hk, r, C, dv]
    gc = jnp.cumsum(chunks(g.astype(f32), True), axis=-1)  # [B,n,Hk,r,C]
    bc = chunks(beta.astype(f32), True)

    # 1. the decays, masked before exp, and the solve
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))          # [B, n, Hk, r, C, C]
    kk = jnp.einsum("bnhcd,bnhsd->bnhcs", kc, kc, preferred_element_type=f32)
    strict = jnp.where(jnp.tril(lower, -1), bc[..., :, None]
                       * kk[:, :, :, None] * decay, 0.0)
    solve = unit_lower_inverse(strict).astype(dtype)

    # 2. what each token writes
    u = jnp.einsum("bnhrcs,bnhrsd->bnhrcd", solve,
                   (vc.astype(f32) * bc[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    w = jnp.einsum("bnhrcs,bnhrsd->bnhrcd", solve,
                   (kc[:, :, :, None].astype(f32)
                    * (bc * jnp.exp(gc))[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    to_end = jnp.exp(gc[..., -1:] - gc)               # [B, n, Hk, r, C]
    k_to_end = (kc[:, :, :, None].astype(f32) * to_end[..., None]).astype(
        dtype)

    # 3. across the chunks: the chunks lead, as the scan wants them
    def carry_on(s_in, chunk_):
        u_c, w_c, k_c, kept = chunk_
        s_op = s_in.astype(dtype)
        written = u_c.astype(f32) - jnp.einsum(
            "bhrcd,bhrde->bhrce", w_c, s_op, preferred_element_type=f32)
        written = written.astype(dtype)
        s_out = s_in * kept[..., None, None] + jnp.einsum(
            "bhrcd,bhrce->bhrde", k_c, written, preferred_element_type=f32)
        return s_out, (s_op, written)

    lead = (lambda x: jnp.moveaxis(x, 1, 0))
    _, (s_in, written) = lax.scan(
        carry_on, jnp.zeros((bsz, hk, r, dk, dv), f32),
        (lead(u), lead(w), lead(k_to_end), lead(jnp.exp(gc[..., -1]))))
    s_in, written = jnp.moveaxis(s_in, 0, 1), jnp.moveaxis(written, 0, 1)

    # 4. the output
    q_decayed = (qc[:, :, :, None].astype(f32)
                 * jnp.exp(gc)[..., None]).astype(dtype)
    qk = jnp.einsum("bnhcd,bnhsd->bnhcs", qc, kc, preferred_element_type=f32)
    scores = (qk[:, :, :, None] * decay).astype(dtype)
    o = jnp.einsum("bnhrcd,bnhrde->bnhrce", q_decayed, s_in,
                   preferred_element_type=f32) \
        + jnp.einsum("bnhrcs,bnhrse->bnhrce", scores, written,
                     preferred_element_type=f32)
    # [B, n, Hk, r, C, dv] -> [B, T, Hv, dv], value head hk * r + j
    return jnp.moveaxis(o, 4, 2).reshape(bsz, t, hv, dv)
