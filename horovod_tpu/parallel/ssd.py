"""The chunked state-space scan (SSD, the matrix form of Mamba-2's
selective state space: arXiv:2405.21060 section 6) as a function of its own.

Per head ``h`` (reading group ``h // (H / G)`` of ``B`` and ``C``), with a
state ``S`` in ``[P, N]`` that starts at zero::

    S_t = exp(dt_t A) S_{t-1} + dt_t X_t B_t^T;    y_t = S_t C_t + D X_t

The recurrence is never run token by token. Over chunks of ``chunk``
tokens, with ``l_t`` the cumulative sum of ``dt A`` since the chunk began:

1. within a chunk, ``y_i += sum_{j <= i} (C_i . B_j) exp(l_i - l_j) dt_j
   X_j``: the masked decay-weighted ``C B^T`` scores times ``X``;
2. one state a chunk, ``sum_j exp(l_last - l_j) dt_j X_j B_j^T``: what the
   chunk alone leaves behind;
3. the states carried across the chunks, ``S_in[c + 1] = exp(l_last[c])
   S_in[c] + state[c]``: a ``lax.scan`` over ``T / chunk`` steps;
4. the carried state read out, ``y_i += exp(l_i) C_i . S_in[c]``.

Four matrix products a chunk and head; their operands are in ``X``'s dtype
(bfloat16 in a training step) and every one accumulates in float32. The
decays, the cumulative sums and the carried state are float32. The backward
pass is autodiff's. ``benchmark/reference/nemotron-3-nano-30b-a3b.py`` runs
the recurrence itself, and ``tests/test_nemotron_lm.py`` holds the two
together.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..common import scopes


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """``y [B, T, H, P]`` in float32 of the recurrence above.

    ``x [B, T, H, P]``; ``dt [B, T, H]``, the step sizes (after their
    softplus); ``a [H]``, negative; ``b``, ``c`` ``[B, T, G, N]`` with ``G``
    a divisor of ``H``; ``d [H]``. ``T`` must be a multiple of ``chunk``:
    a shorter last chunk would be a second program, and no caller has one.
    """
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk:
        raise ValueError(
            f"ssd_chunked: {t} tokens are not a multiple of the chunk "
            f"{chunk}: pad the row or choose a chunk that divides it")
    if h % g:
        raise ValueError(f"ssd_chunked: {g} groups do not divide {h} heads")
    nc, r, f32, dtype = t // chunk, h // g, jnp.float32, x.dtype
    with jax.named_scope(scopes.SSM_SCAN):
        dt = dt.astype(f32)
        # [B, c, G, R, L] the log of the decay since the chunk began
        la = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, chunk, g, r),
                        axis=2).transpose(0, 1, 3, 4, 2)
        # dt_j X_j, and the same decayed to the chunk's end
        xdt = (x.astype(f32) * dt[..., None]).reshape(
            bsz, nc, chunk, g, r, p)
        to_end = jnp.exp(la[..., -1:] - la).transpose(0, 1, 4, 2, 3)
        bc = b.astype(dtype).reshape(bsz, nc, chunk, g, n)
        cc = c.astype(dtype).reshape(bsz, nc, chunk, g, n)

        # 1. within the chunks
        scores = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                            preferred_element_type=f32)
        seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
        decay = jnp.exp(jnp.where(seen, la[..., :, None] - la[..., None, :],
                                  -jnp.inf))        # [B, c, G, R, L, L]
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                       (scores[:, :, :, None] * decay).astype(dtype),
                       xdt.astype(dtype), preferred_element_type=f32)

        # 2. what each chunk leaves behind, [c, B, G, R, P, N]: the chunks
        # lead, as the scan over them wants, so nothing is copied for it
        states = jnp.einsum("bclgrp,bclgn->cbgrpn",
                            (xdt * to_end[..., None]).astype(dtype), bc,
                            preferred_element_type=f32)

        # 3. across the chunks
        def carry_on(s_in, chunk_):
            state, kept = chunk_
            return s_in * kept[..., None, None] + state, s_in

        _, s_in = lax.scan(carry_on, jnp.zeros(states.shape[1:], f32),
                           (states, jnp.exp(la[..., -1]).swapaxes(0, 1)))

        # 4. the carried state read out
        y = y + jnp.einsum("bclgn,cbgrpn->bclgrp", cc, s_in.astype(dtype),
                           preferred_element_type=f32) \
            * jnp.exp(la).transpose(0, 1, 4, 2, 3)[..., None]
        return y.reshape(bsz, t, h, p) \
            + x.astype(f32) * d.astype(f32)[:, None]
