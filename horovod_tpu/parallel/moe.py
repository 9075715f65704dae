"""Expert parallelism: Switch-style top-1 MoE with all-to-all dispatch.

SURVEY §2.8: the reference has no EP, "but **alltoall** — EP's transport
primitive — is first-class" (operations.cc:951, NCCLAlltoall). This module
builds the EP layer natively on ``lax.all_to_all`` over an ``expert`` mesh
axis: tokens are routed top-1, packed into per-expert capacity slots,
exchanged so each device holds the tokens for ITS experts (from every peer),
run through the local expert FFNs as one batched einsum (MXU-friendly:
[E_local, n·C, d] x [E_local, d, f]), and exchanged back.

Capacity semantics follow Switch Transformer: per source device each expert
accepts at most ``ceil(T·capacity_factor/E)`` tokens; overflow tokens
contribute zero (the caller's residual connection carries them through).
The auxiliary load-balancing loss is the standard fraction·probability dot.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax


class MoEParams(NamedTuple):
    router: jax.Array   # [d_model, n_experts_total]
    w_in: jax.Array     # [E_local, d_model, d_ff]
    w_out: jax.Array    # [E_local, d_ff, d_model]


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             n_expert_shards: int = 1, dtype=jnp.float32) -> MoEParams:
    """Per-shard expert weights: call under shard_map (or slice per rank)."""
    if n_experts % n_expert_shards:
        raise ValueError(f"n_experts {n_experts} must divide over "
                         f"{n_expert_shards} expert shards")
    e_local = n_experts // n_expert_shards
    k1, k2, k3 = jax.random.split(key, 3)
    return MoEParams(
        router=jax.random.normal(k1, (d_model, n_experts), dtype) * 0.02,
        w_in=jax.random.normal(k2, (e_local, d_model, d_ff), dtype)
        * math.sqrt(2.0 / d_model),
        w_out=jax.random.normal(k3, (e_local, d_ff, d_model), dtype)
        * math.sqrt(2.0 / d_ff))


class MoERoute(NamedTuple):
    """Where :func:`moe_dispatch` put every token; :func:`moe_combine`
    takes the first three."""
    expert: jax.Array   # [T] int: the expert a token is routed to
    slot: jax.Array     # [T] int: its place in that expert's queue
    weight: jax.Array   # [T] fp32: gate probability, 0 for a dropped token
    counts: jax.Array   # [E] fp32: valid tokens routed to each expert,
    #                     before the capacity cut


def moe_capacity(n_tokens: int, capacity_factor: float,
                 n_experts: int) -> int:
    """Slots an expert keeps for one source's ``n_tokens``."""
    return max(int(math.ceil(n_tokens * capacity_factor / n_experts)), 1)


def moe_dispatch(x, router, capacity: int, valid_mask=None):
    """Top-1 routing of the local tokens ``x [T, d]`` and their packing into
    the dispatch buffer: ``(disp [E, C, d], aux_loss, MoERoute)``. Dim 0 of
    ``disp`` in ``n`` equal blocks is the exchange layout: block ``i`` holds
    the tokens for the experts of shard ``i``."""
    t, d = x.shape
    e_total = router.shape[1]
    logits = (x @ router.astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)              # [T, E]
    expert = jnp.argmax(probs, axis=-1)                  # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]

    if valid_mask is None:
        valid = jnp.ones((t,), jnp.float32)
    else:
        valid = valid_mask.astype(jnp.float32)
    n_valid = jnp.maximum(jnp.sum(valid), 1.0)

    # Switch aux loss: E · Σ_e (fraction of tokens on e)·(mean prob of e),
    # over VALID tokens only (pad rows would otherwise skew both factors)
    onehot = jax.nn.one_hot(expert, e_total, dtype=jnp.float32) * valid[:, None]
    counts = jnp.sum(onehot, axis=0)
    aux = e_total * jnp.sum(
        (counts / n_valid) *
        (jnp.sum(probs * valid[:, None], axis=0) / n_valid))

    # capacity slotting: position of each token in its expert's queue
    # (invalid tokens take no slot)
    pos_in_expert = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot,
                            axis=-1).astype(jnp.int32) - 1     # [T]
    keep = jnp.logical_and(pos_in_expert < capacity,
                           pos_in_expert >= 0)
    slot = jnp.where(keep, pos_in_expert, capacity - 1)

    # dispatch buffer [E, C, d]; dropped tokens masked to zero contributions
    disp = jnp.zeros((e_total, capacity, d), x.dtype)
    disp = disp.at[expert, slot].add(x * keep[:, None].astype(x.dtype))
    return disp, aux, MoERoute(expert, slot, gate * keep, counts)


def moe_experts(recv, w_in, w_out):
    """The local experts' FFN over what the dispatch exchange delivered:
    ``recv [n, E_local·C, d]``, block ``i`` from source ``i``. Returns the
    same layout, block ``i`` going back to source ``i``."""
    n, _, d = recv.shape
    e_local = w_in.shape[0]
    expert_in = recv.reshape(n, e_local, -1, d).transpose(1, 0, 2, 3) \
        .reshape(e_local, -1, d)
    # batched expert FFN on the MXU: [E_local, nC, d]·[E_local, d, f]
    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", expert_in,
                               w_in.astype(recv.dtype)))
    y = jnp.einsum("ecf,efd->ecd", h, w_out.astype(recv.dtype))
    return y.reshape(e_local, n, -1, d).transpose(1, 0, 2, 3) \
        .reshape(recv.shape)


def moe_combine(combined, expert, slot, weight):
    """Every token's expert output from ``combined [E, C, d]`` (the combine
    exchange's result) by its :class:`MoERoute`, scaled by its gate:
    ``[T, d]``, zeros for dropped tokens."""
    return combined[expert, slot] * weight.astype(combined.dtype)[:, None]


def moe_layer_p(x, params: MoEParams, axis_name: str, axis_size: int,
                capacity_factor: float = 1.25,
                valid_mask=None) -> Tuple[jax.Array, jax.Array]:
    """Top-1 MoE over ``axis_name`` (size may be 1 = no EP):
    :func:`moe_dispatch`, :func:`moe_experts` and :func:`moe_combine` with
    a ``lax.all_to_all`` between them.

    Capacity and the aux loss are **per dispatch group** (this call's ``x``
    plus its axis peers) — the standard Switch/GShard semantics; global-batch
    statistics would need the caller to psum across its other mesh axes.

    Args:
      x: local tokens ``[T, d_model]`` (flatten batch×seq first).
      params: this shard's :class:`MoEParams` (experts sharded over the
        axis; router replicated).
      valid_mask: optional ``[T]`` bool — False rows (e.g. padding) are
        excluded from routing statistics, consume no expert capacity, and
        produce zero output.

    Returns ``(y, aux_loss)``: y ``[T, d_model]`` (zeros for dropped
    tokens — add the residual outside), and the scalar load-balance loss.
    """
    n = axis_size
    e_total = params.w_in.shape[0] * n
    capacity = moe_capacity(x.shape[0], capacity_factor, e_total)

    def exchange(buf):      # [n, E_local·C, d]; slice i goes to shard i
        return lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                              tiled=False) if n > 1 else buf

    disp, aux, route = moe_dispatch(x, params.router, capacity, valid_mask)
    y = moe_experts(exchange(disp.reshape(n, -1, x.shape[1])),
                    params.w_in, params.w_out)
    return moe_combine(exchange(y).reshape(disp.shape), route.expert,
                       route.slot, route.weight), aux
