"""Expert parallelism: two MoE layers, each as three functions (route and
pack, the held experts' FFN, combine) with the exchange between them left to
the caller.

1. Switch-style top-1 with capacity slots and all-to-all dispatch
   (:func:`moe_dispatch`, :func:`moe_experts`, :func:`moe_combine`;
   :func:`moe_layer_p` puts ``lax.all_to_all`` between them), below.
2. Sigmoid top-k with no capacity and no drop, SwiGLU experts and a selection
   bias that balances the load without an auxiliary loss
   (:func:`topk_route`, :func:`topk_dispatch`, :func:`grouped_swiglu` or
   :func:`grouped_relu2` where an expert is ``relu(x wu)^2 wd``,
   :func:`topk_combine`; :func:`topk_moe_held` runs them on ONE chip's
   share of the experts with no exchange), at the end of this file.

The first, in this module's first words:

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

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..common import scopes
from .flash_attention import flash_available


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


# --------------------------------------------------------------------------
# Sigmoid top-k routing without drops (DeepSeek-V3-style: arXiv:2412.19437
# section 2.1.2; the bias update: arXiv:2408.15664).


class TopKRoute(NamedTuple):
    """Where :func:`topk_route` sent every token."""
    expert: jax.Array   # [T, k] int32: the experts a token chose
    weight: jax.Array   # [T, k] fp32: what each choice's output is scaled by
    counts: jax.Array   # [E] int32: assignments to each expert, all E


def topk_route(x, router, bias, k: int, route_scale: float = 1.0,
               route_norm: bool = True, eps: float = 1e-20) -> TopKRoute:
    """``s = sigmoid(float32(x) router)`` over all ``E`` experts; the choice
    is the ``k`` largest of ``s + bias``; the weights are ``s`` of the chosen
    (``bias`` enters the choice only, and takes no gradient), divided by
    their sum plus ``eps`` under ``route_norm``, times ``route_scale``. fp32
    at the highest matmul precision: a near-tie should fall as it does in
    exact arithmetic."""
    with jax.named_scope(scopes.ROUTER):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, expert = lax.top_k(s + lax.stop_gradient(bias), k)
        # the chosen scores and the counts by comparison, not by index: one
        # non-zero term a sum, so take_along_axis and a scatter-add of ones
        # to the bit, in two fused passes over [T, k, E]; by index the v5e
        # takes 8-9 ns an element (PERF.md section 6, PR 33)
        chosen = expert[..., None] == jnp.arange(router.shape[1])
        w = jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1)
        if route_norm:
            # (behind a barrier the sum over the chosen stays the reduction
            # over [T, k] it was: merged into the one over E above it adds
            # in another order, and the weights' last bit moves)
            w = lax.optimization_barrier(w)
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return TopKRoute(expert.astype(jnp.int32), w * route_scale,
                         jnp.sum(chosen, axis=(0, 1), dtype=jnp.int32))


class RowPlaces(NamedTuple):
    """Where the rows of a token's choices are (:func:`topk_places`: in the
    sorted order; ``within``: in one buffer of it): what the gather form of
    the row sums reads. Choice-major, ``[k, T]``: a choice's rows of every
    token are one gather of ``[T, d]``."""
    place: jax.Array        # [k, T] int32: the row of choice (t, s), 0 where
    #                         it has none
    mine: jax.Array         # [k, T] bool: it has one
    live: jax.Array         # [] int32: the rows that hold a choice, the
    #                         first ones

    def within(self, start, n_rows: int) -> "RowPlaces":
        """The places among rows ``start .. start + n_rows - 1``, counted
        from ``start``."""
        mine = self.mine & (self.place >= start) & (self.place
                                                    < start + n_rows)
        return RowPlaces(jnp.where(mine, self.place - start, 0), mine,
                         jnp.clip(self.live - start, 0, n_rows))


class TopKPacked(NamedTuple):
    """:func:`topk_dispatch`'s buffer and how to undo it."""
    rows: jax.Array         # [R, d]: tokens in order of their expert
    group_sizes: jax.Array  # [held] int32: rows of each held expert
    token: jax.Array        # [R] int32: the token a row came from
    weight: jax.Array       # [R] fp32: its choice's weight, 0 past the last
    #                         held assignment
    places: Optional[RowPlaces] = None  # the gather form: the same map from
    #                         the tokens' side


def topk_order(route: TopKRoute, first_expert: int, experts_held: int):
    """``(token [T x k], weight [T x k], group_sizes [held])``: the token
    and the weight of each of the ``T x k`` assignments, sorted by expert
    (and by token within one), those of the held experts ``first_expert ..
    first_expert + experts_held - 1`` first, and how many each held expert
    got. The sort carries what would be gathered after it."""
    with jax.named_scope(scopes.MOE_DISPATCH):
        local = route.expert - first_expert
        held = jnp.logical_and(local >= 0, local < experts_held)
        _, token, weight = lax.sort(
            (jnp.where(held, local, experts_held).reshape(-1),
             lax.broadcasted_iota(jnp.int32, local.shape, 0).reshape(-1),
             route.weight.reshape(-1)), num_keys=1, is_stable=True)
        return token, weight, lax.dynamic_slice_in_dim(
            route.counts, first_expert, experts_held)


def topk_places(route: TopKRoute, first_expert: int,
                experts_held: int) -> RowPlaces:
    """The row of :func:`topk_order`'s sorted order at which each choice
    ``(t, s)`` of a held expert sits: the start of the expert's group plus
    the tokens before ``t`` that chose it (a token chooses an expert once:
    ``top_k``'s). Comparisons, one cumulative sum along T and a selection:
    no index operation, no second sort."""
    with jax.named_scope(scopes.MOE_DISPATCH):
        chosen = (route.expert.T - first_expert)[..., None] \
            == jnp.arange(experts_held)                     # [k, T, held]
        sizes = lax.dynamic_slice_in_dim(route.counts, first_expert,
                                         experts_held)
        takes = jnp.sum(chosen, axis=0, dtype=jnp.int32)    # [T, held]
        at = jnp.cumsum(takes, axis=0) - takes + jnp.cumsum(sizes) - sizes
        return RowPlaces(jnp.sum(jnp.where(chosen, at, 0), axis=-1),
                         jnp.any(chosen, axis=-1), jnp.sum(sizes))


# The two forms of the row sum (the combine; the dispatch's backward pass),
# on the v5e (tools/grouped_matmul_sweep.py --mode rows; PERF.md section 6,
# PR 33 to PR 35).
# "chunks": a scatter-add of the first n_live rows, ROW_CHUNK at a time. A
# row of 2,048 costs a scatter-add 105-270 ns, dead or live, dropped or not,
# its indices sorted or not, so that form adds as few rows as it can; at
# 2,048 a call the carried sum is copied every time.
# "gather": each choice's rows gathered by RowPlaces and added in fp32 in one
# pass: all T x k of them, live or not, at 6-7 ns a row.
ROW_CHUNK = 1024
# T x k over the live rows of an even router (experts over experts held) up
# to which the sum is a gather. Of 65,536 choices of rows of 2,048 a layer's
# two sums take 3.4 ms gathered whatever is live, and in chunks 2.0 / 3.7 /
# 6.8 ms at 4,096 / 8,192 / 16,384 live rows: the chunks win by 1.7 times at
# a sixteenth, the two tie at an eighth, the gather wins by 2 at a quarter
GATHER_UP_TO = 8


def row_sum_form(n_tokens: int, k: int, n_experts: int,
                 experts_held: int) -> str:
    """``"gather"`` or ``"chunks"``: the form of the row sums of a layer
    that holds ``experts_held`` of ``n_experts`` experts over ``n_tokens``
    tokens of ``k`` choices. Under a chunk of live rows the chunked sum is
    one small scatter-add and stays (either form then costs less than a
    call's own 0.2 ms)."""
    live = n_tokens * k * experts_held / n_experts
    return "gather" if ROW_CHUNK < live and n_tokens * k \
        <= GATHER_UP_TO * live else "chunks"


def row_sum_rows(n_tokens: int, k: int, n_experts: int, experts_held: int,
                 n_live: int, row_bytes: int) -> int:
    """The rows ONE row sum visits when ``n_live`` of the choices are of
    held experts, a row of the buffer ``row_bytes`` long: as a gather ``T x
    k`` for every slab of every buffer that holds a live row
    (:func:`_slabs`), else the live rows in whole chunks."""
    n_rows = topk_buffer_rows(n_tokens, k, n_experts, experts_held, n_live)
    if row_sum_form(n_tokens, k, n_experts, experts_held) != "gather":
        chunk = math.gcd(n_rows, ROW_CHUNK)
        return -(-n_live // chunk) * chunk
    slab = _slabs(n_rows, row_bytes)[1]
    full, rest = divmod(n_live, n_rows)     # buffers; the first always runs
    return (full * -(-n_rows // slab)
            + max(-(-rest // slab), 0 if full else 1)) * n_tokens * k


# the largest operand the v5e gathers rows from at the speed of writing them
# (6-7 ns a row of 4 KB): out of an array of 128 MiB or more the same rows
# cost 35-40 ns each, whichever of them are read and in whatever order
# (PERF.md section 6, PR 35: 65,536 rows out of 64 / 96 / 128 / 160 MiB in
# 0.43 / 0.43 / 2.27 / 2.27 ms; fp32 alike)
NEAR_BYTES = 96 * 2 ** 20


def _slabs(n_rows: int, row_bytes: int) -> Tuple[int, int]:
    """``(how many, rows of each)``: the equal parts of at most
    ``NEAR_BYTES`` a buffer of ``n_rows`` rows is gathered from."""
    n_slabs = -(-n_rows * row_bytes // NEAR_BYTES)
    return n_slabs, -(-n_rows // n_slabs)


def _over_slabs(rows, places: RowPlaces, term):
    """``sum over slabs of term([(here [T], rows[place[s]] [T, d] fp32) for
    each choice s])``, the slabs (:func:`_slabs`) copied out of ``rows``
    one at a time and gathered from, and only as many as hold a live row:
    ``here`` says whether a choice's row is in the slab at hand (one that
    is not reads row 0: cut it by ``where``, 0 * junk is not 0). A gather a
    choice, so that what is made of the rows fuses into one pass over them,
    their conversion too."""
    n_rows = rows.shape[0]
    n_slabs, slab = _slabs(n_rows, rows.shape[1] * rows.dtype.itemsize)

    def part(at):
        # (the last slab moved back over the one before it, as
        # dynamic_slice would move it)
        start = jnp.minimum(at, n_rows - slab)
        src = lax.dynamic_slice_in_dim(rows, start, slab) \
            if n_slabs > 1 else rows
        pairs = []
        for s in range(places.place.shape[0]):
            place, mine = places.place[s], places.mine[s]
            here = mine & (place >= at) & (place < at + slab)
            pairs.append((here, src.at[jnp.where(here, place - start, 0)].get(
                mode="promise_in_bounds").astype(jnp.float32)))
        return term(pairs)

    if n_slabs == 1:
        return part(0)
    return lax.while_loop(
        lambda c: c[0] < places.live,
        lambda c: (c[0] + slab, c[1] + part(c[0])),
        (jnp.full((), slab, places.live.dtype), part(0)))[1]


def rows_at_places(rows, places: RowPlaces, weight=None):
    """``sum_s rows[place[s, t]]`` over a token's own choices, each times
    ``weight[t, s]`` if given: the rows gathered in their dtype, the
    products and the sum in fp32, ``[T, d]`` fp32."""
    return _over_slabs(rows, places, lambda pairs: sum(
        jnp.where(here[:, None],
                  got if weight is None else got * weight[:, s:s + 1], 0.0)
        for s, (here, got) in enumerate(pairs)))


@jax.custom_vjp
def rows_from_tokens(x, token, live):
    """``x[token]``: the buffer's rows ``[R, d]`` from the tokens ``x [T,
    d]``. Its transpose is :func:`tokens_from_rows`, and that one's is this:
    the form of the sum is chosen there, for the combine and for the
    dispatch's backward pass alike. ``live``: see there."""
    return x[token]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def tokens_from_rows(rows, token, live, n_tokens: int):
    """``rows [R, d]`` summed into the tokens they came from, ``[n_tokens,
    d]`` in ``rows``' dtype. ``live`` says which form: the :class:`RowPlaces`
    of the rows (added in fp32, rounded once), or the number of live rows:
    the first of them, ``ROW_CHUNK`` at a time (the rest are past the last
    held assignment and hold zeros: a chunk of them costs nothing, as a
    buffer that is not needed does)."""
    if isinstance(live, RowPlaces):
        return rows_at_places(rows, live).astype(rows.dtype)
    chunk = math.gcd(rows.shape[0], ROW_CHUNK)

    def add(c):
        return c[0] + chunk, c[1].at[
            lax.dynamic_slice_in_dim(token, c[0], chunk)].add(
                lax.dynamic_slice_in_dim(rows, c[0], chunk))

    return lax.while_loop(
        lambda c: c[0] < live, add,
        (jnp.zeros((), live.dtype),
         jnp.zeros((n_tokens, rows.shape[1]), rows.dtype)))[1]


rows_from_tokens.defvjp(
    # (x for its shape alone)
    lambda x, token, live: (x[token], (x, token, live)),
    lambda kept, g: (tokens_from_rows(g, kept[1], kept[2], kept[0].shape[0]),
                     None, None))
tokens_from_rows.defvjp(
    lambda rows, token, live, n_tokens: (
        tokens_from_rows(rows, token, live, n_tokens), (token, live)),
    lambda n_tokens, kept, g: (rows_from_tokens(g, *kept), None, None))


def topk_dispatch(x, token, weight, group_sizes, start, n_rows: int,
                  places: Optional[RowPlaces] = None) -> TopKPacked:
    """Rows ``start .. start + n_rows - 1`` of the sorted assignments
    (:func:`topk_order`'s ``token`` and ``weight``) gathered from ``x [T,
    d]``, and the part of every held expert's group that falls among them.
    This is the dispatch exchange's send side; with one chip's share and no
    exchange, its whole. ``places`` (:func:`topk_places`, all of the sorted
    order's): the row sums take the gather form."""
    with jax.named_scope(scopes.MOE_DISPATCH):
        ends = jnp.cumsum(group_sizes)
        cut = lambda edge: jnp.clip(edge - start, 0, n_rows)  # noqa: E731
        token = lax.dynamic_slice_in_dim(token, start, n_rows)
        live = jnp.arange(n_rows) < cut(ends[-1])
        weight = jnp.where(
            live, lax.dynamic_slice_in_dim(weight, start, n_rows), 0.0)
        # (the where cuts a dead row's cotangent too: the grouped kernels
        # compute nothing there, in either pass)
        if places is not None:
            places = places.within(start, n_rows)
        return TopKPacked(
            jnp.where(live[:, None], rows_from_tokens(
                x, token, cut(ends[-1]) if places is None else places), 0),
            cut(ends) - cut(ends - group_sizes), token, weight, places)


# (m, k, n) tiles of the megablox kernel: tools/grouped_matmul_sweep.py on the
# v5e (PERF.md section 6, PR 32), the three products of grouped_swiglu
# forward + backward over 5,120 rows of 8 experts / 10,240 of 16: 1.70 / 3.50
# ms against 1.69 / 3.84 at (128, 1024, 1024), 1.93 / 3.92 at (512, 1024,
# 1024), and 2.27 / 5.01 for lax.ragged_dot. Experts of 1792 over 40,960
# rows, 8k / 16k / 32k of them live (PR 34, the same tool; forward + backward):
# 7.69 / 11.14 / 18.05 ms with a tiling a call (gmm_tiles: 896 where 1024
# does not divide) against 8.13 / 11.96 / 19.68 for the stock rule at (256,
# 1024, 1024) with its ragged tile, 8.86 / 13.28 / 22.13 at (256, 1024, 896),
# which is ragged in the backward's calls, 7.67 / 11.10 / 17.95 at (256, 2048,
# 512) (which is a third slower at 1024: 3.56 against 2.76), 10.59 / 15.35 /
# 24.79 for lax.ragged_dot; a row tile of 512 gains 4% at 32k live and loses
# 3% at 8k
GMM_TILING = (256, 1024, 1024)


def gmm_tiles(m: int, k: int, n: int) -> tuple:
    """The (m, k, n) tiles of ONE megablox call over ``m`` rows, a
    contraction of ``k`` and ``n`` columns: ``GMM_TILING``, with the k and n
    tile cut to the largest multiple of 128 under it that divides the
    dimension (1792 = 2 x 896: no ragged last tile, whose masked part the
    kernel multiplies all the same), the dimension itself where it is
    smaller, the tile with a ragged last one where nothing divides (1856 =
    14.5 x 128 under a hidden size of 2688: 1024 + 832. Alone on the v5e the
    two products of relu2 experts of 1856, forward + backward over 7,680
    rows, take 4.32 ms so, 3.68 with the 1856 whole, 4.41 / 4.78 at 640 /
    512; inside the whole step the weights' gradient at a whole 1856 ran out
    of VMEM on the chip: PERF.md section 6, PR 39). Every call gets its own,
    the backward's two too: they contract over another dimension than the
    forward's."""
    def tile(size, most):
        fits = [t for t in range(most, 127, -128) if size % t == 0]
        return fits[0] if fits else min(most, size)
    return (min(GMM_TILING[0], m), tile(k, GMM_TILING[1]),
            tile(n, GMM_TILING[2]))


@jax.custom_vjp
def _gmm(rows, w, group_sizes):
    """megablox's ``gmm`` (``rows [m, k]``, ``w [groups, k, n]``) under the
    rule of its own ``custom_vjp``, but for the tiles: the stock rule hands
    the forward's (m, k, n) tiles to the backward's two calls, where k and n
    have changed places."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(rows, w, group_sizes, rows.dtype,
               gmm_tiles(rows.shape[0], *w.shape[1:]))


def _gmm_bwd(kept, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    rows, w, group_sizes = kept
    (m, k), n = rows.shape, w.shape[2]
    return (gmm(g, w, group_sizes, rows.dtype, gmm_tiles(m, n, k),
                transpose_rhs=True),
            tgmm(rows.swapaxes(0, 1), g, group_sizes, w.dtype,
                 gmm_tiles(m, k, n), num_actual_groups=w.shape[0]),
            None)


_gmm.defvjp(lambda rows, w, group_sizes: (_gmm(rows, w, group_sizes),
                                          (rows, w, group_sizes)), _gmm_bwd)


def grouped_matmul(rows, w, group_sizes):
    """``rows[g's rows] @ w[g]`` for every group ``g``, rows in order of
    their group. On the TPU the installed megablox kernels (custom calls
    ``gmm`` forward and for the rows' gradient, ``tgmm`` for the weights'),
    each call at :func:`gmm_tiles`, where the row tile divides the buffer;
    ``lax.ragged_dot`` elsewhere (libtpu's own ``ragged-dot`` kernel on the
    TPU, a quarter slower there: see ``GMM_TILING``). What either leaves in
    the rows past the last group is not to be read: :func:`topk_dispatch`
    and :func:`topk_combine` cut them on both passes."""
    w = w.astype(rows.dtype)
    if flash_available() and rows.shape[0] % GMM_TILING[0] == 0:
        return _gmm(rows, w, group_sizes)
    return lax.ragged_dot(rows, w, group_sizes)


def grouped_swiglu(rows, group_sizes, wg, wu, wd):
    """The held experts' FFN over the dispatch buffer, ``(silu(x wg_e) *
    (x wu_e)) wd_e`` for the rows of each expert ``e``: three grouped
    matrix products. ``wg``, ``wu`` [held, d, f]; ``wd`` [held, f, d]."""
    with jax.named_scope(scopes.EXPERTS):
        u = jax.nn.silu(grouped_matmul(rows, wg, group_sizes)) \
            * grouped_matmul(rows, wu, group_sizes)
        return grouped_matmul(u, wd, group_sizes)


def grouped_relu2(rows, group_sizes, wu, wd):
    """The held experts' FFN where an expert has no gate, ``relu(x wu_e)^2
    wd_e`` for the rows of each expert ``e``: two grouped matrix products.
    ``wu`` [held, d, f]; ``wd`` [held, f, d]."""
    with jax.named_scope(scopes.EXPERTS):
        u = jnp.square(jax.nn.relu(grouped_matmul(rows, wu, group_sizes)))
        return grouped_matmul(u, wd, group_sizes)


@jax.custom_vjp
def _combine_at_places(y, weight, token, row_weight, places):
    """The combine in the gather form, ``rows_at_places(y, places,
    weight)``. Its backward pass is the chunked form's, a gather by row:
    ``d_y[r] = row_weight[r] g[token[r]]``; the weights take theirs where
    they are, ``d_weight[t, s] = g[t] . y[place[s, t]]``."""
    return rows_at_places(y, places, weight)


def _combine_at_places_bwd(kept, g):
    y, _, token, row_weight, places = kept
    return (jnp.where(row_weight[:, None] != 0.0,
                      rows_from_tokens(g, token, places)
                      * row_weight[:, None], 0.0).astype(y.dtype),
            _over_slabs(y, places, lambda pairs: jnp.stack([
                jnp.where(here, jnp.sum(got * g, axis=-1), 0.0)
                for here, got in pairs], axis=1)),
            None, None, None)


_combine_at_places.defvjp(
    lambda *args: (_combine_at_places(*args), args), _combine_at_places_bwd)


def topk_combine(y, packed: TopKPacked, n_tokens: int, weight=None):
    """Every token's weighted sum of its held experts' outputs ``y [R, d]``,
    ``[T, d]`` accumulated in fp32; nothing for a row past the last held
    assignment, zero for a token none of whose rows is here. In the gather
    form (``packed.places``) the weights are ``weight [T, k]``, the
    choices', and ``packed.weight`` serves the backward pass alone."""
    with jax.named_scope(scopes.MOE_COMBINE):
        if packed.places is not None:
            return _combine_at_places(y, weight, packed.token,
                                      packed.weight, packed.places)
        # (0 * junk is not 0: such rows are cut by where, not by weight)
        scaled = jnp.where(packed.weight[:, None] != 0.0,
                           y.astype(jnp.float32) * packed.weight[:, None],
                           0.0)
        return tokens_from_rows(scaled, packed.token,
                                jnp.sum(packed.group_sizes), n_tokens)


# The dispatch buffer has two sizes, each a factor times an even router's
# share of the ``T x k`` assignments, and a layer runs the one that fits the
# held assignments its router counted (:func:`topk_buffer_rows`).
# BUFFER_FACTOR, the wide one, with further buffers of it past it: of 48
# readings of the sparse-expert cell's layers (3 seeds, 4 batches, 4 layers;
# v5e, PR 32) the held experts drew 0.51 to 2.10 times their even share (of
# 960, PR 38: 0.36 to 2.05), and a second buffer costs 9 ms there.
# TIGHT_FACTOR, ONE buffer and no loop where the loads allow: every XLA pass
# over the buffer touches all of its rows, live or dead, and a loop carries
# its sums in and out. On the v5e (PR 38; 960 readings a cell: 4 seeds, 60
# steps, 4 layers) the conv/attention cell's layers, a quarter of the experts
# held, drew 0.977 to 1.026 times even, and the sparse-expert cell's, a
# sixteenth, fit 1.125 / 1.25 / 1.5 / 2 times even in 74 / 83 / 94 / 99.9%
# of the readings. A step of the first at 1.125 / 1.25 / 1.5: 286.5 / 287.7
# / 290.2 ms (314.5 at the wide size alone: the buffer-sized passes 27.4 ->
# 12.8 ms, the loop's carries and copies 9.1 -> 0); of the second at 1.25 /
# 1.5, two seeds: 249.4, 252.1 / 250.3, 251.2 (258.8, 259.8). 1.25 and not
# 1.125: those loads are uniform random tokens through a settled bias, the
# evenest a router gets, and a layer that does not fit pays the wide price
BUFFER_FACTOR = 2.5
TIGHT_FACTOR = 1.25


def topk_buffer_sizes(n_tokens: int, k: int, n_experts: int,
                      experts_held: int) -> Tuple[int, int]:
    """``(tight, wide)``: the two sizes of the dispatch buffer in rows,
    ``TIGHT_FACTOR`` and ``BUFFER_FACTOR`` times an even router's share of
    the ``T x k`` assignments, each in multiples of 512 and at most all of
    them (with every expert held both are ``T x k``)."""
    even = n_tokens * k * experts_held / n_experts
    return tuple(min(n_tokens * k,
                     -(-int(math.ceil(even * factor)) // 512) * 512)
                 for factor in (TIGHT_FACTOR, BUFFER_FACTOR))


def topk_buffer_rows(n_tokens: int, k: int, n_experts: int,
                     experts_held: int, n_held):
    """Rows of the dispatch buffer a layer runs when ``n_held`` of its
    assignments are of held experts: the tight size where they fit it (one
    buffer then holds them all), else the wide one (and as many buffers of
    it as they fill). ``n_held``: a count, an array of counts or the traced
    sum of the layer's own ``group_sizes``; :func:`topk_moe_held` decides by
    this inside the step, ``models/transformer.py routing_stats`` reports
    by it on the host."""
    tight, wide = topk_buffer_sizes(n_tokens, k, n_experts, experts_held)
    return tight + (wide - tight) * (n_held > tight)


def topk_moe_held(x, route: TopKRoute, wg, wu, wd, first_expert: int = 0):
    """One chip's share of the routed experts, no exchange: the sum over the
    held experts ``first_expert .. first_expert + wg.shape[0] - 1`` of
    ``weight_e Expert_e(x)`` for every token that chose them, ``[T, d]`` in
    ``x``'s dtype; what the absent experts would add is left out.
    ``wg`` None: the experts have no gate (:func:`grouped_relu2` in place of
    :func:`grouped_swiglu`).

    No assignment is ever dropped, and the buffer is never ``T x k`` rows
    on a share: it fits the loads the router counted
    (:func:`topk_buffer_rows`, a ``lax.cond`` on the sum of ``group_sizes``,
    the same on both passes). Where the held assignments fit the tight size,
    ONE buffer of it holds them all. Where they do not, the sorted
    assignments go through buffers of the wide size, as many as they fill:
    one wherever the held experts draw less than ``BUFFER_FACTOR`` times
    their even share; the further buffers are the no-drop guarantee under
    any routing, and what the tests force. With every expert held the two
    sizes are one and no conditional is built.
    The wide path is a ``lax.while_loop`` on each pass; either path is under
    a ``custom_vjp`` whose backward pass runs a buffer again and pulls the
    cotangent back through it, buffer by buffer: the layer keeps its inputs
    and nothing else, and a buffer that is not needed costs nothing. The
    rows come back to the tokens, on both passes, in the form
    :func:`row_sum_form` answers from the share held. (Differentiated
    through, a scan with a ``lax.cond`` a buffer handed out every weight's
    zero cotangent and a [T, d] of zeros for each buffer it did not run: 80
    ms of a 350 ms step on the v5e, PERF.md PR 32; the conditional here is
    inside each pass, and each branch returns the whole result.)"""
    t, k = route.expert.shape
    # cast once, ahead of every buffer: the loop then carries the weights'
    # cotangents in the compute dtype, not three fp32 copies
    ws = tuple(w.astype(x.dtype) for w in (wg, wu, wd) if w is not None)
    held, n_experts = wu.shape[0], route.counts.shape[0]
    tight, wide = topk_buffer_sizes(t, k, n_experts, held)
    token, weight, group_sizes = topk_order(route, first_expert, held)
    # whole wide buffers: a slice past the end would be moved back over rows
    # already taken (the padding is past the last held assignment: dead);
    # the tight buffer is the first rows of at most T x k
    token, weight = (jnp.pad(a, (0, -(t * k) % wide))
                     for a in (token, weight))
    order = (token, group_sizes)
    if row_sum_form(t, k, n_experts, held) == "gather":
        # the weights take their gradient where the choices are; the sorted
        # copy serves the combine's backward pass, a constant
        order += (lax.stop_gradient(weight),
                  topk_places(route, first_expert, held))
        weight = route.weight

    def buffer(n_rows, start, order, x, weight, *ws):
        token, group_sizes, *gather = order
        row_weight, places = gather or (weight, None)
        packed = topk_dispatch(x, token, row_weight, group_sizes, start,
                               n_rows, places)
        ffn = grouped_swiglu if len(ws) == 3 else grouped_relu2
        y = ffn(packed.rows, packed.group_sizes, *ws)
        return topk_combine(y, packed, t, weight)

    def over_the_buffers(group_sizes, body, init):
        """``body(start, carry)`` for the start of every wide buffer the
        held assignments reach: at least the first."""
        n_held = jnp.sum(group_sizes)
        return lax.while_loop(
            lambda c: jnp.logical_or(c[0] == 0, c[0] < n_held),
            lambda c: (c[0] + wide, body(c[0], c[1])),
            (jnp.zeros((), n_held.dtype), init))[1]

    def by_fit(group_sizes, one_tight, all_wide, *operands):
        """``one_tight(*operands)`` where the held assignments fit the
        tight buffer, else ``all_wide(*operands)``."""
        if tight == wide:
            return all_wide(*operands)
        return lax.cond(
            topk_buffer_rows(t, k, n_experts, held, jnp.sum(group_sizes))
            == tight, one_tight, all_wide, *operands)

    @jax.custom_vjp
    def run(order, *args):
        return by_fit(
            order[1], functools.partial(buffer, tight, 0),
            lambda order, *args: over_the_buffers(
                order[1],
                lambda start, out: out + buffer(wide, start, order, *args),
                jnp.zeros((t, x.shape[1]), jnp.float32)),
            order, *args)

    def run_bwd(kept, g):
        def pull_back(n_rows, start, g, order, *args):
            return jax.vjp(functools.partial(buffer, n_rows, start, order),
                           *args)[1](g)

        return (None,) + by_fit(
            kept[0][1], functools.partial(pull_back, tight, 0),
            lambda g, order, *args: over_the_buffers(
                order[1],
                lambda start, sums: jax.tree_util.tree_map(
                    jnp.add, sums, pull_back(wide, start, g, order, *args)),
                tuple(jnp.zeros_like(a) for a in args)),
            g, *kept)

    run.defvjp(lambda *kept: (run(*kept), kept), run_bwd)
    return run(order, x, weight, *ws).astype(x.dtype)


def router_bias_update(bias, counts, rate: float):
    """The auxiliary-loss-free balance step (arXiv:2408.15664): ``b_e +=
    rate * sign(mean(c) - c_e)`` from the step's assignments ``c`` to every
    expert. ``bias``, ``counts`` [..., E]."""
    c = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)
