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
   S_in[c] + state[c]``, one step a chunk;
4. the carried state read out, ``y_i += exp(l_i) C_i . S_in[c]``.

Four matrix products a chunk and head; their operands are in ``X``'s dtype
(bfloat16 in a training step) and every one accumulates in float32. The
decays, the cumulative sums and the carried state are float32.
``benchmark/reference/nemotron-3-nano-30b-a3b.py`` runs the recurrence
itself, and ``tests/test_nemotron_lm.py`` holds the forms below to it.

Two forms compute it, and :func:`scan_form` answers which from the backend
and the shapes alone; :func:`ssd_chunked` dispatches on it.

``"chunked"``: ``jax.numpy`` with autodiff's backward, the specification.
It writes every ``[L, L]`` array of a chunk and head (the decay in float32,
``scores * decay`` in the operands' dtype) and the chunk states to HBM and
carries the states with a ``lax.scan``. It runs off the TPU and wherever
the shapes do not tile (the rehearsal's chunk of 16, head of 8, state of
16).

``"kernel"``: on a TPU where chunk, state and a group's ``R P`` columns are
multiples of the 128 lanes, two Pallas kernels under a ``jax.custom_vjp``
(:func:`_scan`), the same sums in the same precisions with the roundings
where the chunked form has them. The grid is ``(B, G, T / L)``, the chunks
the sequential axis; a step takes one group's ``X [L, R P]``, ``B`` and
``C [L, N]`` as ``mamba_mix`` has them (tokens second-minor, nothing
transposed), the step sizes and the log-decay of its ``R`` heads both as
columns ``[L, R]`` and, the log-decay, as rows ``[R, L]``. In VMEM and
never in HBM: ``C B^T`` (once a group), the masked decay and their product
a head, ``dt X`` and its decayed twin, and the group's carried states
``[N, R P]`` in float32, a scratch the chunk axis carries. The state
product and the read-out run for all ``R`` heads at once (they share ``B``
and ``C``); ``(scores * decay_h) X_h`` is a product a head, two heads of 64
to a tile of 128 lanes. What reaches HBM: ``y`` in float32 and, only where
a backward pass will follow, each chunk's INCOMING states in float32
(``[B, G, T / L, N, R P]``: the forward keeps them, the backward reads them
once); under ``jax.checkpoint`` the first forward writes none
(``optimize_remat``).

The backward kernel walks the chunks in reverse with the states' cotangent
``[N, R P]`` carried in VMEM; it computes a chunk's scores and decays again
from the operands, reads the chunk's incoming states, and writes the
cotangents of ``X``, ``B``, ``C`` (summed over a group's heads inside the
kernel), of ``dt`` and the log-decay (as columns, and the part of the
log-decay's that sums over rows as rows) and of ``D`` (summed over the
chunks in VMEM). The cumulative sum ``l`` of ``dt A`` is ``jax.numpy``
outside the kernels with autodiff's backward: ``[B, T, H]`` float32, 4 MB.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import scopes

LANES = 128


def scan_form(x_shape, bc_shape, chunk: int) -> dict:
    """Which form :func:`ssd_chunked` runs for ``x [B, T, H, P]`` and ``b``,
    ``c [B, T, G, N]`` on this backend, as the labels of the gauge
    ``hvd_tpu_lm_scan_kernel``: ``form`` (``"kernel"``: the Pallas kernels;
    ``"chunked"``: ``jax.numpy``), the ``chunk`` and ``heads_per_block``,
    the heads of a group, which one step of the kernels' grid takes
    together (0 for the chunked form). A function of the backend and the
    shapes alone. The kernels want a TPU; whole lane tiles: the chunk
    (the ``[L, L]`` scores' minor dimension), the state ``N`` and a group's
    ``R P`` columns multiples of 128, a head as wide as a tile or several,
    or a whole number of heads to a tile; and a step's blocks in VMEM: a
    chunk's ``y [L, R P]`` and the group's states ``[N, R P]`` at most 1 MiB
    each in float32 (compiled for the v5e up to there; 512 x 1024 is
    refused for its scoped VMEM)."""
    _, _, h, p = x_shape
    g, n = bc_shape[2:]
    r = h // g
    tiles = (chunk % LANES == 0 and n % LANES == 0 and (r * p) % LANES == 0
             and (p % LANES == 0 or LANES % p == 0))
    fits = max(chunk, n) * r * p * 4 <= 2 ** 20
    kernel = jax.default_backend() == "tpu" and tiles and fits
    return {"form": "kernel" if kernel else "chunked", "chunk": str(chunk),
            "heads_per_block": str(r if kernel else 0)}


def ssd_chunked(x, dt, a, b, c, d, chunk: int):
    """``y [B, T, H, P]`` in float32 of the recurrence above.

    ``x [B, T, H, P]``; ``dt [B, T, H]``, the step sizes (after their
    softplus); ``a [H]``, negative; ``b``, ``c`` ``[B, T, G, N]`` with ``G``
    a divisor of ``H``; ``d [H]``. ``T`` must be a multiple of ``chunk``:
    a shorter last chunk would be a second program, and no caller has one.
    """
    t, h = x.shape[1:3]
    g = b.shape[2]
    if t % chunk:
        raise ValueError(
            f"ssd_chunked: {t} tokens are not a multiple of the chunk "
            f"{chunk}: pad the row or choose a chunk that divides it")
    if h % g:
        raise ValueError(f"ssd_chunked: {g} groups do not divide {h} heads")
    with jax.named_scope(scopes.SSM_SCAN):
        if scan_form(x.shape, b.shape, chunk)["form"] == "kernel":
            return ssd_kernels(x, dt, a, b, c, d, chunk)
        return _ssd_numpy(x, dt, a, b, c, d, chunk)


def _ssd_numpy(x, dt, a, b, c, d, chunk: int):
    """The ``"chunked"`` form."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, r, f32, dtype = t // chunk, h // g, jnp.float32, x.dtype
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


# -- the kernels -------------------------------------------------------------

def ssd_kernels(x, dt, a, b, c, d, chunk: int, interpret: bool = False):
    """The ``"kernel"`` form of :func:`ssd_chunked`, same arguments and
    result (``interpret``: through Pallas's interpreter, for the CPU's
    tests). ``dt A``, its cumulative sum within a chunk and the layouts of
    the ``[B, T, H]`` quantities are ``jax.numpy`` here; the rest is
    :func:`_scan`."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    nc, r, f32, dtype = t // chunk, h // g, jnp.float32, x.dtype
    dt = dt.astype(f32)
    la = jnp.cumsum((dt * a.astype(f32)).reshape(bsz, nc, chunk, g, r),
                    axis=2).reshape(bsz, t, g, r)
    dt = dt.reshape(bsz, t, g, r)
    y = _scan((chunk, p, interpret),
              x.reshape(bsz, t, h * p), b.astype(dtype).reshape(bsz, t, g * n),
              c.astype(dtype).reshape(bsz, t, g * n),
              dt.transpose(0, 2, 1, 3), la.transpose(0, 2, 1, 3),
              la.transpose(0, 2, 3, 1),
              jnp.repeat(d.astype(f32), p).reshape(g, 1, r * p))
    return y.reshape(bsz, t, h, p)


def _tiles(r: int, p: int):
    """The ``r`` heads of a group by the tile of lanes their columns share
    (128 lanes: ``128 / p`` heads of ``p <= 128`` columns; a head's own
    ``p`` where that is a multiple of 128): ``[(the tile's columns, its
    heads), ...]``. Nothing in the kernels slices or joins off a tile's
    edge: a head inside a tile is a select on the lane."""
    per = max(1, LANES // p)
    return [(slice(first * p, (first + per) * p),
             tuple(range(first, first + per))) for first in range(0, r, per)]


def _own(lane, heads, h: int, p: int):
    """The lanes of a tile that are head ``h``'s (``lane``: the tile's
    lane index; ``heads``: the tile's)."""
    first = (h - heads[0]) * p
    return (lane >= first) & (lane < first + p)


def _by_head(of_head, rows: int, r: int, p: int):
    """``[rows, r p]`` whose columns are, head by head, those of
    ``of_head(h, the columns of h's tile, which lanes of it are h's)``: each
    head's result computed over its whole tile, its own lanes kept."""
    tiles = []
    for cut, heads in _tiles(r, p):
        lane = lax.broadcasted_iota(jnp.int32, (rows, cut.stop - cut.start),
                                    1)
        tile = None
        for h in heads:
            own = _own(lane, heads, h, p)
            mine = of_head(h, cut, own)
            tile = mine if tile is None else jnp.where(own, mine, tile)
        tiles.append(tile)
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _wide(cols, p: int):
    """``[L, R] -> [L, R p]``: head ``h``'s column over its ``p`` lanes."""
    rows, r = cols.shape
    return _by_head(lambda h, cut, own: jnp.broadcast_to(
        cols[:, h:h + 1], own.shape), rows, r, p)


def _narrow(wide, p: int):
    """``[L, R p] -> [L, R]``: each head's ``p`` lanes summed."""
    rows, r = wide.shape[0], wide.shape[1] // p
    at = lax.broadcasted_iota(jnp.int32, (rows, r), 1)
    out = jnp.zeros((rows, r), jnp.float32)
    for cut, heads in _tiles(r, p):
        tile = wide[:, cut]
        lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
        for h in heads:
            mine = tile if len(heads) == 1 else jnp.where(
                _own(lane, heads, h, p), tile, 0.0)
            out = jnp.where(at == h, jnp.sum(mine, axis=1, keepdims=True),
                            out)
    return out


def _dot(left, right, contract):
    """A product in the operands' dtype summed in float32; ``contract``:
    the contracted dimension of each (``(1, 0)``: a plain product)."""
    return lax.dot_general(left, right, ((contract[:1], contract[1:]),
                                         ((), ())),
                           preferred_element_type=jnp.float32)


def _chunk_terms(x_ref, b_ref, c_ref, dt_ref, lac_ref, p: int):
    """What both kernels compute of a chunk before anything a head's own:
    the operands, the ``C B^T`` scores, the causal mask, and the float32
    multipliers of each head's columns."""
    x, bm, cm = x_ref[0], b_ref[0], c_ref[0]
    dtc, lac = dt_ref[0, 0], lac_ref[0, 0]
    ln = x.shape[0]
    scores = _dot(cm, bm, (1, 1))                   # [L, L], once a group
    seen = lax.broadcasted_iota(jnp.int32, (ln, ln), 0) \
        >= lax.broadcasted_iota(jnp.int32, (ln, ln), 1)
    xf = x.astype(jnp.float32)
    dt_w = _wide(dtc, p)
    xdt = xf * dt_w                                 # dt_j X_j, float32
    # (the log-decay is spread over its head's columns once and the
    # exponentials taken there: the EUP has the room, the lanes' broadcasts
    # are what the kernels wait for)
    la_w = _wide(lac, p)
    last_w = la_w[ln - 1:ln, :]
    return types.SimpleNamespace(
        x=x, bm=bm, cm=cm, xf=xf, scores=scores, seen=seen, lac=lac,
        xdt=xdt, xb=xdt.astype(x.dtype), dt_w=dt_w,
        e_w=jnp.exp(la_w),                          # exp(l_i)
        end_w=jnp.exp(last_w - la_w),               # exp(l_last - l_i)
        kept_c=jnp.exp(lac[ln - 1:ln, :]), kept_w=jnp.exp(last_w))


def _masked(scores, seen, lac, lar, h: int):
    """Head ``h``'s decay ``exp(l_i - l_j)`` over ``j <= i`` and
    ``scores * decay``, both ``[L, L]`` float32."""
    decay = jnp.exp(jnp.where(seen, lac[:, h:h + 1] - lar[h:h + 1, :],
                              -jnp.inf))
    return decay, scores * decay


def _forward_kernel(x_ref, b_ref, c_ref, dt_ref, lac_ref, lar_ref, d_ref,
                    y_ref, *rest, p: int):
    """One chunk of one group: ``y`` of its ``R`` heads, the carried states
    moved on. ``rest``: the incoming states' output where they are kept,
    then the scratch that carries them."""
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    k = _chunk_terms(x_ref, b_ref, c_ref, dt_ref, lac_ref, p)
    lar, dtype = lar_ref[0, 0], k.x.dtype
    s_in = s_ref[...]                               # [N, R p] float32
    if len(rest) == 2:
        rest[0][0, 0, 0] = s_in
    y = _by_head(lambda h, cut, own: _dot(
        _masked(k.scores, k.seen, k.lac, lar, h)[1].astype(dtype),
        k.xb[:, cut], (1, 0)), k.xf.shape[0], k.lac.shape[1], p)
    y_ref[0] = y + _dot(k.cm, s_in.astype(dtype), (1, 0)) * k.e_w \
        + k.xf * d_ref[0]
    s_ref[...] = s_in * k.kept_w + _dot(
        k.bm, (k.xdt * k.end_w).astype(dtype), (0, 0))


def _backward_kernel(x_ref, b_ref, c_ref, dt_ref, lac_ref, lar_ref, d_ref,
                     s_ref, g_ref, dx_ref, db_ref, dc_ref, ddt_ref, dlac_ref,
                     dlar_ref, dd_ref, ds_ref, *, p: int):
    """One chunk of one group, the chunks last to first: the cotangents of
    its operands from ``g``, that of ``y``, and ``ds_ref``, the cotangent of
    the states the chunk hands on (``[N, R p]`` float32, carried)."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    k = _chunk_terms(x_ref, b_ref, c_ref, dt_ref, lac_ref, p)
    lar, dtype, f32 = lar_ref[0, 0], k.x.dtype, jnp.float32
    ln, r = k.lac.shape
    g = g_ref[0]
    gb = g.astype(dtype)
    s_in, ds_out = s_ref[0, 0, 0], ds_ref[...]
    sb, dsb = s_in.astype(dtype), ds_out.astype(dtype)

    # 4. the read-out: z = C S_in^T, y += exp(l) z
    dz = (g * k.e_w).astype(dtype)
    dc = _dot(dz, sb, (1, 1))
    grown = g * _dot(k.cm, sb, (1, 0)) * k.e_w    # l's share, wide
    # 2., 3. the states: S_out = exp(l_last) S_in + B^T u, u = dt X to_end
    u = k.xdt * k.end_w
    du = _dot(k.bm, dsb, (1, 0))
    db = _dot(u.astype(dtype), dsb, (1, 1))
    faded = du * u                                      # l's share, wide
    ds_ref[...] = ds_out * k.kept_w + _dot(k.cm, dz, (0, 0))
    at_end = jnp.sum(_narrow(faded, p), axis=0, keepdims=True) \
        + k.kept_c * _narrow(
            jnp.sum(ds_out * s_in, axis=0, keepdims=True), p)

    # 1. within the chunk, a head at a time
    col_at = lax.broadcasted_iota(jnp.int32, (ln, r), 1)
    row_at = lax.broadcasted_iota(jnp.int32, (r, ln), 0)
    sums = {"dscores": jnp.zeros((ln, ln), f32),
            "dlac": jnp.zeros((ln, r), f32), "dlar": jnp.zeros((r, ln), f32)}

    def of_head(h, cut, own):
        """``d(dt X)`` of head ``h`` through its ``[L, L]`` product, over
        its tile's columns; what the scores and the log-decay take of it is
        added to ``sums``."""
        decay, m = _masked(k.scores, k.seen, k.lac, lar, h)
        gt = gb[:, cut]
        mine = jnp.where(own, gt, jnp.zeros_like(gt))
        dm = _dot(mine, k.xb[:, cut], (1, 1))           # [L, L]
        q = dm * m
        sums["dscores"] += dm * decay
        sums["dlac"] = jnp.where(
            col_at == h, jnp.sum(q, axis=1, keepdims=True), sums["dlac"])
        sums["dlar"] = jnp.where(
            row_at == h, -jnp.sum(q, axis=0, keepdims=True), sums["dlar"])
        return _dot(m.astype(dtype), gt, (0, 0))

    dxb = _by_head(of_head, ln, r, p)
    dscores, dlac, dlar = sums["dscores"], sums["dlac"], sums["dlar"]
    dsb_ = dscores.astype(dtype)
    dc_ref[0] = (dc + _dot(dsb_, k.bm, (1, 0))).astype(dc_ref.dtype)
    db_ref[0] = (db + _dot(dsb_, k.cm, (0, 0))).astype(db_ref.dtype)

    dxdt = dxb + du * k.end_w
    dx_ref[0] = (dxdt * k.dt_w + g * d_ref[0]).astype(dx_ref.dtype)
    ddt_ref[0, 0] = _narrow(dxdt * k.xf, p)
    dd_ref[0, 0] += jnp.sum(g * k.xf, axis=0, keepdims=True)
    is_last = lax.broadcasted_iota(jnp.int32, (ln, r), 0) == ln - 1
    dlac_ref[0, 0] = dlac + _narrow(grown - faded, p) \
        + jnp.where(is_last, at_end, 0.0)
    dlar_ref[0, 0] = dlar


def _specs(shapes, chunk: int, p: int, backward: bool):
    """The grid and the operands' blocks: ``(grid, of(name))``. The chunk
    axis is last and sequential; the backward walks it in reverse."""
    bsz, t, g, r, n = shapes
    nc = t // chunk

    def at(i):
        return nc - 1 - i if backward else i

    blocks = {
        "x": ((1, chunk, r * p), lambda b, g_, i: (b, at(i), g_)),
        "bc": ((1, chunk, n), lambda b, g_, i: (b, at(i), g_)),
        "col": ((1, 1, chunk, r), lambda b, g_, i: (b, g_, at(i), 0)),
        "row": ((1, 1, r, chunk), lambda b, g_, i: (b, g_, 0, at(i))),
        "d": ((1, 1, r * p), lambda b, g_, i: (g_, 0, 0)),
        "dd": ((1, 1, 1, r * p), lambda b, g_, i: (b, g_, 0, 0)),
        "s": ((1, 1, 1, n, r * p), lambda b, g_, i: (b, g_, at(i), 0, 0))}
    return (bsz, g, nc), lambda name: pl.BlockSpec(*blocks[name])


def _shapes(x, b, dt_c):
    """``(B, T, G, R, N)`` of ``_scan``'s operands."""
    bsz, t, _ = x.shape
    g, r = dt_c.shape[1], dt_c.shape[3]
    return bsz, t, g, r, b.shape[2] // g


_SEQUENTIAL_CHUNKS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(static, keep: bool, x, b, c, dt_c, la_c, la_r, d_w):
    """``y [B, T, H P]`` float32 and, with ``keep``, every chunk's incoming
    states ``[B, G, T / L, N, R P]`` float32."""
    chunk, p, interpret = static
    shapes = bsz, t, g, r, n = _shapes(x, b, dt_c)
    grid, of = _specs(shapes, chunk, p, backward=False)
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(_forward_kernel, p=p), grid=grid,
        in_specs=[of("x"), of("bc"), of("bc"), of("col"), of("col"),
                  of("row"), of("d")],
        out_specs=[of("x")] + [of("s")] * keep,
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32)] + [
            jax.ShapeDtypeStruct((bsz, g, t // chunk, n, r * p), f32)] * keep,
        scratch_shapes=[pltpu.VMEM((n, r * p), f32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name="ssd_scan_fwd_states" if keep else "ssd_scan_fwd",
    )(x, b, c, dt_c, la_c, la_r, d_w)
    return out if keep else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(static, x, b, c, dt_c, la_c, la_r, d_w):
    """The scan of ``x [B, T, H P]``, ``b``, ``c [B, T, G N]`` given the
    step sizes and the log-decay since the chunk began as columns ``[B, G,
    T, R]``, the log-decay as rows ``[B, G, R, T]`` too, and ``D`` over its
    head's columns ``[G, 1, R P]``; ``static``: ``(chunk, P, interpret)``.
    The cotangent of the log-decay comes back in two parts, one through
    each of its layouts."""
    return _forward(static, False, x, b, c, dt_c, la_c, la_r, d_w)


def _scan_fwd(static, *operands):
    y, states = _forward(static, True, *operands)
    return y, (operands, states)


def _scan_bwd(static, kept, g):
    chunk, p, interpret = static
    (x, b, c, dt_c, la_c, la_r, d_w), states = kept
    shapes = bsz, t, groups, r, n = _shapes(x, b, dt_c)
    grid, of = _specs(shapes, chunk, p, backward=True)
    f32 = jnp.float32
    # (traced under the scopes its call was written under: ``ssm_scan``)
    dx, db, dc, ddt, dla_c, dla_r, dd = pl.pallas_call(
        functools.partial(_backward_kernel, p=p), grid=grid,
        in_specs=[of("x"), of("bc"), of("bc"), of("col"), of("col"),
                  of("row"), of("d"), of("s"), of("x")],
        out_specs=[of("x"), of("bc"), of("bc"), of("col"), of("col"),
                   of("row"), of("dd")],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in (x, b, c, dt_c, la_c, la_r)]
        + [jax.ShapeDtypeStruct((bsz, groups, 1, r * p), f32)],
        scratch_shapes=[pltpu.VMEM((n, r * p), f32)],
        compiler_params=_SEQUENTIAL_CHUNKS, interpret=interpret,
        name="ssd_scan_bwd",
    )(x, b, c, dt_c, la_c, la_r, d_w, states, g)
    return dx, db, dc, ddt, dla_c, dla_r, jnp.sum(dd, axis=0)


_scan.defvjp(_scan_fwd, _scan_bwd, optimize_remat=True)
