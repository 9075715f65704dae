#!/usr/bin/env python3
"""The routed experts' pieces alone on the chip. Nothing a cell runs imports
this file; what it led to is in ``parallel/moe.py``.

``--mode products`` (ISSUE 32): the three products of ``grouped_swiglu`` over
a dispatch buffer, as ``lax.ragged_dot`` (libtpu's own ``ragged-dot``
kernel) and as the installed megablox ``gmm`` at a few tilings; it led to
``grouped_matmul``. The shape is the sparse-expert cell's: ``--rows`` buffer
rows of 2048, ``--experts`` held experts of width ``--width`` (1024), group
sizes drawn like an even router's (multinomial) over ``--live`` rows (four
fifths of the buffer; several: one table each), the rest of the buffer past
the last group. ISSUE 34 (experts of 1792, 40,960 rows): the stock
``megablox.gmm`` hands ONE (m, k, n) tiling to the forward and to the
backward's two calls, where k and n have changed places, so a tile that
divides 1792 in one is ragged in another; ``moe.grouped_matmul`` gives every
call its own (``moe.gmm_tiles``) and is the candidate ``gmm_tiles``.

    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py --rows 40960 \\
        --width 1792 --live 8192 16384 32768 --out chiprun_out/gmm_1792.json

``--mode rows`` (ISSUE 33): the buffer's rows summed into the tokens they
came from, ``tokens_from_rows`` (the combine, fp32 rows; the backward of the
dispatch's gather, bf16 rows), in the forms of ``row_sums`` below, and its
transpose the row gather. ``--rows`` rows of 2048 into ``--tokens`` (8,192)
tokens, the tokens as ``topk_order`` leaves them for a router that sends
``--live`` (2,048 / 4,096 / 8,192) of the ``tokens x --top-k`` (8)
assignments to the ``--experts`` (8) held experts of ``--router-outputs``
(128); the rows past the last held assignment hold zeros. Every form is held
to the first one's result. Form (h) (ISSUE 35) is the shipped gather form (a
gather a choice out of slabs of at most ``moe.NEAR_BYTES``, one fused pass
over what they bring), the places of the rows found inside the timed call
(``place`` times that alone), and ``h:combine`` the combine as that form
runs it: bf16 rows in, each times its choice's weight, fp32 out.

    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py --mode rows \\
        --rows 10240 --out chiprun_out/row_sums.json
    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py --mode rows \\
        --rows 40960 --tokens 16384 --top-k 4 --router-outputs 32 \\
        --live 8192 16384 32768 --out chiprun_out/row_sums_40960.json
    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py --mode rows \
        --rows 40960 --tokens 16384 --top-k 4 --router-outputs 32 \
        --live 4096 8192 16384 --forms b h --out chiprun_out/row_sums_h.json

ISSUE 39 (relu2 experts of 1856 = 14.5 x 128 under a hidden size of 2688):
``--form relu2`` times the TWO products of ``grouped_relu2`` in place of the
SwiGLU's three, ``--hidden`` is the rows' width, and ``--tile-where-none-
divides`` adds, for each size given, ``moe.grouped_matmul``'s own calls
under a ``gmm_tiles`` that cuts a dimension no multiple of 128 divides to
that tile (0: the whole dimension) where the shipped rule takes 1024.

    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py --form relu2 \
        --hidden 2688 --width 1856 --rows 8192 --live 6144 \
        --tile-where-none-divides 0 512 640 --out chiprun_out/gmm_1856.json

``--rehearse``: tiny sizes, interpreted on the CPU: a test of the script.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

TILINGS = ((128, 1024, 1024), (256, 1024, 1024), (512, 1024, 1024),
           (512, 512, 512), (256, 2048, 512))
# a width that 1024 does not divide (1792 = 2 x 896): the stock rule at the
# tile that divides it forward
TILINGS_896 = ((256, 1024, 896), (512, 1024, 896), (256, 896, 896))


def swiglu(matmul):
    def ffn(rows, sizes, wg, wu, wd):
        u = jax.nn.silu(matmul(rows, wg, sizes)) * matmul(rows, wu, sizes)
        return matmul(u, wd, sizes)
    return ffn


def relu2(matmul):
    def ffn(rows, sizes, wu, wd):
        return matmul(jnp.square(jax.nn.relu(matmul(rows, wu, sizes))), wd,
                      sizes)
    return ffn


def tiles_with(fallback: int):
    """``moe.gmm_tiles`` but for a dimension that no multiple of 128
    divides: cut to ``fallback`` (0: left whole)."""
    from horovod_tpu.parallel import moe

    def rule(m, k, n):
        def tile(size, most):
            fits = [t for t in range(most, 127, -128) if size % t == 0]
            return fits[0] if fits else min(fallback or size, size)
        return (min(moe.GMM_TILING[0], m), tile(k, moe.GMM_TILING[1]),
                tile(n, moe.GMM_TILING[2]))
    return rule


def interpreted_megablox():
    """The megablox kernels interpreted, for ``moe.grouped_matmul``'s own
    calls on the CPU too (a rehearsal, a test)."""
    import importlib
    # (the package's own ``gmm`` attribute is the function of ops.py)
    backend = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    if not isinstance(backend.gmm, functools.partial):
        backend.gmm = functools.partial(backend.gmm, interpret=True)
        backend.tgmm = functools.partial(backend.tgmm, interpret=True)


def candidates(interpret: bool, width: int = 1024, fallbacks=()) -> dict:
    """name -> (the grouped product, the ``gmm_tiles`` it runs under: None
    the shipped one)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    from horovod_tpu.parallel import moe
    found = {"ragged_dot": (lambda x, w, s: lax.ragged_dot(x, w, s), None)}
    for tiling in TILINGS + (TILINGS_896 if width % 1024 else ()):
        found["gmm:%d/%d/%d" % tiling] = (
            lambda x, w, s, tiling=tiling: megablox.gmm(
                x, w, s, x.dtype, tiling, interpret=interpret), None)
    if interpret:
        interpreted_megablox()
    found["gmm_tiles (moe.grouped_matmul's)"] = (moe._gmm, None)
    for fallback in fallbacks:
        found["gmm_tiles, %s where none divides" % (fallback or "whole")] = (
            moe._gmm, tiles_with(fallback))
    return found


def ms_per_call(fn, xs, calls: int) -> float:
    jax.block_until_ready(fn(*xs))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*xs)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls * 1e3)
    return best


def products(args) -> dict:
    if len(args.live or ()) > 1:    # a table a fill of the buffer
        return {"live=%d" % live: products(argparse.Namespace(
            **{**vars(args), "live": [live]})) for live in args.live}
    from horovod_tpu.parallel import moe
    rows, d, f = (512, 128, 128) if args.rehearse else (
        args.rows, args.hidden, args.width)
    gated = args.form == "swiglu"
    rng = np.random.RandomState(0)
    live = args.live[0] if args.live and not args.rehearse else rows * 4 // 5
    sizes = rng.multinomial(live, [1 / args.experts] * args.experts)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    xs = [jax.random.normal(keys[0], (rows, d), jnp.bfloat16),
          jnp.asarray(sizes, jnp.int32)] + [
        jax.random.normal(k, (args.experts,) + s, jnp.bfloat16) * 0.02
        for k, s in zip(keys[1:], ((d, f),) * (1 + gated) + ((f, d),))]
    out = {"device": jax.devices()[0].device_kind, "rows": rows, "width": f,
           "group_sizes": sizes.tolist(), "ms": {}}
    want = None
    shipped = moe.gmm_tiles
    for name, (matmul, rule) in candidates(
            args.rehearse, args.width,
            args.tile_where_none_divides or ()).items():
        ffn = (swiglu if gated else relu2)(matmul)
        fwd = jax.jit(ffn)
        step = jax.jit(jax.grad(
            lambda *a: jnp.sum(ffn(*a).astype(jnp.float32) ** 2),
            (0,) + tuple(range(2, len(xs)))))
        # (traced at the first call below, both passes: under the rule)
        moe.gmm_tiles = rule or shipped
        try:
            got = np.asarray(fwd(*xs), np.float32)[:sizes.sum()]
            want = got if want is None else want
            rec = {"fwd_ms": ms_per_call(fwd, xs, 2 if args.rehearse else 50),
                   "step_ms": ms_per_call(step, xs,
                                          2 if args.rehearse else 50),
                   "against_first": float(np.abs(got - want).max()
                                          / np.abs(want).max())}
        except Exception as e:      # a tiling the compiler refuses, kept
            rec = {"failed": str(e).replace("\n", " ")[:300]}
        finally:
            moe.gmm_tiles = shipped
        out["ms"][name] = rec
        print(name, rec, flush=True)
    return out


# -- the row sums (ISSUE 33) ------------------------------------------------
# each form: (rows [R, d], token [R], n_live, n_tokens) -> [n_tokens, d] in
# rows' dtype; rows n_live.. are zeros and their tokens ascend, repeated

def _zeros(rows, n_tokens):
    return jnp.zeros((n_tokens, rows.shape[1]), rows.dtype)


def scatter_add(rows, token, n_live, n_tokens):
    return _zeros(rows, n_tokens).at[token].add(rows)


def live_chunks(chunk):
    def form(rows, token, n_live, n_tokens):
        def add(c):
            at, out = c
            return at + chunk, out.at[
                lax.dynamic_slice_in_dim(token, at, chunk)].add(
                    lax.dynamic_slice_in_dim(rows, at, chunk))
        return lax.while_loop(lambda c: c[0] < n_live, add,
                              (jnp.zeros((), jnp.int32),
                               _zeros(rows, n_tokens)))[1]
    return form


def _dead_out_of_range(token, n_live, n_tokens):
    return jnp.where(jnp.arange(token.shape[0]) < n_live, token, n_tokens)


def drop_dead(rows, token, n_live, n_tokens):
    return _zeros(rows, n_tokens).at[
        _dead_out_of_range(token, n_live, n_tokens)].add(rows, mode="drop")


def _by_token(rows, token, n_live, n_tokens):
    """Rows and tokens in order of the token, the dead ones (token
    ``n_tokens``) last."""
    token, at = lax.sort((_dead_out_of_range(token, n_live, n_tokens),
                          jnp.arange(token.shape[0])), num_keys=1)
    return rows[at], token


def sorted_add(rows, token, n_live, n_tokens):
    rows, token = _by_token(rows, token, n_live, n_tokens)
    return _zeros(rows, n_tokens).at[token].add(
        rows, indices_are_sorted=True, mode="drop")


def by_rank(ranks):
    """A call a rank of a row within its token, each over unique indices
    (a row of another rank goes to an index of its own past the end)."""
    def form(rows, token, n_live, n_tokens):
        rows, token = _by_token(rows, token, n_live, n_tokens)
        at = jnp.arange(token.shape[0])
        first = lax.cummax(jnp.where(
            token != jnp.roll(token, 1), at, 0).at[0].set(0))
        out = _zeros(rows, n_tokens)
        for rank in range(ranks):
            out = out.at[jnp.where(at - first == rank, token,
                                   n_tokens + at)].add(
                rows, unique_indices=True, mode="drop")
        return out
    return form


ONEHOT_TILE = 256       # tokens a group of the one-hot product


def onehot_tgmm(interpret):
    """The sum on the MXU: rows sorted by token, ``onehot^T rows`` a tile of
    256 tokens as one megablox ``tgmm`` over the tiles' rows; fp32 rows as a
    bfloat16 high and low part, summed in fp32. MEASURED, NOT SHIPPED: its
    custom call would be counted among the experts' products
    (``benchmark/layer_metrics/moe_experts_roofline.json``)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    def form(rows, token, n_live, n_tokens):
        rows, token = _by_token(rows, token, n_live, n_tokens)
        d, tile = rows.shape[1], min(ONEHOT_TILE, n_tokens)
        sizes = jnp.sum(
            (token // tile)[:, None] == jnp.arange(n_tokens // tile),
            axis=0, dtype=jnp.int32)
        onehot = ((token % tile)[None, :]
                  == jnp.arange(tile)[:, None]).astype(jnp.bfloat16)
        parts = rows.astype(jnp.bfloat16)
        if rows.dtype == jnp.float32:
            parts = jnp.concatenate(
                [parts, (rows - parts.astype(jnp.float32)).astype(
                    jnp.bfloat16)], axis=1)
        out = tgmm(
            onehot, parts, sizes, jnp.float32,
            (min(512, rows.shape[0]), tile, min(1024, d)),
            interpret=interpret).reshape(n_tokens, -1)
        return sum(out[:, i:i + d]
                   for i in range(0, out.shape[1], d)).astype(rows.dtype)
    return form


def gathered(ranks):
    """The sum as a gather, as ISSUE 34 tried it: rows sorted by token,
    every token's first row found by bisection, and its at most ``ranks``
    rows gathered and summed in fp32. MEASURED, NOT SHIPPED, and no measure
    of a gather either: what it timed was its own sort of the rows,
    ``searchsorted``'s 16 rounds of element gathers, the element gather of
    the places, and ``T x ranks`` fp32 rows where the buffer is bf16 (ISSUE
    35). Form (h) needs none of the four."""
    def form(rows, token, n_live, n_tokens):
        token, at = lax.sort((_dead_out_of_range(token, n_live, n_tokens),
                              jnp.arange(token.shape[0])), num_keys=1)
        first = jnp.searchsorted(token, jnp.arange(n_tokens + 1))
        place = first[:-1, None] + jnp.arange(ranks)[None, :]   # [T, ranks]
        mine = place < first[1:, None]
        got = rows[at[jnp.minimum(place, token.shape[0] - 1)]]
        return jnp.sum(jnp.where(mine[..., None], got.astype(jnp.float32),
                                 0.0), axis=1).astype(rows.dtype)
    return form


# -- the gather form as shipped (ISSUE 35): these take the route too, and
# find the rows' places from it inside the timed call ----------------------

def places_alone(held, rows, token, n_live, route, n_tokens):
    from horovod_tpu.parallel import moe
    return moe.topk_places(route, 0, held).within(0, rows.shape[0])


def shipped_gather(held, rows, token, n_live, route, n_tokens):
    """``moe.tokens_from_rows`` in its gather form: the dispatch's backward
    pass as shipped (bf16 rows; fp32 rows for the comparison with (a))."""
    from horovod_tpu.parallel import moe
    return moe.tokens_from_rows(
        rows, token, places_alone(held, rows, token, n_live, route,
                                  n_tokens), n_tokens)


def shipped_combine(held, rows, token, n_live, route, n_tokens):
    """``moe.topk_combine``'s sum in the gather form: bf16 rows, each times
    its choice's weight, summed in fp32."""
    from horovod_tpu.parallel import moe
    return moe.rows_at_places(
        rows.astype(jnp.bfloat16), places_alone(
            held, rows, token, n_live, route, n_tokens), route.weight)


def routed_forms(held: int) -> dict:
    return {"h:gathered (moe.tokens_from_rows)":
                functools.partial(shipped_gather, held),
            "h:combine (bf16 rows, weighted, fp32 out)":
                functools.partial(shipped_combine, held)}


def row_sums(rehearse: bool, ranks: int) -> dict:
    """The forms by the letters of ISSUE 33; (a) first: the others are held
    to it. (b) at the shipped chunk IS the shipped function (in rehearsal
    its chunk is the whole toy buffer)."""
    from horovod_tpu.parallel import moe
    return {"a:scatter-add": scatter_add,
            "b:live-chunks-256": live_chunks(8 if rehearse else 256),
            "b:live-chunks-512": live_chunks(16 if rehearse else 512),
            "b:live-chunks-1024 (moe.tokens_from_rows)":
                moe.tokens_from_rows,
            "b:live-chunks-2048": live_chunks(64 if rehearse else 2048),
            "c:dead-dropped": drop_dead,
            "d:sorted": sorted_add,
            "e:by-rank-unique": by_rank(ranks),
            "f:onehot-tgmm": onehot_tgmm(rehearse),
            "g:gathered": gathered(ranks)}


def routed_tokens(t: int, k: int, n_experts: int, held: int, n_live: int,
                  n_rows: int, seed: int):
    """``(token [n_rows], n_live, route)`` of the first buffer, as
    ``topk_order`` leaves them, for a router whose held experts ``0 .. held
    - 1`` draw about ``n_live`` of the ``t x k`` assignments (weights of
    1)."""
    from horovod_tpu.parallel import moe
    rng = np.random.RandomState(seed)
    scores = rng.rand(t, n_experts)
    lo, hi = -1.0, 1.0      # the held experts' handicap, by bisection
    for _ in range(30):
        lift = (lo + hi) / 2
        lifted = scores + lift * (np.arange(n_experts) < held)
        expert = np.argpartition(-lifted, k - 1, axis=1)[:, :k]
        lo, hi = (lift, hi) if (expert < held).sum() < n_live else (lo, lift)
    expert = jnp.asarray(expert, jnp.int32)
    route = moe.TopKRoute(
        expert, jnp.ones(expert.shape, jnp.float32),
        jnp.bincount(expert.reshape(-1), length=n_experts).astype(jnp.int32))
    token, _, sizes = moe.topk_order(route, 0, held)
    return token[:n_rows], jnp.minimum(jnp.sum(sizes), n_rows), route


def rows(args) -> dict:
    t, k, n_experts, held, n_rows, d, lives = (
        (64, 4, 16, 4, 128, 128, (16, 64, 100)) if args.rehearse else
        (args.tokens, args.top_k, args.router_outputs, args.experts,
         args.rows, 2048, tuple(args.live or (2048, 4096, 8192))))
    calls = 2 if args.rehearse else 50
    forms = {name: form for name, form in {
        **row_sums(args.rehearse, min(k, held)),
        **routed_forms(held)}.items()
        if not args.forms or name[0] in ["a"] + args.forms}
    out = {"device": jax.devices()[0].device_kind, "rows": n_rows,
           "tokens": t, "d": d, "ms": {}}
    for n_live in lives:
        token, live, route = routed_tokens(t, k, n_experts, held, n_live,
                                           n_rows, seed=n_live)
        cut = (jnp.arange(n_rows) < live)[:, None]
        for dtype in (jnp.float32, jnp.bfloat16):
            x = jnp.where(cut, jax.random.normal(
                jax.random.PRNGKey(n_live), (n_rows, d), dtype), 0)
            want = None
            for name, form in forms.items():
                if "combine" in name and dtype != jnp.float32:
                    continue
                fn = jax.jit(functools.partial(form, n_tokens=t))
                # (the shipped form finds its places from the route, inside
                # the timed call)
                xs = (x, token, live) + ((route,) if name[0] == "h" else ())
                try:
                    got = np.asarray(fn(*xs), np.float32)
                    want = got if want is None else want
                    rec = {"ms": ms_per_call(fn, xs, calls),
                           "against_first": float(
                               np.abs(got - want).max() / np.abs(want).max())}
                except Exception as e:  # a form the compiler refuses, kept
                    rec = {"failed": str(e).replace("\n", " ")[:300]}
                key = "%s live=%d %s" % (name, int(live),
                                         jnp.dtype(dtype).name)
                out["ms"][key] = rec
                print(key, rec, flush=True)
            # the transpose: the rows of [t, d] by token
            g = jax.random.normal(jax.random.PRNGKey(1), (t, d), dtype)
            gather = jax.jit(lambda g, token: g[token])
            key = "gather live=%d %s" % (int(live), jnp.dtype(dtype).name)
            out["ms"][key] = {"ms": ms_per_call(gather, (g, token), calls)}
            print(key, out["ms"][key], flush=True)
        if not args.forms or "h" in args.forms:
            key = "place live=%d" % int(live)
            out["ms"][key] = {"ms": ms_per_call(
                jax.jit(functools.partial(places_alone, held, n_tokens=t)),
                (x, token, live, route), calls)}
            print(key, out["ms"][key], flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--mode", choices=("products", "rows"),
                    default="products")
    ap.add_argument("--forms", nargs="*",
                    help="rows: these forms alone, by their first letter "
                         "(a is always timed: the others are held to it)")
    ap.add_argument("--rows", type=int, default=5120)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--width", type=int, default=1024,
                    help="products: the experts' width")
    ap.add_argument("--hidden", type=int, default=2048,
                    help="products: the rows' width")
    ap.add_argument("--form", choices=("swiglu", "relu2"), default="swiglu",
                    help="products: the SwiGLU's three or relu2's two")
    ap.add_argument("--tile-where-none-divides", type=int, nargs="*",
                    help="products: see the module's first words")
    ap.add_argument("--live", type=int, nargs="*",
                    help="rows of the buffer that hold an assignment")
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--router-outputs", type=int, default=128)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("grouped_matmul_sweep: no TPU here; times come from the "
                 "chip only")
    out = {"products": products, "rows": rows}[args.mode](args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
