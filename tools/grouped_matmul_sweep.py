#!/usr/bin/env python3
"""Which grouped matrix product the routed experts run (ISSUE 32): the three
products of ``parallel/moe.py grouped_swiglu`` over a dispatch buffer, timed
on the chip as ``lax.ragged_dot`` (libtpu's own ``ragged-dot`` kernel) and as
the installed megablox ``gmm`` at a few tilings. Nothing a cell runs imports
this file; what it led to is ``parallel/moe.py grouped_matmul``.

    chiprun --chips 1 -- python tools/grouped_matmul_sweep.py \\
        --out chiprun_out/grouped_matmul.json

The shape is the sparse-expert cell's: ``--rows`` buffer rows of 2048,
``--experts`` held experts of width 1024, group sizes drawn like an even
router's (multinomial), the rest of the buffer past the last group.
``--rehearse``: tiny sizes, interpreted on the CPU: a test of the script.
"""

from __future__ import annotations

import argparse
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


def swiglu(matmul):
    def ffn(rows, sizes, wg, wu, wd):
        u = jax.nn.silu(matmul(rows, wg, sizes)) * matmul(rows, wu, sizes)
        return matmul(u, wd, sizes)
    return ffn


def candidates(interpret: bool) -> dict:
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
    found = {"ragged_dot": lambda x, w, s: lax.ragged_dot(x, w, s)}
    for tiling in TILINGS:
        found["gmm:%d/%d/%d" % tiling] = (
            lambda x, w, s, tiling=tiling: megablox.gmm(
                x, w, s, x.dtype, tiling, interpret=interpret))
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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--rows", type=int, default=5120)
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("grouped_matmul_sweep: no TPU here; times come from the "
                 "chip only")
    rows, d, f = (256, 128, 128) if args.rehearse else (args.rows, 2048, 1024)
    rng = np.random.RandomState(0)
    sizes = rng.multinomial(rows * 4 // 5, [1 / args.experts] * args.experts)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    xs = [jax.random.normal(keys[0], (rows, d), jnp.bfloat16),
          jnp.asarray(sizes, jnp.int32)] + [
        jax.random.normal(k, (args.experts,) + s, jnp.bfloat16) * 0.02
        for k, s in zip(keys[1:], ((d, f), (d, f), (f, d)))]
    out = {"device": jax.devices()[0].device_kind, "rows": rows,
           "group_sizes": sizes.tolist(), "ms": {}}
    want = None
    for name, matmul in candidates(args.rehearse).items():
        ffn = swiglu(matmul)
        fwd = jax.jit(ffn)
        step = jax.jit(jax.grad(
            lambda *a: jnp.sum(ffn(*a).astype(jnp.float32) ** 2),
            (0, 2, 3, 4)))
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
        out["ms"][name] = rec
        print(name, rec, flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)


if __name__ == "__main__":
    main()
