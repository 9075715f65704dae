"""Embedding-table training with sparse gradient reduction.

Analog of the reference's IndexedSlices / sparse-gradient handling inside
the optimizer (tensorflow/__init__.py:52-131, torch sparse grads): an
embedding model's gradient is dense under JAX but touches only the rows of
the tokens in the batch. Marking the leaf with ``sparse_rows`` ships the
top-k touched rows as (indices, values) allgathers — wire bytes scale with
tokens-per-batch instead of vocabulary size — and recombines them with a
jitted on-device scatter-add.

Run single-process:   python examples/sparse_embedding.py
Run multi-process:    tpurun -np 2 python examples/sparse_embedding.py
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.optimizer import DistributedEagerOptimizer


def main():
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=50_000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=50)
    args = ap.parse_args()

    hvd.init()
    rank, size = hvd.rank(), hvd.size()

    params = {
        "embed": jnp.asarray(
            np.random.RandomState(0).randn(args.vocab, args.dim) * 0.02,
            jnp.float32),
        "proj": jnp.asarray(np.eye(args.dim), jnp.float32),
    }
    params = hvd.broadcast_parameters(params, root_rank=0)
    # The "embed" grad leaf touches at most batch_size rows per step; the
    # dense table never crosses the wire. Everything else reduces densely.
    opt = DistributedEagerOptimizer(
        optax.adagrad(0.1), op=hvd.Average,
        sparse_rows={"embed": args.batch_size})
    opt_state = opt.init(params)

    def loss_fn(p, tok, tgt):
        h = p["embed"][tok] @ p["proj"]
        return jnp.mean((h - tgt) ** 2)

    grad_fn = jax.jit(jax.grad(loss_fn))
    rng = np.random.RandomState(100 + rank)
    t0 = time.perf_counter()
    for step in range(args.steps):
        tok = jnp.asarray(rng.randint(0, args.vocab, args.batch_size))
        tgt = jnp.asarray(rng.randn(args.batch_size, args.dim)
                          .astype(np.float32))
        grads = grad_fn(params, tok, tgt)
        # chained: the jitted update rides the reduced-rows futures with
        # no host block; the top-k extraction + scatter-add are jitted
        params, opt_state = opt.update_and_apply(grads, opt_state, params)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0
    dense_bytes = args.vocab * args.dim * 4
    sparse_bytes = args.batch_size * (args.dim + 1) * 4
    if rank == 0:
        print(f"size={size} steps={args.steps} "
              f"({dt / args.steps * 1e3:.2f} ms/step); per-step embed wire: "
              f"{sparse_bytes / 1e3:.0f} KB sparse vs "
              f"{dense_bytes / 1e6:.1f} MB dense "
              f"({dense_bytes / sparse_bytes:.0f}x saved)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
