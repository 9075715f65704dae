"""MNIST-style MLP training with the eager (Horovod-style) API.

Analog of the reference's examples/tensorflow2_mnist.py: init the runtime,
broadcast initial parameters from rank 0, wrap the optimizer so gradients are
averaged across workers, scale the learning rate by world size.

Run single-process:   python examples/mnist_mlp.py
Run multi-process:    tpurun -np 2 python examples/mnist_mlp.py

Uses synthetic MNIST-shaped data so the example runs hermetically (no
download); swap `synthetic_mnist` for a real loader in production.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models.mlp import init_mlp, mlp_forward, softmax_cross_entropy


def synthetic_mnist(rank: int, n: int = 4096):
    rng = np.random.RandomState(1234 + rank)  # each rank gets its own shard
    x = rng.rand(n, 784).astype(np.float32)
    y = rng.randint(0, 10, size=(n,)).astype(np.int32)
    return x, y


def main():
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    hvd.init()

    # Scale the learning rate by world size (reference examples do the same).
    opt = optax.adam(args.lr * hvd.size())
    # DistributedOptimizer: gradients are fused + averaged across workers
    # between grad() and the optax update.
    dist_opt = hvd.DistributedOptimizer(opt, op=hvd.Average)

    params = init_mlp(jax.random.PRNGKey(42))
    # All workers start from rank 0's weights (reference:
    # broadcast_parameters / BroadcastGlobalVariablesCallback).
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = dist_opt.init(params)

    @jax.jit
    def grad_fn(params, x, y):
        def loss(p):
            return softmax_cross_entropy(mlp_forward(p, x), y)
        return jax.value_and_grad(loss)(params)

    x, y = synthetic_mnist(hvd.rank())
    steps_per_epoch = len(x) // args.batch_size
    for epoch in range(args.epochs):
        t0 = time.perf_counter()
        last_loss = None
        for step in range(steps_per_epoch):
            lo = step * args.batch_size
            bx, by = x[lo:lo + args.batch_size], y[lo:lo + args.batch_size]
            loss, grads = grad_fn(params, bx, by)
            params, opt_state = dist_opt.update_and_apply(grads, opt_state,
                                                          params)
            last_loss = loss
        dt = time.perf_counter() - t0
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(last_loss):.4f} "
                  f"({steps_per_epoch / dt:.1f} steps/s, size={hvd.size()})")

    hvd.shutdown()


if __name__ == "__main__":
    main()
