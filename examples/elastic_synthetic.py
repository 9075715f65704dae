"""Elastic training example — analog of the reference's
examples/elastic/pytorch_synthetic_benchmark_elastic.py.

Run with a discovery script whose output can change while the job runs:

    tpurun -np 2 --min-np 1 --max-np 4 \
        --host-discovery-script ./discover_hosts.sh \
        python examples/elastic_synthetic.py

State (model params, optimizer state, batch counter) is committed every
``--batches-per-commit`` batches; on membership change or worker failure the
job restores the last commit and continues at the new world size.
"""

from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models.mlp import init_mlp, mlp_forward, softmax_cross_entropy


def main():
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--total-batches", type=int, default=500)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--batches-per-commit", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    hvd.init()

    opt = optax.adam(args.lr)
    dist_opt = hvd.DistributedOptimizer(opt, op=hvd.Average)
    params = init_mlp(jax.random.PRNGKey(0), sizes=(256, 128, 10))
    opt_state = dist_opt.init(params)

    # TPUState keeps host-RAM copies of the pytrees on commit() and
    # broadcast-syncs them to new/restored workers (reference:
    # hvd.elastic.TorchState).
    state = hvd.elastic.TPUState(params=params, opt_state=opt_state, batch=0)

    @jax.jit
    def grad_fn(params, x, y):
        return jax.value_and_grad(
            lambda p: softmax_cross_entropy(mlp_forward(p, x), y))(params)

    @hvd.elastic.run
    def train(state):
        rng = np.random.RandomState(100 + hvd.rank())
        while state.batch < args.total_batches:
            x = jnp.asarray(rng.rand(args.batch_size, 256), jnp.float32)
            y = jnp.asarray(rng.randint(0, 10, size=(args.batch_size,)),
                            jnp.int32)
            loss, grads = grad_fn(state.params, x, y)
            state.params, state.opt_state = dist_opt.update_and_apply(
                grads, state.opt_state, state.params)
            state.batch += 1
            if state.batch % args.batches_per_commit == 0:
                state.commit()
                if hvd.rank() == 0 and state.batch % 100 == 0:
                    print(f"batch {state.batch}: loss={float(loss):.4f} "
                          f"size={hvd.size()}")
        return float(loss)

    final_loss = train(state)
    if final_loss is not None and hvd.rank() == 0:
        print(f"done: final loss {final_loss:.4f} at size {hvd.size()}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
