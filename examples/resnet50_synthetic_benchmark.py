"""ResNet-50 synthetic benchmark — the user-facing analog of the reference's
examples/tensorflow2_synthetic_benchmark.py (docs/benchmarks.rst:68-75).

SPMD flavor (default, TPU-idiomatic): one process drives every local chip
through a shard_map'd train step whose gradient reduction is the framework's
distributed optax wrapper.

    python examples/resnet50_synthetic_benchmark.py --batch-size 128

Eager flavor (one process per chip, Horovod-style):

    tpurun -np 4 python examples/resnet50_synthetic_benchmark.py --mode eager
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from jax import shard_map
from horovod_tpu import optimizer as hvd_opt
from horovod_tpu.models.resnet import ResNet50


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("spmd", "eager"), default="spmd")
    ap.add_argument("--batch-size", type=int, default=64,
                    help="per-chip batch size")
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-warmup", type=int, default=2)
    ap.add_argument("--fp16-allreduce", action="store_true",
                    help="compress eager-mode gradients to bf16 "
                         "(reference --fp16-allreduce)")
    return ap.parse_args()


def make_model_and_data(batch):
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(batch, 224, 224, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, size=(batch,)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:2], train=True)
    return model, variables, images, labels


def loss_fn(model, params, batch_stats, images, labels):
    logits, mutated = model.apply(
        {"params": params, "batch_stats": batch_stats}, images, train=True,
        mutable=["batch_stats"])
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
    return loss, mutated["batch_stats"]


def run_spmd(args):
    n_chips = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    batch = args.batch_size * n_chips
    model, variables, images, labels = make_model_and_data(batch)
    params, batch_stats = variables["params"], variables["batch_stats"]
    images = jax.device_put(images, NamedSharding(mesh, P("data")))
    labels = jax.device_put(labels, NamedSharding(mesh, P("data")))

    opt = hvd_opt.distributed(optax.sgd(0.01, momentum=0.9),
                              axis_name="data", op=hvd.Average,
                              axis_size=n_chips)

    def body(params, batch_stats, opt_state, images, labels):
        (loss, new_bs), grads = jax.value_and_grad(
            lambda p, b: loss_fn(model, p, b, images, labels),
            has_aux=True)(params, batch_stats)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        new_bs = jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, "data"), new_bs)
        return params, new_bs, opt_state, jax.lax.pmean(loss, "data")

    step = jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(P(), P(), P(), P("data"), P("data")),
                             out_specs=(P(), P(), P(), P())))
    state = (params, batch_stats, opt.init(params))
    for _ in range(max(args.num_warmup, 2)):
        out = step(*state, images, labels)
        state = out[:-1]
        float(np.asarray(out[-1]))
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        out = step(*state, images, labels)
        state = out[:-1]
    float(np.asarray(out[-1]))
    dt = time.perf_counter() - t0
    img_s = batch * args.num_iters / dt
    print(f"Total img/sec on {n_chips} chip(s): {img_s:.1f} "
          f"({img_s / n_chips:.1f}/chip)")


def run_eager(args):
    hvd.init()
    model, variables, images, labels = make_model_and_data(args.batch_size)
    params, batch_stats = variables["params"], variables["batch_stats"]
    compression = (hvd.Compression.fp16 if args.fp16_allreduce
                   else hvd.Compression.none)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   op=hvd.Average, compression=compression)
    opt_state = opt.init(params)

    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(model, p, b, images, labels), has_aux=True))

    def step(params, batch_stats, opt_state):
        (loss, new_bs), grads = grad_fn(params, batch_stats)
        params, opt_state = opt.update_and_apply(grads, opt_state, params)
        return params, new_bs, opt_state, loss

    state = (params, batch_stats, opt_state)
    for _ in range(max(args.num_warmup, 2)):
        out = step(*state)
        state = out[:-1]
        float(np.asarray(out[-1]))
    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        out = step(*state)
        state = out[:-1]
    float(np.asarray(out[-1]))
    dt = time.perf_counter() - t0
    img_s = args.batch_size * args.num_iters / dt
    if hvd.rank() == 0:
        print(f"Img/sec per worker: {img_s:.1f}; "
              f"total ({hvd.size()} workers): {img_s * hvd.size():.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    args = parse_args()
    if args.mode == "spmd":
        run_spmd(args)
    else:
        run_eager(args)
