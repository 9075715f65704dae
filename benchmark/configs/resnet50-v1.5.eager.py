"""ResNet-50 through the Horovod-parity eager product, the loop of
``examples/resnet50_synthetic_benchmark.py --mode eager`` (upstream's
synthetic benchmark): ``hvd.init``, ``broadcast_parameters``, a jitted
``value_and_grad``, ``hvd.DistributedOptimizer(op=Average)
.update_and_apply``, in a world of one process, where the optimizer
issues no collective.

Unlike the example, the batch is an argument of the jitted gradient
function, taken in turn from a pool of seeded batches on the device, and
not a constant closed over (154 MB baked into the program at 256 images).
"""

from __future__ import annotations

import time

import jax
import optax
from jax.profiler import TraceAnnotation

import horovod_tpu as hvd

from job import Job


def build(model, spec, traffic, env) -> Job:
    if env.chips != 1:
        raise ValueError("this loop drives a world of one process, on one "
                         f"chip; the cell asks for {env.chips}")
    hvd.init()
    s = model.sizes(spec, env.rehearse)
    t = model.sizes(traffic, env.rehearse)
    net = model.make_model(s)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b, x, y: model.loss_fn(net, p, b, x, y), has_aux=True))
    opt = hvd.DistributedOptimizer(
        optax.sgd(model.LR, momentum=model.MOMENTUM), op=hvd.Average)
    images, labels = model.make_pool(s, t["rows_per_chip"],
                                     t["pool_batches"], env.seed * 1000)
    pool = [(images[k], labels[k]) for k in range(t["pool_batches"])]
    del images, labels

    def init():
        variables = model.make_variables(net, s, env.seed)
        params = hvd.broadcast_parameters(variables["params"], root_rank=0)
        return params, variables["batch_stats"], jax.jit(opt.init)(params)

    def step(state, batch):
        params, batch_stats, opt_state = state
        with TraceAnnotation("bench.grad"):
            (loss, batch_stats), grads = grad_fn(params, batch_stats, *batch)
        with TraceAnnotation("bench.update_apply"):
            params, opt_state = opt.update_and_apply(grads, opt_state,
                                                     params)
        return (params, batch_stats, opt_state), loss

    def probe(state, batch):
        """One step with the gradients awaited first, so that the time
        from calling ``update_and_apply`` to its outputs being ready is the
        optimizer's and the engine's alone."""
        params, batch_stats, opt_state = state
        (_, batch_stats), grads = grad_fn(params, batch_stats, *batch)
        jax.block_until_ready(grads)
        t0 = time.perf_counter()
        with TraceAnnotation("bench.probe.update_apply"):
            params, opt_state = jax.block_until_ready(
                opt.update_and_apply(grads, opt_state, params))
        return ((params, batch_stats, opt_state),
                {"update_apply_ms": (time.perf_counter() - t0) * 1e3})

    def reference_checks(state):
        return model.reference_checks(
            s, {"params": state[0], "batch_stats": state[1]},
            env.reference, env.seed)

    return Job(samples_per_step=t["rows_per_chip"],
               flops_per_sample=model.flops_per_sample(s),
               init=init, batch=lambda i: pool[i % len(pool)], step=step,
               reference_checks=reference_checks, probe=probe,
               close=hvd.shutdown)
