"""Scaling-efficiency harness (SURVEY §7 Slice 7: allreduce scaling
efficiency over sub-meshes; the reference's docs/benchmarks.rst:7-13
published 90% at 512 GPUs). What this repo measures on the chip is
``python3 benchmark/run.py --workload <cell>`` (PERF.md); a number this
script prints on the forced-host CPU world is a harness check, not a
rate.

For each world size n (sub-meshes of the available devices — real chips on a
pod, or the forced-host CPU world for harness validation):

- **allreduce bus bandwidth**: fused ring-allreduce of a fixed per-chip
  buffer; algorithmic bandwidth = 2·(n−1)/n · bytes / time.
- **weak-scaling efficiency**: a data-parallel train step at fixed per-chip
  batch; efficiency(n) = throughput(n) / (n · throughput(1)).

Prints one JSON line per (size, measurement).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/scaling_benchmark.py --sizes 1,2,4,8
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _fetch(x):
    """Completion barrier: pull ONE element to the host (materializing the
    whole buffer would add a size-dependent D2H transfer to the timed
    window)."""
    return float(np.asarray(x.ravel()[0:1])[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default=None,
                    help="comma list of world sizes (default: 1,2,4,...,N)")
    ap.add_argument("--bytes", type=int, default=64 * 1024 * 1024,
                    help="allreduce buffer size per chip")
    ap.add_argument("--batch-per-chip", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jax import shard_map

    from horovod_tpu import optimizer as hvd_opt
    from horovod_tpu.common.reduce_ops import Average, ReduceOp
    from horovod_tpu.models.mlp import (init_mlp, mlp_forward,
                                        softmax_cross_entropy)
    from horovod_tpu.ops.collectives import build_allreduce

    devices = jax.devices()
    n_dev = len(devices)
    if args.sizes:
        requested = [int(s) for s in args.sizes.split(",")]
        sizes = [s for s in requested if s <= n_dev]
        dropped = [s for s in requested if s > n_dev]
        if dropped:
            print(f"warning: dropping sizes {dropped} (> {n_dev} devices)",
                  file=__import__("sys").stderr)
        if not sizes:
            raise SystemExit(
                f"no requested world size fits the {n_dev} visible devices")
        sizes = sorted(set(sizes))   # efficiency baseline must run first
    else:
        sizes = [s for s in (2 ** i for i in range(n_dev.bit_length()))
                 if s <= n_dev]

    n_elems = args.bytes // 4
    base_throughput = None
    for n in sizes:
        mesh = Mesh(np.array(devices[:n]), ("data",))

        # -- allreduce bandwidth (through the framework's builder, so the
        # metric certifies the framework path, not raw XLA) ---------------
        buf = jax.device_put(
            jnp.ones((n, n_elems), jnp.float32),
            NamedSharding(mesh, P("data")))
        ar = build_allreduce(mesh, "data", ReduceOp.SUM)
        out = ar(buf)
        _fetch(out)
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = ar(buf)
        _fetch(out)
        dt = (time.perf_counter() - t0) / args.iters
        busbw = 2 * (n - 1) / n * args.bytes / dt if n > 1 else 0.0
        print(json.dumps({
            "bench": "allreduce", "world": n,
            "bytes_per_chip": args.bytes,
            "time_ms": round(dt * 1e3, 3),
            "algo_busbw_gbps": round(busbw / 1e9, 3),
        }))

        # -- weak-scaling train step ------------------------------------
        batch = args.batch_per_chip * n
        rng = np.random.RandomState(0)
        x = jax.device_put(jnp.asarray(rng.rand(batch, 784), jnp.float32),
                           NamedSharding(mesh, P("data")))
        y = jax.device_put(jnp.asarray(rng.randint(0, 10, size=(batch,)),
                                       jnp.int32),
                           NamedSharding(mesh, P("data")))
        params = init_mlp(jax.random.PRNGKey(0))
        opt = hvd_opt.distributed(optax.sgd(0.01), axis_name="data",
                                  op=Average, axis_size=n)

        def body(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(
                lambda p: softmax_cross_entropy(mlp_forward(p, x), y))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, \
                jax.lax.pmean(loss, "data")

        step = jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P(), P(), P("data"), P("data")),
                                 out_specs=(P(), P(), P())))
        state = (params, opt.init(params))
        for _ in range(2):
            out = step(*state, x, y)
            state = out[:-1]
            _fetch(out[-1])
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = step(*state, x, y)
            state = out[:-1]
        _fetch(out[-1])
        dt = (time.perf_counter() - t0) / args.iters
        throughput = batch / dt
        if n == min(sizes):
            base_throughput = throughput / n
        # efficiency is relative to the SMALLEST measured size (==1 when
        # present, matching the docstring formula)
        eff = throughput / (n * base_throughput) if base_throughput else None
        print(json.dumps({
            "bench": "weak_scaling_train", "world": n,
            "batch_per_chip": args.batch_per_chip,
            "samples_per_sec": round(throughput, 1),
            "scaling_efficiency": round(eff, 4) if eff else None,
        }))


if __name__ == "__main__":
    main()
