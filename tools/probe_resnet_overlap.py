"""Overlap experiment for the ResNet-50 roofline (VERDICT r3 item 7).

docs/roofline.md establishes the step is HBM-bound: measured ~98 ms vs a
62 ms perfect-DMA/MXU-overlap floor. This probe measures the single-chip
train step under candidate XLA scheduler knobs (latency-hiding scheduler,
larger scoped VMEM for deeper fusion) to see whether scheduler-level levers
recover any of the overlap gap. Run once per flag set:

    python tools/probe_resnet_overlap.py                # baseline
    XLA_FLAGS="--xla_tpu_enable_latency_hiding_scheduler=true" \
        python tools/probe_resnet_overlap.py
    XLA_FLAGS="--xla_tpu_scoped_vmem_limit_kib=65536" \
        python tools/probe_resnet_overlap.py

Prints one line: flags + median step ms (dependent-steps timing, the cost
of the closing host fetch subtracted) so runs can be compared across the
run-to-run noise band (repeat >= 2x per flag set). Runs on a TPU only.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax

    from bench import _time_steps
    from horovod_tpu.common.env import use_compile_cache
    from horovod_tpu.models.resnet import ResNet50

    use_compile_cache()
    platforms = sorted({d.platform for d in jax.devices()})
    if platforms != ["tpu"]:
        raise SystemExit(f"probe_resnet_overlap.py measures the TPU step; "
                         f"visible platforms are {platforms}")

    batch = int(os.environ.get("BENCH_BATCH", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(np.random.RandomState(0).rand(batch, 224, 224, 3),
                         jnp.float32)
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, 1000, size=(batch,)), jnp.int32)
    variables = model.init(rng, images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        return loss, mutated["batch_stats"]

    opt = optax.sgd(0.01, momentum=0.9)

    def step_fn(params, batch_stats, opt_state, images, labels):
        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_bs, opt_state, loss

    # per-compile compiler options reach the TPU compiler whenever they
    # are given, XLA_FLAGS only before the first backend touch:
    # PROBE_COMPILER_OPTIONS="xla_tpu_enable_latency_hiding_scheduler=true"
    opts_env = os.environ.get("PROBE_COMPILER_OPTIONS", "")
    copts = dict(kv.split("=", 1) for kv in opts_env.split(",") if "=" in kv)
    state = (params, batch_stats, opt.init(params))
    lowered = jax.jit(step_fn).lower(*state, images, labels)
    step = (lowered.compile(compiler_options=copts) if copts
            else lowered.compile())
    dt, rtt, _spread = _time_steps(step, state, (images, labels),
                                   iters)
    print(f"opts={opts_env!r} "
          f"step_ms={dt * 1e3:.2f} rtt_ms={rtt * 1e3:.1f} "
          f"img_s={batch / dt:.1f}", flush=True)


if __name__ == "__main__":
    main()
