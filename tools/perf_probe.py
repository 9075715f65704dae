"""Quick perf probe: raw-jit ResNet-50 train step MFU at various batch sizes.

Not part of the benchmark surface — a scratch tool for profile-driven tuning
(VERDICT r2 item 1). Run: python tools/perf_probe.py 128 256 512
"""
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, ".")
from horovod_tpu.models.resnet import ResNet50  # noqa: E402

PEAK = 197.0  # v5e bf16
FLOPS_IMG = 3 * 4.1e9


def fetch(x):
    return float(np.asarray(x).reshape(-1)[0])


def probe(batch, iters=10):
    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    images = jnp.asarray(np.random.RandomState(0).rand(batch, 224, 224, 3),
                         jnp.float32)
    labels = jnp.asarray(
        np.random.RandomState(1).randint(0, 1000, size=(batch,)), jnp.int32)
    variables = model.init(rng, images[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.01, momentum=0.9)

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images, train=True,
            mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
        return loss, mutated["batch_stats"]

    @jax.jit
    def step(params, batch_stats, opt_state, images, labels):
        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats, images, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_bs, opt_state, loss

    state = (params, batch_stats, opt.init(params))
    out = step(*state, images, labels)
    fetch(out[-1])
    out = step(*out[:-1], images, labels)
    fetch(out[-1])
    state = out[:-1]
    # cost analysis
    try:
        ca = step.lower(*state, images, labels).compile().cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        xla_flops = float(ca.get("flops", 0.0))
    except Exception:
        xla_flops = 0.0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*state, images, labels)
        state = out[:-1]
    fetch(out[-1])
    dt = (time.perf_counter() - t0) / iters
    tflops = (xla_flops or FLOPS_IMG * batch) / dt / 1e12
    print(f"batch={batch:4d} step={dt*1e3:8.2f}ms img/s={batch/dt:9.1f} "
          f"xla_flops={xla_flops/1e12:.3f}T tflops={tflops:7.2f} "
          f"mfu={100*tflops/PEAK:5.1f}%", flush=True)


if __name__ == "__main__":
    from horovod_tpu.common.env import use_compile_cache
    use_compile_cache()
    platforms = sorted({d.platform for d in jax.devices()})
    if platforms != ["tpu"]:
        raise SystemExit(f"perf_probe.py measures the TPU step; visible "
                         f"platforms are {platforms}")
    for b in [int(a) for a in sys.argv[1:]] or [128, 256]:
        probe(b)
