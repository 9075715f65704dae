"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Drives the two trainers the README opens with, through the public entry
points only (the code of ``examples/transformer_lm.py --mode spmd|eager``),
at the full width of the flagship LM (d2048 x L4 x ff8192, V32768, T2048,
bf16, flash attention, 4 rows per chip), with random weights from a seed:

- **spmd**: ``make_train_step`` over ``training_mesh(data=n)``, a few adamw
  steps on one fixed batch, then one ``remat="block"`` step at 8 rows per
  chip (the flash kernel splash degrades to). On four chips also
  ``data=2,seq=2`` (ring attention over real hops) and a one-device
  reference loss on the same global batches.
- **eager**: ``hvd.init()``, broadcast, ``hvd.DistributedOptimizer`` — in
  this process on one chip, under ``python -m horovod_tpu.runner.launch
  -np n`` (one process per chip) on several — plus the hand-rolled
  ``with hvd.step(): hvd.grouped_allreduce_async(...)`` loop, which runs the
  engine, step replay and HBM telemetry even in a size-1 world. Its losses
  must follow the SPMD losses from the same seed.
- **quickstart** (four chips): ``jax.grad`` + ``hvd_opt.distributed`` inside
  one ``shard_map(check_vma=False)`` gives ``make_train_step``'s update.
- **kernels**: every Pallas kernel of the repo against its ``lax`` twin.

The parent process never initialises a jax backend: a chip belongs to one
process, so each phase is a child, one after another. The first thing every
child does is the device gate: any visible device that is not a TPU ends
the run with a non-zero exit code and no result. ``--rehearse`` runs the
same functions at toy widths on forced CPU devices, stamps its output as a
rehearsal, and is what the tier-1 test calls; it proves the control flow,
never the chip.

Last line of stdout on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

FULL = dict(vocab_size=32768, d_model=2048, n_heads=16, n_layers=4,
            d_ff=8192, max_seq=2048)
TOY = dict(vocab_size=256, d_model=32, n_heads=2, n_layers=1, d_ff=64,
           max_seq=128)
ROWS_PER_CHIP = 4
SEED = 0           # weights and batch; the bands below were measured at it
LR = 3e-4
SPMD_STEPS = 7     # covers the longest eager series compared against it
EAGER_STEPS = 3    # README form: hvd.DistributedOptimizer
ENGINE_STEPS = 7   # hand-rolled hvd.step() loop: 3 warm-up steps arm replay

# Bands, relative, each set from what PR 21's chip runs measured (v5e,
# one chip and four; CHANGES.md, PR 21) with about a factor of ten to spare.
# Eager loss against SPMD loss from the same seed, worst step: measured
# 3.5e-4 (hvd.step loop, one chip), 1.1e-5 (DistributedOptimizer, one chip),
# 2.3e-5 (launch -np 4 against data=4). The eager loss is the bf16 lean
# loss, the SPMD loss an fp32 log-softmax.
BAND_EAGER_VS_SPMD = 5e-3
# First loss of a mesh against one device on the same global batch:
# measured 6.2e-7 (data=4) and 2.7e-7 (data=2,seq=2: ring against splash).
BAND_MESH_VS_ONE_DEVICE = 1e-4
# Quickstart first update against make_train_step's, relative L2: measured
# 3.4e-3 on four chips. A gradient left unreduced gives 1.6, one summed
# instead of averaged 3.0 (forced-CPU count at toy widths, PR 21).
BAND_QUICKSTART_UPDATE = 3e-2

DEADLINE_S = 1150      # the contract allows 1200 s, compilation included
PHASE_S = 600          # no phase may hold the chips longer (cold: <= 330 s)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# children: everything below here that touches jax runs in a child process
# ---------------------------------------------------------------------------

class CacheCounter:
    """Counts jax's persistent-compilation-cache requests and hits."""

    def __init__(self):
        import jax.monitoring
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self) -> dict:
        return {"requests": self.requests, "hits": self.hits}


def child_prologue(args, before_gate=None):
    """Compile cache, then the device gate. Returns (devices, cache).
    ``before_gate`` runs between the two: ``hvd.init()`` must join the
    launcher's world before anything asks jax for its devices."""
    from horovod_tpu.common.env import use_compile_cache
    # a rehearsal leaves no CPU programs in the chip's cache
    cache_dir = "none (rehearsal)" if args.rehearse else use_compile_cache()
    cache = CacheCounter()
    if before_gate is not None:
        before_gate()
    import jax
    devs = jax.devices()
    platforms = sorted({d.platform for d in devs})
    if not args.rehearse and platforms != ["tpu"]:
        log(f"device gate: visible platforms {platforms}, need only 'tpu'")
        sys.exit(1)
    log(f"child {args.child}: {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}), compile cache at {cache_dir}")
    return devs, cache


def write_result(args, name: str, result: dict) -> None:
    path = os.path.join(args.out, f"{name}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(path + ".tmp", path)


def versions() -> dict:
    import importlib.metadata as md
    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def make_cfg(args, **kw):
    import jax.numpy as jnp
    from horovod_tpu.models.transformer import TransformerConfig
    widths = dict(TOY if args.rehearse else FULL)
    widths.update(kw)
    return TransformerConfig(dtype=jnp.bfloat16, **widths)


def make_batch(args, cfg, rows: int):
    """(inputs, targets), each [rows, max_seq], from the seed. A batch of
    fewer rows is a prefix of a batch of more."""
    import numpy as np
    tok = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, size=(rows, cfg.max_seq + 1))
    return tok[:, :-1], tok[:, 1:]


def need_custom_calls(args, what: str, text: str) -> int:
    """The Pallas kernels must be IN the program: count Mosaic custom calls
    in lowered text. CPU rehearsals lower no kernel and skip the check."""
    n = text.count("tpu_custom_call")
    if args.rehearse:
        log(f"{what}: rehearsal, Pallas custom-call check not applicable")
    elif n == 0:
        raise AssertionError(f"{what}: no tpu_custom_call in the lowered "
                             f"program — the Pallas kernel was not taken")
    else:
        log(f"{what}: {n} tpu_custom_call sites in the lowered program")
    return n


def check_losses(what: str, losses) -> None:
    import math
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: non-finite loss in {losses}")
    if len(losses) > 1 and not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")
    log(f"{what}: losses {[round(x, 4) for x in losses]}")


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def spmd_run(args, cfg, mesh_axes: dict, rows: int, steps: int,
             what: str) -> dict:
    """The code of examples/transformer_lm.py --mode spmd."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models.transformer import (init_params, make_train_step,
                                                shard_params)
    from horovod_tpu.parallel.mesh import training_mesh

    mesh = training_mesh(mesh_axes)
    opt = optax.adamw(LR)
    params = shard_params(init_params(jax.random.PRNGKey(SEED), cfg),
                          mesh, cfg)
    step = make_train_step(mesh, cfg, opt)
    opt_state = opt.init(params)
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    inputs, targets = (jax.device_put(jnp.asarray(a), tok_sh)
                       for a in make_batch(args, cfg, rows))
    text = step.lower(params, opt_state, inputs, targets).as_text()
    custom_calls = need_custom_calls(args, what, text)
    if mesh_axes.get("seq", 1) > 1 and "collective_permute" not in text:
        raise AssertionError(f"{what}: no collective_permute in the lowered "
                             f"program — the ring does not rotate")
    t0 = time.perf_counter()
    losses = []
    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, inputs, targets)
        losses.append(float(loss))      # the host read is the barrier
        if i == 0:
            setup_s = time.perf_counter() - t0
    check_losses(what, losses)
    n_dev = mesh.devices.size
    for leaf in jax.tree_util.tree_leaves(params):
        if len(leaf.sharding.device_set) != n_dev:
            raise AssertionError(
                f"{what}: a parameter lives on "
                f"{len(leaf.sharding.device_set)} of {n_dev} devices")
    in_use = []
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        if stats is None and args.rehearse:
            continue                    # CPU devices report none
        if not stats["bytes_in_use"] > 0:
            raise AssertionError(f"{what}: device {d} holds no bytes")
        in_use.append(stats["bytes_in_use"])
    return {"mesh": mesh_axes, "rows": rows, "losses": losses,
            "custom_calls": custom_calls, "bytes_in_use": in_use,
            "compile_and_first_step_s": round(setup_s, 1)}


def one_device_loss(args, cfg, rows: int) -> float:
    """Loss of the seed's parameters on the seed's batch, on ONE device:
    the one-chip reference for the same global batch. Taken eight rows at
    a time (a size one chip holds beside the parameters); the mean of
    equal chunks' means is the mean."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.models.transformer import (init_params, make_spmd_loss,
                                                shard_params)
    from horovod_tpu.parallel.mesh import training_mesh
    mesh = training_mesh({"data": 1, "seq": 1, "tensor": 1},
                         jax.devices()[:1])
    params = shard_params(init_params(jax.random.PRNGKey(SEED), cfg),
                          mesh, cfg)
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    inputs, targets = make_batch(args, cfg, rows)
    loss_fn = jax.jit(make_spmd_loss(mesh, cfg))
    chunk = min(rows, 8)
    if rows % chunk:
        raise ValueError(f"{rows} rows do not split into chunks of {chunk}")
    losses = [float(loss_fn(params, *(
        jax.device_put(jnp.asarray(a[lo:lo + chunk]), tok_sh)
        for a in (inputs, targets)))) for lo in range(0, rows, chunk)]
    return sum(losses) / len(losses)


def child_spmd(args) -> None:
    devs, cache = child_prologue(args)
    n = len(devs)
    write_result(args, "device", {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": n, "versions": versions()})
    result = {}
    cfg = make_cfg(args, attention="flash")
    rows = ROWS_PER_CHIP * n
    result["data"] = spmd_run(
        args, cfg, {"data": n, "seq": 1, "tensor": 1}, rows, SPMD_STEPS,
        f"spmd data={n} B{rows}")
    write_result(args, "spmd", result)      # what is done stays known
    result["remat"] = spmd_run(
        args, make_cfg(args, attention="flash", remat="block"),
        {"data": n, "seq": 1, "tensor": 1}, 2 * rows, 1,
        f"spmd data={n} B{2 * rows} remat=block")
    write_result(args, "spmd", result)
    if n == 4:
        half = rows // 2
        result["ring"] = spmd_run(
            args, make_cfg(args, attention="ring"),
            {"data": 2, "seq": 2, "tensor": 1}, half, 2,
            f"spmd data=2,seq=2 B{half}")
        write_result(args, "spmd", result)
        for key, what, r in (("data", "data=4", rows),
                             ("ring", "data=2,seq=2", half)):
            ref = one_device_loss(args, cfg, r)
            got = result[key]["losses"][0]
            log(f"{what} B{r}: first loss {got:.6f}, one device {ref:.6f}, "
                f"rel {rel(got, ref):.2e}")
            result[key]["one_device_loss"] = ref
            write_result(args, "spmd", result)
            if rel(got, ref) > BAND_MESH_VS_ONE_DEVICE:
                raise AssertionError(f"{what} first loss leaves the band")
    else:
        log(f"four-chip SPMD layouts (data=2,seq=2; one-device reference) "
            f"not run: need 4 devices, {n} visible")
    result["cache"] = cache.report()
    write_result(args, "spmd", result)


def child_eager(args) -> None:
    """The code of examples/transformer_lm.py --mode eager, alone (a size-1
    world) or as one of the launcher's workers."""
    import horovod_tpu as hvd
    slot = int(os.environ.get("HOROVOD_RANK", 0))       # the launcher's
    n_slots = int(os.environ.get("HOROVOD_SIZE", 1))
    if args.rehearse and n_slots > 1:
        # imitate the four-chip host, where libtpu numbers the processes by
        # their chip's place in the grid and not by the launcher's slots
        os.environ["HOROVOD_TPU_PROCESS_ID"] = str((slot + 1) % n_slots)
    devs, cache = child_prologue(args, before_gate=hvd.init)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from horovod_tpu.metrics import counter_total
    from horovod_tpu.models.transformer import init_params, lean_lm_loss

    rank, size = hvd.rank(), hvd.size()
    result = {"rank": rank, "size": size,
              "local_device_count": jax.local_device_count(),
              "device_count": jax.device_count(),
              "device_id": jax.local_devices()[0].id}
    if size > 1:
        # one identity: the launcher's slot is the rank, whatever order the
        # backend numbers its processes in (libtpu: the chip's grid place)
        result["process_index"] = jax.process_index()
        if rank != slot or (args.rehearse and jax.process_index() == slot):
            raise AssertionError(f"launcher's slot {slot}: hvd.rank() "
                                 f"{rank}, jax process "
                                 f"{jax.process_index()}")
        # one process per chip, one world (a rehearsal's CPU workers each
        # force their own four devices)
        if not args.rehearse and (jax.local_device_count() != 1
                                  or jax.device_count() != size):
            raise AssertionError(f"rank {rank}: sees "
                                 f"{jax.local_device_count()} local of "
                                 f"{jax.device_count()} devices in a world "
                                 f"of {size}")
        ranks, ids = np.asarray(hvd.allgather(jnp.asarray(
            [[rank, result["device_id"]]]))).T
        if ranks.tolist() != list(range(size)):
            raise AssertionError(f"the world is not ordered by rank: {ranks}")
        if len(set(ids.tolist())) != size:
            raise AssertionError(f"ranks share chips: device ids {ids}")
        total = float(hvd.allreduce(jnp.asarray(float(rank)),
                                    name="smoke.rank", op=hvd.Sum))
        if total != size * (size - 1) / 2:
            raise AssertionError(f"allreduce of the rank gave {total}")
        log(f"rank {rank}/{size}: jax process {result['process_index']}, "
            f"chip {result['device_id']}, chips of the world by rank "
            f"{ids.tolist()}, allreduce(rank) = {total}")

    cfg = make_cfg(args, attention="flash")
    inputs, targets = make_batch(args, cfg, ROWS_PER_CHIP * size)
    lo = rank * ROWS_PER_CHIP
    bx = jnp.asarray(inputs[lo:lo + ROWS_PER_CHIP])
    by = jnp.asarray(targets[lo:lo + ROWS_PER_CHIP])
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: lean_lm_loss(p, x, y, cfg)))

    def fresh_params():
        return hvd.broadcast_parameters(
            init_params(jax.random.PRNGKey(SEED), cfg), root_rank=0)

    params = fresh_params()
    result["custom_calls"] = need_custom_calls(
        args, f"eager grad_fn (rank {rank})",
        grad_fn.lower(params, bx, by).as_text())

    def step_done(what, i, t0, params):
        # wall time of each step, compilation included: set-up, not speed
        jax.block_until_ready(params)
        log(f"rank {rank}: {what} step {i}: "
            f"{time.perf_counter() - t0:.1f} s")

    def optimizer_loop(params, steps):
        """The README form: hvd.DistributedOptimizer."""
        opt = hvd.DistributedOptimizer(optax.adamw(LR), op=hvd.Average)
        opt_state = opt.init(params)
        losses = []
        for i in range(steps):
            t0 = time.perf_counter()
            loss, grads = grad_fn(params, bx, by)
            params, opt_state = opt.update_and_apply(grads, opt_state,
                                                     params)
            losses.append(loss)
            step_done("DistributedOptimizer", i, t0, params)
        return params, losses

    def engine_loop(params, steps):
        """The hand-rolled Horovod loop of hvd.step's docstring."""
        inner = optax.adamw(LR)

        @jax.jit
        def apply(grads, state, params):
            updates, state = inner.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        state = inner.init(params)
        losses = []
        for i in range(steps):
            t0 = time.perf_counter()
            loss, grads = grad_fn(params, bx, by)
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            with hvd.step():
                handles = hvd.grouped_allreduce_async(
                    leaves, name="smoke.grads", op=hvd.Average)
            grads = jax.tree_util.tree_unflatten(
                treedef, [hvd.synchronize(h) for h in handles])
            params, state = apply(grads, state, params)
            losses.append(loss)
            step_done("hvd.step loop", i, t0, params)
        return params, losses

    t0 = time.perf_counter()
    if size > 1:
        # the optimizer itself drives the engine; a second loop of another
        # shape in the same process would read as replay divergence
        params, local = optimizer_loop(params, ENGINE_STEPS)
        n_opt = ENGINE_STEPS
    else:
        # a size-1 world has nothing to reduce and the optimizer issues no
        # collective; the hand-rolled loop is what runs the engine, step
        # replay and the telemetry on one chip
        params, local = optimizer_loop(params, EAGER_STEPS)
        params = None       # free them before the next set is made
        params, engine_losses = engine_loop(fresh_params(), ENGINE_STEPS)
        local += engine_losses
        n_opt = EAGER_STEPS
    # every rank's local losses, gathered once: [size, steps]
    table = np.asarray(hvd.allgather(jnp.stack(local)[None]))
    result["train_s"] = round(time.perf_counter() - t0, 1)
    series = table.mean(axis=0).tolist()
    result["losses_optimizer"] = series[:n_opt]
    result["losses_engine"] = series[n_opt:]
    check_losses(f"eager DistributedOptimizer (rank {rank})",
                 result["losses_optimizer"])
    if result["losses_engine"]:
        check_losses(f"eager hvd.step loop (rank {rank})",
                     result["losses_engine"])
    # lockstep: the trained parameters are the same bits on every rank
    digest = jnp.stack([jnp.sum(jnp.abs(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(params)])
    digests = np.asarray(hvd.allgather(digest[None]))
    if not (digests == digests[0]).all():
        raise AssertionError(f"ranks out of lockstep: parameter digests "
                             f"differ across ranks:\n{digests}")

    # replay armed, nothing fell back, and the chip reports its memory
    deadline = time.monotonic() + 30
    while True:
        snap = hvd.metrics_snapshot()
        hbm = sum(v for labels, v in snap["gauges"].get(
            "hvd_tpu_hbm_bytes", {}).get("values", [])
            if labels.get("kind") == "in_use")
        if hbm > 0 or args.rehearse or time.monotonic() > deadline:
            break
        time.sleep(0.5)     # the emitter thread samples once a second
    result["replayed_steps"] = counter_total(
        snap, "hvd_tpu_replay_replayed_steps_total")
    result["replay_fallbacks"] = counter_total(
        snap, "hvd_tpu_replay_fallbacks_total")
    result["dispatches"] = counter_total(snap, "hvd_tpu_dispatches_total")
    result["hbm_in_use"] = hbm
    log(f"rank {rank}: replayed steps {result['replayed_steps']}, fallbacks "
        f"{result['replay_fallbacks']}, hvd_tpu_hbm_bytes{{in_use}} {hbm}")
    if not result["replayed_steps"] > 0:
        raise AssertionError("step replay never armed")
    if result["replay_fallbacks"] != 0:
        raise AssertionError("step replay fell back")
    if args.rehearse:
        log("rehearsal: CPU devices report no memory, HBM gauge not checked")
    elif not hbm > 0:
        raise AssertionError('hvd_tpu_hbm_bytes{kind="in_use"} stayed 0')
    result["cache"] = cache.report()
    hvd.shutdown()
    write_result(args, f"eager_rank{rank}", result)


def child_quickstart(args) -> None:
    """README 'Quickstart — SPMD': jax.grad and hvd_opt.distributed inside
    one shard_map whose loss holds the flash kernel (hence
    check_vma=False) must give make_train_step's first update."""
    devs, cache = child_prologue(args)
    import jax
    import jax.numpy as jnp
    import optax
    from jax import lax, shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    import horovod_tpu as hvd
    from horovod_tpu import optimizer as hvd_opt
    from horovod_tpu.models.transformer import (init_params, lean_lm_loss,
                                                make_train_step, shard_params)
    from horovod_tpu.parallel.mesh import training_mesh

    n = len(devs)
    cfg = make_cfg(args, attention="flash")
    rows = ROWS_PER_CHIP * n
    mesh = training_mesh({"data": n, "seq": 1, "tensor": 1})
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    inputs, targets = (jax.device_put(jnp.asarray(a), tok_sh)
                       for a in make_batch(args, cfg, rows))

    def start():
        return shard_params(init_params(jax.random.PRNGKey(SEED), cfg),
                            mesh, cfg)

    # sgd(1.0): the update IS minus the reduced gradient, so a gradient
    # that was not reduced, or summed instead of averaged, cannot hide
    # behind adam's normalisation
    opt = hvd_opt.distributed(optax.sgd(1.0), axis_name="data",
                              op=hvd.Average)

    def qs_step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: lean_lm_loss(p, x, y, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, "data"))

    tok = P("data", None)
    qs = jax.jit(shard_map(qs_step, mesh=mesh,
                           in_specs=(P(), P(), tok, tok),
                           out_specs=(P(), P(), P()), check_vma=False))
    p0 = start()
    need_custom_calls(args, "quickstart step",
                      qs.lower(p0, opt.init(p0), inputs, targets).as_text())
    p_qs, _, loss_qs = qs(p0, opt.init(p0), inputs, targets)

    ref_opt = optax.sgd(1.0)
    p0 = start()
    origin = jax.tree_util.tree_map(jnp.copy, p0)   # p0 is donated below
    p_ref, _, loss_ref = make_train_step(mesh, cfg, ref_opt)(
        p0, ref_opt.init(p0), inputs, targets)

    def l2(tree):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                  for x in jax.tree_util.tree_leaves(tree))))

    sub = lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b)
    upd_ref = sub(p_ref, origin)
    err = l2(sub(sub(p_qs, origin), upd_ref)) / l2(upd_ref)
    log(f"quickstart: loss {float(loss_qs):.5f} vs make_train_step "
        f"{float(loss_ref):.5f}; first update differs by {err:.2e} "
        f"(relative L2; band {BAND_QUICKSTART_UPDATE})")
    if not err <= BAND_QUICKSTART_UPDATE:
        raise AssertionError("quickstart update leaves the band: the "
                             "gradient was not reduced as make_train_step's")
    write_result(args, "quickstart", {
        "update_rel_l2": err, "loss": float(loss_qs),
        "loss_make_train_step": float(loss_ref), "cache": cache.report()})


def child_kernels(args) -> None:
    """Every Pallas kernel of the repo, compiled for the chip (not
    interpreted) at one aligned and one ragged size, against its lax twin;
    and the stock attention kernels against materialized attention."""
    devs, cache = child_prologue(args)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.ops import pallas_kernels as pk
    from horovod_tpu.ops.adasum import adasum_combine
    from horovod_tpu.parallel.flash_attention import (attention_kernel,
                                                      flash_attention_local)
    from horovod_tpu.parallel.ring_attention import (local_attention,
                                                     ring_attention_p)

    if not args.rehearse and pk._interpret():
        raise AssertionError("Pallas kernels would run interpreted on a TPU")
    key = jax.random.PRNGKey(SEED)
    failed = []

    def close(what, got, want, tol):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)) /
                    max(float(np.max(np.abs(want))), 1e-12))
        if not err <= tol:
            raise AssertionError(f"{what}: max error {err:.2e} of the "
                                 f"reference's scale, tolerance {tol}")
        return err

    def check(name, fn):
        # every kernel gets its run, so one call to the chip names every
        # refusal; any failure still fails the phase
        try:
            err = fn()
        except Exception:
            failed.append(name)
            log(f"kernel {name}: FAILED\n{traceback.format_exc()}")
        else:
            log(f"kernel {name}: ok (max rel err {err:.2e})")

    def adasum(n, dtype):
        a, b = (jax.random.normal(k, (n,), jnp.float32).astype(dtype)
                for k in jax.random.split(key))
        return close("adasum", pk.adasum_combine_pallas(a, b),
                     adasum_combine(a, b), 2e-2 if dtype == jnp.bfloat16
                     else 1e-5)

    def pack(shapes, dtype):
        # the engine offers the kernel only what this predicate accepts
        # (Mosaic refuses ragged tensors; tests/test_pallas_kernels.py)
        if not pk.pack_pallas_supported(shapes, dtype):
            raise AssertionError(f"pack_pallas_supported refuses {shapes}")
        ts = [jax.random.normal(k, s, jnp.float32).astype(dtype)
              for k, s in zip(jax.random.split(key, len(shapes)), shapes)]
        return close("pack", pk.pack_pallas(ts),
                     jnp.concatenate([t.ravel() for t in ts]), 0.0)

    def bn(m, c):
        kx, kd = jax.random.split(key)
        x = jax.random.normal(kx, (m, c), jnp.float32).astype(jnp.bfloat16)
        dy = jax.random.normal(kd, (m, c), jnp.float32).astype(jnp.bfloat16)
        xf, dyf = x.astype(jnp.float32), dy.astype(jnp.float32)
        s, q = pk.bn_stats_pallas(x)
        e1 = close("bn sum", s, xf.sum(0), 1e-3)
        e2 = close("bn sumsq", q, (xf * xf).sum(0), 1e-3)
        mean = xf.mean(0)
        invstd = 1.0 / jnp.sqrt(xf.var(0) + 1e-5)
        s1, s2 = pk.bn_bwd_stats_pallas(dy, x, mean, invstd)
        e3 = close("bn bwd sum(dy)", s1, dyf.sum(0), 1e-3)
        e4 = close("bn bwd sum(dy*xhat)", s2,
                   (dyf * (xf - mean) * invstd).sum(0), 1e-3)
        return max(e1, e2, e3, e4)

    block = pk._ROW_BLOCK * pk._LANES
    for dtype in (jnp.float32, jnp.bfloat16):
        tag = jnp.dtype(dtype).name
        check(f"adasum_combine_pallas aligned {tag}",
              lambda: adasum(2 * block, dtype))
        check(f"adasum_combine_pallas ragged {tag}",
              lambda: adasum(100003, dtype))
    check("pack_pallas float32",
          lambda: pack([(256, 128), (4, 3, 1024), (1024,)], jnp.float32))
    check("pack_pallas bfloat16",
          lambda: pack([(256, 128), (2048,), (3, 2048)], jnp.bfloat16))
    check("bn_stats/bn_bwd_stats_pallas aligned", lambda: bn(4096, 256))
    check("bn_stats/bn_bwd_stats_pallas ragged", lambda: bn(2002, 64))

    # attention: [B, H, T, D] at the flagship head size (toy in rehearsal)
    d = 16 if args.rehearse else 128

    def attention(t, under_remat, ring):
        q, k, v, w = (jax.random.normal(kk, (1, 2, t, d), jnp.float32)
                      .astype(jnp.bfloat16) * 0.5
                      for kk in jax.random.split(key, 4))
        bthk = lambda x: x.transpose(0, 2, 1, 3)

        def ref(q, k, v):
            return bthk(local_attention(bthk(q), bthk(k), bthk(v)))

        if ring:
            # the ring's per-segment kernels, on a one-device ring
            mesh = Mesh(np.array(devs[:1]), ("seq",))
            spec = P(None, "seq")

            def fn(q, k, v):
                ring_fn = shard_map(
                    lambda q, k, v: ring_attention_p(
                        q, k, v, "seq", 1, causal=True, force_ring=True),
                    mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                    check_vma=False)
                return bthk(ring_fn(bthk(q), bthk(k), bthk(v)))
        else:
            def fn(q, k, v):
                return flash_attention_local(q, k, v, causal=True,
                                             layout="bhtk",
                                             under_remat=under_remat)
            if under_remat:     # the backward runs the forward again
                fn = jax.checkpoint(fn)

        def loss(f):
            return lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

        need_custom_calls(args, f"attention t={t}",
                          jax.jit(fn).lower(q, k, v).as_text())
        got = jax.jit(jax.value_and_grad(loss(fn), (0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(loss(ref), (0, 1, 2)))(q, k, v)
        errs = [close("attention loss", got[0], want[0], 2e-2)]
        errs += [close(f"attention d{n}", g, r, 5e-2)
                 for n, g, r in zip("qkv", got[1], want[1])]
        return max(errs)

    t = 128 if args.rehearse else 2048
    if not args.rehearse:
        for under_remat in (False, True):
            took = attention_kernel((1, 2, t, d), (1, 2, t, d), True,
                                    under_remat)
            if (took["kernel"], took["fused_bwd"]) != ("splash", "1"):
                raise AssertionError(
                    "the flagship shape no longer takes splash with the "
                    f"fused backward (under_remat={under_remat}): {took}")
    check(f"splash attention fwd+bwd T{t}",
          lambda: attention(t, False, False))
    check(f"splash attention under remat fwd+bwd T{t}",
          lambda: attention(t, True, False))
    # a length splash does not take (not a multiple of 1024): the stock
    # flash kernel at its own 128 blocks
    check(f"flash attention fwd+bwd T{3 * t // 4}",
          lambda: attention(3 * t // 4, False, False))
    check(f"ring segment kernels fwd+bwd T{t // 2}",
          lambda: attention(t // 2, False, True))
    if failed:
        log(f"kernels FAILED: {failed}")
        sys.exit(1)
    write_result(args, "kernels", {"cache": cache.report()})


CHILDREN = {"spmd": child_spmd, "eager": child_eager,
            "quickstart": child_quickstart, "kernels": child_kernels}


# ---------------------------------------------------------------------------
# parent: starts children, never imports jax
# ---------------------------------------------------------------------------

class Runner:
    def __init__(self, args, out: str):
        self.args, self.out = args, out
        self.deadline = time.monotonic() + DEADLINE_S
        self.live = []
        self.env = dict(os.environ)
        # the emitter thread that samples HBM runs when metrics have a sink
        self.env["HOROVOD_TPU_METRICS_FILE"] = os.path.join(out,
                                                            "metrics.jsonl")
        self.env["HOROVOD_TPU_METRICS_INTERVAL"] = "1"
        if args.rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    def child_cmd(self, name: str):
        cmd = [sys.executable, os.path.join(HERE, "chip_smoke.py"),
               "--child", name, "--out", self.out]
        return cmd + (["--rehearse"] if self.args.rehearse else [])

    def start(self, name: str, cmd):
        log(f"phase {name}: starting")
        proc = subprocess.Popen(cmd, cwd=HERE, env=self.env,
                                start_new_session=True)
        self.live.append(proc)
        return name, proc, time.monotonic()

    def wait(self, started) -> int:
        name, proc, t0 = started
        try:
            rc = proc.wait(timeout=max(min(
                self.deadline - time.monotonic(),
                t0 + PHASE_S - time.monotonic()), 1))
        except subprocess.TimeoutExpired:
            log(f"phase {name}: out of time, killing it")
            rc = 124
        self.kill(proc)     # the whole group: a launcher's workers too
        log(f"phase {name}: exit code {rc} after "
            f"{time.monotonic() - t0:.0f} s")
        return rc

    def kill(self, proc) -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()

    def kill_all(self) -> None:
        for proc in self.live:
            self.kill(proc)

    def read(self, name: str) -> dict:
        with open(os.path.join(self.out, f"{name}.json")) as f:
            return json.load(f)


def follow(what: str, eager, spmd) -> float:
    worst = max(rel(e, s) for e, s in zip(eager, spmd))
    log(f"{what} vs SPMD, same seed: worst relative difference "
        f"{worst:.2e} (band {BAND_EAGER_VS_SPMD})")
    return worst


def parent(args) -> int:
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out:
        return run_phases(args, out)


def run_phases(args, out: str) -> int:
    run = Runner(args, out)
    failed = []
    report = {"rehearsal": args.rehearse}
    try:
        # the first child is also the device gate: it writes device.json
        # right after passing it
        first = run.start("spmd", run.child_cmd("spmd"))
        device_json = os.path.join(out, "device.json")
        while not os.path.exists(device_json) and first[1].poll() is None:
            time.sleep(0.2)
        if not os.path.exists(device_json):
            run.wait(first)
            log("no result: the device gate refused, or the first child "
                "could not start")
            return 1
        device = run.read("device")
        n = device["count"]
        log(f"platform={device['platform']} device_kind={device['kind']} "
            f"devices={n} " + " ".join(
                f"{k}={v}" for k, v in device["versions"].items()))

        phases = [("kernels", run.child_cmd("kernels"))]
        if n == 4:
            phases.append(("quickstart", run.child_cmd("quickstart")))
        else:
            log(f"phase quickstart not run: needs 4 devices, {n} visible")
        if n == 1:
            phases.append(("eager", run.child_cmd("eager")))
        else:
            # one process per chip: the launcher binds local rank i to
            # chip i
            phases.append(("eager", [
                sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
                str(n)] + run.child_cmd("eager")))
        if args.rehearse:
            # CPU children do not contend for a chip: all side by side
            started = [first] + [run.start(*phase) for phase in phases]
            codes = [run.wait(s) for s in started]
        else:
            codes = [run.wait(first)] + [run.wait(run.start(*phase))
                                         for phase in phases]
        failed += [name for name, rc in zip(
            ["spmd"] + [name for name, _ in phases], codes) if rc != 0]

        for name in ("spmd", "kernels", "quickstart"):
            if os.path.exists(os.path.join(out, f"{name}.json")):
                report[name] = run.read(name)
        ranks = [run.read(f"eager_rank{r}") for r in range(n)
                 if os.path.exists(os.path.join(out,
                                                f"eager_rank{r}.json"))]
        report["eager"] = ranks
        if "spmd" not in failed and "eager" not in failed:
            # (every rank reports the one gathered series; that the ranks
            # are in lockstep is the worker's own parameter-digest check)
            spmd = report["spmd"]["data"]["losses"]
            worst = max(
                follow(what, ranks[0][key], spmd)
                for what, key in (("eager DistributedOptimizer",
                                   "losses_optimizer"),
                                  ("eager hvd.step loop", "losses_engine"))
                if ranks[0][key])
            report["eager_vs_spmd_worst_rel"] = worst
            if not worst <= BAND_EAGER_VS_SPMD:
                failed.append("eager follows spmd")
        cache = {"requests": 0, "hits": 0}
        for part in [report.get(k) for k in ("spmd", "kernels",
                                             "quickstart")] + ranks:
            for k in cache:
                cache[k] += (part or {}).get("cache", {}).get(k, 0)
        report["cache"] = cache
        log(f"persistent compile cache: {cache['hits']} hits of "
            f"{cache['requests']} requests")
        report["device"] = device
        report["failed"] = failed
    finally:
        run.kill_all()
        # the whole report, for whoever reads chiprun's output directory
        try:
            os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
            with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"),
                      "a") as f:
                f.write(json.dumps(report) + "\n")
        except OSError as e:
            log(f"report not written: {e}")
    if failed:
        log(f"FAILED: {failed}")
        return 1
    result = {"ok": True, "device": {k: device[k] for k in
                                     ("platform", "kind", "count")}}
    print(json.dumps({"rehearsal": True, **result} if args.rehearse
                     else result))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on forced CPU devices; proves the "
                         "control flow, never the chip")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        CHILDREN[args.child](args)
        return 0
    return parent(args)


if __name__ == "__main__":
    sys.exit(main())
