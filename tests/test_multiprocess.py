"""Real multi-process integration tests on localhost — the TPU-native analog
of the reference's keystone pattern of running the suite under
``horovodrun -np 2 --gloo`` (SURVEY.md §4, gen-pipeline.sh:113,217).

Each test uses the programmatic ``horovod_tpu.run()`` API to spawn two
genuine worker processes that rendezvous through the JAX coordinator and run
real cross-process collectives on the CPU backend.
"""

import os
import sys

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("HVD_TPU_SKIP_MULTIPROC") == "1",
    reason="multi-process tier disabled")


def _mp_env():
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
    }
    return env


def _worker_allreduce():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank, size = hvd.rank(), hvd.size()
    x = np.arange(4.0) * (rank + 1)
    out = np.asarray(hvd.allreduce(x, name="t0", op=hvd.Sum))
    expected = np.arange(4.0) * sum(r + 1 for r in range(size))
    np.testing.assert_allclose(out, expected)
    g = np.asarray(hvd.allgather(np.array([float(rank)]), name="g0"))
    np.testing.assert_allclose(g, np.arange(float(size)))
    b = np.asarray(hvd.broadcast(np.array([rank + 10.0]), root_rank=0,
                                 name="b0"))
    np.testing.assert_allclose(b, [10.0])
    return (rank, size)


def _worker_topology():
    import horovod_tpu as hvd
    return (hvd.rank(), hvd.size(), hvd.local_rank(), hvd.local_size(),
            hvd.cross_rank(), hvd.cross_size())


@pytest.mark.integration
def test_two_process_collectives():
    from horovod_tpu.runner import run
    results = run(_worker_allreduce, np=2, env=_mp_env())
    assert results == [(0, 2), (1, 2)]


@pytest.mark.integration
def test_two_process_topology():
    from horovod_tpu.runner import run
    results = run(_worker_topology, np=2, env=_mp_env())
    assert results[0] == (0, 2, 0, 2, 0, 1)
    assert results[1] == (1, 2, 1, 2, 0, 1)


@pytest.mark.integration
def test_nonzero_exit_fails_job(tmp_path):
    from horovod_tpu.runner.hosts import HostInfo
    from horovod_tpu.runner.launch import launch_static
    with pytest.raises(RuntimeError, match="non-zero"):
        launch_static([HostInfo("localhost", 2)], 2,
                      [sys.executable, "-c", "import sys; sys.exit(3)"],
                      dict(os.environ))


def _worker_alltoall_rs():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank, size = hvd.rank(), hvd.size()
    out = {}
    # equal-split alltoall: rank r sends row "100*r + dest" to each dest
    x = np.stack([np.full((2,), 100 * rank + d, np.float32)
                  for d in range(size)])
    out["alltoall"] = np.asarray(hvd.alltoall(x, name="at")).tolist()
    # uneven splits: rank 0 sends 1 row to each, rank 1 sends 2 rows to each
    rows = (rank + 1) * size
    xs = np.full((rows, 1), float(rank), np.float32)
    recv, counts = hvd.alltoall(xs, splits=[rank + 1] * size, name="atv")
    out["recv_counts"] = [int(c) for c in np.asarray(counts)]
    out["recv_rows"] = int(recv.shape[0])
    # reducescatter
    rs = np.asarray(hvd.reducescatter(
        np.arange(size * 3, dtype=np.float32).reshape(size, 3), name="rs"))
    out["rs"] = rs.tolist()
    # odd-length reducescatter (ISSUE 2 satellite): dim0=5 does not divide
    # np=2 — the builder pads internally; rank0 keeps ceil(5/2)=3 rows,
    # rank1 the remaining 2
    rs_odd = np.asarray(hvd.reducescatter(
        np.arange(5 * 2, dtype=np.float32).reshape(5, 2), name="rs.odd"))
    out["rs_odd"] = rs_odd.tolist()
    return out


@pytest.mark.integration
def test_two_process_alltoall_reducescatter():
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_alltoall_rs, np=2, env=_mp_env())
    # alltoall: rank 0 receives [own dest-0 chunk, rank1's dest-0 chunk]
    assert r0["alltoall"] == [[0.0, 0.0], [100.0, 100.0]], r0
    assert r1["alltoall"] == [[1.0, 1.0], [101.0, 101.0]], r1
    # uneven: each rank receives 1 row from rank0 and 2 rows from rank1
    for r in (r0, r1):
        assert r["recv_counts"] == [1, 2], r
        assert r["recv_rows"] == 3, r
    # reducescatter of identical (2,3) tensors: row r summed → 2x values
    assert r0["rs"] == [[0.0, 2.0, 4.0]], r0
    assert r1["rs"] == [[6.0, 8.0, 10.0]], r1
    # odd dim0: both ranks submitted identical (5,2) tensors -> doubled
    # rows; rank0 holds rows 0-2, rank1 rows 3-4, nothing lost to padding
    assert r0["rs_odd"] == [[0.0, 2.0], [4.0, 6.0], [8.0, 10.0]], r0
    assert r1["rs_odd"] == [[12.0, 14.0], [16.0, 18.0]], r1


def _elastic_fn(total):
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    state = hvd.elastic.ObjectState(batch=0)

    @hvd.elastic.run
    def train(state):
        while state.batch < total:
            out = np.asarray(hvd.allreduce(np.ones(2), name=f"b{state.batch}",
                                           op=hvd.Sum))
            assert out[0] == hvd.size()
            state.batch += 1
            state.commit()
        return {"rank": hvd.rank(), "size": hvd.size(), "batch": state.batch}

    return train(state)


@pytest.mark.integration
def test_run_elastic_programmatic():
    """Programmatic elastic API (reference spark run_elastic parity): the
    function runs under the elastic runtime and per-final-rank results come
    back in order."""
    from horovod_tpu.runner import run_elastic
    results = run_elastic(_elastic_fn, args=(10,), np=2, max_np=2,
                          env=_mp_env(), timeout=120)
    assert results == [{"rank": 0, "size": 2, "batch": 10},
                       {"rank": 1, "size": 2, "batch": 10}], results


def _worker_steady_state_no_fetch():
    """Steady-state eager allreduce must not perform host round-trips: the
    join advertisement is fire-and-forget (engine._join_sync) and the
    collective itself returns async handles. host_fetches counts blocking
    metadata read-backs (engine._fetch_exchange)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    eng = hvd._engine()
    # warmup: builder compiles, one-time topology checks
    for i in range(3):
        hvd.allreduce(np.ones(8), name=f"warm{i}", op=hvd.Sum)
        hvd.grouped_allreduce([np.ones(4), np.ones((2, 3))],
                              name=f"warmg{i}", op=hvd.Sum)
    before = eng.host_fetches
    outs = []
    for i in range(10):
        outs.append(hvd.allreduce_async(np.ones(8) * (i + 1), name=f"s{i}",
                                        op=hvd.Sum))
        outs.extend(hvd.grouped_allreduce_async(
            [np.ones(4) * i, np.ones((2, 3))], name=f"g{i}", op=hvd.Sum))
    fetches_during_submission = eng.host_fetches - before
    # synchronize only at the end (results still correct)
    vals = [float(np.asarray(hvd.synchronize(h)).ravel()[0]) for h in outs]
    return (fetches_during_submission, vals[0], vals[3])


@pytest.mark.integration
def test_steady_state_eager_has_no_host_roundtrips():
    """VERDICT r2 item 2: with join enabled (the default), steady-state
    eager submission must issue no blocking metadata fetches per op."""
    from horovod_tpu.runner import run
    results = run(_worker_steady_state_no_fetch, np=2, env=_mp_env())
    for fetches, v0, v3 in results:
        assert fetches == 0, f"host fetches during submission: {fetches}"
        assert v0 == 2.0          # s0: ones from both ranks
        assert v3 == 4.0          # s1: ones*2 from both ranks


def _worker_steady_state_sized_ops():
    """VERDICT r3 item 2: steady-state allgather (uneven), alltoall (uneven
    splits) and broadcast must stop paying a blocking size exchange per call
    once the per-name cache goes hot; the consistency check is deferred to
    extract time (deferred_meta_checks)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    eng = hvd._engine()
    rank, size = hvd.rank(), hvd.size()
    d0 = rank + 1                       # uneven allgather rows
    splits = [rank + 1] * size          # uneven alltoall splits

    def one_round():
        g = np.asarray(hvd.allgather(
            np.full((d0, 2), float(rank), np.float32), name="ss.ag"))
        recv, counts = hvd.alltoall(
            np.full(((rank + 1) * size, 1), float(rank), np.float32),
            splits=splits, name="ss.a2a")
        b = np.asarray(hvd.broadcast(np.array([rank + 7.0]), root_rank=0,
                                     name="ss.bc"))
        return g, np.asarray(recv), np.asarray(counts), b

    for _ in range(3):                  # warmup: cache goes hot at streak 2
        one_round()
    f0, d0c = eng.host_fetches, eng.deferred_meta_checks
    rounds = [one_round() for _ in range(10)]
    fetches = eng.host_fetches - f0
    checks = eng.deferred_meta_checks - d0c
    g, recv, counts, b = rounds[-1]
    return {"rank": rank, "fetches": fetches, "checks": checks,
            "g_rows": int(g.shape[0]), "counts": counts[:, 0].tolist()
            if counts.ndim > 1 else counts.tolist(),
            "recv_rows": int(recv.shape[0]), "b": float(b[0])}


@pytest.mark.integration
def test_steady_state_sized_ops_no_host_roundtrips():
    """Allgather/alltoall/broadcast in steady state: zero blocking metadata
    fetches; the deferred extract-time checks run instead and the results
    stay correct."""
    from horovod_tpu.runner import run
    results = run(_worker_steady_state_sized_ops, np=2, env=_mp_env())
    for r in results:
        assert r["fetches"] == 0, r
        assert r["checks"] == 20, r      # 10 allgather + 10 alltoall rounds
        assert r["g_rows"] == 3, r       # 1 + 2 uneven rows
        assert r["counts"] == [1, 2], r  # 1 row from rank0, 2 from rank1
        assert r["recv_rows"] == 3, r
        assert r["b"] == 7.0, r


@pytest.mark.slow          # (13s) knob-off variant of the tier-1
@pytest.mark.integration   # steady-state sized-ops case
def test_sized_ops_with_meta_cache_disabled():
    """HOROVOD_TPU_META_CACHE=0 restores the always-negotiate behavior:
    one blocking size exchange per sized op (20 over the measured rounds),
    zero deferred checks, same results."""
    from horovod_tpu.runner import run
    env = _mp_env()
    env["HOROVOD_TPU_META_CACHE"] = "0"
    results = run(_worker_steady_state_sized_ops, np=2, env=env)
    for r in results:
        assert r["fetches"] == 20, r     # 10 allgather + 10 alltoall
        assert r["checks"] == 0, r
        assert r["g_rows"] == 3 and r["recv_rows"] == 3, r
        assert r["b"] == 7.0, r


def _worker_meta_cache_mismatch():
    """When a rank's sizes change after the per-name cache went hot, every
    rank must RAISE (never hang, never return garbage): hot peers via the
    deferred advertisement check, the changed rank via its stale-local
    marker — and the op sequence stays aligned so the next, consistent op
    succeeds after renegotiation."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError

    rank = hvd.rank()
    for _ in range(3):   # cache hot at streak 2
        hvd.allgather(np.ones((1, 2), np.float32) * rank, name="mm.ag")
    d0 = 2 if rank == 1 else 1   # rank 1's row count changes
    h = hvd._engine().allgather(np.ones((d0, 2), np.float32), name="mm.ag")
    raised = False
    try:
        h.synchronize()
    except HorovodInternalError:
        raised = True
    # after the mismatch the entry is invalidated -> blocking renegotiation
    out = np.asarray(hvd.allgather(np.ones((d0, 2), np.float32) * (rank + 1),
                                   name="mm.ag"))
    return {"rank": rank, "raised": raised, "rows": int(out.shape[0])}


@pytest.mark.integration
def test_meta_cache_mismatch_raises_everywhere():
    from horovod_tpu.runner import run
    results = run(_worker_meta_cache_mismatch, np=2, env=_mp_env())
    for r in results:
        assert r["raised"], r
        assert r["rows"] == 3, r      # 1 + 2 rows gathered correctly after


def _worker_join_allgather_hot_cache():
    """A join substitute must replay a hot-cached UNEVEN allgather with the
    joined rank's own previously-advertised size: same collective
    sequence, same program shapes, hot peers' deferred check untouched —
    no hang, no spurious mismatch error (code-review r4 finding)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    rank = hvd.rank()
    d0 = rank + 2   # rank0: 2 rows, rank1: 3 rows — uneven but stable
    for _ in range(3):   # hot at streak 2
        hvd.allgather(np.full((d0, 2), float(rank), np.float32), name="ju.ag")
    if rank == 0:
        # one more hot allgather while rank 1 sits in join()
        g = np.asarray(hvd.allgather(np.full((d0, 2), 7.0, np.float32),
                                     name="ju.ag"))
        last = hvd.join()
        return {"rank": 0, "rows": int(g.shape[0]),
                "head_ok": bool((g[:2] == 7.0).all()),
                "tail_zero": bool((g[2:] == 0.0).all()), "last": last}
    last = hvd.join()
    return {"rank": 1, "last": last}


@pytest.mark.integration
def test_join_substitute_respects_hot_size_cache():
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_join_allgather_hot_cache, np=2, env=_mp_env())
    assert r0["rows"] == 5, r0            # 2 live + 3 zero-substitute rows
    assert r0["head_ok"] and r0["tail_zero"], r0
    assert r0["last"] == r1["last"] == 0  # rank 0 joined last


def _worker_chained_optimizer():
    """VERDICT r3 item 1a: the eager optimizer chains the update onto the
    reduced gradient arrays with ZERO host blocks — dataflow is the
    synchronization. host_blocks counts Handle.synchronize waits;
    host_fetches counts blocking metadata read-backs."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    eng = hvd._engine()
    rank = hvd.rank()
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    opt = DistributedEagerOptimizer(optax.sgd(0.1))
    state = opt.init(params)

    def loss(p, x):
        return jnp.sum((x @ p["w"] + p["b"]) ** 2)

    grad_fn = jax.jit(jax.grad(loss))
    x = jnp.ones((2, 4)) * (rank + 1)
    # warmup: compile grad/pack/reduce/apply programs
    for _ in range(3):
        g = grad_fn(params, x)
        params, state = opt.update_and_apply(g, state, params)
    jax.block_until_ready(params)
    blocks0, fetches0 = eng.host_blocks, eng.host_fetches
    for _ in range(10):
        g = grad_fn(params, x)
        params, state = opt.update_and_apply(g, state, params)
    blocks = eng.host_blocks - blocks0
    fetches = eng.host_fetches - fetches0
    jax.block_until_ready(params)
    # --- ZeRO-1 sharded phase (same worker: process spawns are the
    # suite's dominant cost): the sharded trajectory must match the dense
    # one exactly (both average the same cross-rank gradients), stay in
    # lockstep, and hold ~half the inner optimizer-state bytes per rank.
    from horovod_tpu.optimizer import DistributedEagerOptimizer as _DEO
    sopt = _DEO(optax.sgd(0.1), sharded=True)
    sp = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    ss = sopt.init(sp)
    for _ in range(13):   # 3 warmup + 10 measured steps of the dense loop
        sp, ss = sopt.update_and_apply(grad_fn(sp, x), ss, sp)
    jax.block_until_ready(sp["w"])
    # state-shrink check on a stateful inner (plain sgd has no state):
    # init-only, no extra training steps
    mom = optax.sgd(0.1, momentum=0.9)
    dense_state_bytes = sum(
        l.nbytes for l in jax.tree_util.tree_leaves(mom.init(sp)))
    shard_state_bytes = sum(
        l.nbytes for l in jax.tree_util.tree_leaves(
            _DEO(mom, sharded=True).init(sp).inner_state))
    sharded_err = float(max(
        np.max(np.abs(np.asarray(a) - np.asarray(b)))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(sp))))
    return {"rank": rank, "host_blocks": blocks, "host_fetches": fetches,
            "w": np.asarray(params["w"]).tolist(),
            "finite": bool(np.isfinite(np.asarray(params["w"])).all()),
            "sharded_err": sharded_err,
            "dense_state_bytes": dense_state_bytes,
            "shard_state_bytes": shard_state_bytes}


@pytest.mark.integration
def test_chained_eager_optimizer_no_host_blocks():
    """Dense phase: zero host blocks/fetches (VERDICT r3 item 1a). Sharded
    phase (ISSUE 2): same trajectory as dense from the same start, with the
    per-rank inner optimizer state halved (ZeRO-1 shard)."""
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_chained_optimizer, np=2, env=_mp_env())
    for r in (r0, r1):
        assert r["host_blocks"] == 0, r
        assert r["host_fetches"] == 0, r
        assert r["finite"], r
        assert r["sharded_err"] < 1e-5, r
        # sgd momentum over a 10-element shard vs 20 params: ~half bytes
        assert r["shard_state_bytes"] <= r["dense_state_bytes"] / 2 + 16, r
    # averaged gradients -> replicas stay in lockstep
    assert r0["w"] == r1["w"]


def _worker_delta_adasum():
    """Delta-model Adasum (torch/optimizer.py:196-364): each rank applies
    its LOCAL Adam step, the parameter deltas are Adasum-combined through
    the engine, and the result must equal the NumPy VHDD formula applied
    to the per-rank updates — and stay in lockstep across ranks."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd

    rank = hvd.rank()
    rng = np.random.RandomState(3)
    params = {"w": jnp.asarray(rng.randn(4).astype(np.float32))}
    all_grads = rng.randn(2, 4).astype(np.float32)  # same on both ranks

    inner = optax.adam(1e-2)
    opt = hvd.DistributedDeltaAdasumOptimizer(optax.adam(1e-2))
    st = opt.init(params)
    g = {"w": jnp.asarray(all_grads[rank])}
    out, _ = opt.update_and_apply(g, st, params)
    jax.block_until_ready(out)

    # host-side expectation: VHDD over both ranks' local Adam updates
    from horovod_tpu.ops.adasum import adasum_reference
    ups = []
    for r in range(2):
        u, _ = inner.update({"w": jnp.asarray(all_grads[r])},
                            inner.init(params), params)
        ups.append(np.asarray(u["w"]))
    expect = np.asarray(params["w"]) + adasum_reference(ups)
    return {"rank": rank, "w": np.asarray(out["w"]).tolist(),
            "expect": expect.tolist()}


@pytest.mark.slow          # (13s) adasum math is covered in-process
@pytest.mark.integration   # (test_adasum.py); this is the np=2 re-run
def test_delta_adasum_two_process():
    import numpy as _np
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_delta_adasum, np=2, env=_mp_env())
    assert r0["w"] == r1["w"]  # lockstep
    _np.testing.assert_allclose(_np.asarray(r0["w"]),
                                _np.asarray(r0["expect"]), rtol=1e-4,
                                atol=1e-5)


def _worker_throughput():
    """VERDICT r3 item 1b: eager-vs-SPMD throughput where dispatch is cheap
    (CPU backend, ~100us per dispatch) — framework cost alone. Same model,
    same world."""
    import time
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map
    import horovod_tpu as hvd
    from horovod_tpu import optimizer as hvd_opt
    from horovod_tpu.optimizer import DistributedEagerOptimizer
    from horovod_tpu.parallel.mesh import WORLD_AXIS

    eng = hvd._engine()
    size, rank = hvd.size(), hvd.rank()
    D, H, B = 256, 1024, 256
    rng = np.random.RandomState(rank)
    x = jnp.asarray(rng.rand(B, D).astype(np.float32))
    y = jnp.asarray(rng.rand(B, 1).astype(np.float32))
    params = {
        "w1": jnp.asarray(np.random.RandomState(0).randn(D, H) * 0.05,
                          jnp.float32),
        "w2": jnp.asarray(np.random.RandomState(1).randn(H, H) * 0.05,
                          jnp.float32),
        "w3": jnp.asarray(np.random.RandomState(2).randn(H, 1) * 0.05,
                          jnp.float32),
    }

    def loss(p, x, y):
        h = jnp.tanh(x @ p["w1"])
        h = jnp.tanh(h @ p["w2"])
        return jnp.mean((h @ p["w3"] - y) ** 2)

    iters = 30

    # ---- eager path: jitted grad -> engine grouped_allreduce -> chained
    # jitted apply (3 dispatches/step, zero host blocks)
    grad_fn = jax.jit(jax.grad(loss))
    opt = DistributedEagerOptimizer(optax.sgd(0.01))
    ep, es = jax.tree_util.tree_map(lambda a: a, params), None
    es = opt.init(ep)
    for _ in range(3):
        ep, es = opt.update_and_apply(grad_fn(ep, x, y), es, ep)
    jax.block_until_ready(ep)
    t0 = time.perf_counter()
    for _ in range(iters):
        ep, es = opt.update_and_apply(grad_fn(ep, x, y), es, ep)
    jax.block_until_ready(ep)
    eager_dt = (time.perf_counter() - t0) / iters

    # ---- SPMD path: one jitted shard_map step over the group mesh with the
    # framework's distributed optax wrapper (psum inside the program)
    mesh = eng.backend.group_mesh
    dist = hvd_opt.distributed(optax.sgd(0.01), axis_name=WORLD_AXIS,
                               op=hvd.Average)

    def body(p, s, xg, yg):
        g = jax.grad(loss)(p, xg[0], yg[0])
        u, s = dist.update(g, s, p)
        return optax.apply_updates(p, u), s

    step = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P(WORLD_AXIS), P(WORLD_AXIS)),
        out_specs=(P(), P())))
    rep = NamedSharding(mesh, P())
    sp = jax.device_put(params, rep)
    ss = jax.device_put(dist.init(params), rep)
    xg, yg = eng.backend.to_global(x), eng.backend.to_global(y)
    for _ in range(3):
        sp, ss = step(sp, ss, xg, yg)
    jax.block_until_ready(sp)
    t0 = time.perf_counter()
    for _ in range(iters):
        sp, ss = step(sp, ss, xg, yg)
    jax.block_until_ready(sp)
    spmd_dt = (time.perf_counter() - t0) / iters
    return {"rank": rank, "eager_ms": eager_dt * 1e3,
            "spmd_ms": spmd_dt * 1e3,
            "ratio": spmd_dt / eager_dt}


# Tier-1 budget (ISSUE 9 satellite): of the ~15 np>=2 subprocess cases
# in this file, the four below are comparative/bench or variant-knob
# re-runs of scenarios another tier-1 case already covers (durations in
# parentheses from the --durations=25 profile); each subsystem keeps at
# least one multiprocess case in tier-1 — collectives
# (test_two_process_collectives, test_two_process_alltoall_reducescatter,
# test_four_process_allreduce_join), elastic
# (test_run_elastic_programmatic), meta-cache/steady-state
# (test_steady_state_sized_ops_no_host_roundtrips), sparse
# (test_allreduce_sparse_two_process), ZeRO-1
# (test_sharded_prefetch_survives_world_version_bump).
@pytest.mark.slow          # (20s) throughput comparison, a bench not a gate
@pytest.mark.integration
def test_eager_vs_spmd_cpu_throughput():
    """VERDICT r3 item 1 'done' bar: eager >= 50% of SPMD throughput on a
    2-process CPU bench (a count of framework cost, not a device speed)."""
    from horovod_tpu.runner import run
    results = run(_worker_throughput, np=2, env=_mp_env())
    for r in results:
        assert r["ratio"] >= 0.5, (
            f"eager path is {r['ratio']:.1%} of SPMD throughput "
            f"(eager {r['eager_ms']:.2f} ms vs spmd {r['spmd_ms']:.2f} ms); "
            f"target >=50%: {r}")


def _worker_sparse_optimizer():
    """VERDICT r3 item 9: an embedding model trained through
    sparse_rows-marked gradients must (a) match the dense-allreduce path
    numerically and (b) put far fewer bytes on the wire (counted at
    engine enqueue), with the duplicate-combine jitted (no host NumPy)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    eng = hvd._engine()
    rank = hvd.rank()
    V, Dm, B = 1024, 16, 8
    tok = jnp.asarray((np.random.RandomState(rank).randint(0, V, B))
                      .astype(np.int32))
    tgt = jnp.asarray(np.random.RandomState(100 + rank).rand(B, Dm)
                      .astype(np.float32))

    def loss(p, tok, tgt):
        return jnp.mean((p["embed"][tok] @ p["proj"] - tgt) ** 2)

    grad_fn = jax.jit(jax.grad(loss))

    def train(sparse_rows, steps=4):
        params = {"embed": jnp.ones((V, Dm)) * 0.1,
                  "proj": jnp.eye(Dm)}
        opt = DistributedEagerOptimizer(optax.sgd(0.5),
                                        sparse_rows=sparse_rows)
        st = opt.init(params)
        nbytes = [0]
        orig = eng.on_enqueue

        def count(name, kind, nb):
            nbytes[0] += nb
            if orig:
                orig(name, kind, nb)

        eng.on_enqueue = count
        try:
            for _ in range(steps):
                g = grad_fn(params, tok, tgt)
                params, st = opt.update_and_apply(g, st, params)
            jax.block_until_ready(params)
        finally:
            eng.on_enqueue = orig
        return params, nbytes[0]

    dense_params, dense_bytes = train(None)
    sparse_params, sparse_bytes = train({"embed": B})
    err = float(jnp.max(jnp.abs(dense_params["embed"]
                                - sparse_params["embed"])))
    return {"rank": rank, "dense_bytes": dense_bytes,
            "sparse_bytes": sparse_bytes, "max_err": err}


@pytest.mark.slow          # (15s) wire-bytes comparison; sparse path
@pytest.mark.integration   # itself stays via test_allreduce_sparse_two_process
def test_sparse_optimizer_beats_dense_on_wire_bytes():
    from horovod_tpu.runner import run
    results = run(_worker_sparse_optimizer, np=2, env=_mp_env())
    for r in results:
        assert r["max_err"] < 1e-6, r
        # embed leaf: dense ships V*Dm floats/step; sparse ships B*(Dm+1)
        assert r["sparse_bytes"] < r["dense_bytes"] / 5, r


def _worker_join_np4():
    """np=4 eager allreduce + join protocol (VERDICT r5: the cross-process
    engine protocol was only validated at np=2): rank r runs r+1 reduction
    rounds then joins, so every round k sees ranks {k..3} live and joined
    ranks matching with zero substitutes."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank, size = hvd.rank(), hvd.size()
    sums = []
    for k in range(rank + 1):
        out = np.asarray(hvd.allreduce(np.ones(3) * (rank + 1),
                                       name=f"j{k}", op=hvd.Sum))
        sums.append(float(out[0]))
    last = hvd.join()
    return {"rank": rank, "sums": sums, "last": last}


@pytest.mark.integration
def test_four_process_allreduce_join():
    from horovod_tpu.runner import run
    results = run(_worker_join_np4, np=4, env=_mp_env())
    # round k is live for ranks >= k: sum of (r+1) over r in {k..3}
    expect = [10.0, 9.0, 7.0, 4.0]
    for r in results:
        assert r["sums"] == expect[:r["rank"] + 1], r
        # rank 3 ran the most rounds, so it joins last (deterministic on
        # every rank)
        assert r["last"] == 3, r


def _worker_sparse():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    r = hvd.rank()
    # rank 0 touches rows {1, 3}; rank 1 touches rows {3, 5}
    idx = np.array([1, 3]) if r == 0 else np.array([3, 5])
    val = np.full((2, 2), float(r + 1), np.float32)
    u, c = hvd.allreduce_sparse(idx, val, n_rows=8, average=False)
    return u.tolist(), c[:, 0].tolist()


@pytest.mark.integration
def test_allreduce_sparse_two_process():
    from horovod_tpu.runner import run
    results = run(_worker_sparse, np=2, env=_mp_env())
    for u, c in results:
        assert u == [1, 3, 5], u
        assert c == [1.0, 3.0, 2.0], c   # row 3 = 1 (r0) + 2 (r1)


def _worker_sharded_prefetch_bump():
    """ISSUE 6 tentpole at np=2: staged overlap + ZeRO-1 all-gather
    prefetch across two REAL processes, with an elastic world-version bump
    mid-run. The prefetch must invalidate (counter moves, stepping
    continues, trajectory stays in lockstep with the replicated dense
    optimizer) — never poison."""
    import os
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    eng = hvd._engine()
    rank = hvd.rank()

    def ctr(name):
        return hvd_metrics.counter_total(hvd_metrics.snapshot(), name)

    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}

    def loss(p, x):
        return jnp.sum((x @ p["w"] + p["b"]) ** 2)

    grad_fn = jax.jit(jax.grad(loss))
    x = jnp.ones((2, 4)) * (rank + 1)
    # dense replicated reference (same cross-rank averaged gradients);
    # plain sgd keeps the (divergent-lr) trajectory small enough that the
    # two paths' fp rounding stays under the absolute tolerance, the
    # test_chained_eager_optimizer_no_host_blocks convention
    dopt = DistributedEagerOptimizer(optax.sgd(0.1))
    dp, ds = dict(params), dopt.init(params)
    for _ in range(10):
        dp, ds = dopt.update_and_apply(grad_fn(dp, x), ds, dp)
    jax.block_until_ready(dp["w"])
    # sharded + staged overlap + prefetch (env forces staged; join is
    # disabled in this worker's env so replay stays staged at np=2)
    sopt = DistributedEagerOptimizer(optax.sgd(0.1), sharded=True)
    sp, ss = dict(params), sopt.init(params)
    for _ in range(5):
        sp, ss = sopt.update_and_apply(grad_fn(sp, x), ss, sp)
    held_before = len(eng._zero1_prefetch)
    inval0 = ctr("hvd_tpu_overlap_prefetch_invalidations_total")
    # every rank observes the same bump at its next step_begin
    os.environ["HOROVOD_TPU_WORLD_VERSION"] = str(eng.world_version + 2)
    for _ in range(5):
        sp, ss = sopt.update_and_apply(grad_fn(sp, x), ss, sp)
    jax.block_until_ready(sp["w"])
    err = float(max(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                    for a, b in zip(jax.tree_util.tree_leaves(dp),
                                    jax.tree_util.tree_leaves(sp))))
    return {"rank": rank, "err": err,
            "prefetch_legs": ctr("hvd_tpu_overlap_prefetch_total"),
            "held_before_bump": held_before,
            "invalidations": (
                ctr("hvd_tpu_overlap_prefetch_invalidations_total")
                - inval0),
            "replayed": eng.replay.replayed_steps,
            "w": np.asarray(sp["w"]).tolist()}


@pytest.mark.integration
def test_sharded_prefetch_survives_world_version_bump():
    """np=2 trajectory parity for the prefetched all-gather across an
    elastic world-version bump (ISSUE 6 acceptance): prefetch legs were
    actually launched and held, the bump invalidated them, and the
    post-bump trajectory still matches the replicated dense optimizer."""
    from horovod_tpu.runner import run
    env = dict(_mp_env())
    env["HOROVOD_JOIN_DISABLE"] = "1"
    env["HOROVOD_TPU_OVERLAP_PIPELINE"] = "staged"
    r0, r1 = run(_worker_sharded_prefetch_bump, np=2, env=env)
    for r in (r0, r1):
        assert r["err"] < 1e-5, r
        assert r["prefetch_legs"] > 0, r
        assert r["held_before_bump"] > 0, r
        assert r["invalidations"] >= 1, r
    # averaged gradients -> replicas stay in lockstep
    assert r0["w"] == r1["w"]


# ---------------------------------------------------------------------------
# ISSUE 9: durable checkpoint N→M reshard parity across a REAL np=2 world
# ---------------------------------------------------------------------------

def _worker_ckpt_train():
    """Five committed training steps over averaged deterministic grads
    with the durable tier on (HOROVOD_TPU_CHECKPOINT_DIR in the env):
    every commit() also writes this rank's 1/2 byte shard + its peer
    replica. Returns the final params for the parity check."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.core.state import global_state

    state = hvd.elastic.TPUState(
        params={"w": np.zeros(13, np.float32)}, batch=0)
    state.sync()
    while state.batch < 5:
        g = np.asarray(hvd.allreduce(
            np.arange(13, dtype=np.float32) * (state.batch + 1),
            name=f"ckpt.g{state.batch}", op=hvd.Average))
        state.params = {"w": np.asarray(state.params["w"]) - 0.01 * g}
        state.batch += 1
        state.commit()
    mgr = global_state().checkpoint_manager
    assert mgr is not None, "checkpoint manager was not wired"
    assert mgr.wait_idle(60), "durable writes never drained"
    return {"w": np.asarray(state.params["w"]).tolist(),
            "last_step": mgr.last_written_step}


@pytest.mark.integration
def test_np2_checkpoint_reshard_restore_parity(tmp_path):
    """Acceptance (ISSUE 9): a checkpoint generation written by a REAL
    np=2 world — each rank writing only its byte shard plus the peer
    replica — restores at np=1 (an elastic downsize) to BITWISE the
    committed parameters, and survives losing either rank's disk."""
    import numpy as np
    from horovod_tpu.checkpoint import CheckpointManager, manifest as mf
    from horovod_tpu.runner import run

    ckpt_dir = str(tmp_path / "ckpt")
    env = dict(_mp_env())
    env["HOROVOD_TPU_CHECKPOINT_DIR"] = ckpt_dir
    r0, r1 = run(_worker_ckpt_train, np=2, env=env)
    assert r0["w"] == r1["w"]           # averaged grads keep replicas equal
    assert r0["last_step"] == 5

    template = {"pytrees": {"params": {"w": np.zeros(13, np.float32)}}}
    m = CheckpointManager(ckpt_dir, rank=0, world_size=1)
    try:
        # the np=2 commit barrier holds on disk
        found = m.latest_generation()
        assert found is not None and found[0] == 5
        ok, errs = mf.generation_complete(found[1])
        assert ok, errs
        assert found[1][0]["world_size"] == 2
        res = m.restore_latest(template=template)
        np.testing.assert_array_equal(
            res.tree["pytrees"]["params"]["w"],
            np.asarray(r0["w"], np.float32))
        assert res.extras.get("batch") == 5
    finally:
        m.close(flush=False)

    # lose either host's disk: the survivor's replica still restores the
    # np=1 world (peer-redundant placement, no blob storage)
    import shutil
    shutil.rmtree(os.path.join(ckpt_dir, "rank1"))
    m = CheckpointManager(ckpt_dir, rank=0, world_size=1)
    try:
        res = m.restore_latest(template=template)
        np.testing.assert_array_equal(
            res.tree["pytrees"]["params"]["w"],
            np.asarray(r0["w"], np.float32))
    finally:
        m.close(flush=False)


def _worker_algo_parity():
    """Force the collective-algorithm knob to every value IN-PROCESS (one
    np=2 world, four forcings — the knob is re-read per call) and assert
    every collective kind stays exact under each lowering. At np=2 the
    forced 'hierarchical' has no non-trivial factorization and must
    DEMOTE to flat (warning, never a crash) — the ISSUE 10 satellite's
    degradation contract exercised on a real world."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank, size = hvd.rank(), hvd.size()
    eng = hvd._engine()
    for algo in ("auto", "flat", "tree", "hierarchical"):
        eng.config.collective_algo = algo
        eng.replay.invalidate_all(f"force {algo}")
        x = np.arange(8.0, dtype=np.float32) * (rank + 1)
        out = np.asarray(hvd.allreduce(x, name=f"ar.{algo}", op=hvd.Sum))
        np.testing.assert_allclose(out, np.arange(8.0) * 3.0, rtol=1e-6)
        g0, g1 = hvd.grouped_allreduce([x, x + 1.0], name=f"g.{algo}",
                                       op=hvd.Sum)
        np.testing.assert_allclose(np.asarray(g0),
                                   np.arange(8.0) * 3.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(g1),
                                   np.arange(8.0) * 3.0 + 2.0, rtol=1e-6)
        g = np.asarray(hvd.allgather(np.array([float(rank)]),
                                     name=f"ag.{algo}"))
        np.testing.assert_allclose(g, np.arange(float(size)))
        rs = np.asarray(hvd.reducescatter(
            np.ones((size, 3), np.float32) * (rank + 1),
            name=f"rs.{algo}"))
        np.testing.assert_allclose(rs, np.full((1, 3), 3.0))
    snap = hvd.metrics_snapshot()
    algos_seen = {
        (l.get("kind"), l.get("algo"))
        for l, _ in snap["counters"].get(
            "hvd_tpu_collective_algo_total", {"values": []})["values"]}
    links_seen = {
        l.get("link")
        for l, _ in snap["counters"]["hvd_tpu_wire_bytes_total"]["values"]}
    return {"rank": rank, "algos": sorted(map(list, algos_seen)),
            "links": sorted(links_seen)}


@pytest.mark.integration
def test_two_process_forced_algo_parity():
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_algo_parity, np=2, env=_mp_env())
    for r in (r0, r1):
        algos = {tuple(a) for a in r["algos"]}
        # forced tree really ran as tree; forced hierarchical demoted to
        # flat at np=2 (no non-trivial factorization) — so no
        # hierarchical selection may appear
        assert ("allreduce", "tree") in algos, algos
        assert ("allreduce", "flat") in algos, algos
        assert not any(a == "hierarchical" for _, a in algos), algos
        # every wire byte carries the fabric-link label
        assert r["links"] == ["flat"], r["links"]


def _worker_hetero_topology():
    """Ranks 0-1 hold a LOCAL topology view that factorizes
    (local_size=2), ranks 2-3 the flat launcher view (local_size=4 ==
    world): auto selection of a large bucket must NOT deadlock on a
    rank-divergent entry into the homogeneity exchange — every rank
    enters it at the first selection, the non-uniform local sizes agree
    on "no hierarchy", and everyone lowers flat (the code-review
    deadlock regression for Engine._choose_algo; the divergent view is
    installed on the live engine because hvd.init() runs before worker
    bodies, exactly how a heterogeneous host assignment would diverge)."""
    import dataclasses
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    eng = hvd._engine()
    if hvd.rank() < 2:
        eng.topology = dataclasses.replace(eng.topology, local_size=2)
        assert eng.topology.hierarchical_ok     # genuinely divergent view
    eng._hier_ok = None                          # agreement not yet run
    big = np.ones(128 * 1024, np.float32)    # 512 KB: past the tree band
    out = np.asarray(hvd.allreduce(big, name="het", op=hvd.Sum))
    np.testing.assert_allclose(out[:4], 4.0)
    snap = hvd.metrics_snapshot()
    algos = {
        (l.get("kind"), l.get("algo"))
        for l, _ in snap["counters"].get(
            "hvd_tpu_collective_algo_total", {"values": []})["values"]}
    return {"rank": hvd.rank(), "local": eng.topology.local_size,
            "hier_ok": bool(eng._hierarchical_ok()),
            "algos": sorted(map(list, algos))}


@pytest.mark.integration
def test_heterogeneous_topology_agrees_on_flat():
    from horovod_tpu.runner import run
    results = run(_worker_hetero_topology, np=4, env=_mp_env())
    locals_seen = sorted(r["local"] for r in results)
    assert locals_seen == [2, 2, 4, 4], locals_seen   # views really diverged
    for r in results:
        assert r["hier_ok"] is False, r                # uniform agreement
        assert not any(a == "hierarchical" for _, a in map(tuple, r["algos"])), r


# ---------------------------------------------------------------------------
# ISSUE 13: link-aware gradient compression acceptance
# ---------------------------------------------------------------------------


def _worker_compression_trajectory():
    """np=2 trajectory acceptance (ISSUE 13): the int8 error-feedback
    codec trains to the "none" loss trajectory within the documented
    tolerance, while codec "none" stays BITWISE identical to the
    pre-codec path; residual buffers live in engine state and replay
    arms over the compressed stream."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    eng = hvd._engine()
    rank = hvd.rank()

    def ctr(name):
        return hvd_metrics.counter_total(hvd_metrics.snapshot(), name)

    params = {"w": jnp.ones((8, 8)), "b": jnp.zeros((8,))}

    def loss(p, x):
        return jnp.sum((x @ p["w"] + p["b"]) ** 2)

    grad_fn = jax.jit(jax.grad(loss))
    x = jnp.ones((2, 8)) * (rank + 1) * 0.1

    def train(compression, steps=10):
        opt = DistributedEagerOptimizer(optax.sgd(0.05),
                                        compression=compression)
        p, s = dict(params), opt.init(params)
        for _ in range(steps):
            p, s = opt.update_and_apply(grad_fn(p, x), s, p)
        jax.block_until_ready(p["w"])
        return p

    def dist(a, b):
        return float(max(np.max(np.abs(np.asarray(u) - np.asarray(v)))
                         for u, v in zip(jax.tree_util.tree_leaves(a),
                                         jax.tree_util.tree_leaves(b))))

    p_none = train(hvd.Compression.none)
    # bitwise: a second "none" run (codec machinery resolved but off)
    # reproduces the first exactly
    p_none2 = train(hvd.Compression.none)
    sel0 = ctr("hvd_tpu_compression_codec_total")
    p_int8 = train(hvd.Compression.int8)
    return {"rank": rank,
            "bitwise_none": dist(p_none, p_none2) == 0.0,
            "err_int8": dist(p_none, p_int8),
            "codec_selections": ctr("hvd_tpu_compression_codec_total")
            - sel0,
            "bytes_saved": ctr("hvd_tpu_compression_bytes_saved_total"),
            "residuals_held": len(eng._ef_residuals),
            "replayed": eng.replay.replayed_steps,
            "w": np.asarray(p_int8["w"]).tolist()}


@pytest.mark.integration
def test_np2_compression_trajectory_parity():
    from horovod_tpu.runner import run
    env = dict(_mp_env())
    env["HOROVOD_JOIN_DISABLE"] = "1"
    r0, r1 = run(_worker_compression_trajectory, np=2, env=env)
    for r in (r0, r1):
        assert r["bitwise_none"], r
        # documented tolerance (docs/compression.md): int8 EF on this
        # convex problem tracks the uncompressed trajectory to ~1e-3
        assert r["err_int8"] < 1e-3, r
        assert r["codec_selections"] > 0, r
        assert r["bytes_saved"] > 0, r
        assert r["residuals_held"] > 0, r
        assert r["replayed"] > 0, r       # replay armed over the codec
    assert r0["w"] == r1["w"]             # lockstep across ranks


def _worker_compression_dcn_drop():
    """np=4 hierarchical acceptance (ISSUE 13): with local_size=2 and
    the int8 codec, link-labeled wire_bytes{link="dcn"} drops >= 3x vs
    codec none at unchanged ICI bytes, and the compressed sum stays
    within the quantization error bound."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu import metrics as hvd_metrics

    eng = hvd._engine()
    rank = hvd.rank()
    assert eng.topology.local_size == 2
    assert eng._hierarchical_ok()

    def link_val(snap, link):
        ent = snap.get("counters", {}).get("hvd_tpu_wire_bytes_total")
        if not ent:
            return 0.0
        return sum(v for l, v in ent["values"]
                   if l.get("link") == link
                   and l.get("kind") == "grouped_allreduce")

    elems = 1 << 18   # 1 MiB fp32: past the tree band -> hierarchical
    x = jnp.asarray(
        np.random.RandomState(rank).randn(elems).astype(np.float32))
    exact = sum(np.random.RandomState(r).randn(elems).astype(np.float32)
                for r in range(4))
    m0 = hvd_metrics.snapshot()
    out_none = np.asarray(
        hvd.grouped_allreduce([x], name="cmp.none", op=hvd.Sum)[0])
    m1 = hvd_metrics.snapshot()
    eng.config.compression = "int8"
    try:
        h = eng.grouped_allreduce([x], name="cmp.i8",
                                  op=hvd.ReduceOp.SUM)
        out_i8 = np.asarray(h[0].synchronize())
    finally:
        eng.config.compression = "none"
    m2 = hvd_metrics.snapshot()
    return {"rank": rank,
            "dcn_none": link_val(m1, "dcn") - link_val(m0, "dcn"),
            "dcn_i8": link_val(m2, "dcn") - link_val(m1, "dcn"),
            "ici_none": link_val(m1, "ici") - link_val(m0, "ici"),
            "ici_i8": link_val(m2, "ici") - link_val(m1, "ici"),
            "err_none": float(np.abs(out_none - exact).max()),
            "err_i8": float(np.abs(out_i8 - exact).max())}


@pytest.mark.integration
def test_np4_compression_dcn_drop_hierarchical():
    from horovod_tpu.runner import run
    env = dict(_mp_env())
    env["HOROVOD_JOIN_DISABLE"] = "1"
    env["HOROVOD_TPU_LOCAL_SIZE"] = "2"
    results = run(_worker_compression_dcn_drop, np=4, env=env)
    for r in results:
        assert r["dcn_none"] >= 3 * r["dcn_i8"] > 0, r   # >= 3x drop
        assert r["ici_none"] == r["ici_i8"] > 0, r       # ICI unchanged
        assert r["err_none"] < 1e-3, r
        assert r["err_i8"] < 0.5, r   # bounded quantization error


def _worker_calibrated_selection():
    """ISSUE 14 acceptance: np=2 with probing ON — the init-time link
    probe runs rank-collectively, the fitted model rides the agreement
    exchange, and every rank derives the SAME calibrated thresholds and
    the SAME per-bucket algorithm choice (selection determinism, the
    divcheck invariant, now over measured inputs)."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank = hvd.rank()
    eng = hvd._engine()
    topo = eng.topology
    # the per-bucket selection the engine would make across the band a
    # real step's fusion buckets span
    sizes = [4 * 1024, 64 * 1024, 1024 ** 2, 8 * 1024 ** 2]
    choices = [eng._choose_algo("allreduce", s) for s in sizes]
    # calibrated selection must still be EXACT end to end
    x = np.arange(8.0, dtype=np.float32) * (rank + 1)
    out = np.asarray(hvd.allreduce(x, name="cal.ar", op=hvd.Sum))
    np.testing.assert_allclose(out, np.arange(8.0) * 3.0, rtol=1e-6)
    g0, g1 = hvd.grouped_allreduce([x, x + 1.0], name="cal.g",
                                   op=hvd.Sum)
    np.testing.assert_allclose(np.asarray(g0), np.arange(8.0) * 3.0,
                               rtol=1e-6)
    return {"rank": rank,
            "calibrated": topo.calibrated,
            "describe": topo.describe(),
            "choices": choices,
            "tree_thr": eng.config.tree_threshold_bytes,
            "hier_thr": eng.config.hier_threshold_bytes,
            "model_sig": eng.model_signature()}


@pytest.mark.integration
def test_np2_calibrated_selection_deterministic():
    from horovod_tpu.runner import run
    env = dict(_mp_env())
    env["HOROVOD_TPU_CALIBRATE"] = "1"
    r0, r1 = run(_worker_calibrated_selection, np=2, env=env)
    # the probe ran and the measured overlay is installed on both ranks
    assert r0["calibrated"] and r1["calibrated"]
    # every rank fitted the IDENTICAL model (the agreement exchange) and
    # therefore derives identical thresholds and identical per-bucket
    # algorithm choices — bit-equality, not approximate
    assert r0["describe"] == r1["describe"]
    assert r0["choices"] == r1["choices"]
    assert r0["tree_thr"] == r1["tree_thr"]
    assert r0["hier_thr"] == r1["hier_thr"]
    # the frozen bucket-layout digest (the persistence key) agrees too
    assert r0["model_sig"] == r1["model_sig"] is not None


def _worker_uneven_alltoall_wire_bytes():
    """ISSUE 17 satellite: the uneven alltoall pads every chunk to the
    world max inside the program, but wire accounting must book the
    SUBMITTED payload (x.nbytes, pre-padding) — and the splits exchange
    must go meta-cache hot on the repeat call with identical results."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.metrics import registry

    rank, size = hvd.rank(), hvd.size()

    def a2a_wire_bytes():
        ent = registry().snapshot()["counters"].get(
            "hvd_tpu_wire_bytes_total", {})
        return sum(v for l, v in ent.get("values", [])
                   if l.get("kind") == "alltoall")

    d = 3
    out = {"rank": rank}
    # rank 0 sends 1 row per peer, rank 1 sends 3 (max chunk 3: rank 0's
    # program pads 2 rows per chunk — those must NOT be counted)
    splits = [1 + 2 * rank] * size
    x = np.full((sum(splits), d), float(100 * rank), np.float32)
    base = a2a_wire_bytes()
    recv, counts = hvd.alltoall(x, splits=splits, name="uw.0")
    out["counts0"] = np.asarray(counts).tolist()
    out["recv0"] = np.asarray(recv)[:, 0].tolist()
    out["wire_delta"] = a2a_wire_bytes() - base
    out["payload_bytes"] = int(x.nbytes)
    out["padded_bytes"] = int(size * max(1 + 2 * r for r in range(size))
                              * d * 4)
    # repeat with the SAME splits: the cache goes hot at streak 2, after
    # which the sizes exchange costs zero blocking fetches and the
    # routing stays identical
    eng = hvd._engine()
    hvd.alltoall(x, splits=splits, name="uw.0")     # streak 2 -> hot
    f0 = eng.host_fetches
    recv2, counts2 = hvd.alltoall(x, splits=splits, name="uw.0")
    out["counts_repeat"] = np.asarray(counts2).tolist()
    out["recv_equal"] = bool(
        np.array_equal(np.asarray(recv), np.asarray(recv2)))
    out["extra_fetches"] = eng.host_fetches - f0
    return out


@pytest.mark.integration
def test_uneven_alltoall_padding_not_counted_as_wire_bytes():
    from horovod_tpu.runner import run
    r0, r1 = run(_worker_uneven_alltoall_wire_bytes, np=2, env=_mp_env())
    for r in (r0, r1):
        # submitted-payload accounting: exactly x.nbytes, and the padded
        # program is strictly bigger, so the distinction is observable
        assert r["wire_delta"] == r["payload_bytes"], r
        assert r["padded_bytes"] >= r["payload_bytes"]
        assert r["recv_equal"], r
        # hot meta cache: the repeat call's splits exchange costs zero
        # blocking host fetches
        assert r["extra_fetches"] == 0, r
    # rank 0 ships 24 B against a 72 B padded program: the 48 B of
    # padding must be invisible to the wire counter
    assert r0["padded_bytes"] > r0["payload_bytes"]
    # recv splits through the exchanged matrix: recv_splits[r] = sender
    # r's split for me — rank0 receives [1, 3], rank1 receives [1, 3]
    assert r0["counts0"] == [1, 3] and r1["counts0"] == [1, 3]
    assert r0["counts_repeat"] == r0["counts0"]
    assert r0["recv0"] == [0.0] * 1 + [100.0] * 3, r0
    assert r1["recv0"] == [0.0] * 1 + [100.0] * 3, r1


def _worker_noop_teardown():
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    return hvd.rank()


@pytest.mark.integration
def test_static_world_teardown_has_no_shutdown_order_stall():
    """Static (non-recoverable) worlds must tear down through the
    coordination service's own shutdown barrier, NOT the elastic KV
    ordering protocol: with the barrier present, a non-zero rank's
    jax.distributed.shutdown() blocks inside the barrier until rank 0
    enters it, so the KV flag could only ever be posted after rank 0
    exhausted the full ordering deadline — every np>1 run paid
    HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT (default 10 s) of dead wait at
    exit. With the deadline pinned far above the real teardown cost,
    finishing under it proves the KV wait never ran."""
    import time
    from horovod_tpu.runner import run
    env = _mp_env()
    env["HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT"] = "60"
    t0 = time.monotonic()
    r = run(_worker_noop_teardown, np=2, env=env)
    elapsed = time.monotonic() - t0
    assert sorted(r) == [0, 1]
    assert elapsed < 60, (
        f"teardown took {elapsed:.1f}s — the static world fell back to "
        "the elastic KV shutdown-ordering wait")
