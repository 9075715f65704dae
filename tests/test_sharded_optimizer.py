"""ZeRO-1 optimizer-state sharding (ISSUE 2 tentpole): sharded and
replicated training must produce identical parameter trajectories on the
CPU mesh — SPMD (``distributed(shard_optimizer=True)``) and eager
(``DistributedEagerOptimizer(sharded=True)``) — and the eager sharded path
must go through step-capture replay with a single dispatch per step.

The MLP's leaves (512 + 32 + 128 + 4 floats) deliberately do NOT divide
the 8-rank world, so every test also exercises the divisibility padding
(ops/collectives.shard_spec).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from jax import shard_map
from horovod_tpu import optimizer as hopt
from horovod_tpu.optimizer import (DistributedEagerOptimizer,
                                   ShardedEagerState, zero1_state_specs)
from horovod_tpu.models.mlp import init_mlp, mlp_loss
from horovod_tpu.ops.compression import Compression


def _params():
    return init_mlp(jax.random.PRNGKey(0), sizes=(16, 32, 4))


def _batch(n=64, din=16, nclass=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, din).astype(np.float32),
            rng.randint(0, nclass, size=(n,)).astype(np.int32))


# ---------------------------------------------------------------------------
# SPMD path
# ---------------------------------------------------------------------------


def _spmd_train(dist, params, x, y, mesh, state_specs, steps=4,
                init_inside=False):
    def local_step(p, s, xb, yb):
        g = jax.grad(mlp_loss)(p, (xb, yb))
        u, s = dist.update(g, s, p)
        return optax.apply_updates(p, u), s

    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), state_specs, P("world"), P("world")),
        out_specs=(P(), state_specs), check_vma=False))
    sh = NamedSharding(mesh, P("world"))
    xb, yb = jax.device_put(x, sh), jax.device_put(y, sh)
    p = jax.device_put(params, NamedSharding(mesh, P()))
    if init_inside:
        s = jax.jit(shard_map(dist.init, mesh=mesh, in_specs=(P(),),
                              out_specs=state_specs, check_vma=False))(p)
    else:
        s = dist.init(p)
    for _ in range(steps):
        p, s = step(p, s, xb, yb)
    return p


@pytest.mark.parametrize("shard_optimizer", [False, True],
                         ids=["dense", "zero1"])
def test_spmd_update_is_global_mean_gradient(shard_optimizer):
    """jax.grad and hvd_opt.distributed inside ONE shard_map (the README's
    SPMD quickstart): with sgd(1.0) the first update must be minus the
    gradient of the whole batch. Under check_vma=False — what a shard_map
    holding a Pallas kernel has to use on the chip — nothing is tracked,
    jax.grad inserts no psum, and the gradients are local: taking them for
    pre-summed skipped the allreduce (ISSUE 21; chip_smoke.py's quickstart
    phase is the chip-side check)."""
    mesh = Mesh(np.array(jax.devices()), ("world",))
    params = _params()
    x, y = _batch()
    dist = hopt.distributed(optax.sgd(1.0), axis_name="world",
                            op=hvd.Average, axis_size=8,
                            shard_optimizer=shard_optimizer)
    specs = (zero1_state_specs(jax.eval_shape(dist.init, params), "world")
             if shard_optimizer else P())
    got = _spmd_train(dist, params, x, y, mesh, specs, steps=1,
                      init_inside=shard_optimizer)
    want = jax.tree_util.tree_map(
        lambda p, g: p - g, params, jax.grad(mlp_loss)(params, (x, y)))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("make_inner", [
    lambda: optax.adam(1e-2),
    lambda: optax.sgd(0.05, momentum=0.9),
], ids=["adam", "sgd_momentum"])
def test_spmd_sharded_matches_dense(make_inner):
    """The numeric acceptance bar: sharded (rs -> shard update -> ag) and
    replicated (allreduce -> full update) trajectories match on the 8-dev
    CPU mesh, including the non-divisible bucket padding."""
    mesh = Mesh(np.array(jax.devices()), ("world",))
    params = _params()
    x, y = _batch()

    dense = hopt.distributed(make_inner(), axis_name="world", op=hvd.Average)
    dp = _spmd_train(dense, params, x, y, mesh, P())

    zer = hopt.distributed(make_inner(), axis_name="world", op=hvd.Average,
                           axis_size=8, shard_optimizer=True)
    zspecs = zero1_state_specs(jax.eval_shape(zer.init, params), "world")
    zp = _spmd_train(zer, params, x, y, mesh, zspecs, init_inside=True)

    for a, b in zip(jax.tree_util.tree_leaves(dp),
                    jax.tree_util.tree_leaves(zp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_spmd_sharded_init_outside_axis_matches():
    """init() outside the mesh axis materializes zero shard placeholders —
    exact for the zeros-initialized elementwise inner family, so the
    trajectory still matches dense."""
    mesh = Mesh(np.array(jax.devices()), ("world",))
    params = _params()
    x, y = _batch(seed=2)
    dense = hopt.distributed(optax.adam(1e-2), axis_name="world",
                             op=hvd.Average)
    dp = _spmd_train(dense, params, x, y, mesh, P())
    zer = hopt.distributed(optax.adam(1e-2), axis_name="world",
                           op=hvd.Average, axis_size=8, shard_optimizer=True)
    zspecs = zero1_state_specs(jax.eval_shape(zer.init, params), "world")
    # outside-axis init: shard-shaped zeros, replicated in -> the step's
    # in_specs then see identical (zero) shards on each rank, which is the
    # true per-shard init for adam/sgd
    st = zer.init(params)
    st = jax.tree_util.tree_map(lambda l: np.asarray(l), st)

    def local_step(p, s, xb, yb):
        g = jax.grad(mlp_loss)(p, (xb, yb))
        u, s = zer.update(g, s, p)
        return optax.apply_updates(p, u), s

    # state travels replicated here (every rank holds the same zeros at
    # t=0 and evolves its own copy of its shard thereafter — but with P()
    # out-specs the per-rank shards would be merged; use the stacked specs
    # by lifting the zero shards to the stacked global layout instead)
    def lift(l):
        # scalars stay replicated (zero1_state_specs rule); shard arrays
        # stack 8 identical zero shards into the P("world") global layout
        if getattr(l, "ndim", 0) == 0:
            return jnp.asarray(l)
        return jnp.tile(jnp.asarray(l), (8,) + (1,) * (l.ndim - 1))

    st = jax.tree_util.tree_map(lift, st)
    step = jax.jit(shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), zspecs, P("world"), P("world")),
        out_specs=(P(), zspecs), check_vma=False))
    sh = NamedSharding(mesh, P("world"))
    xb, yb = jax.device_put(x, sh), jax.device_put(y, sh)
    p = jax.device_put(params, NamedSharding(mesh, P()))
    for _ in range(4):
        p, st = step(p, st, xb, yb)
    for a, b in zip(jax.tree_util.tree_leaves(dp),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_spmd_sharded_validation():
    with pytest.raises(ValueError, match="axis_size"):
        hopt.distributed(optax.adam(1e-2), shard_optimizer=True)
    with pytest.raises(ValueError, match="Average|Sum"):
        hopt.distributed(optax.adam(1e-2), shard_optimizer=True,
                         axis_size=8, op=hvd.Adasum)
    with pytest.raises(ValueError, match="compression"):
        hopt.distributed(optax.adam(1e-2), shard_optimizer=True,
                         axis_size=8, compression=Compression.bf16)
    with pytest.raises(ValueError, match="backward_passes_per_step"):
        hopt.distributed(optax.adam(1e-2), shard_optimizer=True,
                         axis_size=8, backward_passes_per_step=2)


# ---------------------------------------------------------------------------
# Eager path
# ---------------------------------------------------------------------------


@pytest.fixture()
def engine():
    hvd.init()
    eng = hvd._engine()
    prev_warm, prev_on = (eng.config.step_replay_warmup,
                          eng.config.step_replay)
    eng.config.step_replay_warmup = 2
    eng.config.step_replay = True
    eng.replay.invalidate_all("test isolation")
    yield eng
    eng.replay.invalidate_all("test isolation")
    eng.config.step_replay_warmup = prev_warm
    eng.config.step_replay = prev_on


def _eager_train(opt, params, x, y, steps):
    grad_fn = jax.jit(jax.grad(mlp_loss))
    p, s = params, opt.init(params)
    for _ in range(steps):
        g = grad_fn(p, (jnp.asarray(x), jnp.asarray(y)))
        p, s = opt.update_and_apply(g, s, p)
    jax.block_until_ready(p)
    return p, s


def test_eager_sharded_matches_dense(engine):
    params = _params()
    x, y = _batch(seed=5)
    dp, _ = _eager_train(DistributedEagerOptimizer(optax.adam(1e-2)),
                         params, x, y, 5)
    sp, ss = _eager_train(
        DistributedEagerOptimizer(optax.adam(1e-2), sharded=True),
        params, x, y, 5)
    assert isinstance(ss, ShardedEagerState)
    for a, b in zip(jax.tree_util.tree_leaves(dp),
                    jax.tree_util.tree_leaves(sp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eager_sharded_state_layout(engine):
    """init materializes shard-sized state: one flat master-copy shard of
    ceil(total/world) per fusion bucket, inner state over the shards."""
    params = _params()
    opt = DistributedEagerOptimizer(optax.adam(1e-2), sharded=True)
    st = opt.init(params)
    size = engine.backend.size()
    total = sum(int(np.prod(l.shape))
                for l in jax.tree_util.tree_leaves(params))
    assert len(st.shards) == 1  # everything fits one 64 MB bucket
    assert st.shards[0].shape == (-(-total // size),)
    # adam: mu/nu mirror the shard vectors, not the tensor shapes
    mu_leaves = jax.tree_util.tree_leaves(st.inner_state)
    assert any(l.shape == st.shards[0].shape for l in mu_leaves)


def test_eager_sharded_replay_single_dispatch(engine):
    """Acceptance bar: the sharded eager step goes through replay with
    engine.dispatch_count of 1 per steady-state step."""
    params = _params()
    x, y = _batch(seed=6)
    opt = DistributedEagerOptimizer(optax.sgd(0.05, momentum=0.9),
                                    sharded=True)
    grad_fn = jax.jit(jax.grad(mlp_loss))
    p, s = params, opt.init(params)
    for _ in range(4):  # warmup=2: record, record, arm+replay...
        g = grad_fn(p, (jnp.asarray(x), jnp.asarray(y)))
        p, s = opt.update_and_apply(g, s, p)
    jax.block_until_ready(p)
    assert engine.replay.replayed_steps >= 1
    g = grad_fn(p, (jnp.asarray(x), jnp.asarray(y)))
    d0 = engine.dispatch_count
    p, s = opt.update_and_apply(g, s, p)
    assert engine.dispatch_count - d0 == 1, \
        "a steady-state sharded step must be ONE engine dispatch"
    jax.block_until_ready(p)
    # and the replayed step still matches the recorded path numerically
    dp, _ = _eager_train(
        DistributedEagerOptimizer(optax.sgd(0.05, momentum=0.9)),
        params, x, y, 5)
    for a, b in zip(jax.tree_util.tree_leaves(dp),
                    jax.tree_util.tree_leaves(p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


def test_eager_sharded_with_accumulation(engine):
    """backward_passes_per_step composes with sharding (accumulation is
    host-side, before the reduce-scatter)."""
    params = _params()
    x, y = _batch(seed=7)
    grad_fn = jax.jit(jax.grad(mlp_loss))

    dense = DistributedEagerOptimizer(optax.sgd(0.1),
                                      backward_passes_per_step=2)
    shard = DistributedEagerOptimizer(optax.sgd(0.1),
                                      backward_passes_per_step=2,
                                      sharded=True)
    dp, ds = params, dense.init(params)
    sp, ss = params, shard.init(params)
    for _ in range(4):
        g = grad_fn(dp, (jnp.asarray(x), jnp.asarray(y)))
        dp, ds = dense.update_and_apply(g, ds, dp)
        g = grad_fn(sp, (jnp.asarray(x), jnp.asarray(y)))
        sp, ss = shard.update_and_apply(g, ss, sp)
    for a, b in zip(jax.tree_util.tree_leaves(dp),
                    jax.tree_util.tree_leaves(sp)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eager_sharded_validation(engine):
    with pytest.raises(ValueError, match="compression"):
        DistributedEagerOptimizer(optax.sgd(0.1), sharded=True,
                                  compression=Compression.bf16)
    with pytest.raises(ValueError, match="sparse_rows"):
        DistributedEagerOptimizer(optax.sgd(0.1), sharded=True,
                                  sparse_rows={"embed": 4})
    with pytest.raises(ValueError, match="Average|Sum"):
        DistributedEagerOptimizer(optax.sgd(0.1), sharded=True,
                                  op=hvd.Adasum)
    # a non-sharded state fed to a sharded optimizer fails loudly
    params = _params()
    opt = DistributedEagerOptimizer(optax.sgd(0.1), sharded=True)
    dense_state = optax.sgd(0.1).init(params)
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    with pytest.raises(ValueError, match="non-sharded state"):
        opt.update_and_apply(g, dense_state, params)


def test_eager_sharded_survives_threshold_move(engine):
    """The bucket layout is FROZEN at state init: a live fusion-threshold
    move (autotune retunes it every sample) must neither crash nor
    re-bucket a sharded run — the cached layout keeps serving the live
    state."""
    params = _params()
    opt = DistributedEagerOptimizer(optax.sgd(0.1), sharded=True)
    st = opt.init(params)
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    prev = engine.config.fusion_threshold_bytes
    engine.config.fusion_threshold_bytes = 256  # would force tiny buckets
    try:
        p2, st2 = opt.update_and_apply(g, st, params)
        jax.block_until_ready(jax.tree_util.tree_leaves(p2)[0])
        assert st2.shards[0].shape == st.shards[0].shape
    finally:
        engine.config.fusion_threshold_bytes = prev


def test_eager_sharded_lost_layout_raises(engine):
    """If the frozen layout is genuinely gone (cache evicted across a
    threshold move — or state from another world size), the shape
    validation fails loudly instead of corrupting the shards."""
    params = _params()
    opt = DistributedEagerOptimizer(optax.sgd(0.1), sharded=True)
    st = opt.init(params)
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    prev = engine.config.fusion_threshold_bytes
    opt._layout_cache.clear()                   # simulate LRU eviction
    engine.config.fusion_threshold_bytes = 256  # recompute re-buckets
    try:
        with pytest.raises(ValueError, match="layout mismatch"):
            opt.update_and_apply(g, st, params)
    finally:
        engine.config.fusion_threshold_bytes = prev


def test_broadcast_optimizer_state_refuses_sharded(engine):
    """broadcast_optimizer_state on a ZeRO-1 state would overwrite every
    rank's distinct parameter-master shard with rank 0's — it must refuse
    loudly and point at the broadcast-params-then-reinit recipe."""
    params = _params()
    opt = DistributedEagerOptimizer(optax.adam(1e-2), sharded=True)
    st = opt.init(params)
    with pytest.raises(ValueError, match="rank-local shards"):
        hvd.broadcast_optimizer_state(st, root_rank=0)


def test_config_knob_defaults_sharded(engine, monkeypatch):
    """sharded=None defers to the HOROVOD_TPU_SHARD_OPTIMIZER-backed
    config (the autotune categorical's target)."""
    params = _params()
    monkeypatch.setattr(engine.config, "shard_optimizer", True)
    opt = DistributedEagerOptimizer(optax.sgd(0.1))
    st = opt.init(params)
    assert isinstance(st, ShardedEagerState)
    monkeypatch.setattr(engine.config, "shard_optimizer", False)
    opt2 = DistributedEagerOptimizer(optax.sgd(0.1))
    assert not isinstance(opt2.init(params), ShardedEagerState)
