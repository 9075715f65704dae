"""Compiled-program structure assertions (VERDICT r2 item 3).

Multi-chip perf can't be *measured* on this rig (one real chip), but the
*structure* of the compiled programs — the thing that determines collective
count and fusion on a real pod — can be asserted on the 8-virtual-device CPU
mesh: grouped_allreduce must compile to one collective per fusion bucket,
hierarchical allreduce must lower to the RS/AG ladder with node-local
``replica_groups``, EP dispatch must be a single all-to-all, and the SPMD
flagship step must contain gradient all-reduces at all.

Reference bar: fusion as *the* latency optimization
(controller.cc:652-773 FuseResponses); hierarchical decomposition
(nccl_operations.cc:180-383).
"""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.common.reduce_ops import ReduceOp
from horovod_tpu.ops import collectives as C


def _world_mesh(n=8):
    devs = jax.devices()[:n]
    return Mesh(np.array(devs), ("world",))


def _hlo(jitted, *args):
    return jitted.lower(*args).compile().as_text()


def _count(pattern, hlo):
    return len(re.findall(pattern, hlo))


def test_fused_allreduce_is_one_collective_per_bucket():
    """50 small tensors packed into one bucket -> exactly ONE all-reduce in
    the optimized HLO (the fusion-buffer guarantee)."""
    mesh = _world_mesh()
    shapes = tuple((7, 3) for _ in range(50))
    fn = C.build_fused_allreduce(mesh, "world", ReduceOp.SUM, shapes,
                                 jnp.float32, 1.0, 1.0, 0)
    total = sum(int(np.prod(s)) for s in shapes)
    packed = jnp.zeros((8, total), jnp.float32)  # stacked (n, total)
    garr = jax.device_put(packed, NamedSharding(mesh, P("world")))
    hlo = _hlo(fn, garr)
    n_ar = _count(r"all-reduce(?:-start)?\(", hlo)
    assert n_ar == 1, f"expected 1 fused all-reduce, found {n_ar}"


def test_bucketing_bounds_collective_count():
    """bucket_by_size: 20 tensors under a threshold that forces 4 buckets ->
    at most 4 collectives across the bucket programs."""
    from horovod_tpu.core.engine import bucket_by_size
    tensors = [jnp.ones((256,), jnp.float32) for _ in range(20)]
    # 256 floats = 1 KiB each; 5 KiB threshold -> 5 per bucket -> 4 buckets
    buckets = bucket_by_size(tensors, 5 * 1024)
    assert len(buckets) == 4
    mesh = _world_mesh()
    total_collectives = 0
    for idxs in buckets:
        shapes = tuple((256,) for _ in idxs)
        fn = C.build_fused_allreduce(mesh, "world", ReduceOp.SUM, shapes,
                                     jnp.float32, 1.0, 1.0, 0)
        packed = jax.device_put(
            jnp.zeros((8, 256 * len(idxs)), jnp.float32),
            NamedSharding(mesh, P("world")))
        total_collectives += _count(r"all-reduce(?:-start)?\(", _hlo(fn, packed))
    assert total_collectives == 4


def test_hierarchical_allreduce_lowers_to_ladder():
    """local_size=4 on 8 devices: reduce-scatter within node, all-reduce
    across nodes, all-gather back — with 2-node replica groups of size 4."""
    mesh = _world_mesh()
    fn = C.build_hierarchical_allreduce(mesh, "world", 4, ReduceOp.SUM,
                                        1.0, 1.0)
    x = jax.device_put(jnp.zeros((64,), jnp.float32),
                       NamedSharding(mesh, P()))
    hlo = _hlo(fn, x)
    # the RS/AG ladder: at least one reduce-scatter and one all-gather (XLA
    # may lower psum_scatter to reduce-scatter or all-reduce+slice depending
    # on backend; accept either spelling but require node-local groups)
    has_ladder = (_count(r"reduce-scatter", hlo) >= 1
                  or _count(r"all-reduce", hlo) >= 2)
    assert has_ladder, "hierarchical program collapsed to a flat all-reduce"
    assert _count(r"all-gather", hlo) >= 1, "missing all-gather stage"
    # node-local replica groups {0..3} {4..7} must appear somewhere
    local_groups = re.search(r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}",
                             hlo.replace(" ", ""))
    assert local_groups, "no node-local (0-3 / 4-7) replica groups in HLO"


def test_moe_dispatch_is_single_all_to_all():
    """EP token dispatch over the tensor axis: exactly one all-to-all each
    way (dispatch + return), not per-expert sends."""
    from horovod_tpu.parallel.moe import MoEParams, moe_layer_p
    n, d, e, f = 8, 16, 8, 32
    mesh = _world_mesh()
    router = jnp.zeros((d, e), jnp.float32)
    w1 = jnp.zeros((e, d, f), jnp.float32)
    w2 = jnp.zeros((e, f, d), jnp.float32)

    def body(tok, router, w1, w2):
        y, aux = moe_layer_p(tok, MoEParams(router, w1, w2), "world", n,
                             capacity_factor=2.0)
        return y, jax.lax.pmean(aux, "world")

    tok_sh = NamedSharding(mesh, P("world"))
    rep = NamedSharding(mesh, P())
    ep_sh = NamedSharding(mesh, P("world"))
    import functools
    from jax import shard_map
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P("world"), P(), P("world"), P("world")),
        out_specs=(P("world"), P())))
    tok = jax.device_put(jnp.zeros((n * 4, d), jnp.float32), tok_sh)
    hlo = _hlo(fn, tok, jax.device_put(router, rep),
               jax.device_put(w1, ep_sh), jax.device_put(w2, ep_sh))
    n_a2a = _count(r"all-to-all(?:-start)?\(", hlo)
    assert 1 <= n_a2a <= 2, f"EP dispatch should be 1-2 all-to-alls, got {n_a2a}"


def test_flagship_spmd_step_contains_gradient_reduction():
    """The flagship transformer train step over (data=2, seq=2, tensor=2)
    compiles with collective ops present (the gradient psum the reference
    implements as NCCLAllreduce)."""
    import optax
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params, make_train_step,
                                                shard_params)
    devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "seq", "tensor"))
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=16, dtype=jnp.float32)
    params = shard_params(init_params(jax.random.PRNGKey(0), cfg), mesh, cfg)
    opt = optax.sgd(0.01)
    step = make_train_step(mesh, cfg, opt)
    tok = jax.device_put(jnp.zeros((4, 16), jnp.int32),
                         NamedSharding(mesh, P("data", "seq")))
    opt_state = opt.init(params)
    hlo = step.lower(params, opt_state, tok, tok).compile().as_text()
    n_coll = (_count(r"all-reduce", hlo) + _count(r"reduce-scatter", hlo)
              + _count(r"all-gather", hlo) + _count(r"collective-permute", hlo))
    assert n_coll >= 3, f"expected gradient/activation collectives, got {n_coll}"


def test_fused_broadcast_is_one_collective_per_bucket():
    """grouped_broadcast's bucket program (r4): 40 packed leaves + the
    root-active flag -> the broadcastable data travels as ONE collective
    (the masked-psum broadcast of the packed buffer), with only the tiny
    flag as a second one — never one collective per leaf."""
    mesh = _world_mesh()
    shapes = tuple((5, 4) for _ in range(40))
    fn = C.build_fused_broadcast(mesh, "world", 0, shapes, jnp.float32)
    total = sum(int(np.prod(s)) for s in shapes)
    packed = jax.device_put(jnp.zeros((8, total), jnp.float32),
                            NamedSharding(mesh, P("world")))
    active = jax.device_put(jnp.ones((8, 1), jnp.int32),
                            NamedSharding(mesh, P("world")))
    hlo = _hlo(fn, packed, active)
    n_ar = _count(r"all-reduce(?:-start)?\(", hlo)
    assert n_ar <= 2, \
        f"expected <=2 collectives (packed data + flag), found {n_ar}"


def test_grouped_allreduce_single_launch_one_program():
    """VERDICT r4 weak #1 lever: the whole grouped allreduce — every
    bucket's pack, collective, and unpack — is ONE compiled program with
    exactly one all-reduce per bucket (2 here), so the eager step pays one
    dispatch instead of 2 per bucket."""
    mesh = _world_mesh()
    shapes = tuple((64,) for _ in range(6))
    buckets = [[0, 1, 2], [3, 4, 5]]
    fn = C.build_grouped_allreduce(mesh, "world", ReduceOp.SUM, shapes,
                                   [jnp.float32] * 6, buckets)
    args = [jax.device_put(jnp.zeros((8, 192), jnp.float32),
                           NamedSharding(mesh, P("world")))
            for _ in buckets]
    hlo = _hlo(fn, *args)
    n_ar = _count(r"all-reduce(?:-start)?\(", hlo)
    # at MOST one collective per bucket; XLA's all-reduce combiner may
    # merge small buckets further (fewer launches still upholds the
    # fusion-buffer guarantee — the bound is what bucketing promises)
    assert 1 <= n_ar <= 2, \
        f"expected <= one all-reduce per bucket (2), got {n_ar}"


def test_replay_step_lowers_to_single_fused_program():
    """Step-capture replay (core/replay.py): a captured step of many
    per-leaf allreduces is ONE compiled program — pack, one all-reduce per
    fusion bucket, unpack — so the whole steady-state step is a single
    dispatch (the ISSUE r5 acceptance bar)."""
    from jax.sharding import NamedSharding
    mesh = _world_mesh()
    shapes = tuple((7, 3) for _ in range(20))
    # one reduce segment, all 20 tensors in one bucket
    segments = (("reduce", int(ReduceOp.SUM), 1.0, 1.0, 0, shapes,
                 (tuple(range(20)),)),)
    fn = C.build_replay_step(mesh, "world", segments)
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.ones(s, jnp.float32), rep) for s in shapes]
    hlo = _hlo(fn, *args)
    n_ar = _count(r"all-reduce(?:-start)?\(", hlo)
    assert n_ar == 1, f"expected ONE fused all-reduce, found {n_ar}"
    # and it computes the allreduce: every output = 8x its input here
    # (8 'ranks', each contributing the same replicated value)
    outs = fn(*args)
    np.testing.assert_allclose(np.asarray(outs[0]), 8.0 * np.ones((7, 3)),
                               rtol=1e-6)


def test_replay_step_multi_segment_bounded_collectives():
    """A mixed captured step (two reduce segments with different ops + a
    broadcast segment) still lowers to one program with at most one
    collective per bucket."""
    from jax.sharding import NamedSharding
    mesh = _world_mesh()
    segments = (
        ("reduce", int(ReduceOp.SUM), 1.0, 1.0, 0,
         ((16,), (16,)), ((0, 1),)),
        ("reduce", int(ReduceOp.MAX), 1.0, 1.0, 0, ((8,),), ((0,),)),
        ("bcast", 0, 1.0, 1.0, 0, ((4,),), ((0,),)),
    )
    fn = C.build_replay_step(mesh, "world", segments)
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.ones(s, jnp.float32), rep)
            for s in ((16,), (16,), (8,), (4,))]
    hlo = _hlo(fn, *args)
    n_coll = (_count(r"all-reduce(?:-start)?\(", hlo)
              + _count(r"reduce-scatter", hlo))
    # sum bucket + max bucket + broadcast's masked psum = at most 3
    assert 1 <= n_coll <= 3, f"expected <=3 collectives, got {n_coll}"
    outs = fn(*args)
    np.testing.assert_allclose(np.asarray(outs[0]), 8.0 * np.ones((16,)))
    np.testing.assert_allclose(np.asarray(outs[2]), np.ones((8,)))  # MAX
    np.testing.assert_allclose(np.asarray(outs[3]), np.ones((4,)))  # bcast


def test_grouped_reducescatter_one_collective_per_bucket():
    """ZeRO-1 sync leg: the grouped reduce-scatter program must lower to
    exactly one reduce-scatter per fusion bucket (no stray allreduce), and
    the grouped allgather inverse must reconstruct the reduced values
    through exactly one all-gather per bucket — padding included (totals
    192 and 100 do not divide 8)."""
    mesh = _world_mesh()
    shapes = tuple((64,) for _ in range(3)) + ((25,), (75,))
    buckets = [[0, 1, 2], [3, 4]]
    rs = C.build_grouped_reducescatter(mesh, "world", ReduceOp.SUM, shapes,
                                       [jnp.float32] * 5, buckets)
    rng = np.random.RandomState(0)
    data = [rng.randn(8, 192).astype(np.float32),
            rng.randn(8, 100).astype(np.float32)]
    args = [jax.device_put(jnp.asarray(d), NamedSharding(mesh, P("world")))
            for d in data]
    hlo = _hlo(rs, *args)
    assert _count(r"reduce-scatter(?:-start)?\(", hlo) == 2, hlo[:400]
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 0
    shards = rs(*args)
    ag = C.build_grouped_allgather(mesh, "world", shapes,
                                   [jnp.float32] * 5, buckets)
    hlo = _hlo(ag, *shards)
    assert _count(r"all-gather(?:-start)?\(", hlo) == 2
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 0
    outs = ag(*shards)
    flat0 = data[0].sum(axis=0)
    for k in range(3):
        np.testing.assert_allclose(np.asarray(outs[k]),
                                   flat0[k * 64:(k + 1) * 64], rtol=1e-5)
    flat1 = data[1].sum(axis=0)
    np.testing.assert_allclose(np.asarray(outs[3]), flat1[:25], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[4]), flat1[25:], rtol=1e-5)


def test_sharded_replay_step_structure():
    """ISSUE 2 CI satellite: the sharded replay step — a captured ZeRO-1
    eager step — lowers to exactly one reduce-scatter and one all-gather
    per fusion bucket, with NO stray all-reduce (the fusion contract of
    the rs -> shard-update -> ag pipeline)."""
    from jax.sharding import NamedSharding
    mesh = _world_mesh()
    grad_shapes = tuple((7, 3) for _ in range(10)) + tuple((11,) for _ in range(4))
    n_grads = len(grad_shapes)
    # a momentum-style shard state leaf per bucket (2 buckets below) plus
    # the flat parameter master shards
    buckets = ((0, 1, 2, 3, 4, 5, 6, 7, 8, 9), (10, 11, 12, 13))
    totals = [210, 44]
    shard_sizes = [-(-t // 8) for t in totals]
    state_shapes = tuple((s,) for s in shard_sizes) * 2  # mu + master copy
    shapes = grad_shapes + state_shapes

    def update(shards, state):
        mu = state[:2]
        master = state[2:]
        new_mu = [0.9 * m + s for m, s in zip(mu, shards)]
        new_master = [p - 0.1 * m for p, m in zip(master, new_mu)]
        return list(new_master), new_mu + new_master

    segments = (("sharded", (int(ReduceOp.SUM), "upd", n_grads),
                 1.0, 1.0, 0, shapes, buckets),)
    fn = C.build_replay_step(mesh, "world", segments,
                             sharded_updates={"upd": update})
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.ones(s, jnp.float32), rep) for s in shapes]
    hlo = _hlo(fn, *args)
    n_rs = _count(r"reduce-scatter(?:-start)?\(", hlo)
    n_ag = _count(r"all-gather(?:-start)?\(", hlo)
    n_ar = _count(r"all-reduce(?:-start)?\(", hlo)
    assert n_rs == 2, f"expected one reduce-scatter per bucket (2), got {n_rs}"
    assert n_ag == 2, f"expected one all-gather per bucket (2), got {n_ag}"
    assert n_ar == 0, f"expected NO stray all-reduce, got {n_ar}"
    # numerics on the replicated claim: 8 identical rank contributions sum
    # to 8; mu' = 0.9*1 + 8 = 8.9; master' = 1 - 0.1*8.9 = 0.11
    outs = fn(*args)
    np.testing.assert_allclose(np.asarray(outs[0]),
                               np.full((7, 3), 0.11), rtol=1e-5)
    # new mu state leaf (first state output) = 0.9*1 + 8
    np.testing.assert_allclose(np.asarray(outs[n_grads]),
                               np.full((shard_sizes[0],), 8.9), rtol=1e-6)


def test_reducescatter_builder_pads_odd_dim0():
    """Engine satellite: dim0=7 over 8 ranks — the builder pads to 8 rows
    inside the program; concatenating the per-rank shards (trimmed of the
    zero tail) reconstructs the full reduced tensor."""
    mesh = _world_mesh()
    fn = C.build_reducescatter(mesh, "world", ReduceOp.SUM, pad_rows=1)
    rng = np.random.RandomState(1)
    data = rng.randn(8, 7, 3).astype(np.float32)
    out = fn(jax.device_put(jnp.asarray(data),
                            NamedSharding(mesh, P("world"))))
    got = np.asarray(out)            # (8, 1, 3): one padded row per rank
    expect = data.sum(axis=0)
    np.testing.assert_allclose(got[:7, 0], expect, rtol=1e-5)
    np.testing.assert_allclose(got[7, 0], 0.0, atol=1e-6)


def test_grouped_allreduce_rejects_mixed_dtype_bucket():
    """The dtypes parameter now enforces the bucket_by_size contract
    (ADVICE r5): a hand-rolled mixed-dtype bucket fails loudly."""
    mesh = _world_mesh()
    with pytest.raises(ValueError, match="mixes dtypes"):
        C.build_grouped_allreduce(mesh, "world", ReduceOp.SUM,
                                  ((4,), (4,)), [jnp.float32, jnp.int32],
                                  [[0, 1]])


# -- ISSUE 6: bucket-pipelined overlap structure ----------------------------

_COLLECTIVE_PRIMS = {"psum", "reduce_scatter", "all_gather", "all_to_all",
                     "ppermute", "psum_scatter"}


def _shard_map_body(jaxpr):
    """The innermost sub-jaxpr holding the collective primitives (the
    shard_map manual region)."""
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            jv = getattr(v, "jaxpr", v)
            if hasattr(jv, "eqns"):
                if eqn.primitive.name == "shard_map":
                    return jv
                body = _shard_map_body(jv)
                if body is not None:
                    return body
    return None


def _collective_interpose_violations(body):
    """IR-level serialization check (the ISSUE 6 acceptance bar): walk the
    manual region's eqns in trace order and report every NON-collective
    eqn that consumes (transitively) an earlier collective's output while
    at least one collective is still to be issued after it. In the serial
    PR 1 form, bucket i's unpack (dynamic_slice of the psum result) sits
    between reduce(i) and reduce(i+1) — exactly such a violation; the
    pipelined form must have none (collective-to-collective chains, e.g.
    the hierarchical RS->AG ladder, are the wire itself and are allowed).
    Returns (violations, n_collectives)."""
    tainted = set()       # vars derived from a collective output
    coll_pos = [i for i, e in enumerate(body.eqns)
                if e.primitive.name in _COLLECTIVE_PRIMS]
    if not coll_pos:
        return [], 0
    last = coll_pos[-1]
    violations = []
    for i, eqn in enumerate(body.eqns):
        is_coll = eqn.primitive.name in _COLLECTIVE_PRIMS
        consumes = any(getattr(v, "count", None) is not None and v in tainted
                       for v in eqn.invars)
        if consumes and not is_coll and i < last:
            violations.append((i, eqn.primitive.name))
        if is_coll or consumes:
            tainted.update(v for v in eqn.outvars)
    return violations, len(coll_pos)


def test_pipelined_replay_step_no_cross_bucket_dependency():
    """The pipelined replay step on the 8-device (2x4) CPU world: one
    collective per bucket, and NO non-collective op between two
    collectives consumes an earlier collective's result — i.e. bucket
    i+1's pack does not wait behind bucket i's reduce; the serialization
    PR 1 introduced is actually gone at the IR level. The serial builder
    is asserted to STILL have the interposing consumers, so this test
    distinguishes the two forms rather than passing vacuously."""
    mesh = _world_mesh()
    shapes = tuple((7, 3) for _ in range(9))
    buckets = ((0, 1, 2), (3, 4, 5), (6, 7, 8))
    segments = (("reduce", int(ReduceOp.SUM), 1.0, 1.0, 0, shapes,
                 buckets),)
    args = [jnp.ones(s, jnp.float32) for s in shapes]

    pipelined = C.build_replay_step(mesh, "world", segments, pipeline=True)
    body = _shard_map_body(jax.make_jaxpr(pipelined)(*args).jaxpr)
    assert body is not None
    violations, n_coll = _collective_interpose_violations(body)
    assert n_coll == len(buckets), \
        f"expected one collective per bucket ({len(buckets)}), got {n_coll}"
    assert not violations, \
        f"pipelined form still serializes at the IR level: {violations}"

    serial = C.build_replay_step(mesh, "world", segments, pipeline=False)
    sbody = _shard_map_body(jax.make_jaxpr(serial)(*args).jaxpr)
    sviol, _ = _collective_interpose_violations(sbody)
    assert sviol, ("the serial form no longer interposes unpacks between "
                   "bucket collectives — this test is vacuous, update it")

    # same values either way (8 identical 'rank' contributions -> x8)
    o0, o1 = serial(*args), pipelined(*args)
    for a, b in zip(o0, o1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(o1[0]), 8.0 * np.ones((7, 3)),
                               rtol=1e-6)


def test_pipelined_sharded_replay_step_structure():
    """The pipelined SHARDED replay step: per-bucket reduce-scatter and
    all-gather stages with no stray all-reduce (the PR 2 bar holds under
    the new schedule), and no non-collective consumer interposing between
    collectives except the shard-local update itself — which is the one
    legitimate synchronization point (it needs every bucket's shard)."""
    mesh = _world_mesh()
    grad_shapes = tuple((6,) for _ in range(4))
    buckets = ((0, 1), (2, 3))
    shard_sizes = [-(-12 // 8)] * 2
    state_shapes = tuple((s,) for s in shard_sizes)
    shapes = grad_shapes + state_shapes

    def update(shards, state):
        new_mu = [0.9 * m + s for m, s in zip(state, shards)]
        return [s - 0.1 * m for s, m in zip(shards, new_mu)], new_mu

    segments = (("sharded", (int(ReduceOp.SUM), "upd", 4), 1.0, 1.0, 0,
                 shapes, buckets),)
    fn = C.build_replay_step(mesh, "world", segments,
                             sharded_updates={"upd": update},
                             pipeline=True)
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.ones(s, jnp.float32), rep) for s in shapes]
    hlo = _hlo(fn, *args)
    assert _count(r"reduce-scatter(?:-start)?\(", hlo) == 2
    assert _count(r"all-gather(?:-start)?\(", hlo) == 2
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 0
    # trace order: both reduce-scatters issue before ANY all-gather (the
    # rs(i+1)-behind-ag(i) serialization is gone)
    body = _shard_map_body(jax.make_jaxpr(fn)(*args).jaxpr)
    names = [e.primitive.name for e in body.eqns
             if e.primitive.name in _COLLECTIVE_PRIMS]
    assert names == ["reduce_scatter", "reduce_scatter",
                     "all_gather", "all_gather"], names


def test_split_sharded_update_has_no_allgather():
    """The prefetch split (ISSUE 6 tentpole): the rs->update stage program
    contains the per-bucket reduce-scatters and NO all-gather — the
    gather rides the separate prefetch leg
    (build_grouped_allgather), whose program contains only the per-bucket
    all-gathers. Combined they reproduce the fused step exactly."""
    mesh = _world_mesh()
    grad_shapes = tuple((6,) for _ in range(4))
    buckets = [[0, 1], [2, 3]]
    st_shapes = ((2,), (2,))

    def update(shards, state):
        return [s + m for s, m in zip(shards, state)], list(state)

    upd = C.build_sharded_update(mesh, "world", ReduceOp.SUM, grad_shapes,
                                 [jnp.float32] * 4, buckets, st_shapes,
                                 None, update, packed=True)
    ag = C.build_grouped_allgather(mesh, "world", grad_shapes,
                                   [jnp.float32] * 4, buckets,
                                   pipeline=True)
    fused = C.build_sharded_step(mesh, "world", ReduceOp.SUM, grad_shapes,
                                 [jnp.float32] * 4, buckets, st_shapes,
                                 None, update, pipeline=True)
    rng = np.random.RandomState(3)
    packed = [jax.device_put(
        jnp.asarray(rng.randn(8, 12).astype(np.float32)),
        NamedSharding(mesh, P("world"))) for _ in buckets]
    state = [jax.device_put(jnp.ones((2,), jnp.float32),
                            NamedSharding(mesh, P())) for _ in range(2)]
    hlo_upd = _hlo(upd, *packed, *state)
    assert _count(r"reduce-scatter(?:-start)?\(", hlo_upd) == 2
    assert _count(r"all-gather(?:-start)?\(", hlo_upd) == 0
    assert _count(r"all-reduce(?:-start)?\(", hlo_upd) == 0
    shards = upd(*packed, *state)
    hlo_ag = _hlo(ag, *shards[:2])
    assert _count(r"all-gather(?:-start)?\(", hlo_ag) == 2
    assert _count(r"reduce-scatter(?:-start)?\(", hlo_ag) == 0
    split_params = ag(*shards[:2])
    fused_outs = fused(*packed, *state)
    for a, b in zip(fused_outs[:4], split_params):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(fused_outs[4:], shards[2:]):
        np.testing.assert_array_equal(
            np.asarray(a.addressable_shards[0].data),
            np.asarray(b.addressable_shards[0].data))


# -- ISSUE 10: topology-aware algorithm selection ---------------------------

_PAIR_GROUPS = r"replica_groups=\{\{0,1\},\{2,3\},\{4,5\},\{6,7\}\}"
_NODE_GROUPS = r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}"


def _topo84():
    from horovod_tpu.parallel.mesh import Topology
    return Topology(size=8, local_size=4, platform="tpu", source="override")


def test_auto_selection_lowers_tree_and_hierarchical_per_bucket():
    """The ISSUE 10 acceptance bar: on an 8-device 2-slice topology,
    ``auto`` lowers a small latency-bound bucket to the TREE form
    (log2(8)=3 chained pair-group all-reduces) and a large bucket to the
    hierarchical RS/AG ladder with node-local replica groups — in ONE
    grouped program. Forcing ``flat`` collapses both buckets to plain
    whole-world all-reduces with neither group structure, so the test
    distinguishes the selections rather than passing vacuously."""
    topo = _topo84()
    small_elems, large_elems = 1024, 256 * 1024      # 4 KB vs 1 MB fp32
    shapes = ((small_elems,), (large_elems,))
    buckets = [[0], [1]]
    algos = tuple(
        C.choose_algorithm("allreduce", 4 * e, topo)
        for e in (small_elems, large_elems))
    assert algos == ("tree", "hierarchical"), algos
    mesh = _world_mesh()
    args = [jax.device_put(jnp.ones((8, e), jnp.float32),
                           NamedSharding(mesh, P("world")))
            for e in (small_elems, large_elems)]

    auto_fn = C.build_grouped_allreduce(
        mesh, "world", ReduceOp.SUM, shapes, [jnp.float32] * 2, buckets,
        local_size=topo.local_size, algos=algos)
    hlo = _hlo(auto_fn, *args).replace(" ", "")
    # tree bucket: exactly 3 chained pair-group psums (dependent rounds
    # the combiner cannot merge)
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 3, hlo[:400]
    assert re.search(_PAIR_GROUPS, hlo), "tree pair groups missing"
    # hierarchical bucket: the RS/AG ladder over node-local groups
    assert re.search(_NODE_GROUPS, hlo), "node-local ladder groups missing"
    assert (_count(r"reduce-scatter", hlo) >= 1
            or _count(r"all-gather", hlo) >= 1)

    flat_fn = C.build_grouped_allreduce(
        mesh, "world", ReduceOp.SUM, shapes, [jnp.float32] * 2, buckets,
        local_size=topo.local_size, algos=("flat", "flat"))
    fhlo = _hlo(flat_fn, *args).replace(" ", "")
    n_ar = _count(r"all-reduce(?:-start)?\(", fhlo)
    assert 1 <= n_ar <= 2, f"flat should be whole-world all-reduce: {n_ar}"
    assert not re.search(_PAIR_GROUPS, fhlo)
    assert not re.search(_NODE_GROUPS, fhlo)
    assert _count(r"reduce-scatter", fhlo) == 0

    # same numbers either way (8 identical 'rank' contributions -> x8)
    for a, b in zip(auto_fn(*args), flat_fn(*args)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_tree_allreduce_builder_structure_and_values():
    mesh = _world_mesh()
    fn = C.build_tree_allreduce(mesh, "world", ReduceOp.SUM)
    x = jax.device_put(jnp.arange(8 * 6, dtype=jnp.float32).reshape(8, 6),
                       NamedSharding(mesh, P("world")))
    hlo = _hlo(fn, x).replace(" ", "")
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 3
    assert re.search(_PAIR_GROUPS, hlo)
    out = np.asarray(fn(x))
    np.testing.assert_allclose(
        out, np.arange(8 * 6, dtype=np.float32).reshape(8, 6).sum(0))


def test_replay_step_per_bucket_algo_segments():
    """The replay segment's topology field carries per-bucket algorithms
    (the (local_size, algos) tuple form): the armed program lowers its
    small bucket to the tree and its large bucket to the ladder — so
    warmup and steady state resolve the same topology-aware schedule."""
    from jax.sharding import NamedSharding
    mesh = _world_mesh()
    shapes = ((64,), (4096,))
    segments = (("reduce", int(ReduceOp.SUM), 1.0, 1.0,
                 (4, ("tree", "hierarchical")), shapes, ((0,), (1,))),)
    fn = C.build_replay_step(mesh, "world", segments, pipeline=True)
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(jnp.ones(s, jnp.float32), rep) for s in shapes]
    hlo = _hlo(fn, *args).replace(" ", "")
    assert _count(r"all-reduce(?:-start)?\(", hlo) == 3  # the tree rounds
    assert re.search(_PAIR_GROUPS, hlo)
    assert re.search(_NODE_GROUPS, hlo)
    outs = fn(*args)
    np.testing.assert_allclose(np.asarray(outs[0]), 8.0 * np.ones((64,)))
    np.testing.assert_allclose(np.asarray(outs[1]), 8.0 * np.ones((4096,)))
    # legacy int field still means "one algorithm everywhere" (flat here)
    legacy = C.build_replay_step(
        mesh, "world",
        (("reduce", int(ReduceOp.SUM), 1.0, 1.0, 0, shapes,
          ((0,), (1,))),))
    lhlo = _hlo(legacy, *args).replace(" ", "")
    assert not re.search(_PAIR_GROUPS, lhlo)
    assert not re.search(_NODE_GROUPS, lhlo)


def test_sharded_step_hierarchical_ag_leg():
    """ZeRO-1 with a hierarchical return all-gather: the reduce-scatter
    leg stays the flat whole-world scatter (shard-ownership invariant)
    while the gather lowers to the two-level ladder — and the result is
    bitwise-identical to the flat-gather program."""
    mesh = _world_mesh()
    grad_shapes = tuple((6,) for _ in range(4))
    buckets = [[0, 1], [2, 3]]
    st_shapes = ((2,), (2,))

    def update(shards, state):
        return [s + m for s, m in zip(shards, state)], list(state)

    kw = dict(pipeline=True)
    hier = C.build_sharded_step(mesh, "world", ReduceOp.SUM, grad_shapes,
                                [jnp.float32] * 4, buckets, st_shapes,
                                None, update, local_size=4,
                                ag_algos=("hierarchical", "hierarchical"),
                                **kw)
    flat = C.build_sharded_step(mesh, "world", ReduceOp.SUM, grad_shapes,
                                [jnp.float32] * 4, buckets, st_shapes,
                                None, update, **kw)
    rng = np.random.RandomState(5)
    packed = [jax.device_put(
        jnp.asarray(rng.randn(8, 12).astype(np.float32)),
        NamedSharding(mesh, P("world"))) for _ in buckets]
    state = [jax.device_put(jnp.ones((2,), jnp.float32),
                            NamedSharding(mesh, P())) for _ in range(2)]
    hhlo = _hlo(hier, *packed, *state).replace(" ", "")
    # whole-world scatters survive; gathers go node-local two-level
    assert _count(r"reduce-scatter(?:-start)?\(", hhlo) >= 2
    assert re.search(_NODE_GROUPS, hhlo), "no two-level gather groups"
    for a, b in zip(hier(*packed, *state), flat(*packed, *state)):
        np.testing.assert_array_equal(
            np.asarray(a.addressable_shards[0].data),
            np.asarray(b.addressable_shards[0].data))


def test_grouped_allreduce_hierarchical_ladder():
    """The single-launch grouped program with local_size=4 must lower each
    bucket's reduction to the hierarchical RS/AG ladder with node-local
    replica groups — the same structural bar the per-bucket fused program
    meets — AND produce numerically correct sums."""
    import re
    mesh = _world_mesh()
    shapes = tuple((32,) for _ in range(4))
    buckets = [[0, 1], [2, 3]]
    fn = C.build_grouped_allreduce(mesh, "world", ReduceOp.SUM, shapes,
                                   [jnp.float32] * 4, buckets,
                                   local_size=4)
    rng = np.random.RandomState(0)
    data = [rng.randn(8, 64).astype(np.float32) for _ in buckets]
    args = [jax.device_put(jnp.asarray(d),
                           NamedSharding(mesh, P("world")))
            for d in data]
    hlo = _hlo(fn, *args)
    local_groups = re.search(r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}",
                             hlo.replace(" ", ""))
    assert local_groups, "no node-local replica groups in grouped ladder"
    outs = fn(*args)
    for b, idxs in enumerate(buckets):
        expect = data[b].sum(axis=0)
        np.testing.assert_allclose(np.asarray(outs[idxs[0]]), expect[:32],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(outs[idxs[1]]), expect[32:],
                                   rtol=1e-5, atol=1e-5)


def test_hierarchical_alltoall_lowers_to_two_level_exchange():
    """ISSUE 17 acceptance (IR structure): the two-phase alltoall on the
    8-device world with local_size=4 must lower to exactly TWO
    all-to-alls — one over the intra-slice (ICI) groups {0-3}/{4-7}, one
    over the cross-slice (DCN) column groups {0,4}/{1,5}/... — while the
    forced-flat program collapses to ONE whole-world all-to-all; both
    routings are pure chunk moves, so the outputs are bitwise-equal."""
    mesh = _world_mesh()
    hfn = C.build_hierarchical_alltoall(mesh, "world", 4)
    ffn = C.build_alltoall(mesh, "world")
    x = jax.device_put(
        jnp.arange(8 * 16 * 3, dtype=jnp.float32).reshape(8, 16, 3),
        NamedSharding(mesh, P("world")))
    hhlo = _hlo(hfn, x)
    fhlo = _hlo(ffn, x)
    assert _count(r"all-to-all(?:-start)?\(", hhlo) == 2, \
        "two-phase program did not lower to exactly two exchanges"
    assert _count(r"all-to-all(?:-start)?\(", fhlo) == 1, \
        "flat program is not one whole-world exchange"
    hflat = hhlo.replace(" ", "")
    assert re.search(r"replica_groups=\{\{0,1,2,3\},\{4,5,6,7\}\}", hflat), \
        "no intra-slice (ICI) replica groups in the two-phase HLO"
    assert re.search(r"replica_groups=\{\{0,4\},\{1,5\},\{2,6\},\{3,7\}\}",
                     hflat), \
        "no cross-slice (DCN) replica groups in the two-phase HLO"
    assert re.search(r"replica_groups=\{\{0,1,2,3,4,5,6,7\}\}",
                     fhlo.replace(" ", "")), \
        "flat exchange is not whole-world"
    np.testing.assert_array_equal(
        np.asarray(jax.block_until_ready(hfn(x))),
        np.asarray(jax.block_until_ready(ffn(x))))


def test_grouped_alltoall_per_bucket_algos_structure():
    """Per-bucket alltoall selection in ONE grouped program: a flat
    bucket contributes one whole-world all-to-all, a hierarchical bucket
    two sliced ones — three exchanges total, numerics identical to
    all-flat."""
    mesh = _world_mesh()
    shapes = ((16, 4), (24, 4))
    dtypes = [jnp.float32] * 2
    buckets = [[0], [1]]
    mixed = C.build_grouped_alltoall(
        mesh, "world", shapes, dtypes, buckets, local_size=4,
        algos=(C.ALGO_FLAT, C.ALGO_HIERARCHICAL))
    flat = C.build_grouped_alltoall(
        mesh, "world", shapes, dtypes, buckets, local_size=4,
        algos=(C.ALGO_FLAT, C.ALGO_FLAT))
    rng = np.random.RandomState(0)
    args = [jax.device_put(
        jnp.asarray(rng.randn(8, *s).astype(np.float32)),
        NamedSharding(mesh, P("world"))) for s in shapes]
    hlo = _hlo(mixed, *args)
    assert _count(r"all-to-all(?:-start)?\(", hlo) == 3, \
        "expected 1 flat + 2 hierarchical-phase exchanges"
    for a, b in zip(mixed(*args), flat(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_reducescatter_selection_stays_flat():
    """The ISSUE 17 selection surface is alltoall-only on the scatter
    side: reducescatter never takes the hierarchical ladder (auto OR
    forced — forcing demotes), even on a fabric where allreduce and
    alltoall both would."""
    from horovod_tpu.parallel.mesh import Topology
    topo = Topology(size=8, local_size=4, platform="tpu", source="test")
    nbytes = 32 * 1024 ** 2
    assert C.choose_algorithm("allreduce", nbytes, topo,
                              tree_threshold_bytes=0) == \
        C.ALGO_HIERARCHICAL
    assert C.choose_algorithm("alltoall", nbytes, topo,
                              tree_threshold_bytes=0) == \
        C.ALGO_HIERARCHICAL
    assert C.choose_algorithm("reducescatter", nbytes, topo,
                              tree_threshold_bytes=0) == C.ALGO_FLAT
    assert C.validate_algorithm("reducescatter", C.ALGO_HIERARCHICAL,
                                8, 4) == C.ALGO_FLAT


# ---------------------------------------------------------------------------
# Names inside the programs (common/scopes.py): what a device trace is read
# by. The compiled text of a program built here, never a trace or a cached
# executable: jax keeps metadata out of the persistent cache's key, and the
# tests run without that cache.
# ---------------------------------------------------------------------------

import functools

from horovod_tpu.common import scopes

_LM_SCOPES = (scopes.EMBED, scopes.LAYERS, scopes.ATTN, scopes.FFN,
              scopes.HEAD, scopes.LOSS)


def _op_names(hlo):
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _under(op_name, scope):
    """``scope`` is a component of the path, bare or inside the transforms
    jax wraps around the names that were open when it was applied
    (``jvp()/head`` through a shard_map, ``jvp(head)`` without)."""
    return re.search(rf"(^|[/(]){scope}([/)]|$)", op_name) is not None


def _tiny_lm():
    from horovod_tpu.models.transformer import TransformerConfig, init_params
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=16, attention="flash")
    return (cfg, init_params(jax.random.PRNGKey(0), cfg),
            jnp.zeros((2, 16), jnp.int32))


@functools.lru_cache(maxsize=None)
def _lm_step_hlo(builder):
    """The compiled step of the tiny LM through ``make_train_step`` (a mesh
    of one) or ``make_pp_train_step`` (two stages): both run the one block."""
    import optax
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel.mesh import training_mesh
    cfg, params, tok = _tiny_lm()
    if builder == "make_train_step":
        opt = optax.adamw(1e-3)
        step = tfm.make_train_step(training_mesh(
            {"data": 1, "seq": 1, "tensor": 1}, jax.devices()[:1]), cfg, opt)
    else:
        opt = optax.sgd(0.1)
        step = tfm.make_pp_train_step(
            Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,)), cfg, opt,
            n_micro=2)
    return _hlo(step, params, opt.init(params), tok, tok)


@pytest.fixture(scope="module")
def train_step_hlo():
    return _lm_step_hlo("make_train_step")


@pytest.mark.parametrize("scope", _LM_SCOPES)
@pytest.mark.parametrize("phase", ["forward", "backward"])
@pytest.mark.parametrize("builder", ["make_train_step", "make_pp_train_step"])
def test_train_step_scope_in_each_pass(builder, scope, phase):
    """Every scope of the LM step names operations of the forward pass
    (``jvp(`` and no ``transpose(``: jax writes both) and of the backward
    pass (``transpose(jvp(``), in the pipeline's step (its own vjp inside
    the schedule's scan) as in the SPMD step."""
    found = [n for n in _op_names(_lm_step_hlo(builder)) if _under(n, scope)]
    if phase == "forward":
        found = [n for n in found if "jvp(" in n and "transpose(" not in n]
    else:
        found = [n for n in found if "transpose(jvp(" in n]
    assert found, f"no {phase} operation under scope {scope!r}"


@pytest.mark.parametrize("segment,scope", [
    ("embed", scopes.EMBED), ("route", scopes.ATTN), ("route", scopes.FFN),
    ("expert_ffn", scopes.FFN), ("combine", scopes.FFN),
    ("loss", scopes.HEAD), ("loss", scopes.LOSS)])
def test_moe_ep_segments_carry_the_blocks_names(segment, scope):
    """``make_moe_ep_train_step`` chains jitted segments of the same block
    through the engine's exchanges: each program names what it holds of
    it."""
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    hvd.init()
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq=16,
                                attention="flash", use_moe=True, n_experts=4)
    shared, expert = tfm.moe_ep_partition(
        tfm.init_params(jax.random.PRNGKey(0), cfg), 0, 1, cfg)
    seg = tfm.make_moe_ep_train_step(hvd._engine(), cfg,
                                     optax.sgd(0.1)).segments
    tok = jnp.zeros((2, 16), jnp.int32)
    h = jnp.zeros((2, 16, 32), cfg.dtype)
    buf = jnp.zeros((4 * 16, 32), cfg.dtype)        # [E * capacity, D]
    route = jnp.zeros((32,), jnp.int32)
    hlo = {"embed": lambda: _hlo(seg["embed"], shared, tok),
           "route": lambda: _hlo(seg["route"][0], shared, h, 16),
           "expert_ffn": lambda: _hlo(seg["expert_ffn"][0], expert, buf),
           "combine": lambda: _hlo(seg["combine"], {}, h, buf,
                                   jnp.ones((32,)), route, route),
           "loss": lambda: _hlo(seg["loss"], shared, h, tok)}[segment]()
    assert [n for n in _op_names(hlo) if _under(n, scope)]


def test_train_step_optimizer_scope_outside_both_passes(train_step_hlo):
    found = [n for n in _op_names(train_step_hlo)
             if _under(n, scopes.OPTIMIZER)]
    assert found, "no operation under the optimizer scope"
    assert not [n for n in found if "jvp(" in n or "transpose(" in n]
    # and nothing of the model's scopes leaks into it
    assert not [n for n in found if any(_under(n, s) for s in _LM_SCOPES)]


@pytest.mark.parametrize("scope", [scopes.HEAD, scopes.LOSS])
def test_lean_lm_loss_keeps_head_and_loss_scopes(scope):
    from horovod_tpu.models.transformer import lean_lm_loss
    cfg, params, tok = _tiny_lm()
    hlo = _hlo(jax.jit(jax.grad(
        lambda p: lean_lm_loss(p, tok, tok, cfg))), params)
    assert [n for n in _op_names(hlo) if _under(n, scope)]


@functools.lru_cache(maxsize=None)
def _xent_caller_hlo(builder):
    if builder != "lean_lm_loss":
        return _lm_step_hlo(builder)
    from horovod_tpu.models import transformer as tfm
    cfg, params, tok = _tiny_lm()
    return _hlo(jax.jit(jax.grad(
        lambda p: tfm.lean_lm_loss(p, tok, tok, cfg))), params)


@pytest.mark.parametrize("builder", ["lean_lm_loss", "make_pp_train_step"])
@pytest.mark.parametrize("phase", ["forward", "backward"])
def test_one_xent_names_loss_in_each_pass(builder, phase):
    """Both rules of the cross-entropy's custom_vjp open scope ``loss``, so
    every builder that calls it shows the name in both passes, the pipeline
    tables (their own vjp inside a cond) too."""
    found = [n for n in _op_names(_xent_caller_hlo(builder))
             if _under(n, scopes.LOSS)]
    if phase == "forward":
        found = [n for n in found if "jvp(" in n and "transpose(" not in n]
    else:
        found = [n for n in found if "transpose(jvp(" in n]
    assert found, f"no {phase} operation under scope 'loss' in {builder}"


@functools.lru_cache(maxsize=None)
def _eager_apply_hlo(adasum):
    """The eager optimizers' apply program over a bfloat16 wire, so that
    ``decompress`` has a cast to name."""
    import optax
    from horovod_tpu.ops.compression import Compression
    from horovod_tpu.optimizer import (DistributedDeltaAdasumOptimizer,
                                       DistributedEagerOptimizer)
    params = {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))}
    leaves, treedef = jax.tree_util.tree_flatten(params)
    wire = [x.astype(jnp.bfloat16) for x in leaves]
    ctxs = [jnp.float32] * len(leaves)
    if adasum:
        opt = DistributedDeltaAdasumOptimizer(optax.sgd(0.1),
                                              compression=Compression.bf16)
        return _hlo(opt._apply_fn(treedef, ctxs), wire, params)
    opt = DistributedEagerOptimizer(optax.sgd(0.1, momentum=0.9),
                                    compression=Compression.bf16)
    return _hlo(opt._apply_fn(treedef, ctxs, [None] * len(leaves), 1),
                wire, opt.init(params), params)


@pytest.mark.parametrize("adasum,program", [
    (False, scopes.APPLY_UPDATE), (True, scopes.APPLY_DELTA)])
@pytest.mark.parametrize("scope", [scopes.DECOMPRESS, scopes.OPTIMIZER])
def test_eager_apply_program_scopes(adasum, program, scope):
    names = _op_names(_eager_apply_hlo(adasum))
    found = [n for n in names if _under(n, scope)]
    assert found and all(n.startswith(f"jit({program})/") for n in found)


@pytest.mark.parametrize("scope", [scopes.GRAD_REDUCE, scopes.OPTIMIZER])
def test_spmd_distributed_optimizer_scopes(scope):
    """``hvd.distributed`` inside a shard_map, the README quickstart's
    form: the reduction and the inner update each under its name."""
    import optax
    from horovod_tpu.optimizer import distributed
    mesh = _world_mesh()
    opt = distributed(optax.sgd(0.1, momentum=0.9), axis_name="world",
                      axis_size=8)
    params = {"w": jnp.ones((4, 4))}

    def body(g, state, p):
        updates, state = opt.update(g, state, p)
        return optax.apply_updates(p, updates), state

    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("world"), P(), P()),
        out_specs=(P(), P()), check_vma=False))
    grads = {"w": jnp.ones((32, 4))}
    names = _op_names(_hlo(step, grads, opt.init(params), params))
    found = [n for n in names if _under(n, scope)]
    assert found, f"nothing under {scope!r}"
    if scope == scopes.GRAD_REDUCE:
        assert any(n.endswith("/psum") for n in found)
        assert not [n for n in found if _under(n, scopes.OPTIMIZER)]


@pytest.mark.parametrize("program", [scopes.TRAIN_STEP, scopes.APPLY_UPDATE,
                                     scopes.APPLY_DELTA])
def test_programs_say_what_they_are(train_step_hlo, program):
    """The program's name is part of the persistent cache's key (metadata
    is not), and what the trace's ``XLA Modules`` line shows."""
    hlo = {scopes.TRAIN_STEP: lambda: train_step_hlo,
           scopes.APPLY_UPDATE: lambda: _eager_apply_hlo(False),
           scopes.APPLY_DELTA: lambda: _eager_apply_hlo(True)}[program]()
    assert re.search(rf"^HloModule jit_{program}\b", hlo, re.M)


# ---------------------------------------------------------------------------
# Where make_train_step sums its gradients (ISSUE 29): each stacked layer
# leaf inside the backward scan, a layer at a time; everything else, and
# every leaf of a looped model, once after the backward pass. Read from the
# traced program, where XLA has moved nothing yet, and from the lowered text.

def _psums(jaxpr, inside=()):
    """(name stack, axes, operand avals, enclosing primitives) of every psum
    in ``jaxpr`` and in the jaxprs its equations hold; a scan is written
    ``scan[length,reverse]``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("psum"):
            yield (str(eqn.source_info.name_stack), tuple(eqn.params["axes"]),
                   [v.aval for v in eqn.invars], inside)
        tag = eqn.primitive.name
        if tag == "scan":
            tag = f"scan[{eqn.params['length']},{eqn.params['reverse']}]"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _psums(sub, inside + (tag,))


def _lm_step(axes, **fields):
    """(cfg, traced jaxpr, lowered text with names) of the tiny LM's
    make_train_step over a mesh of forced host devices."""
    import dataclasses
    import optax
    from horovod_tpu.models import transformer as tfm
    cfg = dataclasses.replace(_tiny_lm()[0], attention="ring",
                              n_layers=3, **fields)
    shape = tuple(axes.get(a, 1) for a in ("data", "seq", "tensor"))
    mesh = Mesh(np.array(jax.devices()[:int(np.prod(shape))]).reshape(shape),
                ("data", "seq", "tensor"))
    params = tfm.shard_params(tfm.init_params(jax.random.PRNGKey(0), cfg),
                              mesh, cfg)
    tok = jax.device_put(jnp.zeros((4, 16), jnp.int32),
                         NamedSharding(mesh, P("data", "seq")))
    opt = optax.adamw(1e-3)
    args = (params, opt.init(params), tok, tok)
    step = tfm.make_train_step(mesh, cfg, opt)
    return (cfg, jax.make_jaxpr(step)(*args).jaxpr,
            step.lower(*args).as_text(debug_info=True))


def _grad_psums(jaxpr):
    return [p for p in _psums(jaxpr) if _under(p[0], scopes.GRAD_REDUCE)]


def _leaf_shapes(cfg):
    from horovod_tpu.models.transformer import init_params
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    layers = sorted(tuple(x.shape) for x in
                    jax.tree_util.tree_leaves(shapes["layers"]))
    rest = sorted(tuple(x.shape) for k, v in shapes.items() if k != "layers"
                  for x in jax.tree_util.tree_leaves(v))
    return layers, rest


@pytest.mark.parametrize("remat", ["none", "block", "attention"])
def test_train_step_sums_layer_gradients_inside_the_backward_scan(remat):
    """``data=4``: one psum a layer leaf, of the leaf's per-layer shape,
    inside the reversed scan over the layers and nowhere else (under remat
    not inside the checkpointed function either: no collective is
    recomputed); the embedding and the final norm each once after it; none
    of the stacked ``[n_layers, ...]`` shape; every one float32 over
    ``data`` alone."""
    cfg, jaxpr, text = _lm_step({"data": 4}, remat=remat)
    layers, rest = _leaf_shapes(cfg)
    found = _grad_psums(jaxpr)
    assert all(axes == ("data",) for _, axes, _, _ in found)
    assert all(a.dtype == jnp.float32 for _, _, avals, _ in found
               for a in avals)
    in_scan = [p for p in found if any(t.startswith("scan") for t in p[3])]
    after = [p for p in found if p not in in_scan]
    backward = f"scan[{cfg.n_layers},True]"
    assert all(p[3].count(backward) == 1
               and not any(t.startswith("remat") or t == "checkpoint"
                           for t in p[3]) for p in in_scan), in_scan
    assert sorted(tuple(a.shape) for p in in_scan for a in p[2]) == sorted(
        s[1:] for s in layers)
    assert sorted(tuple(a.shape) for p in after for a in p[2]) == rest
    # and in the lowered text: no all-reduce of a stacked layer leaf, none
    # narrower than float32 among the gradients' shapes
    stacked = {"x".join(map(str, s)) for s in layers}
    per_layer = {"x".join(map(str, s[1:])) for s in layers if len(s) > 2}
    reduced = re.findall(r'"stablehlo.all_reduce".*?\n\s*\}\) : '
                         r'\(tensor<([^>]*)>\)', text, re.S)
    assert not [r for r in reduced if r.rsplit("x", 1)[0] in stacked]
    grads = [r for r in reduced if r.rsplit("x", 1)[0] in per_layer]
    assert len(grads) >= len(per_layer)
    assert all(r.endswith("xf32") for r in grads), grads


def test_train_step_untied_head_is_summed_where_its_backward_ends():
    """An untied ``lm_head``'s gradient is complete when the head's backward
    ends: its psum comes before the backward scan over the layers, not
    after the embedding's."""
    cfg, jaxpr, _ = _lm_step({"data": 4}, tie_embeddings=False)
    (body,) = [e for e in jaxpr.eqns if e.primitive.name == "jit"]
    (smap,) = [e for e in body.params["jaxpr"].eqns
               if e.primitive.name == "shard_map"]
    order = []
    for eqn in smap.params["jaxpr"].eqns:
        if eqn.primitive.name == "scan" and eqn.params["reverse"]:
            order.append("backward scan")
        elif (eqn.primitive.name.startswith("psum")
              and _under(str(eqn.source_info.name_stack), scopes.GRAD_REDUCE)):
            order.append(tuple(eqn.invars[0].aval.shape))
    head = (cfg.vocab_size, cfg.d_model)
    assert order.index(head) < order.index("backward scan")
    assert order.count(head) == 2       # lm_head before it, embed after
    assert order.index("backward scan") < len(order) - 1 - order[::-1].index(
        head)


@pytest.mark.parametrize("remat", ["none", "block"])
def test_train_step_looped_model_sums_layers_once_after_the_pass_loop(remat):
    """``n_loops=2``: a shared layer's gradient is complete only after the
    last backward pass over it, so every leaf, the stacked layers whole, is
    summed exactly once and outside every scan."""
    cfg, jaxpr, _ = _lm_step({"data": 4}, n_loops=2, remat=remat)
    layers, rest = _leaf_shapes(cfg)
    found = _grad_psums(jaxpr)
    assert not [p for p in found if any(t.startswith("scan") for t in p[3])]
    assert sorted(tuple(a.shape) for p in found for a in p[2]) == sorted(
        layers + rest)
    assert all(a.dtype == jnp.float32 for p in found for a in p[2])


@pytest.mark.parametrize("axes", [{"data": 2, "seq": 2},
                                  {"data": 2, "tensor": 2}])
def test_train_step_sums_each_leaf_over_the_axes_its_spec_leaves_out(axes):
    """Data and seq for every leaf; tensor too for what is replicated over
    it (norms, embedding) and not for what is split over it."""
    from horovod_tpu.models.transformer import init_params, param_specs
    cfg, jaxpr, _ = _lm_step(axes)
    specs = param_specs(cfg)["layers"]
    stacked = jax.eval_shape(lambda k: init_params(k, cfg),
                             jax.random.PRNGKey(0))["layers"]
    split = sorted(stacked[k].ndim - 1 for k, s in specs.items()
                   if "tensor" in s)
    live = tuple(a for a in ("data", "seq", "tensor") if axes.get(a, 1) > 1)
    found = _grad_psums(jaxpr)
    over_data_seq = [p for p in found if "tensor" not in p[1]]
    assert all(p[1] == tuple(a for a in live if a != "tensor")
               for p in over_data_seq)
    assert all(p[1] == live for p in found if p not in over_data_seq)
    if axes.get("tensor", 1) > 1:
        # exactly the leaves split over tensor are left unsummed over it
        assert sorted(a.ndim for p in over_data_seq
                      for a in p[2]) == split
    else:
        assert not [p for p in found if "tensor" in p[1]]


@pytest.mark.parametrize("n_loops", [1, 2])
def test_train_step_on_a_mesh_of_one_has_no_gradient_sum(n_loops):
    """Nothing to sum over: no gradient psum, no wrapper (its custom_vjp
    would show in the names), and no all-reduce among more than one chip."""
    cfg, jaxpr, text = _lm_step({}, n_loops=n_loops)
    assert not _grad_psums(jaxpr)
    assert scopes.GRAD_REDUCE not in text
    assert "_summed_cotangent" not in text and "custom_vjp" not in text
    groups = re.findall(r"replica_groups = dense<([^>]*)>", text)
    assert groups and all("," not in g for g in groups), groups


def test_make_spmd_loss_and_forward_hold_no_wrapper():
    """``_run_passes`` is also the body of the forward-only products: with no
    ``layer_grad_axes`` their traces must not know the wrapper at all."""
    from horovod_tpu.models import transformer as tfm
    cfg, params, tok = _tiny_lm()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4, 1, 1),
                ("data", "seq", "tensor"))
    tok4 = jnp.zeros((4, 16), jnp.int32)
    loss = jax.jit(jax.grad(
        lambda p: tfm.make_spmd_loss(mesh, cfg)(p, tok4, tok4)))
    for text in (loss.lower(params).as_text(debug_info=True),
                 jax.jit(lambda p: tfm.forward_block(p, tok, cfg)).lower(
                     params).as_text(debug_info=True)):
        assert "_summed_cotangent" not in text and "custom_vjp" not in text
        assert scopes.GRAD_REDUCE not in text


class _Chip:     # what the builder asks of a device: its platform
    platform = "tpu"


@pytest.mark.parametrize("platform,shape,some", [
    ("cpu", (4, 1, 1), False), ("cpu", (1, 1, 1), False),
    ("tpu", (1, 1, 1), False),
    ("tpu", (4, 1, 1), True), ("tpu", (2, 2, 1), True),
    ("tpu", (1, 4, 1), True),
    ("tpu", (1, 1, 4), False), ("tpu", (2, 1, 2), False),
    ("tpu", (1, 2, 2), False)])
def test_train_step_compiler_options_only_where_a_chip_run_showed_them(
        platform, shape, some):
    """The options that let the TPU compiler run the all-reduces beside
    compute go to ``jax.jit`` for a TPU mesh over ``data`` and ``seq``
    alone (what ran on the chip: the cell's ``data=4``, the smoke's
    ``data=2,seq=2``), and nowhere else: empty off the TPU, on a mesh of
    one, and wherever ``tensor > 1`` (the activations' psums would turn
    asynchronous too, unseen on a chip; mixed with another axis the sums
    run over subgroups of the chips, on which one of the options kills
    libtpu 0.0.34's compiler)."""
    from horovod_tpu.models import transformer as tfm
    n = int(np.prod(shape))
    devices = (jax.devices()[:n] if platform == "cpu"
               else [_Chip() for _ in range(n)])
    mesh = Mesh(np.array(devices).reshape(shape), ("data", "seq", "tensor"))
    assert tfm._TPU_OVERLAP_OPTIONS     # the TPU form does pass some
    assert tfm._overlap_compiler_options(mesh) == (
        tfm._TPU_OVERLAP_OPTIONS if some else {})


@pytest.mark.parametrize("axes,fields,want", [
    ({"data": 4}, {}, "share"), ({}, {}, 0.0), ({"data": 4}, {"n_loops": 2},
                                                0.0)])
def test_grad_reduce_in_backward_share(axes, fields, want):
    """Bytes of the leaves summed inside the backward scan over all the
    gradient bytes a chip puts through an all-reduce: the layers' share on
    ``data=4``, 0 on a mesh of one and for a looped model. The gauge that
    carries it has its METRIC_SPECS entry (``examples/transformer_lm.py``
    sets it; ``tests/test_examples.py``)."""
    from horovod_tpu.metrics import METRIC_SPECS
    import dataclasses
    from horovod_tpu.models import transformer as tfm
    cfg = dataclasses.replace(_tiny_lm()[0], n_layers=3, **fields)
    layers, rest = _leaf_shapes(cfg)
    if want == "share":
        in_scan = sum(int(np.prod(s)) for s in layers)
        want = in_scan / (in_scan + sum(int(np.prod(s)) for s in rest))
        assert 0.3 < want < 1.0
    mesh = Mesh(np.array(jax.devices()[:axes.get("data", 1)]).reshape(
        -1, 1, 1), ("data", "seq", "tensor"))
    assert tfm.grad_reduce_in_backward_share(mesh, cfg) == pytest.approx(
        want, abs=1e-12)
    assert METRIC_SPECS["hvd_tpu_lm_grad_reduce_in_backward_share"][0] == (
        "gauge")
