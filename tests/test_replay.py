"""Step-capture replay (core/replay.py): capture → arm → replay →
divergence fallback, plus the join()/elastic-world-version invalidation
paths the ISSUE's acceptance criteria name.

Runs on the size-1 eager world (one process); the collective math is
identity there, so every assertion checks both the replay plumbing (handle
binding, single-dispatch accounting, fallback flushing) and value
correctness against the inputs. Multi-participant wire behavior of the same
builders is covered by tests/test_compiled_structure.py (HLO) and the
multiprocess suite.
"""

import numpy as np
import pytest
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.common.reduce_ops import ReduceOp


@pytest.fixture()
def engine():
    hvd.init()
    eng = hvd._engine()
    # fast arming for tests; restore after
    prev_warm, prev_on = (eng.config.step_replay_warmup,
                          eng.config.step_replay)
    eng.config.step_replay_warmup = 2
    eng.config.step_replay = True
    eng.replay.invalidate_all("test isolation")
    # the engine is the process-global one: start each test from zero
    eng.replay.replayed_steps = 0
    eng.replay.captured_streams = 0
    eng.replay.fallbacks = 0
    yield eng
    eng.replay.invalidate_all("test isolation")
    eng.config.step_replay_warmup = prev_warm
    eng.config.step_replay = prev_on


def _data():
    rng = np.random.RandomState(0)
    return (jnp.asarray(rng.randn(4, 3).astype(np.float32)),
            jnp.asarray(rng.randn(7).astype(np.float32)))


def _grouped_step(eng, tensors, tag, op=ReduceOp.SUM):
    eng.step_begin()
    hs = eng.grouped_allreduce(list(tensors), name=tag, op=op)
    out = [h.result() for h in hs]
    eng.step_end()
    return out


def test_capture_then_replay_grouped(engine):
    a, b = _data()
    for i in range(4):
        out = _grouped_step(engine, (a, b), f"g.{i}")
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(b),
                                   rtol=1e-6)
    # warmup=2: steps 1-2 record, steps 3-4 replay
    assert engine.replay.captured_streams == 1
    assert engine.replay.replayed_steps == 2
    assert engine.replay.fallbacks == 0


def test_replayed_step_is_single_dispatch(engine):
    a, b = _data()
    for i in range(3):
        _grouped_step(engine, (a, b), f"g.{i}")
    d0 = engine.dispatch_count
    out = _grouped_step(engine, (a, b), "g.9")
    assert engine.dispatch_count - d0 == 1, \
        "a replayed step must be exactly ONE engine dispatch"
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a), rtol=1e-6)


def test_per_leaf_allreduce_stream_fuses(engine):
    """The headline collapse: a step of per-leaf allreduce_async calls is
    serviced by one fused launch once armed."""
    a, b = _data()
    for i in range(4):
        engine.step_begin()
        h1 = engine.allreduce(a, name=f"x.{i}", op=ReduceOp.SUM)
        h2 = engine.allreduce(b, name=f"y.{i}", op=ReduceOp.SUM)
        o1, o2 = h1.synchronize(), h2.synchronize()
        engine.step_end()
        np.testing.assert_allclose(np.asarray(o1), np.asarray(a), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(b), rtol=1e-6)
    assert engine.replay.replayed_steps == 2
    d0 = engine.dispatch_count
    engine.step_begin()
    h1 = engine.allreduce(a, name="x.9", op=ReduceOp.SUM)
    h2 = engine.allreduce(b, name="y.9", op=ReduceOp.SUM)
    h1.synchronize(), h2.synchronize()
    engine.step_end()
    assert engine.dispatch_count - d0 == 1


def test_signature_divergence_falls_back_correctly(engine):
    a, b = _data()
    for i in range(3):
        _grouped_step(engine, (a, b), f"g.{i}")
    assert engine.replay.replayed_steps == 1
    # different shapes: must fall back, produce correct values, and count
    out = _grouped_step(engine, (b, a), "div")
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(a), rtol=1e-6)
    assert engine.replay.fallbacks == 1
    # the armed stream survives a divergence: the next matching step replays
    out = _grouped_step(engine, (a, b), "g.9")
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a), rtol=1e-6)
    assert engine.replay.replayed_steps == 2


def test_midstream_divergence_flushes_buffered_prefix(engine):
    """Divergence after ops were buffered: the prefix handles must still
    yield exact results (zero-padded flush), the diverged op runs on the
    normal path."""
    a, b = _data()
    for i in range(3):
        engine.step_begin()
        engine.allreduce(a, name=f"x.{i}", op=ReduceOp.SUM).synchronize()
        engine.allreduce(b, name=f"y.{i}", op=ReduceOp.SUM).synchronize()
        engine.step_end()
    engine.step_begin()
    h1 = engine.allreduce(a, name="x.9", op=ReduceOp.SUM)   # buffered
    h3 = engine.allgather(b, name="gather.9")               # divergence
    o1 = h1.synchronize()
    o3 = h3.synchronize()
    engine.step_end()
    np.testing.assert_allclose(np.asarray(o1), np.asarray(a), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o3), np.asarray(b), rtol=1e-6)
    assert engine.replay.fallbacks >= 1


def test_early_wait_forces_launch(engine):
    """synchronize() before the recorded stream completes forces the fused
    launch (observable fallback) and still returns exact values."""
    a, b = _data()
    for i in range(3):
        engine.step_begin()
        engine.allreduce(a, name=f"x.{i}", op=ReduceOp.SUM).synchronize()
        engine.allreduce(b, name=f"y.{i}", op=ReduceOp.SUM).synchronize()
        engine.step_end()
    engine.step_begin()
    h1 = engine.allreduce(a, name="x.9", op=ReduceOp.SUM)
    o1 = h1.synchronize()   # stream expected y next — this forces a flush
    h2 = engine.allreduce(b, name="y.9", op=ReduceOp.SUM)  # normal path now
    o2 = h2.synchronize()
    engine.step_end()
    np.testing.assert_allclose(np.asarray(o1), np.asarray(a), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(b), rtol=1e-6)
    assert engine.replay.fallbacks >= 1


def test_join_invalidates_armed_streams(engine):
    a, b = _data()
    for i in range(3):
        _grouped_step(engine, (a, b), f"g.{i}")
    assert engine.replay.replayed_steps == 1
    engine.join()
    # every armed stream dropped: next matching steps re-record from scratch
    assert not any(e.get("armed") for e in engine.replay._seen.values())
    for i in range(2):
        out = _grouped_step(engine, (a, b), f"h.{i}")
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a),
                                   rtol=1e-6)
    assert engine.replay.replayed_steps == 1  # still re-warming
    _grouped_step(engine, (a, b), "h.9")
    assert engine.replay.replayed_steps == 2  # re-armed and replaying again


def test_world_version_bump_invalidates(engine):
    a, b = _data()
    for i in range(3):
        _grouped_step(engine, (a, b), f"g.{i}")
    assert engine.replay.replayed_steps == 1
    engine.world_version += 1  # what an elastic reset does via env
    out = _grouped_step(engine, (a, b), "g.9")
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a), rtol=1e-6)
    # the bump dropped the armed stream: this step recorded, not replayed
    assert engine.replay.replayed_steps == 1


def test_unreplayable_op_blocks_arming(engine):
    a, b = _data()
    for i in range(5):
        engine.step_begin()
        engine.allreduce(a, name=f"x.{i}", op=ReduceOp.SUM).synchronize()
        engine.allgather(b, name=f"ag.{i}").synchronize()
        engine.step_end()
    assert engine.replay.captured_streams == 0
    assert engine.replay.replayed_steps == 0


def test_alternating_signatures_each_arm(engine):
    """Two distinct step signatures (train/eval shape) each get their own
    armed program."""
    a, b = _data()
    for i in range(6):
        if i % 2 == 0:
            out = _grouped_step(engine, (a, b), f"train.{i}")
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a),
                                       rtol=1e-6)
        else:
            out = _grouped_step(engine, (b,), f"eval.{i}")
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(b),
                                       rtol=1e-6)
    # each signature: 2 recordings then 1 replay
    assert engine.replay.captured_streams == 2
    assert engine.replay.replayed_steps == 2


def test_disabled_never_arms(engine):
    engine.config.step_replay = False
    a, b = _data()
    for i in range(5):
        _grouped_step(engine, (a, b), f"g.{i}")
    assert engine.replay.captured_streams == 0
    assert engine.replay.replayed_steps == 0


def test_replay_events_and_fallback_counter(engine):
    events = []
    engine.on_replay = lambda ev, detail: events.append(ev)
    fallback_reasons = []
    engine.replay_fallback_counter = fallback_reasons.append
    a, b = _data()
    try:
        for i in range(4):
            _grouped_step(engine, (a, b), f"g.{i}")
        _grouped_step(engine, (b, a), "div")
    finally:
        engine.on_replay = None
        engine.replay_fallback_counter = None
    assert "capture" in events
    assert "replay" in events
    assert "fallback" in events
    assert len(fallback_reasons) == 1 and "divergence" in fallback_reasons[0]


def test_stall_inspector_replay_counter():
    from horovod_tpu.stall_inspector import StallInspector
    si = StallInspector(warning_seconds=1000.0, check_interval=1000.0)
    try:
        si.record_replay_fallback("signature divergence at op 0")
        si.record_replay_fallback("signature divergence at op 0")
        si.record_replay_fallback("join substitute dispatched mid-step")
        assert si.replay_fallbacks == 3
        reasons = si.replay_fallback_reasons()
        assert reasons["signature divergence at op 0"] == 2
    finally:
        si.stop()


def test_timeline_records_replay_events(tmp_path):
    import json
    import os
    from horovod_tpu.timeline import Timeline
    path = os.path.join(tmp_path, "tl.json")
    tl = Timeline(path)
    tl.start()
    tl.record_replay("capture", "armed after 3 identical steps")
    tl.record_replay("replay", "161 tensors in 1 launch")
    tl.record_replay("fallback", "signature divergence at op 0")
    tl.stop()
    events = json.load(open(path))
    names = [e["name"] for e in events]
    assert "REPLAY_CAPTURE" in names
    assert "REPLAY_REPLAY" in names
    assert "REPLAY_FALLBACK" in names


def test_step_context_manager_and_module_surface(engine):
    a, b = _data()
    for i in range(3):
        with hvd.step():
            h = hvd.grouped_allreduce_async([a, b], name=f"cm.{i}",
                                            op=hvd.Sum)
            out = [x.result() for x in h]
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a),
                                   rtol=1e-6)
    assert engine.replay.replayed_steps == 1


def test_broadcast_stream_replays(engine):
    """grouped_broadcast rides the replay program through the fused
    broadcast segment (join is size-gated off at size 1)."""
    a, b = _data()
    for i in range(4):
        engine.step_begin()
        hs = engine.grouped_broadcast([a, b], root_rank=0, name=f"bc.{i}")
        out = [h.synchronize() for h in hs]
        engine.step_end()
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(a),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(b),
                                   rtol=1e-6)
    assert engine.replay.replayed_steps == 2


def test_eager_optimizer_wraps_steps(engine, monkeypatch):
    """DistributedEagerOptimizer brackets its reduction phase in the step
    markers (the automatic wiring the ISSUE requires)."""
    import optax
    calls = []
    orig_begin, orig_end = engine.step_begin, engine.step_end
    monkeypatch.setattr(engine, "step_begin",
                        lambda: (calls.append("begin"), orig_begin())[1])
    monkeypatch.setattr(engine, "step_end",
                        lambda: (calls.append("end"), orig_end())[1])
    opt = hvd.optimizer.DistributedEagerOptimizer(optax.sgd(0.1))
    params = {"w": jnp.ones((3,), jnp.float32)}
    state = opt.init(params)
    grads = {"w": jnp.ones((3,), jnp.float32)}
    # size-1 worlds skip the reduction; exercise the reduce path directly
    opt.reduce_gradients(grads) if engine.backend.size() > 1 else \
        opt._reduce_async(list(grads.values()), [None])
    assert calls == ["begin", "end"]


# ---------------------------------------------------------------------------
# alltoall replay (ISSUE 17): even-split grouped dispatch arms/replays,
# the uneven eager form stays on the observe path, knob moves re-arm
# ---------------------------------------------------------------------------

def _a2a_step(eng, tensors, tag):
    eng.step_begin()
    hs = eng.grouped_alltoall(list(tensors), name=tag)
    out = [h.synchronize() for h in hs]
    eng.step_end()
    return out


def test_grouped_alltoall_stream_replays(engine):
    """Even-split grouped_alltoall takes intercept (it returns bare
    tensors, so a ReplayHandle can stand in): capture -> arm -> replay.
    Size-1 alltoall is identity, so values check exactly."""
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randn(6, 3).astype(np.float32))
    b = jnp.asarray(rng.randn(4).astype(np.float32))
    for i in range(4):
        out = _a2a_step(engine, (a, b), f"a2a.{i}")
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(a))
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(b))
    assert engine.replay.captured_streams == 1
    assert engine.replay.replayed_steps == 2
    assert engine.replay.fallbacks == 0


def test_replayed_alltoall_step_is_single_dispatch(engine):
    rng = np.random.RandomState(2)
    a = jnp.asarray(rng.randn(8, 2).astype(np.float32))
    for i in range(3):
        _a2a_step(engine, (a,), f"a2a1.{i}")
    d0 = engine.dispatch_count
    out = _a2a_step(engine, (a,), "a2a1.9")
    assert engine.dispatch_count - d0 == 1, \
        "a replayed alltoall step must be exactly ONE engine dispatch"
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(a))


def test_uneven_alltoall_keeps_observe_path(engine):
    """The uneven-capable eager alltoall yields (tensor, recv_splits) —
    a ReplayHandle cannot stand in for that pair, so it must observe
    (never arm), exactly like allgather."""
    a = jnp.asarray(np.arange(6.0, dtype=np.float32).reshape(6, 1))
    for i in range(5):
        engine.step_begin()
        out, counts = engine.alltoall(a, splits=[6],
                                      name=f"ua.{i}").synchronize()
        engine.step_end()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(a))
        assert list(np.asarray(counts)) == [6]
    assert engine.replay.captured_streams == 0
    assert engine.replay.replayed_steps == 0


def test_alltoall_algo_knob_move_rearms(engine):
    """A live HOROVOD_TPU_ALLTOALL_ALGO move lands in _algo_sig, so the
    armed a2a stream rebuilds instead of replaying a stale program."""
    rng = np.random.RandomState(3)
    a = jnp.asarray(rng.randn(4, 5).astype(np.float32))
    prev = engine.config.alltoall_algo
    try:
        for i in range(3):
            _a2a_step(engine, (a,), f"ka.{i}")
        assert engine.replay.replayed_steps == 1
        armed = [e["armed"] for e in engine.replay._seen.values()
                 if e.get("armed")]
        assert armed and armed[0].algo_sig[6] == prev
        engine.config.alltoall_algo = "flat"
        out = _a2a_step(engine, (a,), "ka.3")
        np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(a))
        rearmed = [e["armed"] for e in engine.replay._seen.values()
                   if e.get("armed")]
        assert rearmed and rearmed[0].algo_sig[6] == "flat"
    finally:
        engine.config.alltoall_algo = prev


def test_moe_ep_steady_state_one_dispatch_per_round(engine):
    """ISSUE 17 acceptance: the steady-state MoE-EP train step's exchange
    rounds each replay as exactly ONE fused engine dispatch — 4·L
    alltoall rounds on the size-1 world (the shared-grad allreduce round
    is skipped at n=1), zero fallbacks, finite loss."""
    import jax
    import optax
    from horovod_tpu.models.transformer import (
        TransformerConfig, init_params, make_moe_ep_train_step,
        moe_ep_partition)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=16,
                            dtype=jnp.float32, attention="flash",
                            use_moe=True, n_experts=4,
                            moe_capacity_factor=2.0)
    opt = optax.sgd(0.1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    shared, expert = moe_ep_partition(
        params, engine.backend.rank(), engine.backend.size(), cfg)
    step = make_moe_ep_train_step(engine, cfg, opt)
    st = (shared, expert, opt.init({"shared": shared, "expert": expert}))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
    first = None
    for _ in range(2):      # warmup: every exchange stream arms
        *st, loss = step(*st, tok, tgt)
        first = first if first is not None else float(loss)
    # warmup transient over; steady state must be pure replay
    engine.replay.replayed_steps = 0
    engine.replay.fallbacks = 0
    rounds = 4 * cfg.n_layers
    d0 = engine.dispatch_count
    *st, loss = step(*st, tok, tgt)
    assert engine.replay.replayed_steps == rounds
    assert engine.replay.fallbacks == 0
    assert engine.dispatch_count - d0 == rounds, \
        "each steady-state MoE exchange round must be ONE fused dispatch"
    assert np.isfinite(float(loss))
