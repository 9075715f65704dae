"""Schedule parity + structure suite for the ISSUE 16 pipeline schedules.

The load-bearing claim: interleaved virtual-stage and zero-bubble (B/W
split) schedules are BITWISE identical to 1F1B at matched microbatch
count — same loss, same gradients, same trajectory — because they reorder
when each microbatch's F/B/W work runs, never what it computes, and every
gradient accumulator is added in microbatch order. The suite drives every
schedule against 1F1B on a real 4-stage CPU mesh, including a
non-divisible microbatch count and the m < stages degenerate case (demote
to 1F1B with a one-time WARNING, not a crash), plus the compiled-structure
half of the tentpole: a replayed steady-state PP x DP(engine) step is O(1)
host dispatches regardless of microbatch count.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.pipeline import (
    PIPELINE_SCHEDULES, build_schedule_tables, pipeline_bubble_fraction,
    pipeline_chunk_placement, pipeline_train_step, predict_schedule_bubble,
    predict_schedule_time, resolve_pipeline_schedule, split_microbatches)

S = 4          # stages
NC = 8         # total cells (2 per stage; 1 per chunk at v=2)
D = 16
BM = 6         # rows per microbatch


def _cells(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(NC, D, D), jnp.float32) * 0.3,
            "b": jnp.asarray(rng.randn(NC, D), jnp.float32) * 0.1}


def _cell(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _stage_fn(sp, x):
    h, _ = lax.scan(lambda h, lp: (_cell(lp, h), None), x, sp)
    return h


def _loss(y, tgt):
    return jnp.mean((y - tgt) ** 2)


def _run(schedule, n_virtual, n_micro, steps=2, seed=0, boundary_codec=None):
    """Run `steps` SGD steps of the 8-cell pipeline under `schedule`;
    return (losses, final params in MODEL order) for bitwise comparison."""
    mesh = Mesh(np.array(jax.devices()[:S]), ("pipe",))
    sched, v = resolve_pipeline_schedule(schedule, S, n_micro, n_virtual)
    lpc = NC // (S * v)
    if pipeline_chunk_placement(sched, v) == "roundrobin":
        order = np.concatenate([
            np.arange((j * S + s) * lpc, (j * S + s + 1) * lpc)
            for s in range(S) for j in range(v)])
    else:
        order = np.arange(NC)
    params = jax.device_put(
        {k: np.asarray(a)[order] for k, a in _cells(seed).items()},
        NamedSharding(mesh, P("pipe")))

    def body(params, micro_in, micro_tgt):
        sp = params
        if v > 1:
            sp = jax.tree_util.tree_map(
                lambda a: a.reshape((v, lpc) + a.shape[1:]), params)
        loss, gs, _, _ = pipeline_train_step(
            _stage_fn, sp, micro_in, micro_tgt, _loss, "pipe", S,
            schedule=sched, n_virtual=v, boundary_codec=boundary_codec)
        if v > 1:
            gs = jax.tree_util.tree_map(
                lambda a: a.reshape((v * lpc,) + a.shape[2:]), gs)
        return loss, gs

    fn = jax.jit(jax.shard_map(body, mesh=mesh,
                           in_specs=(P("pipe"), P(), P()),
                           out_specs=(P(), P("pipe")), check_vma=False))
    rng = np.random.RandomState(100 + seed)
    x = split_microbatches(
        jnp.asarray(rng.randn(n_micro * BM, D), jnp.float32), n_micro)
    t = split_microbatches(
        jnp.asarray(rng.randn(n_micro * BM, D), jnp.float32), n_micro)
    losses = []
    for _ in range(steps):
        loss, gs = fn(params, x, t)
        losses.append(float(loss))
        params = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g,
                                        params, gs)
    inv = np.argsort(order)
    final = {k: np.asarray(a)[inv] for k, a in params.items()}
    return losses, final


def _assert_bitwise(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# bitwise trajectory parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule,n_virtual", [("zb", 1),
                                                ("interleaved", 2)])
def test_schedule_bitwise_parity(schedule, n_virtual):
    """zb / interleaved reproduce the 1F1B loss AND parameter trajectory
    bitwise over multiple steps at matched microbatch count."""
    base_l, base_p = _run("1f1b", 1, n_micro=8)
    l, p = _run(schedule, n_virtual, n_micro=8)
    assert l == base_l
    _assert_bitwise(p, base_p)


@pytest.mark.parametrize("schedule,n_virtual", [("zb", 1),
                                                ("interleaved", 2)])
def test_schedule_parity_non_divisible_micro(schedule, n_virtual):
    """m=5 is not divisible by 4 stages: the steady phase is ragged, every
    table row still fires each job exactly once, parity holds bitwise."""
    base_l, base_p = _run("1f1b", 1, n_micro=5)
    l, p = _run(schedule, n_virtual, n_micro=5)
    assert l == base_l
    _assert_bitwise(p, base_p)


@pytest.mark.parametrize("schedule", ["1f1b", "zb"])
def test_a_coded_stage_boundary_is_applied_or_refused(schedule):
    """A wire codec on a stage boundary (stage 1 -> 2 crossing DCN): the
    table executor applies it, the 1F1B executor cannot, and
    ``pipeline_train_step`` says so instead of moving the edge uncoded."""
    coded = ("int8", (False, True, False, False))
    if schedule == "1f1b":
        with pytest.raises(ValueError, match="'interleaved' and 'zb'"):
            _run("1f1b", 1, n_micro=8, steps=1, boundary_codec=coded)
        # no edge coded, or no codec: nothing is asked for, nothing refused
        _run("1f1b", 1, n_micro=8, steps=1,
             boundary_codec=("int8", (False,) * S))
        _run("1f1b", 1, n_micro=8, steps=1,
             boundary_codec=("none", coded[1]))
        return
    (plain,), _ = _run(schedule, 1, n_micro=8, steps=1)
    (quantized,), _ = _run(schedule, 1, n_micro=8, steps=1,
                           boundary_codec=coded)
    assert quantized != plain
    assert abs(quantized - plain) < 0.02 * plain


def test_m_less_than_stages_demotes_once_with_warning():
    """m < stages demotes any schedule to 1F1B with a ONE-TIME RuntimeWarning
    (not a crash), and the demoted run is exactly the 1F1B run."""
    from horovod_tpu.parallel import pipeline as pl
    key = ("micro", "zb", S, 2)
    pl._DEMOTE_WARNED.discard(key)
    with pytest.warns(RuntimeWarning, match="no steady phase"):
        sched, v = resolve_pipeline_schedule("zb", S, 2, 1)
    assert (sched, v) == ("1f1b", 1)
    # second resolution of the same degenerate case is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sched2, _ = resolve_pipeline_schedule("zb", S, 2, 1)
    assert sched2 == "1f1b"
    base_l, base_p = _run("1f1b", 1, n_micro=2)
    l, p = _run("zb", 1, n_micro=2)
    assert l == base_l
    _assert_bitwise(p, base_p)


def test_unknown_schedule_demotes():
    from horovod_tpu.parallel import pipeline as pl
    pl._DEMOTE_WARNED.discard(("schedule", "wavefront"))
    with pytest.warns(RuntimeWarning, match="unknown pipeline schedule"):
        sched, _ = resolve_pipeline_schedule("wavefront", S, 8, 1)
    assert sched == "1f1b"


def test_auto_resolves_to_valid_schedule():
    sched, v = resolve_pipeline_schedule("auto", S, 8, 2)
    assert sched in PIPELINE_SCHEDULES and sched != "auto"
    # auto at m < stages must land on 1f1b (the only correct candidate)
    sched_low, _ = resolve_pipeline_schedule("auto", S, 2, 1)
    assert sched_low == "1f1b"


# ---------------------------------------------------------------------------
# flagship transformer parity
# ---------------------------------------------------------------------------

def test_flagship_zb_matches_1f1b():
    """The transformer flagship under schedule='zb' reproduces the 1F1B
    step bitwise (loss + updated params), embedding/head roles included."""
    import optax
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=4, d_ff=64, max_seq=16,
                                dtype=jnp.float32, attention="flash")
    params = tfm.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.RandomState(7)
    tok = jnp.asarray(rng.randint(0, 64, size=(8, 16)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 64, size=(8, 16)).astype(np.int32))
    mesh = Mesh(np.array(jax.devices()[:4]), (tfm.PIPE_AXIS,))
    specs = tfm.pp_param_specs(cfg)

    def place():
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(np.asarray(x),
                                        NamedSharding(mesh, s)),
            params, specs)

    outs = {}
    for sched in ("1f1b", "zb"):
        p = place()
        opt = optax.sgd(0.1)
        step = tfm.make_pp_train_step(mesh, cfg, opt, n_micro=4,
                                      schedule=sched)
        p, _, loss = step(p, opt.init(p), tok, tgt)
        outs[sched] = (float(loss), jax.tree_util.tree_map(np.asarray, p))
    assert outs["zb"][0] == outs["1f1b"][0]
    _assert_bitwise(outs["zb"][1], outs["1f1b"][1])


# ---------------------------------------------------------------------------
# predictor + table structure
# ---------------------------------------------------------------------------

def test_bubble_fraction_closed_forms():
    assert pipeline_bubble_fraction(4, 4) == pytest.approx(3 / 7)
    assert pipeline_bubble_fraction(4, 8, "1f1b") == pytest.approx(3 / 11)
    # interleaved: q/(m+q), q=(p-1)/v
    q = 3 / 2
    assert pipeline_bubble_fraction(4, 8, "interleaved", 2) \
        == pytest.approx(q / (8 + q))
    # one stage pipelines nothing
    assert pipeline_bubble_fraction(1, 8, "zb") == 0.0


def test_predictor_orders_schedules():
    """The analytic predictor ranks zb < interleaved < 1f1b on bubble at
    (p=4, m=8) — the ordering the paper's schedules exist to deliver."""
    b = {s: predict_schedule_bubble(s, 4, 8, v)
         for s, v in (("1f1b", 1), ("interleaved", 2), ("zb", 1))}
    assert b["zb"] < b["interleaved"] < b["1f1b"]
    # predictor time is positive and increases with m
    assert 0 < predict_schedule_time("zb", 4, 4) \
        < predict_schedule_time("zb", 4, 8)


@pytest.mark.parametrize("schedule,v,m", [("1f1b", 1, 8), ("zb", 1, 8),
                                          ("interleaved", 2, 8),
                                          ("zb", 1, 5),
                                          ("interleaved", 2, 5)])
def test_tables_fire_every_job_exactly_once(schedule, v, m):
    """Structural invariant: each (microbatch, chunk) F and B fires exactly
    once across the table, and under zb the W count equals the B count
    (every deferred weight pass lands)."""
    tb = build_schedule_tables(schedule, S, m, v)
    C = S * v
    f_seen, b_seen, w_seen = set(), set(), set()
    for tick in range(tb.ticks):
        for s in range(S):
            if tb.rows["f_active"][tick, s]:
                job = (int(tb.rows["f_m"][tick, s]),
                       int(tb.rows["f_j"][tick, s]), s)
                assert job not in f_seen
                f_seen.add(job)
            if tb.rows["b_active"][tick, s]:
                job = (int(tb.rows["b_m"][tick, s]),
                       int(tb.rows["b_j"][tick, s]), s)
                assert job not in b_seen
                b_seen.add(job)
            if tb.split_bw and tb.rows["w_active"][tick, s]:
                job = (int(tb.rows["w_m"][tick, s]),
                       int(tb.rows["w_j"][tick, s]), s)
                assert job not in w_seen
                w_seen.add(job)
    # every chunk's B fires for every microbatch
    assert len(b_seen) == m * C
    # F jobs exist for all but the last chunk (its F folds into B)
    assert len(f_seen) == m * (C - 1)
    if tb.split_bw:
        assert len(w_seen) == len(b_seen)


def test_1f1b_tick_count_matches_hand_schedule():
    """The greedy generator reproduces the canonical 1F1B tick count
    m + 2(p-1) — the hand-derived mapping pipeline_train_1f1b runs."""
    for m in (4, 5, 8, 12):
        assert build_schedule_tables("1f1b", S, m, 1).ticks == m + 2 * (S - 1)


def test_chunk_placement_rules():
    assert pipeline_chunk_placement("1f1b", 1) == "contiguous"
    assert pipeline_chunk_placement("1f1b", 2) == "contiguous"
    assert pipeline_chunk_placement("interleaved", 2) == "roundrobin"
    # at v=1 (one chunk per stage) the placements coincide
    assert pipeline_chunk_placement("zb", 1) == "contiguous"
    assert pipeline_chunk_placement("zb", 2) == "roundrobin"


# ---------------------------------------------------------------------------
# O(1) dispatches: PP x DP(engine) with replay
# ---------------------------------------------------------------------------

def test_replayed_pipeline_step_is_o1_dispatches():
    """Steady-state engine dispatches per PP x DP step are O(1) in the
    microbatch count: the microbatch loop lives inside ONE jitted scan, and
    the engine's DP-sync + ZeRO-1 update replays as one fused launch."""
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=4, d_ff=64, max_seq=16,
                                dtype=jnp.float32, attention="flash")
    mesh = Mesh(np.array(jax.devices()[:4]), (tfm.PIPE_AXIS,))
    specs = tfm.pp_param_specs(cfg)
    rng = np.random.RandomState(5)
    tok = jnp.asarray(rng.randint(0, 64, size=(8, 16)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 64, size=(8, 16)).astype(np.int32))
    hvd.init()
    eng = hvd._engine()

    def steady_dispatches(n_micro):
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(
                np.asarray(x), NamedSharding(mesh, s)),
            tfm.init_params(jax.random.PRNGKey(4), cfg), specs)
        opt = DistributedEagerOptimizer(optax.sgd(0.05), sharded=True,
                                        op=hvd.Sum)
        st = opt.init(params)
        step = tfm.make_pp_engine_train_step(mesh, cfg, opt, n_micro,
                                             schedule="zb")
        warmup = eng.config.step_replay_warmup + 2
        for _ in range(warmup):
            params, st, loss = step(params, st, tok, tgt)
        jax.block_until_ready(loss)
        d0 = eng.dispatch_count
        params, st, loss = step(params, st, tok, tgt)
        jax.block_until_ready(loss)
        return eng.dispatch_count - d0

    d4 = steady_dispatches(4)
    d8 = steady_dispatches(8)
    # O(1): doubling the microbatch count must not change the engine
    # dispatch count, and the replayed stream is a single fused launch
    assert d4 == d8 == 1
    assert eng.replay.replayed_steps > 0


# ---------------------------------------------------------------------------
# tier-1-safe perf smoke (CI: lint workflow runs -m perf)
# ---------------------------------------------------------------------------

@pytest.mark.perf
def test_pipeline_schedule_smoke_2stage():
    """2-stage tiny-model smoke: the schedule selector fires (env-style
    selector input resolved through resolve_pipeline_schedule) and replay
    capture arms on the engine-ridden step. Build + a few iterations on
    CPU, no timing assertions."""
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    sched, v = resolve_pipeline_schedule("zb", 2, 4, 1)
    assert sched == "zb" and v == 1     # selector fired, no demotion
    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=8,
                                dtype=jnp.float32, attention="flash")
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    specs = tfm.pp_param_specs(cfg)
    rng = np.random.RandomState(9)
    tok = jnp.asarray(rng.randint(0, 32, size=(4, 8)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 32, size=(4, 8)).astype(np.int32))
    hvd.init()
    eng = hvd._engine()
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(np.asarray(x), NamedSharding(mesh, s)),
        tfm.init_params(jax.random.PRNGKey(8), cfg), specs)
    opt = DistributedEagerOptimizer(optax.sgd(0.05), sharded=True,
                                    op=hvd.Sum)
    st = opt.init(params)
    step = tfm.make_pp_engine_train_step(mesh, cfg, opt, n_micro=4,
                                         schedule=sched)
    captured0 = eng.replay.captured_streams
    for _ in range(eng.config.step_replay_warmup + 2):
        params, st, loss = step(params, st, tok, tgt)
    jax.block_until_ready(loss)
    assert np.isfinite(float(loss))
    assert eng.replay.captured_streams > captured0   # replay capture fired


def test_pp_moe_composition_2stage_2expert_bitwise_reproducible():
    """ISSUE 17 satellite: PP x MoE-EP composition — the 2-stage pipeline
    flagship with the MoE FFN (2 experts per stage-local layer) trains
    through make_pp_engine_train_step, loss improves, and the whole loss
    trajectory is bitwise-reproducible from identical state (fresh
    replay both runs, so capture/arm/replay paths line up too)."""
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.optimizer import DistributedEagerOptimizer

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=8,
                                dtype=jnp.float32, attention="flash",
                                use_moe=True, n_experts=2,
                                moe_capacity_factor=2.0)
    mesh = Mesh(np.array(jax.devices()[:2]), (tfm.PIPE_AXIS,))
    specs = tfm.pp_param_specs(cfg)
    assert "router" in specs["layers"], "MoE pp specs must place the router"
    rng = np.random.RandomState(11)
    tok = jnp.asarray(rng.randint(0, 32, size=(4, 8)).astype(np.int32))
    tgt = jnp.asarray(rng.randint(0, 32, size=(4, 8)).astype(np.int32))
    hvd.init()
    eng = hvd._engine()

    def trajectory():
        eng.replay.invalidate_all("test isolation")
        params = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(
                np.asarray(x), NamedSharding(mesh, s)),
            tfm.init_params(jax.random.PRNGKey(8), cfg), specs)
        opt = DistributedEagerOptimizer(optax.sgd(0.05), sharded=True,
                                        op=hvd.Sum)
        st = opt.init(params)
        step = tfm.make_pp_engine_train_step(mesh, cfg, opt, n_micro=4,
                                             schedule="1f1b")
        out = []
        for _ in range(4):
            params, st, loss = step(params, st, tok, tgt)
            out.append(float(loss))
        return out

    l1 = trajectory()
    l2 = trajectory()
    assert l1 == l2, "PP x MoE trajectory must be bitwise-reproducible"
    assert all(np.isfinite(v) for v in l1)
    assert l1[-1] < l1[0], l1
