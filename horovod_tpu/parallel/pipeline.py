"""Pipeline parallelism: microbatched stage pipeline over a mesh axis.

Beyond the reference's DP-only surface (SURVEY §2.8: no PP), built the
TPU-native way: the L layers are split into ``n_stages`` contiguous stages,
one per device along the ``pipe`` axis; microbatches stream through a
``lax.scan`` of pipeline ticks, and activations hop stage→stage with a
single ``lax.ppermute`` per tick (one ICI neighbor link). The schedule is
the classic fill-drain ladder: ``n_micro + n_stages − 1`` ticks, bubble
fraction ``(n_stages−1)/(n_micro+n_stages−1)``.

Training-grade properties (VERDICT r3 item 5):

- **Bubble ticks do no stage work.** Each stage's compute sits under a
  ``lax.cond`` on its (tick, stage) activity window, which XLA compiles to a
  real runtime conditional — fill/drain ticks skip the stage matmuls
  instead of computing garbage that is masked away.
- **Heterogeneous first/last stages.** ``first_fn`` (embedding: runs only on
  stage 0, mapping the raw microbatch to the activation shape) and
  ``last_fn`` (head: runs only on the last stage, mapping the activation to
  the output shape) let a real LM pipeline — embed → blocks → head — run
  with a shape-uniform ring (only the [mb, T, D] activation ever hops).
- **Activation-memory control.** ``remat=True`` wraps each stage application
  in ``jax.checkpoint``: the backward recomputes the stage from its input,
  so per-tick residuals shrink from every intermediate to one activation.
  NOTE the bound this buys is still O(n_micro): AD through ``lax.scan``
  stores (at least) the scan carry per tick, so the backward's live set
  grows with the microbatch count. For n_micro ≫ n_stages use
  :func:`pipeline_train_1f1b` below — a hand-scheduled 1F1B whose stash is
  a static ``2·n_stages−1`` slots, giving O(n_stages) live activations
  independent of n_micro (VERDICT r4 item 4).

``pipeline_apply_p`` stays differentiable end-to-end: AD transposes the
ppermute (reverse hop), the conds, and the scan — the simple choice when
n_micro is moderate. ``pipeline_train_1f1b`` is the training-grade
schedule when it isn't.
"""

from __future__ import annotations

import logging
import warnings
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.collectives import broadcast_p

logger = logging.getLogger("horovod_tpu.pipeline")

#: The schedule selector surface (HOROVOD_TPU_PIPELINE_SCHEDULE):
#: "1f1b" is the hand-scheduled baseline below; "interleaved" runs
#: virtual-stage round-robin chunks (Narayanan et al. 2021);
#: "zb" splits the backward into B (activation-grad) and W (weight-grad)
#: passes with W deferred into the drain (Qi et al. 2023); "auto" picks
#: schedule + microbatch count from the calibrated α–β model.
PIPELINE_SCHEDULES = ("1f1b", "interleaved", "zb", "auto")

# per-cell slot work in F-units for the analytic predictor: a full
# backward recomputes the cell (remat by construction) then pulls both
# grads (≈ 3 F); the zb split pays the recompute in BOTH halves —
# B = recompute + dx (2 F), W = recompute + dw (2 F) — the honest cost
# of the stash-the-input formulation (no linearization residuals are
# carried across ticks).
SLOT_COST_F = 1.0
SLOT_COST_B_FULL = 3.0
SLOT_COST_B_SPLIT = 2.0
SLOT_COST_W = 2.0


def pipeline_bubble_fraction(n_stages: int, n_micro: int,
                             schedule: str = "1f1b",
                             n_virtual: int = 1) -> float:
    """Analytic bubble fraction of one pipeline schedule (the fraction of
    the schedule's wall time that is fill/drain bubble rather than
    microbatch work).

    - ``1f1b`` (= fill-drain): the classic ``(p-1)/(m+p-1)``.
    - ``interleaved`` with ``v`` virtual chunks per stage: the fill/drain
      ramp shrinks to per-CELL hops, ``q/(m+q)`` with ``q=(p-1)/v``
      (Narayanan et al. 2021 eq. 2 in tick units).
    - ``zb``: derived from the generated schedule table with the weighted
      slot costs above (there is no clean closed form once W placement
      and the extra recompute are priced honestly) — see
      :func:`predict_schedule_bubble`.
    """
    p, m, v = n_stages, n_micro, max(1, n_virtual)
    if p <= 1:
        return 0.0
    if schedule in ("1f1b", "auto"):
        return (p - 1) / (m + p - 1)
    if schedule == "interleaved":
        q = (p - 1) / v
        return q / (m + q)
    if schedule == "zb":
        return predict_schedule_bubble("zb", p, m, 1)
    raise ValueError(f"unknown pipeline schedule {schedule!r}")


def pipeline_apply_p(stage_fn: Callable, stage_params, micro_inputs,
                     axis_name: str, n_stages: int,
                     first_fn: Optional[Callable] = None,
                     first_params=None,
                     last_fn: Optional[Callable] = None,
                     last_params=None,
                     out_struct=None,
                     remat: bool = False):
    """Run the pipeline inside ``shard_map`` (the ``pipe`` axis manual).

    Args:
      stage_fn: ``(stage_params, x) -> y`` — one stage's computation; must
        preserve the activation shape ``[mb, ...]`` (the ring is
        shape-uniform; heterogeneous ends go through first_fn/last_fn).
      stage_params: THIS stage's parameter pytree (shard the stacked
        ``[n_stages, ...]`` params over the pipe axis and index block 0).
      micro_inputs: ``[n_micro, mb, ...]`` microbatches (replicated; only
        stage 0 reads them). With ``first_fn`` these may be raw model inputs
        (e.g. int32 token ids) of a different shape/dtype than the
        activation.
      n_stages: size of the pipe axis.
      first_fn: optional ``(first_params, micro) -> activation`` applied on
        stage 0 only (embedding).
      last_fn: optional ``(last_params, y) -> out`` applied on the last
        stage only (head). When given, ``out_struct`` must be a
        ``jax.ShapeDtypeStruct`` (or array) describing one microbatch's
        output.
      remat: jax.checkpoint each stage application (activation-memory
        control for deep stages).

    Returns ``[n_micro, *out_shape]`` outputs, replicated across the axis.
    """
    n_micro = micro_inputs.shape[0]
    stage = lax.axis_index(axis_name)
    total_ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    last = n_stages - 1

    s_fn = stage_fn
    f_fn = first_fn
    l_fn = last_fn
    if remat:
        s_fn = jax.checkpoint(s_fn, prevent_cse=False)
        f_fn = jax.checkpoint(f_fn, prevent_cse=False) if f_fn else None
        l_fn = jax.checkpoint(l_fn, prevent_cse=False) if l_fn else None

    # One activation probe to fix the ring's uniform shape/dtype.
    if f_fn is not None:
        act_struct = jax.eval_shape(f_fn, first_params, micro_inputs[0])
    else:
        act_struct = jax.eval_shape(lambda x: x, micro_inputs[0])
    act0 = jnp.zeros(act_struct.shape, act_struct.dtype)
    if l_fn is not None:
        if out_struct is None:
            out_struct = jax.eval_shape(l_fn, last_params, act0)
        out0 = jnp.zeros((n_micro,) + tuple(out_struct.shape),
                         out_struct.dtype)
    else:
        out0 = jnp.zeros((n_micro,) + tuple(act_struct.shape),
                         act_struct.dtype)

    def tick(carry, t):
        in_flight, outputs = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        micro = lax.dynamic_index_in_dim(micro_inputs, mb_idx, axis=0,
                                         keepdims=False)
        # Stage s works on microbatch t-s; outside [0, n_micro) it is a
        # fill/drain bubble tick — a real lax.cond, so XLA skips the stage
        # compute at runtime instead of masking it.
        active = jnp.logical_and(t >= stage, t - stage < n_micro)

        def do_work(_):
            if f_fn is not None:
                x = lax.cond(stage == 0,
                             lambda _: f_fn(first_params, micro),
                             lambda _: in_flight, None)
            else:
                x = jnp.where(stage == 0, micro, in_flight)
            return s_fn(stage_params, x)

        y = lax.cond(active, do_work, lambda _: jnp.zeros_like(act0), None)

        # the last stage emits microbatch t-(n_stages-1) once the fill phase
        # is over
        out_idx = t - last
        emit = jnp.logical_and(stage == last, out_idx >= 0)
        if l_fn is not None:
            out_val = lax.cond(
                emit, lambda _: l_fn(last_params, y),
                lambda _: jnp.zeros(out_struct.shape, out_struct.dtype),
                None)
        else:
            out_val = y
        upd = lax.dynamic_update_index_in_dim(
            outputs, out_val.astype(outputs.dtype),
            jnp.clip(out_idx, 0, n_micro - 1), axis=0)
        outputs = jnp.where(emit, upd, outputs)
        # hop every stage's activation one stage forward (single ppermute)
        in_flight = lax.ppermute(y, axis_name, fwd_perm)
        return (in_flight, outputs), None

    (_, outputs), _ = lax.scan(tick, (act0, out0), jnp.arange(total_ticks))
    # results live on the last stage; replicate them
    return broadcast_p(outputs, axis_name, root_rank=last)


def _vma_of(x):
    """The set of manual axes ``x`` is varying over (empty outside manual
    regions and under ``check_vma=False``)."""
    return set(jax.typeof(x).vma)


def _vary(x, axes):
    """Mark ``x`` varying over ``axes`` (a name or tuple of names —
    shard_map VMA typing); only the axes it is not ALREADY varying over
    are cast (pcast rejects re-varying an axis). A no-op on the value
    under ``check_vma=False``."""
    if isinstance(axes, str):
        axes = (axes,)
    need = tuple(a for a in axes if a not in _vma_of(x))
    if not need:
        return x
    return lax.pcast(x, need, to="varying")


def pipeline_train_1f1b(stage_fn: Callable, stage_params, micro_inputs,
                        micro_targets, loss_fn: Callable,
                        axis_name: str, n_stages: int,
                        first_fn: Optional[Callable] = None,
                        first_params=None,
                        last_fn: Optional[Callable] = None,
                        last_params=None):
    """Memory-bounded 1F1B pipeline training step (run inside shard_map).

    The schedule: stage s runs the FORWARD of microbatch m at tick
    ``m + s`` and its BACKWARD at tick ``m + 2·(n_stages−1) − s`` — the
    last stage's backward follows its forward immediately (the defining
    1F1B property), cotangents flow back one hop per tick, and every stage
    is doing one F and one B in steady state. Total ticks:
    ``n_micro + 2·(n_stages−1)``; bubble fraction identical to fill-drain.

    Memory is the point (VERDICT r4 item 4): each backward *recomputes* its
    stage from the stashed stage INPUT inside ``jax.vjp`` (remat by
    construction), so a stage keeps at most ``2·n_stages−1`` stashed
    activations — O(n_stages), independent of n_micro — where
    differentiating the fill-drain scan with AD keeps O(n_micro) live.

    Args:
      stage_fn: ``(stage_params, x) -> y`` shape-preserving stage.
      stage_params: THIS stage's parameter pytree (sharded over the axis).
      micro_inputs: ``[n_micro, mb, ...]`` raw microbatch inputs
        (replicated). Stage 0 reads them (through ``first_fn`` if given).
      micro_targets: ``[n_micro, mb, ...]`` per-microbatch targets
        (replicated); only the last stage reads them.
      loss_fn: ``(out, target) -> scalar`` per-microbatch loss (a mean —
        the returned loss is the mean over microbatches).
      first_fn/first_params: optional stage-0 embedding
        ``(first_params, micro) -> activation``.
      last_fn/last_params: optional last-stage head
        ``(last_params, y) -> out``.

    Returns ``(loss, stage_grads, first_grads, last_grads)``: loss is the
    replicated scalar mean; stage_grads is per-stage (varying over the
    axis, like stage_params); first/last grads are replicated (psum'd, so
    every rank can run the same optimizer update on the replicated
    first/last params).
    """
    if n_stages < 2:
        raise ValueError("pipeline_train_1f1b needs n_stages >= 2; a "
                         "single stage is just a plain train step")
    n_micro = micro_inputs.shape[0]
    if n_micro < 1:
        raise ValueError("need at least one microbatch")
    stage = lax.axis_index(axis_name)
    last = n_stages - 1
    total_ticks = n_micro + 2 * last
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    depth = 2 * n_stages - 1  # stash lifetime bound: 2*(last-s)+1 ticks

    has_first = first_fn is not None
    has_last = last_fn is not None
    if first_params is None:
        first_params = ()
    if last_params is None:
        last_params = ()

    # The schedule's internal constants (zero activations, stash, grad
    # accumulators) must be varying over the UNION of the manual axes its
    # data varies over — under a composed (data, pipe) mesh the inputs
    # carry data-varying and the stage computation adds pipe-varying, so
    # varying over pipe alone mistypes every cond/switch branch.
    vary_axes = {axis_name}
    for leaf in jax.tree_util.tree_leaves(
            (micro_inputs, micro_targets, stage_params, first_params,
             last_params)):
        vary_axes |= _vma_of(leaf)
    vary_axes = tuple(sorted(vary_axes))

    # activation struct probing (the ring is shape-uniform)
    if has_first:
        act_struct = jax.eval_shape(first_fn, first_params, micro_inputs[0])
    else:
        act_struct = jax.eval_shape(lambda x: x, micro_inputs[0])
    act0 = _vary(jnp.zeros(act_struct.shape, act_struct.dtype),
                 vary_axes)

    def stage0_composite(sp, fp, micro):
        x = first_fn(fp, micro) if has_first else micro.astype(act0.dtype)
        return stage_fn(sp, x)

    def last_composite(sp, lp, x, tgt):
        y = stage_fn(sp, x)
        out = last_fn(lp, y) if has_last else y
        return loss_fn(out, tgt)

    def zeros_like_tree(t):
        return jax.tree_util.tree_map(
            lambda a: _vary(jnp.zeros(a.shape, a.dtype), vary_axes), t)

    def _zero_loss():
        return _vary(jnp.zeros((), jnp.float32), vary_axes)

    def tick(carry, t):
        fwd_in, bwd_in, stash, gs, gf, gl, loss_acc = carry
        m_f = t - stage
        m_b = t - 2 * last + stage
        # the last stage's F work happens inside its B-slot recompute, so
        # its F slot (and stash) are skipped entirely
        f_active = jnp.logical_and(jnp.logical_and(m_f >= 0,
                                                   m_f < n_micro),
                                   stage != last)
        b_active = jnp.logical_and(m_b >= 0, m_b < n_micro)
        micro_f = lax.dynamic_index_in_dim(
            micro_inputs, jnp.clip(m_f, 0, n_micro - 1), 0, keepdims=False)
        micro_b = lax.dynamic_index_in_dim(
            micro_inputs, jnp.clip(m_b, 0, n_micro - 1), 0, keepdims=False)
        tgt_b = lax.dynamic_index_in_dim(
            micro_targets, jnp.clip(m_b, 0, n_micro - 1), 0, keepdims=False)

        # ---- F slot: compute this stage's activation, stash its input
        def do_f(_):
            x = lax.cond(stage == 0,
                         lambda _: (first_fn(first_params, micro_f)
                                    if has_first
                                    else micro_f.astype(act0.dtype)),
                         lambda _: fwd_in, None)
            return stage_fn(stage_params, x), x

        y_f, x_f = lax.cond(f_active, do_f,
                            lambda _: (act0, act0), None)
        stash = lax.cond(
            f_active,
            lambda st: lax.dynamic_update_index_in_dim(
                st, x_f, jnp.mod(m_f, depth), 0),
            lambda st: st, stash)

        # ---- B slot: recompute the stage from its stashed input inside
        # jax.vjp (remat by construction), pull the cotangent through
        x_b = lax.dynamic_index_in_dim(stash, jnp.mod(m_b, depth), 0,
                                       keepdims=False)

        def vary_tree(t):
            # Params must be marked FULLY varying (over every manual axis
            # the data varies over) BEFORE the vjp: differentiating w.r.t.
            # an input unvarying over some axis makes the transpose insert
            # an implicit psum over that axis — inside a lax.switch branch
            # only SOME ranks execute, i.e. a cross-device deadlock (and
            # under a composed data axis, a premature replica combine).
            # Varying inputs get per-rank cotangents with no collective;
            # the schedule's trailing psum (and the caller's data-axis
            # pmean) do the combines explicitly.
            return jax.tree_util.tree_map(
                lambda a: _vary(a, vary_axes), t)

        def b_first(_):
            _, pull = jax.vjp(
                lambda sp, fp: stage0_composite(sp, fp, micro_b),
                vary_tree(stage_params), vary_tree(first_params))
            dgs, dgf = pull(bwd_in)
            return (dgs, dgf, zeros_like_tree(last_params), act0,
                    _zero_loss())

        def b_mid(_):
            _, pull = jax.vjp(stage_fn, vary_tree(stage_params), x_b)
            dgs, dx = pull(bwd_in)
            return (dgs, zeros_like_tree(first_params),
                    zeros_like_tree(last_params), dx, _zero_loss())

        def b_last(_):
            # x arrives THIS tick via fwd_in (sent by stage last-1 at the
            # previous tick); loss seeds the cotangent chain
            loss_m, pull = jax.vjp(
                lambda sp, lp, x: last_composite(sp, lp, x, tgt_b),
                vary_tree(stage_params), vary_tree(last_params), fwd_in)
            dgs, dgl, dx = pull(jnp.ones_like(loss_m))
            return (dgs, zeros_like_tree(first_params), dgl, dx,
                    loss_m.astype(jnp.float32))

        def do_b(_):
            role = jnp.where(stage == 0, 0,
                             jnp.where(stage == last, 2, 1)).astype(jnp.int32)
            return lax.switch(role, (b_first, b_mid, b_last), None)

        def skip_b(_):
            return (zeros_like_tree(stage_params),
                    zeros_like_tree(first_params),
                    zeros_like_tree(last_params), act0, _zero_loss())

        dgs, dgf, dgl, dx_b, loss_c = lax.cond(b_active, do_b, skip_b, None)

        gs = jax.tree_util.tree_map(jnp.add, gs, dgs)
        gf = jax.tree_util.tree_map(jnp.add, gf, dgf)
        gl = jax.tree_util.tree_map(jnp.add, gl, dgl)
        loss_acc = loss_acc + loss_c

        # communication: activations hop forward, cotangents hop backward
        fwd_in = lax.ppermute(y_f, axis_name, fwd_perm)
        bwd_in = lax.ppermute(dx_b, axis_name, bwd_perm)
        return (fwd_in, bwd_in, stash, gs, gf, gl, loss_acc), None

    stash0 = _vary(jnp.zeros((depth,) + tuple(act_struct.shape),
                             act_struct.dtype), vary_axes)
    carry0 = (act0, act0, stash0,
              zeros_like_tree(stage_params), zeros_like_tree(first_params),
              zeros_like_tree(last_params), _zero_loss())
    (fwd_in, bwd_in, stash, gs, gf, gl,
     loss_acc), _ = lax.scan(tick, carry0, jnp.arange(total_ticks))

    inv = 1.0 / n_micro
    # loss lives on the last stage, first/last grads on their stages: psum
    # replicates them (all other ranks contribute zeros)
    loss = lax.psum(loss_acc, axis_name) * inv
    gf = jax.tree_util.tree_map(
        lambda a: lax.psum(a * inv, axis_name), gf)
    gl = jax.tree_util.tree_map(
        lambda a: lax.psum(a * inv, axis_name), gl)
    gs = jax.tree_util.tree_map(lambda a: a * inv, gs)
    return loss, gs, gf, gl


# ---------------------------------------------------------------------------
# Schedule tables (ISSUE 16 tentpole)
#
# The interleaved and zero-bubble schedules are not hand-mapped like 1F1B
# above: a greedy discrete-event list scheduler (pure Python, static in
# (schedule, p, m, v)) assigns F / B / W jobs to (tick, stage) slots while
# respecting the dataflow (one ring hop of latency per chunk boundary),
# then a second pass allocates stash / inbox buffer slots by interval
# coloring. The emitted int32 tables are closed over by ONE lax.scan — the
# dispatch path never re-derives the schedule (divcheck: resolved once per
# build, no env reads in the tick body).
#
# Chunk placement is round-robin: global chunk c (of C = p·v) lives on
# stage c % p at local index j = c // p, so EVERY chunk boundary is the
# same forward ring hop (the defining interleaved property) and one
# fwd + one bwd ppermute per tick serves any v.
# ---------------------------------------------------------------------------


class _Tables(NamedTuple):
    """Static schedule tables: every array is int32 [total_ticks, p]."""
    ticks: int
    n_chunks: int
    split_bw: bool           # zero-bubble B/W split active
    act_depth: int           # activation stash slots per stage
    ct_depth: int            # cotangent stash slots per stage (zb)
    a_depth: int             # activation inbox slots per stage
    c_depth: int             # cotangent inbox slots per stage
    rows: dict               # name -> np.ndarray [ticks, p]


def _greedy_schedule(schedule: str, p: int, m: int, v: int):
    """Pass 1: greedy list scheduling of the F/B/W job DAG onto
    (tick, stage) slots. Returns ``(fdone, bdone, wdone)`` job->tick maps.

    Dependencies (one ring hop = one tick of latency): F(m,c) needs
    F(m,c-1) done a tick earlier; B(m,C-1) folds the last chunk's forward
    + loss, so it needs F(m,C-2)'s activation; B(m,c) needs B(m,c+1)'s
    cotangent; W(m,c) (zb only) needs B(m,c) (same tick allowed — the
    executor runs the B slot before the W slot).

    Priorities keep per-chunk gradient accumulation in microbatch order
    (the bitwise-parity requirement): B picks smallest m (tie: deepest
    chunk), F picks smallest (m, c) — depth-first, which at v=1
    reproduces the hand 1F1B tick mapping exactly. W fills bubbles: it
    fires only when the stage's F slot idles this tick, unless the
    deferred backlog would exceed p (the ZB-H1-style memory bound — the
    ct stash stays O(p), not O(m))."""
    C = p * v
    split = schedule == "zb"
    f_jobs = {(mm, c) for mm in range(m) for c in range(C - 1)}
    b_jobs = {(mm, c) for mm in range(m) for c in range(C)}
    w_jobs = ({(mm, c) for mm in range(m) for c in range(C)}
              if split else set())
    fdone, bdone, wdone = {}, {}, {}
    t = 0
    guard = 8 * (m + 2) * (C + 2) + 64
    while f_jobs or b_jobs or w_jobs:
        if t >= guard:
            raise RuntimeError(
                f"pipeline schedule generator stalled ({schedule}, p={p}, "
                f"m={m}, v={v})")
        for s in range(p):
            ready_b = []
            for (mm, c) in b_jobs:
                if c % p != s:
                    continue
                dep = (fdone.get((mm, C - 2)) if c == C - 1
                       else bdone.get((mm, c + 1)))
                if dep is not None and dep + 1 <= t:
                    ready_b.append((mm, -c))
            if ready_b:
                mm, negc = min(ready_b)
                bdone[(mm, -negc)] = t
                b_jobs.discard((mm, -negc))
            ready_f = []
            for (mm, c) in f_jobs:
                if c % p != s:
                    continue
                if c == 0 or ((mm, c - 1) in fdone
                              and fdone[(mm, c - 1)] + 1 <= t):
                    ready_f.append((mm, c))
            f_fired = bool(ready_f)
            if ready_f:
                mm, c = min(ready_f)
                fdone[(mm, c)] = t
                f_jobs.discard((mm, c))
            if split:
                ready_w = sorted(
                    (mm, c) for (mm, c) in w_jobs
                    if c % p == s and (mm, c) in bdone
                    and bdone[(mm, c)] <= t)
                if ready_w and (not f_fired or len(ready_w) >= p):
                    mm, c = ready_w[0]
                    wdone[(mm, c)] = t
                    w_jobs.discard((mm, c))
        t += 1
    return fdone, bdone, wdone


def _alloc_slots(intervals):
    """Greedy interval coloring: ``intervals`` is ``{key: (start, end)}``
    with INCLUSIVE conflict (a slot freed by a read at tick T is reusable
    from T+1 — within a tick, writes happen before reads in the executor
    body, so same-tick reuse would clobber). Returns (slot_of_key,
    n_slots)."""
    out, n_slots = {}, 0
    free, busy = [], []  # busy: list of (end, slot)
    for key, (start, end) in sorted(intervals.items(),
                                    key=lambda kv: (kv[1][0], kv[1][1])):
        busy = [(e, sl) for (e, sl) in busy if e >= start or free.append(sl)]
        if free:
            slot = min(free)
            free.remove(slot)
        else:
            slot = n_slots
            n_slots += 1
        busy.append((end, slot))
        out[key] = slot
    return out, max(n_slots, 1)


def build_schedule_tables(schedule: str, n_stages: int, n_micro: int,
                          n_virtual: int = 1) -> _Tables:
    """Build the static per-tick slot tables for one resolved schedule.
    Pure Python — called once per trace/build, cached."""
    key = (schedule, n_stages, n_micro, n_virtual)
    hit = _TABLE_CACHE.get(key)
    if hit is not None:
        return hit
    p, m, v = n_stages, n_micro, n_virtual
    C = p * v
    split = schedule == "zb"
    fdone, bdone, wdone = _greedy_schedule(schedule, p, m, v)
    ticks = max(list(fdone.values()) + list(bdone.values())
                + list(wdone.values())) + 1

    # pass 2: buffer slot allocation by interval coloring, per stage.
    act_iv = [dict() for _ in range(p)]   # (m, c) F-input stash
    ct_iv = [dict() for _ in range(p)]    # (m, c) cotangent stash (zb)
    a_in_iv = [dict() for _ in range(p)]  # (m, c) activation arrival
    c_in_iv = [dict() for _ in range(p)]  # (m, c) cotangent arrival
    for (mm, c), tf in fdone.items():
        s = c % p
        if c > 0:
            # chunk 0 never stashes: its backward re-embeds from the raw
            # microbatch (the stage0 composite), matching the 1F1B role
            last_read = wdone[(mm, c)] if split else bdone[(mm, c)]
            act_iv[s][(mm, c)] = (tf, last_read)
        # arrival of this F's output on the next stage, consumed by
        # F(m,c+1) — or by B(m,C-1) when c == C-2
        cons = (bdone[(mm, C - 1)] if c == C - 2 else fdone[(mm, c + 1)])
        a_in_iv[(c + 1) % p][(mm, c + 1)] = (tf + 1, cons)
    for (mm, c), tb in bdone.items():
        s = c % p
        if c >= 1:  # this B's dx arrives on the previous stage
            c_in_iv[(c - 1) % p][(mm, c - 1)] = (tb + 1, bdone[(mm, c - 1)])
        if split and c < C - 1:
            # incoming cotangent saved for the deferred W pull
            ct_iv[s][(mm, c)] = (tb, wdone[(mm, c)])
        if split and c == C - 1:
            # the last chunk's B consumed its x from the inbox; save it
            # for the W pull (same stash pool as the F inputs)
            act_iv[s][(mm, c)] = (tb, wdone[(mm, c)])
    act_slot, ct_slot, a_slot, c_slot = [], [], [], []
    act_d = ct_d = a_d = c_d = 1
    for s in range(p):
        sl, n = _alloc_slots(act_iv[s]); act_slot.append(sl); act_d = max(act_d, n)
        sl, n = _alloc_slots(ct_iv[s]); ct_slot.append(sl); ct_d = max(ct_d, n)
        sl, n = _alloc_slots(a_in_iv[s]); a_slot.append(sl); a_d = max(a_d, n)
        sl, n = _alloc_slots(c_in_iv[s]); c_slot.append(sl); c_d = max(c_d, n)

    def tab(fill=0):
        return np.full((ticks, p), fill, dtype=np.int32)

    rows = {name: tab(-1) for name in
            ("f_m", "f_j", "f_src", "f_stash",
             "b_m", "b_j", "b_role", "b_x", "b_in", "b_save", "b_ct_save",
             "w_m", "w_j", "w_role", "w_x", "w_ct",
             "a_write", "c_write")}
    for name in ("f_active", "b_active", "w_active"):
        rows[name] = tab(0)
    for (mm, c), tf in fdone.items():
        s = c % p
        rows["f_active"][tf, s] = 1
        rows["f_m"][tf, s] = mm
        rows["f_j"][tf, s] = c // p
        rows["f_src"][tf, s] = (-1 if c == 0 else a_slot[s][(mm, c)])
        if c > 0:
            rows["f_stash"][tf, s] = act_slot[s][(mm, c)]
            rows["a_write"][fdone[(mm, c - 1)] + 1, s] = a_slot[s][(mm, c)]
    for (mm, c), tb in bdone.items():
        s = c % p
        rows["b_active"][tb, s] = 1
        rows["b_m"][tb, s] = mm
        rows["b_j"][tb, s] = c // p
        rows["b_role"][tb, s] = (0 if c == 0 else (2 if c == C - 1 else 1))
        if c == C - 1:
            rows["b_in"][tb, s] = a_slot[s][(mm, c)]
            rows["a_write"][fdone[(mm, c - 1)] + 1, s] = a_slot[s][(mm, c)]
            if split:
                rows["b_save"][tb, s] = act_slot[s][(mm, c)]
        else:
            if c > 0:
                rows["b_x"][tb, s] = act_slot[s][(mm, c)]
            rows["b_in"][tb, s] = c_slot[s][(mm, c)]
            rows["c_write"][bdone[(mm, c + 1)] + 1, s] = c_slot[s][(mm, c)]
            if split:
                rows["b_ct_save"][tb, s] = ct_slot[s][(mm, c)]
    for (mm, c), tw in wdone.items():
        s = c % p
        rows["w_active"][tw, s] = 1
        rows["w_m"][tw, s] = mm
        rows["w_j"][tw, s] = c // p
        rows["w_role"][tw, s] = (0 if c == 0 else (2 if c == C - 1 else 1))
        if c > 0:
            rows["w_x"][tw, s] = act_slot[s][(mm, c)]
        if c < C - 1:
            rows["w_ct"][tw, s] = ct_slot[s][(mm, c)]
    out = _Tables(ticks=ticks, n_chunks=C, split_bw=split,
                  act_depth=act_d, ct_depth=ct_d, a_depth=a_d, c_depth=c_d,
                  rows=rows)
    _TABLE_CACHE[key] = out
    return out


_TABLE_CACHE: dict = {}


# ---------------------------------------------------------------------------
# Per-schedule analytic bubble predictor (ISSUE 16 satellite)
# ---------------------------------------------------------------------------

def _slot_cost(role: int, kind: str, split: bool) -> float:
    if kind == "F":
        return SLOT_COST_F
    if kind == "W":
        return SLOT_COST_W
    if not split:
        return SLOT_COST_B_FULL
    # split B: role 0's pull is ALL weight grads, so its B slot is pure
    # bookkeeping (the work moved wholesale into W)
    return 0.0 if role == 0 else SLOT_COST_B_SPLIT


def predict_schedule_time(schedule: str, n_stages: int, n_micro: int,
                          n_virtual: int = 1) -> float:
    """Total schedule time in F-slot units under the synchronized-tick
    model: stages run in parallel within a tick, so one tick costs the
    max over stages of its active slot work (F=1, full B=3, split
    B=2/0, W=2 — see SLOT_COST_*)."""
    tb = build_schedule_tables(schedule, n_stages, n_micro, n_virtual)
    r = tb.rows
    total = 0.0
    for t in range(tb.ticks):
        worst = 0.0
        for s in range(n_stages):
            cost = 0.0
            if r["f_active"][t, s]:
                cost += _slot_cost(0, "F", tb.split_bw)
            if r["b_active"][t, s]:
                cost += _slot_cost(int(r["b_role"][t, s]), "B", tb.split_bw)
            if r["w_active"][t, s]:
                cost += _slot_cost(int(r["w_role"][t, s]), "W", tb.split_bw)
            worst = max(worst, cost)
        total += worst
    return total


def predict_schedule_bubble(schedule: str, n_stages: int, n_micro: int,
                            n_virtual: int = 1) -> float:
    """Predicted bubble fraction of one schedule, derived the same way the
    bench MEASURES it (marginal-microbatch method): the per-microbatch
    marginal cost c = (T(m) - T(m/2)) / (m/2) prices the bubble-free
    steady phase, ideal = m·c, bubble = (T - ideal)/T. Exact for the
    schedule tables actually executed (including zb's extra recompute and
    W placement), which no closed form captures."""
    m2 = max(1, n_micro // 2)
    t_m = predict_schedule_time(schedule, n_stages, n_micro, n_virtual)
    if m2 == n_micro:
        return pipeline_bubble_fraction(n_stages, n_micro)
    t_2 = predict_schedule_time(schedule, n_stages, m2, n_virtual)
    c = max((t_m - t_2) / (n_micro - m2), 1e-9)
    return max(0.0, (t_m - n_micro * c) / t_m)


# ---------------------------------------------------------------------------
# Schedule resolution (selector + α–β auto mode + degenerate demotion)
# ---------------------------------------------------------------------------

_DEMOTE_WARNED: set = set()


def _demote_once(key: tuple, msg: str):
    if key not in _DEMOTE_WARNED:
        _DEMOTE_WARNED.add(key)
        logger.warning(msg)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def auto_microbatches(n_stages: int, batch: int, topology=None,
                      max_micro: int = 64) -> int:
    """Pick the microbatch count for ``auto``: the largest divisor of
    ``batch`` within ``max_micro`` whose marginal bubble improvement still
    beats the per-tick dispatch/hop cost priced by the calibrated α–β
    model (more microbatches shrink the bubble hyperbolically but add a
    fixed α per extra tick). Without a calibrated topology the α term is
    unknown and the divisor cap alone decides."""
    divisors = [d for d in range(1, min(batch, max_micro) + 1)
                if batch % d == 0]
    if not divisors:
        return 1
    alpha_frac = 0.0
    if topology is not None:
        alpha = _fitted_alpha_s(topology)
        if alpha:
            # α per tick vs ~1 ms of stage compute per tick as the unit
            alpha_frac = min(alpha / 1e-3, 1.0)
    best, best_cost = divisors[0], None
    for d in divisors:
        bubble = pipeline_bubble_fraction(n_stages, d)
        # relative step cost: compute inflated by the bubble, plus α-ticks
        cost = 1.0 / max(1e-9, 1.0 - bubble) + alpha_frac * (d + n_stages)
        if best_cost is None or cost < best_cost - 1e-12:
            best, best_cost = d, cost
    return best


def _fitted_alpha_s(topology) -> float:
    """Per-launch latency (s) from a PR 14 MeasuredTopology, 0.0 when the
    topology is nominal-only."""
    try:
        fit = topology.fitted("flat")
        if fit is not None:
            return float(fit[0])
    except Exception:
        pass
    return float(getattr(topology, "launch_latency_us", 0.0) or 0.0) * 1e-6


def resolve_pipeline_schedule(schedule: str, n_stages: int, n_micro: int,
                              n_virtual: int = 1,
                              topology=None) -> Tuple[str, int]:
    """Resolve the schedule selector ONCE per build (never on the
    dispatch path — divcheck discipline). Returns ``(schedule,
    n_virtual)`` with the degenerate demotions applied:

    - unknown schedule names demote to ``1f1b`` (one-time WARNING);
    - ``interleaved`` with fewer than 2 virtual chunks demotes to
      ``1f1b`` (nothing to interleave);
    - ``m < n_stages`` demotes any schedule to ``1f1b`` (one-time
      WARNING, not a crash): with fewer microbatches than stages the
      steady phase is empty, interleaving/W-deferral have no bubble to
      fill, and the baseline is the memory-cheapest correct schedule.
    - ``auto`` picks the cheapest schedule under the α–β-priced
      synchronized-tick model (env pins win by construction — this path
      only runs when the knob says ``auto``).
    """
    v = max(1, int(n_virtual))
    if schedule not in PIPELINE_SCHEDULES:
        _demote_once(("schedule", schedule),
                     f"unknown pipeline schedule {schedule!r}; demoting to "
                     f"1f1b (valid: {PIPELINE_SCHEDULES})")
        schedule = "1f1b"
    if schedule == "auto":
        candidates = [("1f1b", 1)]
        if n_micro >= n_stages:
            if v >= 2:
                candidates.append(("interleaved", v))
            candidates.append(("zb", 1))
        alpha = _fitted_alpha_s(topology) if topology is not None else 0.0
        alpha_units = min(alpha / 1e-3, 1.0) if alpha else 0.0

        def priced(cand):
            sch, vv = cand
            tb = build_schedule_tables(sch, n_stages, n_micro, vv)
            # v>1 chunks are 1/v of the stage, so normalize work units to
            # whole-stage time before adding the per-tick α toll
            return (predict_schedule_time(sch, n_stages, n_micro, vv) / vv
                    + alpha_units * tb.ticks)

        schedule, v = min(candidates, key=priced)
    if schedule == "interleaved" and v < 2:
        _demote_once(("interleave_v", n_stages),
                     "interleaved pipeline schedule needs n_virtual >= 2 "
                     "chunks per stage; demoting to 1f1b")
        schedule = "1f1b"
    if n_micro < n_stages and schedule != "1f1b":
        _demote_once(("micro", schedule, n_stages, n_micro),
                     f"pipeline schedule {schedule!r} with n_micro="
                     f"{n_micro} < n_stages={n_stages} has no steady phase "
                     "to optimize; demoting to 1f1b")
        schedule = "1f1b"
    return schedule, v


def pipeline_chunk_placement(schedule: str, n_virtual: int) -> str:
    """How the caller must stack per-stage chunk parameters for one
    RESOLVED schedule: ``"contiguous"`` (stage s owns consecutive model
    chunks — the 1f1b composition order) or ``"roundrobin"`` (global
    chunk c = j·p + s lives on stage s at local index j — the
    interleaved ring placement). At ``n_virtual == 1`` both coincide."""
    if n_virtual <= 1:
        return "contiguous"
    return "contiguous" if schedule == "1f1b" else "roundrobin"


# ---------------------------------------------------------------------------
# Table-driven executor (interleaved virtual stages + zero-bubble B/W)
# ---------------------------------------------------------------------------

def _boundary_hops(axis_name, n_stages, boundary_codec, stage, act_dtype):
    """Build the fwd/bwd ring-hop functions, optionally splitting each
    ppermute into a raw half (ICI edges) and a quantized payload+scale
    half (DCN edges) per the PR 13 wire codecs. ``boundary_codec`` is
    ``None`` or ``(codec, coded_edges)`` where ``coded_edges[i]`` says
    boundary i (between stage i and i+1 mod p) crosses DCN. Partial
    ppermutes only move data on the listed edges, so the coded split is a
    genuine wire-byte saving, not a masked decoration."""
    p = n_stages
    fwd_perm = [(i, (i + 1) % p) for i in range(p)]
    bwd_perm = [(i, (i - 1) % p) for i in range(p)]
    if not boundary_codec or not any(boundary_codec[1]):
        return (lambda x: lax.ppermute(x, axis_name, fwd_perm),
                lambda x: lax.ppermute(x, axis_name, bwd_perm))
    from ..ops import compression as _comp
    codec, coded = boundary_codec
    codec = _comp.resolve_codec(codec, act_dtype)
    if codec == _comp.CODEC_NONE:
        return (lambda x: lax.ppermute(x, axis_name, fwd_perm),
                lambda x: lax.ppermute(x, axis_name, bwd_perm))

    def make_hop(perm, boundary_of_sender, boundary_of_recv):
        raw_pairs = [pr for i, pr in enumerate(perm)
                     if not coded[boundary_of_sender(i)]]
        enc_pairs = [pr for i, pr in enumerate(perm)
                     if coded[boundary_of_sender(i)]]
        recv_coded = jnp.asarray(
            [1 if coded[boundary_of_recv(s)] else 0 for s in range(p)],
            jnp.int32)

        def hop(x):
            raw = (lax.ppermute(x, axis_name, raw_pairs)
                   if raw_pairs else jnp.zeros_like(x))
            payload, scale = _comp.encode(x, codec)
            payload = lax.ppermute(payload, axis_name, enc_pairs)
            scale = lax.ppermute(scale, axis_name, enc_pairs)
            dec = _comp.decode(payload, scale, codec, x.dtype)
            sel = jnp.take(recv_coded, stage)
            return jnp.where(sel == 1, dec, raw)

        return hop

    fwd = make_hop(fwd_perm, lambda i: i, lambda s: (s - 1) % p)
    bwd = make_hop(bwd_perm, lambda i: (i - 1) % p, lambda s: s)
    return fwd, bwd


def _pipeline_train_tables(chunk_fn, chunk_params, micro_inputs,
                           micro_targets, loss_fn, axis_name, n_stages,
                           tables: _Tables, n_virtual: int,
                           first_fn=None, first_params=None,
                           last_fn=None, last_params=None,
                           boundary_codec=None):
    """Run one generated schedule table inside shard_map. Semantics match
    :func:`pipeline_train_1f1b` exactly — same composites, same vjp
    pulls, same psum epilogue — only the (tick, stage) -> slot mapping is
    table-driven. Under the zb split the B slot pulls only dx and the W
    slot re-pulls the SAME vjp (same params, same stashed input, same
    cotangent) for only the weight grads: XLA DCEs the unused half of
    each pull, and the per-accumulator addition order stays in microbatch
    order, so the trajectory is bitwise-identical to the fused pull.

    ``chunk_params`` leaves carry a leading [n_virtual] chunk axis when
    ``n_virtual > 1`` (local chunk j is global chunk c = j·p + stage —
    round-robin placement); at n_virtual == 1 they are the plain
    per-stage tree."""
    n_micro = micro_inputs.shape[0]
    stage = lax.axis_index(axis_name)
    split = tables.split_bw
    v = n_virtual
    has_first = first_fn is not None
    has_last = last_fn is not None
    if first_params is None:
        first_params = ()
    if last_params is None:
        last_params = ()

    vary_axes = {axis_name}
    for leaf in jax.tree_util.tree_leaves(
            (micro_inputs, micro_targets, chunk_params, first_params,
             last_params)):
        vary_axes |= _vma_of(leaf)
    vary_axes = tuple(sorted(vary_axes))

    if has_first:
        act_struct = jax.eval_shape(first_fn, first_params, micro_inputs[0])
    else:
        act_struct = jax.eval_shape(lambda x: x, micro_inputs[0])
    act0 = _vary(jnp.zeros(act_struct.shape, act_struct.dtype), vary_axes)

    fwd_hop, bwd_hop = _boundary_hops(axis_name, n_stages, boundary_codec,
                                      stage, act_struct.dtype)

    def params_at(j):
        if v == 1:
            return chunk_params
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
            chunk_params)

    def grads_add(gs, d, j, active):
        if v == 1:
            upd = jax.tree_util.tree_map(jnp.add, gs, d)
        else:
            upd = jax.tree_util.tree_map(
                lambda g, dd: lax.dynamic_update_index_in_dim(
                    g, lax.dynamic_index_in_dim(g, j, 0, keepdims=False)
                    + dd, j, 0), gs, d)
        return lax.cond(active, lambda _: upd, lambda _: gs, None)

    def chunk0_composite(cp, fp, micro):
        x = first_fn(fp, micro) if has_first else micro.astype(act0.dtype)
        return chunk_fn(cp, x)

    def last_composite(cp, lp, x, tgt):
        y = chunk_fn(cp, x)
        out = last_fn(lp, y) if has_last else y
        return loss_fn(out, tgt)

    def zeros_like_tree(t):
        return jax.tree_util.tree_map(
            lambda a: _vary(jnp.zeros(a.shape, a.dtype), vary_axes), t)

    def zeros_chunk():
        return zeros_like_tree(params_at(0))

    def _zero_loss():
        return _vary(jnp.zeros((), jnp.float32), vary_axes)

    def vary_tree(t):
        # see pipeline_train_1f1b: params must be fully varying BEFORE
        # the vjp so the transpose inserts no implicit psum inside a
        # switch branch (cross-device deadlock / premature combine)
        return jax.tree_util.tree_map(lambda a: _vary(a, vary_axes), t)

    rows_x = {name: jnp.asarray(arr)
              for name, arr in tables.rows.items()}

    def buf_write(buf, val, slot, active, depth):
        return lax.cond(
            active,
            lambda b: lax.dynamic_update_index_in_dim(
                b, val.astype(b.dtype), jnp.clip(slot, 0, depth - 1), 0),
            lambda b: b, buf)

    def buf_read(buf, slot, depth):
        return lax.dynamic_index_in_dim(
            buf, jnp.clip(slot, 0, depth - 1), 0, keepdims=False)

    def micro_at(arr, m):
        return lax.dynamic_index_in_dim(
            arr, jnp.clip(m, 0, n_micro - 1), 0, keepdims=False)

    def tick(carry, row):
        (fwd_recv, bwd_recv, a_in, c_in, x_stash, ct_stash, gs, gf, gl,
         loss_acc) = carry

        def gv(name):
            return jnp.take(row[name], stage)

        # 1. inbox writes: last tick's ring arrivals land in their slots
        a_in = buf_write(a_in, fwd_recv, gv("a_write"), gv("a_write") >= 0,
                         tables.a_depth)
        c_in = buf_write(c_in, bwd_recv, gv("c_write"), gv("c_write") >= 0,
                         tables.c_depth)

        # 2. F slot
        f_act = gv("f_active") == 1
        f_src = gv("f_src")
        f_j = jnp.clip(gv("f_j"), 0, v - 1)
        micro_f = micro_at(micro_inputs, gv("f_m"))

        def do_f(_):
            x_ring = buf_read(a_in, f_src, tables.a_depth)
            if has_first:
                x = lax.cond(f_src < 0,
                             lambda _: first_fn(first_params, micro_f),
                             lambda _: x_ring, None)
            else:
                x = jnp.where(f_src < 0, micro_f.astype(act0.dtype), x_ring)
            return chunk_fn(params_at(f_j), x), x

        y_f, x_f = lax.cond(f_act, do_f, lambda _: (act0, act0), None)
        x_stash = buf_write(x_stash, x_f, gv("f_stash"),
                            jnp.logical_and(f_act, gv("f_stash") >= 0),
                            tables.act_depth)

        # 3. B slot
        b_act = gv("b_active") == 1
        b_j = jnp.clip(gv("b_j"), 0, v - 1)
        micro_b = micro_at(micro_inputs, gv("b_m"))
        tgt_b = micro_at(micro_targets, gv("b_m"))
        x_b = buf_read(x_stash, gv("b_x"), tables.act_depth)
        ct_b = buf_read(c_in, gv("b_in"), tables.c_depth)
        x_arrive = buf_read(a_in, gv("b_in"), tables.a_depth)

        def b_first(_):
            if split:
                # role 0's pull is ALL weight grads — the whole job is
                # deferred to the W slot; B only banks the cotangent
                return (zeros_chunk(), zeros_like_tree(first_params),
                        zeros_like_tree(last_params), act0, _zero_loss())
            _, pull = jax.vjp(
                lambda cp, fp: chunk0_composite(cp, fp, micro_b),
                vary_tree(params_at(b_j)), vary_tree(first_params))
            dcp, dfp = pull(ct_b)
            return (dcp, dfp, zeros_like_tree(last_params), act0,
                    _zero_loss())

        def b_mid(_):
            _, pull = jax.vjp(chunk_fn, vary_tree(params_at(b_j)), x_b)
            dcp, dx = pull(ct_b)
            if split:
                dcp = zeros_chunk()  # weight half deferred to W (DCE'd)
            return (dcp, zeros_like_tree(first_params),
                    zeros_like_tree(last_params), dx, _zero_loss())

        def b_last(_):
            loss_m, pull = jax.vjp(
                lambda cp, lp, x: last_composite(cp, lp, x, tgt_b),
                vary_tree(params_at(b_j)), vary_tree(last_params), x_arrive)
            dcp, dlp, dx = pull(jnp.ones_like(loss_m))
            if split:
                dcp = zeros_chunk()
                dlp = zeros_like_tree(last_params)
            return (dcp, zeros_like_tree(first_params), dlp, dx,
                    loss_m.astype(jnp.float32))

        def do_b(_):
            return lax.switch(jnp.clip(gv("b_role"), 0, 2),
                              (b_first, b_mid, b_last), None)

        def skip_b(_):
            return (zeros_chunk(), zeros_like_tree(first_params),
                    zeros_like_tree(last_params), act0, _zero_loss())

        dcp_b, dfp_b, dlp_b, dx_b, loss_c = lax.cond(b_act, do_b, skip_b,
                                                     None)
        gs = grads_add(gs, dcp_b, b_j, b_act)
        gf = jax.tree_util.tree_map(jnp.add, gf, dfp_b)
        gl = jax.tree_util.tree_map(jnp.add, gl, dlp_b)
        loss_acc = loss_acc + loss_c

        if split:
            # bank this B's inputs for its deferred W pull
            ct_stash = buf_write(ct_stash, ct_b, gv("b_ct_save"),
                                 jnp.logical_and(b_act,
                                                 gv("b_ct_save") >= 0),
                                 tables.ct_depth)
            x_stash = buf_write(x_stash, x_arrive, gv("b_save"),
                                jnp.logical_and(b_act, gv("b_save") >= 0),
                                tables.act_depth)

            # 4. W slot: re-pull the SAME vjp for the weight half
            w_act = gv("w_active") == 1
            w_j = jnp.clip(gv("w_j"), 0, v - 1)
            micro_w = micro_at(micro_inputs, gv("w_m"))
            tgt_w = micro_at(micro_targets, gv("w_m"))
            x_w = buf_read(x_stash, gv("w_x"), tables.act_depth)
            ct_w = buf_read(ct_stash, gv("w_ct"), tables.ct_depth)

            def w_first(_):
                _, pull = jax.vjp(
                    lambda cp, fp: chunk0_composite(cp, fp, micro_w),
                    vary_tree(params_at(w_j)), vary_tree(first_params))
                dcp, dfp = pull(ct_w)
                return (dcp, dfp, zeros_like_tree(last_params))

            def w_mid(_):
                _, pull = jax.vjp(chunk_fn, vary_tree(params_at(w_j)), x_w)
                dcp, _dx = pull(ct_w)
                return (dcp, zeros_like_tree(first_params),
                        zeros_like_tree(last_params))

            def w_last(_):
                loss_m, pull = jax.vjp(
                    lambda cp, lp, x: last_composite(cp, lp, x, tgt_w),
                    vary_tree(params_at(w_j)), vary_tree(last_params), x_w)
                dcp, dlp, _dx = pull(jnp.ones_like(loss_m))
                return (dcp, zeros_like_tree(first_params), dlp)

            def do_w(_):
                return lax.switch(jnp.clip(gv("w_role"), 0, 2),
                                  (w_first, w_mid, w_last), None)

            def skip_w(_):
                return (zeros_chunk(), zeros_like_tree(first_params),
                        zeros_like_tree(last_params))

            dcp_w, dfp_w, dlp_w = lax.cond(w_act, do_w, skip_w, None)
            gs = grads_add(gs, dcp_w, w_j, w_act)
            gf = jax.tree_util.tree_map(jnp.add, gf, dfp_w)
            gl = jax.tree_util.tree_map(jnp.add, gl, dlp_w)

        # 5. ring hops (one fwd + one bwd ppermute regardless of v)
        fwd_recv = fwd_hop(y_f)
        bwd_recv = bwd_hop(dx_b)
        return (fwd_recv, bwd_recv, a_in, c_in, x_stash, ct_stash, gs, gf,
                gl, loss_acc), None

    def act_buf(depth):
        return _vary(jnp.zeros((depth,) + tuple(act_struct.shape),
                               act_struct.dtype), vary_axes)

    carry0 = (act0, act0, act_buf(tables.a_depth), act_buf(tables.c_depth),
              act_buf(tables.act_depth), act_buf(tables.ct_depth),
              zeros_like_tree(chunk_params), zeros_like_tree(first_params),
              zeros_like_tree(last_params), _zero_loss())
    (_, _, _, _, _, _, gs, gf, gl,
     loss_acc) = lax.scan(tick, carry0, rows_x)[0]

    inv = 1.0 / n_micro
    loss = lax.psum(loss_acc, axis_name) * inv
    gf = jax.tree_util.tree_map(lambda a: lax.psum(a * inv, axis_name), gf)
    gl = jax.tree_util.tree_map(lambda a: lax.psum(a * inv, axis_name), gl)
    gs = jax.tree_util.tree_map(lambda a: a * inv, gs)
    return loss, gs, gf, gl


def pipeline_train_step(stage_fn: Callable, stage_params, micro_inputs,
                        micro_targets, loss_fn: Callable, axis_name: str,
                        n_stages: int, schedule: str = "1f1b",
                        n_virtual: int = 1,
                        first_fn: Optional[Callable] = None,
                        first_params=None,
                        last_fn: Optional[Callable] = None,
                        last_params=None,
                        boundary_codec=None, topology=None):
    """Schedule-selected pipeline training step (run inside shard_map) —
    the HOROVOD_TPU_PIPELINE_SCHEDULE surface.

    ``schedule`` ∈ :data:`PIPELINE_SCHEDULES`; degenerate combinations
    demote to ``1f1b`` with a one-time WARNING (see
    :func:`resolve_pipeline_schedule`). With ``n_virtual > 1``,
    ``stage_fn`` is one CHUNK's computation and ``stage_params`` leaves
    carry a leading ``[n_virtual]`` chunk axis, stacked per
    :func:`pipeline_chunk_placement` for the RESOLVED schedule —
    contiguous for 1f1b (the chunks compose in a static loop, and the
    vjp returns the same stacked per-chunk grads the table executor
    produces), round-robin for interleaved/zb.

    ``boundary_codec``: optional ``(codec, coded_edges)`` applying the
    PR 13 wire codecs to stage-boundary hops that cross DCN (see
    :func:`horovod_tpu.parallel.mesh.pipeline_boundary_edges`). The table
    executor alone applies it: with a coded edge and a schedule that
    resolves to ``1f1b`` the call raises instead of dropping it.
    ``topology``: optional MeasuredTopology pricing the ``auto`` mode.

    Returns ``(loss, stage_grads, first_grads, last_grads)`` with the
    exact :func:`pipeline_train_1f1b` contract (stage_grads leaves gain
    the leading chunk axis when n_virtual > 1).
    """
    n_micro = micro_inputs.shape[0]
    schedule, v = resolve_pipeline_schedule(schedule, n_stages, n_micro,
                                            n_virtual, topology)
    if schedule == "1f1b":
        if (boundary_codec and boundary_codec[0] != "none"
                and any(boundary_codec[1])):
            raise ValueError(
                f"boundary_codec {boundary_codec[0]!r} on a coded stage "
                f"boundary, but the schedule resolved to '1f1b', whose "
                f"executor moves every boundary uncoded; the schedules that "
                f"apply it are 'interleaved' and 'zb'")
        if v == 1:
            return pipeline_train_1f1b(
                stage_fn, stage_params, micro_inputs, micro_targets,
                loss_fn, axis_name, n_stages, first_fn=first_fn,
                first_params=first_params, last_fn=last_fn,
                last_params=last_params)

        def composed_fn(sp, x):
            # contiguous placement: stage s owns chunks s·v .. s·v+v−1 in
            # model order; static indexing keeps the vjp grads stacked
            for j in range(v):
                x = stage_fn(jax.tree_util.tree_map(lambda a: a[j], sp), x)
            return x

        return pipeline_train_1f1b(
            composed_fn, stage_params, micro_inputs, micro_targets,
            loss_fn, axis_name, n_stages, first_fn=first_fn,
            first_params=first_params, last_fn=last_fn,
            last_params=last_params)
    tables = build_schedule_tables("zb" if schedule == "zb" else
                                   "interleaved", n_stages, n_micro, v)
    return _pipeline_train_tables(
        stage_fn, stage_params, micro_inputs, micro_targets, loss_fn,
        axis_name, n_stages, tables, v, first_fn=first_fn,
        first_params=first_params, last_fn=last_fn, last_params=last_params,
        boundary_codec=boundary_codec)


def split_microbatches(x, n_micro: int):
    """[B, ...] -> [n_micro, B/n_micro, ...] (B must divide)."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible into {n_micro} microbatches")
    return x.reshape(n_micro, b // n_micro, *x.shape[1:])


def merge_microbatches(y):
    """[n_micro, mb, ...] -> [n_micro*mb, ...]."""
    return y.reshape(y.shape[0] * y.shape[1], *y.shape[2:])
