"""The product's host spans on the profiler's clock
(``horovod_tpu/common/scopes.py`` ``host_span``): what the eager
optimizer, the engine and replay write into a ``jax.profiler`` trace on
the CPU, read with the benchmark's own walker (``benchmark/xplane.py``) as
the benchmark's worker reads a chip's trace."""

import ast
import functools
import importlib.util
import os
import threading

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics
from horovod_tpu.common import scopes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "horovod_tpu")
DISPATCHES = ("hvd.engine.dispatch", "hvd.engine.compile_dispatch",
              "hvd.replay.launch")


@pytest.fixture(scope="module")
def xplane():
    """benchmark/xplane.py, which imports its neighbour stats.py by name."""
    import sys
    bench = os.path.join(REPO, "benchmark")
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location(
            "bench_xplane", os.path.join(bench, "xplane.py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.remove(bench)
    return module


@pytest.fixture()
def traced(xplane, tmp_path):
    """``traced(fn)``: runs ``fn`` inside a profiler session set up as the
    benchmark's worker sets its own, and returns the kept spans
    ``[name, start_ns, dur_ns]``, parents before children."""
    def run(fn):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        return xplane.summarize_file(
            xplane.newest_xplane(str(tmp_path)))["spans"]
    return run


@pytest.fixture()
def engine():
    """The process's engine, arming replay after two steps (as
    tests/test_replay.py)."""
    hvd.init()
    eng = hvd._engine()
    prev = eng.config.step_replay_warmup, eng.config.step_replay
    eng.config.step_replay_warmup, eng.config.step_replay = 2, True
    eng.replay.invalidate_all("test isolation")
    yield eng
    eng.replay.invalidate_all("test isolation")
    eng.config.step_replay_warmup, eng.config.step_replay = prev


def named(spans, name):
    return [s for s in spans if s[0] == name]


def within(spans, outer):
    """The spans that lie inside ``outer``, itself left out."""
    _, lo, dur = outer
    return [s for s in spans if s is not outer
            and lo <= s[1] and s[1] + s[2] <= lo + dur]


def small_tree():
    return {"w": jnp.ones((4, 3)), "deep": {"b": jnp.ones((7,))}}


# -- (a) the eager optimizer ---------------------------------------------

def test_update_and_apply_in_a_world_of_one(traced):
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    state = [small_tree(), opt.init(small_tree())]
    grads = small_tree()

    def three_steps():
        for _ in range(3):
            state[:] = opt.update_and_apply(grads, state[1], state[0])
        jax.block_until_ready(state)

    spans = traced(three_steps)
    outers = named(spans, "hvd.opt.update_and_apply")
    assert len(outers) == 3
    for outer in outers:
        inner = within(spans, outer)
        assert [s[0] for s in inner] == [
            "hvd.opt.flatten", "hvd.opt.apply_lookup",
            "hvd.opt.apply_dispatch"]
        ends = [s[1] + s[2] for s in inner]
        assert all(end <= nxt[1] for end, nxt in zip(ends, inner[1:]))
    # the code that would write it does not run in a world of one
    assert not named(spans, "hvd.opt.reduce")
    assert len(named(spans, "hvd.opt.flatten")) == 3
    np.testing.assert_allclose(np.asarray(state[0]["w"]), 1 - 3 * 0.1,
                               rtol=1e-6)


def test_an_accumulation_pass_writes_the_outer_span_alone(traced):
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.sgd(0.1),
                                   backward_passes_per_step=2)
    params = small_tree()
    state = opt.init(params)
    spans = traced(lambda: opt.update_and_apply(small_tree(), state, params))
    assert [s[0] for s in spans] == ["hvd.opt.update_and_apply"]


@pytest.mark.parametrize("make", [
    lambda: hvd.DistributedOptimizer(optax.sgd(0.1), sharded=True),
    lambda: hvd.DistributedDeltaAdasumOptimizer(optax.sgd(0.1))],
    ids=["sharded", "delta_adasum"])
def test_the_twins_write_the_outer_span_only(traced, make):
    hvd.init()
    opt = make()
    params = small_tree()
    state = opt.init(params)
    out = []
    spans = traced(lambda: out.append(jax.block_until_ready(
        opt.update_and_apply(small_tree(), state, params))))
    assert len(named(spans, "hvd.opt.update_and_apply")) == 1
    assert not [s for s in spans if s[0].startswith("hvd.opt.")
                and s[0] != "hvd.opt.update_and_apply"]
    np.testing.assert_allclose(np.asarray(out[0][0]["w"]), 0.9, rtol=1e-6)


# -- (b) the engine and replay -------------------------------------------

def grouped_steps(eng, n):
    """``n`` marked steps of one grouped allreduce; each step's
    dispatch_count delta."""
    a, b = jnp.ones((4, 3)), jnp.arange(7.0)
    deltas = []
    for i in range(n):
        before = eng.dispatch_count
        eng.step_begin()
        hs = eng.grouped_allreduce([a, b], name=f"spans.{i}")
        out = [h.result() for h in hs]
        eng.step_end()
        deltas.append(eng.dispatch_count - before)
        np.testing.assert_allclose(np.asarray(out[1]), np.arange(7.0))
    return deltas


def test_grouped_steps_recorded_then_replayed(engine, traced):
    deltas = []
    replayed = engine.replay.replayed_steps
    spans = traced(lambda: deltas.extend(grouped_steps(engine, 4)))
    assert engine.replay.replayed_steps - replayed == 2
    calls = named(spans, "hvd.engine.grouped_allreduce")
    assert len(calls) == 4
    for call in calls[:2]:          # recorded: the engine's own launches
        inner = [s[0] for s in within(spans, call)]
        assert "hvd.replay.launch" not in inner
        assert {"hvd.engine.dispatch",
                "hvd.engine.compile_dispatch"} & set(inner)
    for call, delta in zip(calls[2:], deltas[2:]):      # replayed
        inner = [s[0] for s in within(spans, call)]
        assert inner.count("hvd.replay.launch") == 1
        assert sum(name in DISPATCHES for name in inner) == delta == 1


def test_a_fresh_builder_is_compile_dispatch_then_dispatch(engine, traced):
    # a builder is fresh only once a process: another file's test that ran
    # in this process before may have reduced the same shape already
    engine._builders.clear()
    x = jnp.arange(11.0)
    spans = traced(lambda: [
        engine.allreduce(x, name=f"fresh.{i}").synchronize()
        for i in range(2)])
    kinds = [s[0] for s in spans if s[0] in DISPATCHES]
    assert kinds == ["hvd.engine.compile_dispatch", "hvd.engine.dispatch"]


# -- (c) the waits and the fetch -----------------------------------------

def slow(x):
    """Work the device is still doing when the host asks for its end."""
    for _ in range(6):
        x = x @ x / x.shape[0]
    return x


def test_a_synchronize_that_waits(engine, traced):
    x = jnp.ones((1200, 1200))
    engine.allreduce(slow(x), name="warm").synchronize()    # the programs
    blocks = engine.host_blocks
    spans = traced(
        lambda: engine.allreduce(slow(x), name="waited").synchronize())
    assert engine.host_blocks - blocks == 1
    assert len(named(spans, "hvd.engine.wait")) == 1
    # a second synchronize of a finished handle waits for nothing
    h = engine.allreduce(x, name="done")
    h.synchronize()
    blocks = engine.host_blocks
    spans = traced(h.synchronize)
    assert engine.host_blocks == blocks
    assert not named(spans, "hvd.engine.wait")


def test_a_replayed_handle_that_waits(engine, traced):
    x = jnp.ones((1200, 1200))

    def step(i):
        engine.step_begin()
        (h,) = engine.grouped_allreduce([slow(x)], name=f"bound.{i}")
        h.synchronize()
        engine.step_end()

    for i in range(3):
        step(i)
    blocks, replayed = engine.host_blocks, engine.replay.replayed_steps
    spans = traced(lambda: step(3))
    assert engine.replay.replayed_steps - replayed == 1
    assert len(named(spans, "hvd.replay.launch")) == 1
    assert len(named(spans, "hvd.engine.wait")) \
        == engine.host_blocks - blocks == 1


def test_the_blocking_metadata_read(engine, traced):
    # _exchange_sizes' two halves: a world of one answers without them
    vec = np.array([5], np.int32)
    fetches = engine.host_fetches
    world = []
    spans = traced(lambda: world.append(engine._fetch_exchange(
        engine._dispatch_exchange(vec), vec.shape)))
    assert engine.host_fetches - fetches == 1
    assert len(named(spans, "hvd.engine.fetch")) == 1
    assert world[0].tolist() == [[5]]


# -- (d) the names ---------------------------------------------------------

@functools.cache
def host_span_calls():
    """(file, line, the ``scopes.<NAME>`` attributes of the argument) of
    every call of ``host_span`` in the package."""
    found = []
    for folder, _, names in os.walk(PACKAGE):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and getattr(
                        node.func, "attr", getattr(node.func, "id", "")) \
                        == "host_span":
                    (arg,) = node.args
                    assert not node.keywords
                    found.append((os.path.relpath(path, REPO), node.lineno, [
                        n.attr for n in ast.walk(arg)
                        if isinstance(n, ast.Attribute)
                        and getattr(n.value, "id", "") == "scopes"]))
    return found


def test_every_call_site_passes_declared_names():
    calls = host_span_calls()
    declared = {k: v for k, v in vars(scopes).items()
                if isinstance(v, str) and v in scopes.HOST_SPANS}
    assert len(declared) == len(scopes.HOST_SPANS) == 11
    used = set()
    for path, line, names in calls:
        # constants of scopes.py and nothing formatted at the call
        assert names and all(n in declared for n in names), (path, line)
        used |= {declared[n] for n in names}
    assert used == set(scopes.HOST_SPANS)


def test_one_trace_annotation_site_in_the_package():
    sites = []
    for folder, _, names in os.walk(PACKAGE):
        for name in names:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path) as f:
                    sites += [(os.path.relpath(path, PACKAGE), i + 1)
                              for i, line in enumerate(f)
                              if "TraceAnnotation" in line
                              and not line.lstrip().startswith("#")]
    assert [s[0] for s in sites] == [os.path.join("common", "scopes.py")]


@pytest.mark.parametrize("name", scopes.HOST_SPANS)
def test_a_name_is_written_and_documented(name):
    assert name.split(".")[0] in ("opt", "engine", "replay")
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        assert f"`hvd.{name}`" in f.read()
    assert any(name in (getattr(scopes, n) for n in names)
               for _, _, names in host_span_calls())


# -- (e) what it costs with no profiler session -----------------------------

def test_the_primitive_is_a_trace_annotation_and_nothing_else(monkeypatch):
    assert scopes.host_span.__code__.co_names == (
        "jax", "profiler", "TraceAnnotation")
    reg = metrics.registry()
    before = set(reg._metrics)
    made, taken = [], []

    class Watched:
        """The registry's lock, counting who takes it."""

        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            taken.append(1)
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)

    real = threading.Lock
    monkeypatch.setattr(reg, "_lock", Watched(reg._lock))
    monkeypatch.setattr(threading, "Lock",
                        lambda: made.append(1) or real())
    for name in scopes.HOST_SPANS:
        span = scopes.host_span(name)
        assert type(span) is jax.profiler.TraceAnnotation
        with span:
            pass
    assert not made and not taken
    assert set(reg._metrics) == before
