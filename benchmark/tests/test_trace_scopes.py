"""The device trace by scope (xplane.py's ``scopes``,
readers/trace_scopes.py and the metric files that read them): on a trace
written by hand and on two steps of ``lm-spmd-1chip`` recorded on the v5e
with the scopes in the program (my chip run, PR 24)."""

import os
import re

import pytest
from jax.profiler import ProfileData

import files
import scopes
import stats
import tracecalc
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "lm-spmd-1chip.scoped.2steps.xplane.pb.gz")
METRICS = ["fwd_ms_per_step", "bwd_ms_per_step", "optimizer_ms_per_step",
           "head_loss_ms_per_step", "unscoped_ms_per_step"]

# microseconds: (program, HLO text, op_name, start, duration). Two programs
# hold a ``%fusion.3`` with one label; the while holds two operations.
F = "f32[64]{0}"
HAND = [
    (1, f"%while.1 = ({F}) while(%t), body=%b",
     "jit(train_step)/jvp()/layers/while", 0, 100),
    (1, f"%fusion.3 = {F} fusion(%p), kind=kLoop, calls=%f",
     "jit(train_step)/jvp()/layers/while/body/closed_call/attn/mul", 10, 20),
    (1, f"%fusion.4 = {F} fusion(%p), kind=kLoop, calls=%f",
     "jit(train_step)/transpose(jvp())/layers/while/body/closed_call/ffn/"
     "mul", 40, 30),
    (1, f"%fusion.5 = {F} fusion(%p), kind=kLoop, calls=%f",
     "jit(train_step)/transpose(jvp())/head/btd,vd->btv/dot_general", 100, 8),
    (1, f"%all-reduce.2 = {F} all-reduce(%y), to_apply=%add",
     "jit(train_step)/transpose(jvp())/psum", 110, 10),
    (1, f"%copy.7 = {F} copy(%z)", None, 120, 5),
    (1, f"%fusion.6 = {F} fusion(%p), kind=kLoop, calls=%f",
     "jit(train_step)/optimizer/jvp(mul)", 125, 5),
    (2, f"%fusion.3 = {F} fusion(%p), kind=kLoop, calls=%f",
     "jit(hvd_apply_update)/optimizer/add", 140, 12)]


def by_hand() -> bytes:
    text = ['planes { id: 1 name: "/device:TPU:0"',
            '  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }',
            '  stat_metadata { key: 8 value { id: 8 name: "program_id" } }']
    for i, (program, name, scope, _, _) in enumerate(HAND):
        stat = (f'stats {{ metadata_id: 7 str_value: "{scope}:" }}'
                if scope else "")
        text.append(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{name}" stats {{ metadata_id: 8 '
                    f'uint64_value: {program} }} {stat} }} }}')
    text.append('  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000')
    for i, (*_, start, dur) in enumerate(HAND):
        text.append(f'    events {{ metadata_id: {i + 1} offset_ps: '
                    f'{start * 10**6} duration_ps: {dur * 10**6} }}')
    text += ['  }', '  lines { id: 2 name: "Steps" timestamp_ns: 1000 '
             'events { metadata_id: 1 offset_ps: 0 duration_ps: 1 } }', '}',
             'planes { id: 2 name: "/host:CPU" }']
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


def ctx_of(summary: dict, steps: int) -> dict:
    return {"record": {"traced": {"trace": summary, "steps": steps}},
            "notes": []}


def read(ctx: dict, name: str):
    spec, reader = files.layer_metric(name)
    return reader(ctx, spec)


def test_by_hand():
    summary = xplane.summarize(by_hand())
    (dev,) = summary["devices"]
    assert dev["plane"] == "/device:TPU:0" and len(dev["ops"]) == len(HAND)
    # one label, two scopes: two operations
    twice = [s for l, s in zip(dev["labels"], dev["scopes"])
             if l == "fusion.3 | fusion kLoop | f32[64]"]
    assert twice == [HAND[1][2], HAND[7][2]]
    assert dev["scopes"][dev["ops"][5][3]] == ""        # the copy has none
    assert dev["ops"][0][:3] == [0.0, 100e3, 50e3]      # 100 - 20 - 30
    # the breakdown's names: pass, scope path, group
    assert [g for g, _ in tracecalc.top_ops(dev, 3)] == [
        "fwd layers | while f32[64]", "bwd layers/ffn | fusion kLoop f32[64]",
        "fwd layers/attn | fusion kLoop f32[64]"]
    ctx = ctx_of(summary, 2)
    # microseconds over two steps, in milliseconds a step
    assert read(ctx, "fwd_ms_per_step") == pytest.approx((50 + 20) / 2e3)
    assert read(ctx, "bwd_ms_per_step") == pytest.approx((30 + 8) / 2e3)
    # jvp( under the optimizer's scope is the optimizer's, in both programs
    assert read(ctx, "optimizer_ms_per_step") == pytest.approx(17 / 2e3)
    assert read(ctx, "head_loss_ms_per_step") == pytest.approx(8 / 2e3)
    # the copy; the all-reduce is in none of the five
    assert read(ctx, "unscoped_ms_per_step") == pytest.approx(5 / 2e3)
    (note,) = ctx["notes"]
    assert "collectives 0.005" in note and note.endswith(": +0.0000%")


def test_a_record_without_scopes_reads_nothing():
    """A record written before PR 36 holds labels alone: the reader then
    says nothing, and does not raise."""
    summary = xplane.summarize_file(SCOPED)
    for dev in summary["devices"]:
        del dev["scopes"]
    ctx = ctx_of(summary, 2)
    assert all(read(ctx, name) is None for name in METRICS)
    assert all(read({"record": {}}, name) is None for name in METRICS)
    assert ctx["notes"] == []


def test_recorded_scoped_trace():
    summary = xplane.summarize_file(SCOPED)
    (dev,) = summary["devices"]
    assert len(dev["ops"]) == 1916
    assert [m[0].split("(")[0] for m in dev["modules"]] == \
        ["jit_train_step"] * 2
    ctx = ctx_of(summary, 2)
    got = {name: read(ctx, name) for name in METRICS}
    assert got == pytest.approx({
        "fwd_ms_per_step": 44.0629, "bwd_ms_per_step": 79.5247,
        "optimizer_ms_per_step": 12.3479, "head_loss_ms_per_step": 34.4404,
        "unscoped_ms_per_step": 2.6456}, abs=1e-3)
    busy = stats.total(tracecalc.busy(dev)) / 1e6 / 2
    parts = sum(got[n] for n in METRICS if n != "head_loss_ms_per_step")
    assert parts == pytest.approx(busy, rel=5e-3)       # one chip: no collective
    assert got["head_loss_ms_per_step"] < \
        got["fwd_ms_per_step"] + got["bwd_ms_per_step"]
    assert got["unscoped_ms_per_step"] < 0.03 * busy
    # an attention kernel lies under the scope 'attn', and the metric that
    # matches labels is not moved by a scope of that name
    assert read(ctx, "attn_kernel_ms_per_step") == \
        pytest.approx(13.14, abs=0.01)
    kernels = {dev["scopes"][i] for *_, i in dev["ops"]
               if "splash" in dev["labels"][i]}
    assert kernels and all("/attn/" in s for s in kernels)
    top = dict(scopes.by_prefix(dev))
    assert top["jit(train_step)/optimizer"] / 2 == \
        pytest.approx(12.3479e-3, abs=1e-6)
    # the breakdown names the pass and the layer: PR 24's program spent
    # most in the head's backward (ISSUE 36 expected a layer's), and the
    # layers' matmuls follow under their own scopes
    named = tracecalc.top_ops(dev)
    assert named[0][0] == "bwd head | fusion kOutput bf16[4,2048,2048]"
    assert named[0][1] / 2 == pytest.approx(10.92e-3, abs=0.01e-3)
    assert "bwd layers/ffn | fusion kOutput bf16[4,2048,8192]" in dict(named)
    assert len(named) == 10 and all(
        g.split()[0] in ("fwd", "bwd") for g, _ in named)


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".xplane.pb.gz")))
def test_the_walker_reads_what_profiledata_read(name):
    """Until PR 36 ``jax.profiler.ProfileData`` read the file for every
    metric but the scopes': the one walker gives its events bit for bit,
    whole nanoseconds on one clock, the three lines and the spans."""
    raw = xplane.read_file(os.path.join(DATA, name))
    (dev,) = xplane.summarize(raw)["devices"]
    profile = ProfileData.from_serialized_xspace(raw)
    (plane,) = [p for p in profile.planes
                if xplane.DEVICE_PLANE.match(p.name)]
    lines = {line.name: list(line.events) for line in plane.lines}
    # the summary's clock starts at the earliest event kept, a span's too
    t0 = lines[xplane.MODULES_LINE][0].start_ns - dev["modules"][0][1]
    for key, line in (("ops", xplane.OPS_LINE), ("async", xplane.ASYNC_LINE)):
        events = lines.get(line, [])
        assert [o[:2] for o in dev[key]] == [
            [e.start_ns - t0, e.duration_ns] for e in events]
        assert [dev["labels"][o[-1]] for o in dev[key]] == [
            xplane.label_of(e.name) for e in events]
    assert [m[0] for m in dev["modules"]] == [
        e.name for e in lines[xplane.MODULES_LINE]]


@pytest.mark.parametrize("name", ["lm-spmd-1chip.2steps.xplane.pb.gz",
                                  "lm-spmd-4chip-dp.chip0.1step.xplane.pb.gz"])
def test_recordings_without_scopes(name):
    """The older recordings kept no ``tf_op``: nothing is forward, backward
    or optimizer, and everything but the collectives is unscoped."""
    summary = xplane.summarize_file(os.path.join(DATA, name))
    (dev,) = summary["devices"]
    assert set(dev["scopes"]) == {""}
    steps = 2 if "2steps" in name else 1
    ctx = ctx_of(summary, steps)
    assert [read(ctx, n) for n in METRICS[:4]] == [None] * 4
    collective = stats.matcher(
        files.layer_metric("collective_ms_per_step")[0]["match"])
    rest = (stats.total(tracecalc.busy(dev))
            - tracecalc.matched_self_ns(dev, collective)) / 1e6 / steps
    assert read(ctx, "unscoped_ms_per_step") == pytest.approx(rest)
    assert (tracecalc.matched_self_ns(dev, collective) > 0) == \
        ("4chip" in name)


def test_the_metric_files_quote_the_programs_names():
    """The patterns are data; the names are the program's
    (horovod_tpu/common/scopes.py), in the forms jax gives them."""
    from horovod_tpu.common import scopes as program
    names = scopes.scope_metrics()
    assert set(METRICS) < set(names) and len(names) == 10
    spec = {n: files.layer_metric(n)[0] for n in names}
    # every scope a file quotes is one the program writes
    written = {v for k, v in vars(program).items()
               if k.isupper() and isinstance(v, str)}
    for n in names:
        for quoted in re.findall(r"\[/\(\]\)\(?([a-z_|]+)\)?\(", "".join(
                spec[n]["match"] + spec[n].get("exclude", []))):
            assert set(quoted.split("|")) <= written, (n, quoted)
    for n in ("moe_experts_roofline", "ep4_moe_experts_roofline"):
        assert files.layer_metric(n)[0]["scope"] == \
            f"(^|[/(]){program.EXPERTS}([/)]|$)"
    (optimizer,) = spec["optimizer_ms_per_step"]["match"]
    assert program.OPTIMIZER in optimizer
    assert optimizer in spec["fwd_ms_per_step"]["exclude"]
    assert optimizer in spec["bwd_ms_per_step"]["exclude"]
    assert optimizer in spec["unscoped_ms_per_step"]["exclude"]
    (head_loss,) = spec["head_loss_ms_per_step"]["match"]
    assert f"({program.HEAD}|{program.LOSS})" in head_loss
    for op_name, want in [
            (f"jit({program.TRAIN_STEP})/optimizer/mul", optimizer),
            (f"jit({program.APPLY_UPDATE})/optimizer/add", optimizer),
            ("jit(train_step)/jvp()/head/btd,vd->btv/dot_general", head_loss),
            ("jit(f)/transpose(jvp(loss))/reduce_sum", head_loss),
            ("jit(f)/jvp(head)/dot_general", head_loss)]:
        assert re.search(want, op_name), op_name
    for op_name in ["jit(f)/jvp()/layers/while/body/attn/header/mul",
                    "jit(f)/jvp()/multihead/mul", "jit(f)/closs/add"]:
        assert not re.search(head_loss, op_name)
        assert not re.search(optimizer, op_name)
    for name in names:
        assert spec[name]["doc"] and "by hand" not in spec[name]["doc"]
