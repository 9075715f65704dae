"""The reduction from a trace to busy time, kernel time and exposed
collective time: on a trace written by hand, whose numbers are worked out
below, and on a trace recorded on the v5e (data/, see make_fixture.py)."""

import json
import os

import pytest
from jax.profiler import ProfileData

import files
import stats
import tracecalc
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# microseconds: (HLO text as the chip's trace names an operation, start,
# duration); "~" marks the events of the line of asynchronous operations
T = "bf16[4,2048,2048]{2,1,0:T(8,128)(2,1)}"
OPS = [("%while.1 = (s32[]{:T(128)}, " + T + ") while(%tuple.1), body=%b", 0, 100),
       ("%fusion.1 = " + T + " fusion(%p.1), kind=kOutput, calls=%f.1", 10, 20),
       ("%splash_mha_fwd_residuals.4 = (f32[4,1024,128]{2,1,0}, "
        "bf16[4,16,2048,128]{3,2,1,0}) custom-call(%q, %k, %v), "
        "custom_call_target=\\\"tpu_custom_call\\\"", 30, 20),
       ("%all-reduce-start.1 = f32[1,25557032]{1,0} all-reduce-start(%x), "
        "replica_groups={{0,1,2,3}}", 100, 2),
       ("%multiply_fusion.2 = f32[64]{0} fusion(%splash_mha_fwd_residuals.4), "
        "kind=kLoop, calls=%f.2", 102, 8),
       ("%all-reduce-done.1 = f32[1,25557032]{1,0} all-reduce-done("
        "%all-reduce-start.1)", 120, 5),
       ("%all-reduce.2 = f32[8]{0} all-reduce(%y), to_apply=%add", 130, 10),
       ("%fusion.3 = f32[64]{0} fusion(%all-reduce.2), kind=kLoop, "
        "calls=%f.3", 160, 10),
       ("~%all-reduce-start.1 = f32[1,25557032]{1,0} all-reduce-start(%x), "
        "replica_groups={{0,1,2,3}}", 100, 25)]
# the program's span (``hvd.``) lies inside the benchmark's ``bench.wait``
SPANS = [("bench.step", 0, 60), ("bench.grad", 5, 20), ("bench.wait", 141, 24),
         ("hvd.replay.wait", 145, 10), ("not.ours", 0, 500)]


def by_hand() -> bytes:
    meta = {name: i + 1 for i, (name, *_) in enumerate(OPS)}
    text = ['planes { id: 1 name: "/device:TPU:0"']
    for name, i in meta.items():
        text.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{name.lstrip("~")}" }} }}')
    for line_id, (line, mark) in enumerate(
            (("XLA Ops", False), ("Async XLA Ops", True))):
        text.append(f'  lines {{ id: {line_id + 1} name: "{line}" '
                    f'timestamp_ns: 1000')
        for name, start, dur in OPS:
            if name.startswith("~") == mark:
                text.append(
                    f'    events {{ metadata_id: {meta[name]} '
                    f'offset_ps: {start * 10**6} '
                    f'duration_ps: {dur * 10**6} }}')
        text.append('  }')
    text.append('  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000 '
                'events { metadata_id: 1 offset_ps: 0 '
                'duration_ps: 170000000 } }')
    text.append('}')
    text.append('planes { id: 2 name: "/host:CPU"')
    for i, (name, *_) in enumerate(SPANS):
        text.append(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                    f'name: "{name}" }} }}')
    text.append('  lines { id: 1 name: "python3" timestamp_ns: 1000')
    for i, (_, start, dur) in enumerate(SPANS):
        text.append(f'    events {{ metadata_id: {i + 1} '
                    f'offset_ps: {start * 10**6} '
                    f'duration_ps: {dur * 10**6} }}')
    text.append('  }')
    text.append('}')
    return ProfileData.text_proto_to_serialized_xspace("\n".join(text))


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(by_hand())


def test_summary_shape(summary):
    (dev,) = summary["devices"]
    assert dev["plane"] == "/device:TPU:0"
    assert len(dev["ops"]) == len(OPS) - 1 and len(dev["async"]) == 1
    assert [s[0] for s in summary["spans"]] == [        # ours only
        "bench.step", "bench.grad", "bench.wait", "hvd.replay.wait"]
    assert set(dev["scopes"]) == {""}       # no tf_op was written
    labels = {tracecalc.op_name(x): x for x in dev["labels"]}
    assert labels["splash_mha_fwd_residuals.4"] == (
        "splash_mha_fwd_residuals.4 | custom-call tpu_custom_call | "
        "bf16[4,16,2048,128]")
    assert labels["fusion.1"] == "fusion.1 | fusion kOutput | bf16[4,2048,2048]"
    assert labels["while.1"] == "while.1 | while | bf16[4,2048,2048]"
    assert xplane.label_of("not HLO text") == "not HLO text"
    json.dumps(summary)     # it goes into the worker's record


def test_busy_and_idle(summary):
    (dev,) = summary["devices"]
    assert tracecalc.window(dev) == pytest.approx((0, 170e3))
    # [0,110] + [120,125] + [130,140] + [160,170] microseconds
    assert stats.total(tracecalc.busy(dev)) == pytest.approx(135e3)
    _, read = files.layer_metric("device_idle_pct")
    ctx = {"record": {"traced": {"trace": summary, "steps": 1}}}
    assert read(ctx, {}) == pytest.approx(35 / 170 * 100)


def test_self_time_of_a_nest(summary):
    (dev,) = summary["devices"]
    selfs = {tracecalc.op_name(dev["labels"][o[3]]): o[2]
             for o in dev["ops"]}
    assert selfs["while.1"] == pytest.approx(60e3)      # 100 - 20 - 20
    assert selfs["fusion.1"] == pytest.approx(20e3)
    assert sum(selfs.values()) == pytest.approx(135e3)  # = busy time
    top = dict(tracecalc.top_ops(dev))      # no op_name: no pass, no scope
    assert top["- | while bf16[4,2048,2048]"] == pytest.approx(60e-6)
    assert top["- | all-reduce f32[8]"] == pytest.approx(10e-6)
    assert top["- | fusion kLoop f32[64]"] == pytest.approx(10e-6)
    assert top["- | multiply_fusion (fusion kLoop) f32[64]"] == \
        pytest.approx(8e-6)
    assert top["- | splash_mha_fwd_residuals bf16[4,16,2048,128]"] == \
        pytest.approx(20e-6)
    # a record written before PR 36 has no scopes: the group alone
    old = {k: v for k, v in dev.items() if k != "scopes"}
    assert dict(tracecalc.top_ops(old))["while bf16[4,2048,2048]"] == \
        pytest.approx(60e-6)


def test_kernel_and_collective_time(summary):
    ctx = {"record": {"traced": {"trace": summary, "steps": 2}}}
    spec, read = files.layer_metric("attn_kernel_ms_per_step")
    # the kernel's own 20; not the fusion that only reads its result
    assert read(ctx, spec) == pytest.approx(20e-3 / 2)
    spec, read = files.layer_metric("collective_ms_per_step")
    # the asynchronous one from 100 to 125, and 130 to 140
    assert read(ctx, spec) == pytest.approx(35e-3 / 2)
    spec, read = files.layer_metric("collective_exposed_ms_per_step")
    # multiply_fusion.2 hides 8 of the asynchronous one's 25
    assert read(ctx, spec) == pytest.approx(27e-3 / 2)


def test_roofline_share(summary):
    ctx = {"record": {"traced": {"trace": summary, "steps": 1},
                      "kernel_costs": {"attn_kernel": {
                          "flops": 197e12 * 5e-6, "bytes": 819e9 * 2e-6}}},
           "peaks": files.peaks("TPU v5 lite"), "notes": []}
    spec, read = files.layer_metric("attn_kernel_roofline")
    assert read(ctx, spec) == pytest.approx(5 / 20 * 100)
    assert "bound by compute" in ctx["notes"][0]


def test_idle_gaps_by_span(summary):
    (dev,) = summary["devices"]
    gaps = dict(tracecalc.idle_by_span(dev, summary["spans"]))
    # [140,160]: its middle falls in bench.wait and, inside that, in the
    # program's own span, the innermost; [110,120] and [125,130] in no span
    assert gaps["hvd.replay.wait"] == pytest.approx(20e-6)
    assert "bench.wait" not in gaps
    assert gaps["(no span)"] == pytest.approx(15e-6)
    assert tracecalc.span_at(summary["spans"], 10e3) == "bench.grad"
    assert tracecalc.span_at(summary["spans"], 143e3) == "bench.wait"
    assert tracecalc.span_at(summary["spans"], 156e3) == "bench.wait"


def test_intervals():
    a = stats.union([(0, 5), (3, 8), (10, 12), (12, 12)])
    assert a == [(0, 8), (10, 12)]
    assert stats.subtract(a, [(2, 3), (7, 11)]) == [(0, 2), (3, 7), (11, 12)]
    assert stats.gaps(a, -1, 13) == [(-1, 0), (8, 10), (12, 13)]
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.spread([10, 10, 10, 11]) == pytest.approx(0.025)


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = {"record": {"window": {"steps": 4}, "kernel_costs": {}},
           "peaks": None, "notes": []}
    for m in files.benchmark_json()["per_layer"]:
        if m["source"] == "device_trace" or m["name"] in (
                "update_apply_ms_per_step", "mfu_pct", "compile_s",
                "cache_hit_pct"):
            spec, read = files.layer_metric(m["name"])
            assert read(ctx, spec) is None, m["name"]


def recorded(name: str) -> dict:
    return xplane.summarize_file(os.path.join(DATA, name))


def pass_and_scope(op_name: str) -> str:
    return f"{tracecalc.pass_of(op_name)} {tracecalc.scope_path(op_name)}"


def test_pass_and_scope_of_an_op_name():
    """What jax wraps around every operation goes, the program's scopes
    stay, the primitive goes; the pass is read off the wrapping."""
    layer = "layers/while/body/closed_call/"
    assert pass_and_scope(
        f"jit(train_step)/transpose(jvp())/{layer}ffn/btd,df->btf/"
        "dot_general") == "bwd layers/ffn"
    assert pass_and_scope(
        f"jit(train_step)/jvp(layers)/while/body/closed_call/ffn/while/body/"
        "experts/jit(gmm)/pallas_call") == "fwd layers/ffn/experts"
    assert pass_and_scope(
        f"jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
        "checkpoint/rematted_computation/attn/attn_full/"
        "vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/"
        "splash_mqa_fwd_residuals/pallas_call") == \
        "remat layers/attn/attn_full/splash_mqa_fwd_residuals"
    assert pass_and_scope(
        "jit(train_step)/shard_map/transpose(jvp(embed))/scatter-add") == \
        "bwd embed"
    # jvp( under the optimizer's scope is the optimizer's, as the metrics
    # read it; an operation XLA made itself has no op_name
    assert pass_and_scope("jit(hvd_apply_update)/optimizer/jvp(mul)") == \
        "opt optimizer"
    assert pass_and_scope("jit(f)/multi_optimizer/mul") == "- multi_optimizer"
    assert pass_and_scope("") == "- "


def test_recorded_lm_trace():
    """Two steps of ``lm-spmd-1chip`` as the v5e's profiler recorded them
    (my chip run, PR 22; cut by make_fixture.py). The numbers were read off
    the profiler's own per-name totals of the whole trace (``python
    benchmark/xplane.py``: splash dkv 40.507 ms, fwd 35.6 ms, dq 29.0 ms
    over 8 steps; a step of 138.59 ms with nothing between steps)."""
    summary = recorded("lm-spmd-1chip.2steps.xplane.pb.gz")
    (dev,) = summary["devices"]
    assert len(dev["ops"]) == 1916 and len(dev["async"]) == 520
    assert [m[0].split("(")[0] for m in dev["modules"]] == ["jit_step"] * 2
    lo, hi = tracecalc.window(dev)
    assert (hi - lo) / 1e6 == pytest.approx(277.164, abs=1e-3)
    idle = hi - lo - stats.total(tracecalc.busy(dev))
    assert idle == pytest.approx(7939, abs=1)           # ns: one gap
    assert tracecalc.idle_by_span(dev, summary["spans"]) == [
        ["bench.wait", pytest.approx(7.939e-6)]]
    ctx = {"record": {"traced": {"trace": summary, "steps": 2},
                      "kernel_costs": {"attn_kernel": {
                          "flops": 4 * 8192 * 25.165824e6, "bytes": 1.61e9}}},
           "peaks": files.peaks("TPU v5 lite"), "notes": []}
    spec, read = files.layer_metric("attn_kernel_ms_per_step")
    assert read(ctx, spec) == pytest.approx(13.1418, abs=1e-3)
    spec, read = files.layer_metric("attn_kernel_roofline")
    assert read(ctx, spec) == pytest.approx(4.1858 / 13.1418 * 100, abs=0.05)
    spec, read = files.layer_metric("collective_ms_per_step")
    assert read(ctx, spec) == 0.0       # one chip: there is none
    top = tracecalc.top_ops(dev, 3)
    assert [g for g, _ in top] == ["- | fusion kOutput bf16[4,2048,2048]",
                                   "- | fusion kOutput f32[50257,2048]",
                                   "- | fusion kOutput bf16[4,2048,50257]"]
    assert top[0][1] / 2 == pytest.approx(26.713e-3, abs=1e-5)


def test_recorded_four_chip_trace():
    """One step of ``lm-spmd-4chip-dp`` on the first chip (my chip run, PR
    22): the one-chip step and after it the gradients' all-reduces, ten
    ``psum`` of the shard_map's transpose and what XLA made of them,
    21.46 ms with nothing beside them (the whole traced window read 21.44
    a step), the embedding's alone 7.24 ms."""
    summary = recorded("lm-spmd-4chip-dp.chip0.1step.xplane.pb.gz")
    (dev,) = summary["devices"]
    assert [m[0].split("(")[0] for m in dev["modules"]] == ["jit_step"]
    lo, hi = tracecalc.window(dev)
    assert (hi - lo) / 1e6 == pytest.approx(160.0426, abs=1e-3)
    ctx = {"record": {"traced": {"trace": summary, "steps": 1}}}
    spec, read = files.layer_metric("collective_ms_per_step")
    assert read(ctx, spec) == pytest.approx(21.457668, abs=1e-6)
    spec, read = files.layer_metric("collective_exposed_ms_per_step")
    assert read(ctx, spec) == pytest.approx(21.457668, abs=1e-6)
    spec, read = files.layer_metric("attn_kernel_ms_per_step")
    assert read(ctx, spec) == pytest.approx(13.1447, abs=1e-3)
    assert tracecalc.idle_by_span(dev, summary["spans"]) == [
        ["bench.wait", pytest.approx(1.795e-6)]]
    top = dict(tracecalc.top_ops(dev, 100))
    assert top["- | psum (all-reduce) f32[50257,2048]"] == \
        pytest.approx(7.240126e-3)
