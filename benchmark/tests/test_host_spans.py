"""readers/host_spans.py and the two metrics that read the program's
``hvd.opt.*`` spans, on a summary written by hand: two traced steps of an
eager loop, times in nanoseconds."""

import os

import pytest

import files
import xplane

# one pipelined step of this PR's program on the v5e (my chip run, PR 37;
# make_fixture.py, step 4 of the traced 8), the worker's record beside it
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "resnet50-eager-1chip.spans.1step.xplane.pb.gz")

# [name, start, duration]: the benchmark's span around the call, the
# product's outer span inside it and its children inside that; the second
# step's reduce holds an engine span; a probe after the trace is not here
SPANS = [
    ["bench.update_apply", 0, 1_000_000],
    ["hvd.opt.update_and_apply", 50_000, 900_000],
    ["hvd.opt.flatten", 60_000, 100_000],
    ["hvd.opt.apply_lookup", 200_000, 200_000],
    ["hvd.opt.apply_dispatch", 450_000, 400_000],
    ["bench.wait", 1_000_000, 3_000_000],
    ["bench.update_apply", 4_000_000, 2_000_000],
    ["hvd.opt.update_and_apply", 4_100_000, 1_700_000],
    ["hvd.opt.flatten", 4_100_000, 100_000],
    ["hvd.opt.reduce", 4_200_000, 500_000],
    ["hvd.engine.grouped_allreduce", 4_250_000, 400_000],
    ["hvd.opt.apply_lookup", 4_700_000, 100_000],
    ["hvd.opt.apply_dispatch", 4_800_000, 1_000_000]]


def ctx_of(spans, steps=2):
    return {"record": {"traced": {"trace": {"devices": [], "spans": spans},
                                  "steps": steps}}, "notes": []}


def read(ctx, name, **more):
    spec, reader = files.layer_metric(name)
    return reader(ctx, {**spec, **more})


def test_totals_and_the_note():
    ctx = ctx_of(SPANS)
    assert read(ctx, "update_apply_host_ms_per_step") == pytest.approx(
        (0.9 + 1.7) / 2)
    assert read(ctx, "update_apply_dispatch_ms_per_step") == pytest.approx(
        (0.4 + 1.0) / 2)
    (note,) = ctx["notes"]      # the first metric's alone
    assert note == (
        "host spans: hvd.opt.update_and_apply 1.300 ms a step = "
        "hvd.opt.flatten 0.100 + hvd.opt.apply_lookup 0.150 + "
        "hvd.opt.apply_dispatch 0.700 + hvd.opt.reduce 0.250 + self 0.100; "
        "bench.update_apply 1.500: -0.200 ms")


def test_self_time_leaves_out_the_spans_inside():
    ctx = ctx_of(SPANS)
    # the outer span less its four kinds of children, not their children
    assert read(ctx, "update_apply_host_ms_per_step", self=True) \
        == pytest.approx((0.2 + 0.0) / 2)
    # reduce holds the engine's span: 0.5 - 0.4
    assert read(ctx, "update_apply_dispatch_ms_per_step",
                span=r"^hvd\.opt\.reduce$", self=True) == pytest.approx(0.05)


@pytest.mark.parametrize("ctx", [
    ctx_of([s for s in SPANS if s[0].startswith("bench.")]),    # the parent
    ctx_of([]),
    {"record": {}, "notes": []}],                               # --trace 0
    ids=["no hvd span", "no span", "no traced"])
def test_nothing_to_read(ctx):
    assert read(ctx, "update_apply_host_ms_per_step") is None
    assert read(ctx, "update_apply_dispatch_ms_per_step") is None
    assert ctx["notes"] == []


def test_the_eager_cells_traced_line_from_a_recorded_step(capsys):
    """The whole reduction on what the chip left: every metric declared for
    the cell is on the line, the two new ones read the program's spans, and
    an idle gap is named by the innermost of them.
    (``test_run.py``'s case of this cell reads a step recorded before the
    program wrote spans, so it finds the two metrics left out.)"""
    import run
    cell = "resnet50-eager-1chip"
    rec = files.load_json(RECORDED.replace(".xplane.pb.gz", ".record.json"))
    rec["traced"]["trace"] = xplane.summarize_file(RECORDED)
    line = run.reduce(files.cell(cell), rec, 1, False)
    said = capsys.readouterr().out
    assert "left out of the line" not in said
    assert set(line["metrics"]) == {
        m["name"] for m in files.benchmark_json()["per_layer"]
        if cell in m.get("workloads", [cell])}
    read = {k: v["value"] for k, v in line["metrics"].items()}
    assert read["update_apply_host_ms_per_step"] == pytest.approx(22.634339)
    assert read["update_apply_dispatch_ms_per_step"] == pytest.approx(
        22.454209)
    (note,) = [x for x in said.splitlines() if "host spans:" in x]
    assert note == (
        "bench: host spans: hvd.opt.update_and_apply 22.634 ms a step = "
        "hvd.opt.flatten 0.081 + hvd.opt.apply_lookup 0.079 + "
        "hvd.opt.apply_dispatch 22.454 + self 0.020; "
        "bench.update_apply 22.640: -0.006 ms")
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert "hvd.opt.apply_dispatch" in gaps
    assert "bench.update_apply" not in gaps
