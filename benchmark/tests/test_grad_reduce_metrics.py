"""The two metrics of an asynchronous collective fusion (PR 29): on a
device entry written by hand, whose numbers are worked out below, and on
the recorded four-chip trace of a program that has no such pair."""

import os

import files
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# nanoseconds: [start, duration, self, label]; two steps of one chip
LABELS = ["async-collective-start.1 | fusion kCustom | f32[8,8]",
          "fusion.7 | fusion kOutput | f32[8,8]",
          "async-collective-done.1 | fusion kCustom | f32[8,8]",
          "async-collective-start | fusion kCustom | f32[4]",
          "async-collective-done | fusion kCustom | f32[4]",
          "psum.2 | all-reduce | f32[]"]
STEP = [[0, 1e6, 1e6, 0], [1e6, 6e6, 6e6, 1], [7e6, 2e6, 2e6, 2],
        [8e6 + 1e6, 1e6, 1e6, 3], [10e6, 3e6, 3e6, 4], [13e6, 1e6, 1e6, 5]]


def ctx_of(ops):
    return {"record": {"traced": {"steps": 2, "trace": {
        "spans": [], "devices": [{"plane": "/device:TPU:0", "labels": LABELS,
                                  "ops": ops, "async": []}]}}}}


def read(name, ctx):
    spec, reader = files.layer_metric(name)
    return reader(ctx, spec)


def test_wait_and_span_by_hand():
    ops = STEP + [[t + 20e6, d, s, i] for t, d, s, i in STEP]
    # the four fusions' own time: 1 + 2 + 1 + 3 ms a step
    assert read("grad_reduce_wait_ms_per_step", ctx_of(ops)) == 7.0
    # pair 1 from 0 to 9 ms, the unnumbered pair from 9 to 13 ms
    assert read("grad_reduce_span_ms_per_step", ctx_of(ops)) == 13.0
    # the accepted metric sees the synchronous scalar alone
    assert read("collective_exposed_ms_per_step", ctx_of(ops)) == 1.0


def test_a_start_without_its_done_reads_nothing():
    assert read("grad_reduce_span_ms_per_step", ctx_of(STEP[:-2])) is None


def test_the_parents_program_has_no_pair():
    trace = xplane.summarize_file(os.path.join(
        DATA, "lm-spmd-4chip-dp.chip0.1step.xplane.pb.gz"))
    ctx = {"record": {"traced": {"steps": 1, "trace": trace}}}
    assert read("grad_reduce_wait_ms_per_step", ctx) == 0.0
    assert read("grad_reduce_span_ms_per_step", ctx) == 0.0
    assert read("collective_ms_per_step", ctx) > 20.0
