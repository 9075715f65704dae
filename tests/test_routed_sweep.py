"""The two tools of ISSUE 33, on the CPU. ``tools/grouped_matmul_sweep.py
--rehearse``: both modes at toy sizes, the megablox kernels interpreted. No
time is read here (those come from the chip alone: PERF.md section 6); what
is held is that every form the tool times computes what its first form
computes, so that a table of the chip's times compares like with like.
``tools/trace_by_operation.py``: a trace recorded on the chip (the
benchmark's own fixture) by (scope, operation).
"""

import importlib.util
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOL = os.path.join(REPO, "tools", "grouped_matmul_sweep.py")


def _tool(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def sweep():
    return _tool(TOOL)


@pytest.mark.parametrize("mode, forms, band", [
    # bfloat16 products against bfloat16 products: the order of the sums
    # (the stock rule at five tilings, ragged_dot, and the program's own
    # call with a tiling a call: moe.gmm_tiles)
    ("products", 7, 1e-2),
    # the row sums: float32 to its rounding (the one-hot product's two
    # bfloat16 parts keep 16 bits), bfloat16 to its own; form (h), the
    # shipped gather, both ways, and the combine as it runs it (bfloat16
    # rows in, float32 out) beside the float32 ones
    ("rows", 11 * 6 + 3, 1e-2),
    # the two products of relu2 experts (ISSUE 39), and the program's own
    # call under two other rules for a dimension no multiple of 128 divides
    ("products --form relu2 --tile-where-none-divides 0 128", 7 + 2, 1e-2)],
    ids=["products", "rows", "relu2"])
def test_every_form_computes_what_the_first_does(sweep, monkeypatch,
                                                 tmp_path, mode, forms,
                                                 band):
    out = tmp_path / "sweep.json"
    mode, *more = mode.split()
    monkeypatch.setattr(sys, "argv", [TOOL, "--mode", mode, "--rehearse",
                                      "--out", str(out)] + more)
    sweep.main()
    timed = {name: rec for name, rec in json.loads(out.read_text())[
        "ms"].items() if name.split()[0] not in ("gather", "place")}
    assert len(timed) == forms
    for name, rec in timed.items():
        assert "failed" not in rec, (name, rec)
        assert rec["against_first"] <= band, (name, rec)
    if mode == "rows":      # float32 forms that only reorder a sum
        for name, rec in timed.items():
            if name.endswith("float32") and "onehot" not in name \
                    and "combine" not in name:
                assert rec["against_first"] <= 1e-6, (name, rec)


def test_a_recorded_trace_by_scope_and_operation():
    """Two steps of ``lm-spmd-1chip`` recorded on the v5e: every operation
    counts for one scope, a scope's sum is its operations', and ``attn``
    inside ``jvp(...)`` and ``transpose(...)`` is found like ``attn``."""
    tool = _tool(os.path.join(REPO, "tools", "trace_by_operation.py"))
    got = tool.by_operation(os.path.join(
        REPO, "benchmark", "tests", "data",
        "lm-spmd-1chip.scoped.2steps.xplane.pb.gz"), 2, ("attn", "ffn"))
    listed = sum(op[0] for op in got["operations"])
    assert listed == pytest.approx(sum(got["scopes"].values()), rel=1e-9)
    assert 0 < got["scopes"]["attn"] < got["scopes"]["ffn"] < got["busy_ms"]
    for kind in ("attn", "ffn"):
        ops = [op for op in got["operations"]
               if "/%s/" % kind in "/" + op[2] + "/"]
        assert sum(op[0] for op in ops) == pytest.approx(
            got["scopes"][kind], rel=1e-9)
        assert {"jvp", "transpose"} <= {op[2].split("(")[0] for op in ops}
