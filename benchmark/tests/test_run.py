"""The command end to end: ``--rehearse`` runs every cell on the CPU
(forced host devices), the last printed line parses, has the contract's
keys and, being a rehearsal, no device metric. A cell more is a traffic
file and one ``workloads`` entry, added here to a copy of the benchmark:
the harness takes cells as data."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import files

CONTRACT = {"correct", "attempted", "failed", "metrics", "device"}
CELLS = [w["name"] for w in files.benchmark_json()["workloads"]]


TWO_CHIPS = {
    "name": "lm-spmd-2chip-dp", "config": "cerebras-gpt-1.3b",
    "traffic": "spmd-2chip-dp-4x2048", "chips": 2,
    "why": "make_train_step over a mesh data=2: not a cell, a proof that "
           "one is data"}


def rehearse(cell: str, trace: int, root: str = files.ROOT):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", cell, "--seed", "7", "--seconds", "2", "--trace",
         str(trace), "--rehearse"], cwd=root, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return done.stdout.strip().splitlines()


@pytest.fixture
def copy_with_one_more_cell(tmp_path):
    """The benchmark's files and the program, with one more cell."""
    shutil.copytree(files.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(files.ROOT, "horovod_tpu"),
               tmp_path / "horovod_tpu")
    traffic = files.load_json(files.traffic_path("spmd-4chip-dp-4x2048"))
    traffic["mesh"]["data"] = 2
    (tmp_path / "benchmark" / "traffic" / f"{TWO_CHIPS['traffic']}.json"
     ).write_text(json.dumps(traffic))
    bench = files.benchmark_json()
    bench["workloads"].append(TWO_CHIPS)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_cell_is_a_traffic_file_and_one_entry(copy_with_one_more_cell):
    lines = rehearse(TWO_CHIPS["name"], 1, copy_with_one_more_cell)
    check(lines, 2)
    assert checks_of(lines) >= {"reference", "mesh_step", "replicas_equal"}


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell(cell):
    lines = rehearse(cell, trace=1)
    chips = files.cell(cell)["chips"]
    check(lines, chips)
    # the gradient sum is checked wherever there are chips to sum over
    assert ({"mesh_step", "replicas_equal"} <= checks_of(lines)) \
        == (chips > 1)


def checks_of(lines) -> set:
    line = next(x for x in lines if x.startswith("bench: checks: "))
    found = json.loads(line[len("bench: checks: "):])
    assert all(found.values())
    return set(found)


def check(lines, chips):
    last = json.loads(lines[-1])
    assert CONTRACT <= set(last)
    assert set(last) - CONTRACT == {"rehearsal", "counts"}
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] == last["counts"]["steps"] >= 8
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    assert any(x.startswith("bench: platform=cpu device_kind=cpu "
                            f"devices={chips}") for x in lines[:-1])


def test_a_tpu_run_needs_a_tpu():
    """Without --rehearse the device gate refuses the CPU: a code other
    than 0 and no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, os.path.join(files.HERE, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=files.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert not any(x.startswith("{") for x in done.stdout.splitlines())
    assert "device gate" in done.stderr


def chip_record(trace: str, chips: int, **more) -> dict:
    """A worker's record as a run on the v5e leaves it: 62 steps of 160 ms
    in the window, and a trace recorded on the chip (data/)."""
    from test_trace import recorded
    stamps = [30.0 + 0.16 * i for i in range(1, 63)]
    return {"device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": chips},
            "phases": [["process", 0.1], ["devices", 9.0]], "warmup_steps": 6,
            "compile": {"built": 15, "seconds": 1.25, "cache_requests": 16,
                        "cache_hits": 12},
            "window": {"t_open": 30.0, "steps": 62, "stamps": stamps,
                       "losses": [11.0 - 0.01 * i for i in range(62)],
                       "failed": 0, "built": 0},
            "memory_peak_bytes": 11824000000, "samples_per_step": 8192 * chips,
            "flops_per_sample": 1.93e9, "kernel_costs": {},
            "traced": {"steps": 1, "stamps": [41.0, 41.16, 41.32],
                       "trace": recorded(trace)},
            "checks": {"reference": {"ok": True, "error": {}}}, **more}


def test_a_chip_record_becomes_the_contracts_line(capsys):
    """The reduction the rehearsal cannot reach: per-layer metrics, the
    device's busy time and the breakdown from a record with a trace."""
    import run
    bench = files.benchmark_json()
    cell = files.cell("lm-spmd-4chip-dp")
    rec = chip_record(
        "lm-spmd-4chip-dp.chip0.1step.xplane.pb.gz", 4,
        kernel_costs={"attn_kernel": {"flops": 8.2e11, "bytes": 1.6e9}})
    rec["checks"]["replicas_equal"] = {"ok": True, "error": {}}

    line = json.loads(json.dumps(run.reduce(cell, rec, 1, False)))
    assert set(line) == CONTRACT | {"breakdown"}
    assert line["correct"] is True and line["attempted"] == 62
    assert set(line["metrics"]) == declared_for(cell["name"])
    assert "update_apply_ms_per_step" not in line["metrics"]
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert all(v["unit"] == units[k] and isinstance(v["value"], float)
               for k, v in line["metrics"].items())
    assert line["metrics"]["collective_ms_per_step"]["value"] == \
        pytest.approx(21.457668)
    assert line["metrics"]["cache_hit_pct"]["value"] == 75.0
    assert line["metrics"]["mfu_pct"]["value"] == pytest.approx(
        1.93e9 * 51200 / 197e12 * 100)
    assert line["device"]["busy_s"] == pytest.approx(0.1600408, abs=1e-6)
    assert line["device"]["window_s"] == pytest.approx(0.1600426, abs=1e-6)
    assert line["device"]["memory_peak_bytes"] == 11824000000
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert line["breakdown"]["idle_gaps"][0][0] == "bench.wait"

    line = run.reduce(cell, rec, 0, False)
    assert set(line) == CONTRACT
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert line["metrics"]["step_ms_p50"]["value"] == pytest.approx(160.0)
    assert line["metrics"]["samples_per_s_per_chip"]["value"] == \
        pytest.approx(51200.0)
    assert line["metrics"]["setup_s"]["value"] == 30.0
    assert line["metrics"]["peak_hbm_gb"]["value"] == pytest.approx(11.824)

    rec["checks"]["replicas_equal"]["ok"] = False
    assert run.reduce(cell, rec, 0, False)["correct"] is False
    rec["window"]["built"] = 1          # a program built inside the window
    assert run.reduce(cell, rec, 0, False) is None
    capsys.readouterr()


def declared_for(cell: str) -> set:
    return {m["name"] for m in files.benchmark_json()["per_layer"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_has_every_metric_of_its_cell(cell, capsys):
    """The driver refuses a traced line that lacks a per-layer metric of
    its workload (it refused PR 22 over ``update_apply_ms_per_step`` in
    ``lm-spmd-1chip``): a metric that exists only in some cells lists them
    in BENCHMARK.json, and every other one is read in every cell."""
    import run
    chips = files.cell(cell)["chips"]
    trace = ("lm-spmd-4chip-dp.chip0.1step" if chips > 1 else
             "lm-spmd-1chip.2steps") + ".xplane.pb.gz"
    rec = chip_record(
        trace, chips,
        kernel_costs={"attn_kernel": {"flops": 8.2e11, "bytes": 1.6e9}})
    if files.load_json(files.traffic_path(
            files.cell(cell)["traffic"]))["mode"] == "eager":
        rec.update(kernel_costs={}, probes=[
            {"update_apply_ms": x} for x in (18.0, 19.0, 30.0)])
    line = run.reduce(files.cell(cell), rec, 1, False)
    assert set(line["metrics"]) == declared_for(cell)
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "left out of the line" not in capsys.readouterr().out
    if "probes" in rec:
        assert line["metrics"]["update_apply_ms_per_step"]["value"] == 19.0


def test_a_reader_that_finds_nothing_leaves_its_metric_out(capsys):
    import run
    rec = chip_record("lm-spmd-1chip.2steps.xplane.pb.gz", 1)
    metrics = run.reduce(files.cell("lm-spmd-1chip"), rec, 1,
                         False)["metrics"]
    assert "attn_kernel_roofline" not in metrics    # no cost from shapes
    assert "attn_kernel_ms_per_step" in metrics
    assert "attn_kernel_roofline: declared for this cell and nothing to " \
        "read" in capsys.readouterr().out
