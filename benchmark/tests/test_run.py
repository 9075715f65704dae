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
import stats
import tracecalc
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
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


def fixture(cell: str) -> str:
    """One traced step of the cell's own program as the v5e's profiler
    recorded it (my chip runs, PR 36; the four-chip cell's first chip), cut
    by make_fixture.py with the three lines, the spans and every
    operation's ``op_name``."""
    return os.path.join(DATA, f"{cell}.scoped.1step.xplane.pb.gz")


def chip_record(cell: str) -> dict:
    """The worker's record of that run as the chip left it, the trace cut
    to the one step."""
    rec = files.load_json(fixture(cell).replace(".xplane.pb.gz",
                                                ".record.json"))
    rec["traced"]["trace"] = xplane.summarize_file(fixture(cell))
    return rec


def declared_for(cell: str) -> set:
    return {m["name"] for m in files.benchmark_json()["per_layer"]
            if cell in m.get("workloads", [cell])}


def test_a_chip_record_becomes_the_contracts_line(capsys):
    """The reduction the rehearsal cannot reach: per-layer metrics, the
    device's busy time and the breakdown from a record with a trace."""
    import run
    bench = files.benchmark_json()
    cell = files.cell("lm-spmd-4chip-dp")
    rec = chip_record(cell["name"])

    line = json.loads(json.dumps(run.reduce(cell, rec, 1, False)))
    assert set(line) == CONTRACT | {"breakdown"}
    assert line["correct"] is True
    assert line["attempted"] == rec["window"]["steps"]
    assert set(line["metrics"]) == declared_for(cell["name"])
    assert "update_apply_ms_per_step" not in line["metrics"]
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    assert all(v["unit"] == units[k] and isinstance(v["value"], float)
               for k, v in line["metrics"].items())
    assert line["metrics"]["cache_hit_pct"]["value"] == pytest.approx(
        100.0 * rec["compile"]["cache_hits"] / rec["compile"]["cache_requests"])
    assert line["metrics"]["mfu_pct"]["value"] == pytest.approx(57.0, abs=0.1)
    (dev,) = rec["traced"]["trace"]["devices"]
    assert line["device"]["busy_s"] == pytest.approx(
        stats.total(tracecalc.busy(dev)) / 1e9)
    assert 0.999 < line["device"]["busy_s"] / line["device"]["window_s"] <= 1
    assert line["device"]["memory_peak_bytes"] == rec["memory_peak_bytes"]
    ops = line["breakdown"]["device_ops"]
    assert len(ops) == 10 and all(
        name.split()[0] in ("fwd", "bwd", "opt", "-") and " | " in name
        and seconds > 0 for name, seconds in ops)
    assert line["breakdown"]["idle_gaps"][0][0].startswith("bench.")

    line = run.reduce(cell, rec, 0, False)
    assert set(line) == CONTRACT
    assert set(line["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    assert line["metrics"]["step_ms_p50"]["value"] == pytest.approx(
        140.5, abs=0.3)
    assert line["metrics"]["samples_per_s_per_chip"]["value"] == \
        pytest.approx(8192 / 0.1405, rel=3e-3)
    assert line["metrics"]["setup_s"]["value"] == rec["window"]["t_open"]
    assert line["metrics"]["peak_hbm_gb"]["value"] == pytest.approx(10.984,
                                                                    abs=1e-3)

    rec["checks"]["replicas_equal"]["ok"] = False
    assert run.reduce(cell, rec, 0, False)["correct"] is False
    rec["window"]["built"] = 1          # a program built inside the window
    assert run.reduce(cell, rec, 0, False) is None
    capsys.readouterr()


# ms a step by hand, PR 24 to PR 35 (PERF.md section 5 as ISSUE 36 quotes
# it), in the order of CELLS: what the traced line reads now
BY_HAND = {
    "fwd_ms_per_step": [39.06, 39.39, 31.71, 100.26, 44.05, 68.52],
    "bwd_ms_per_step": [77.14, 82.91, 63.00, 339.06, 184.28, 219.17],
    "optimizer_ms_per_step": [12.35, 4.34, 0.69, 21.06, 15.90, 17.80],
    "unscoped_ms_per_step": [2.61, 13.77, 3.23, 13.03, 14.61, 9.01],
    "head_loss_ms_per_step": [30.36, 30.24, None, 87.56, 16.49, 19.77],
    "recompute_ms_per_step": [None, None, None, 96.59, 48.38, 47.14],
    "rope_ms_per_step": [None, None, None, 4.11, 7.38, 3.24],
    "moe_routed_ms_per_step": [None, None, None, None, 22.84, 76.57],
    "conv_mixer_ms_per_step": [None, None, None, None, None, 68.98],
    "short_conv_ms_per_step": [None, None, None, None, None, 10.14]}
# one step against the mean of eight: the routed path follows where the
# routers send that batch's tokens (23.0-23.4 ms over a run's eight steps by
# the seed, 24.7 in the step kept: my chip runs, PR 36)
WITHIN = {"moe_routed_ms_per_step": 0.10}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_line_has_every_metric_of_its_cell(cell, capsys):
    """The driver refuses a traced line that lacks a per-layer metric of
    its workload (it refused PR 22 over ``update_apply_ms_per_step`` in
    ``lm-spmd-1chip``): a metric that exists only in some cells lists them
    in BENCHMARK.json, and every other one is read in every cell. Each cell
    reads its own program's trace; the by-scope metrics read there what was
    read by hand, and exactly the cells a metric lists read it."""
    import run
    line = run.reduce(files.cell(cell), chip_record(cell), 1, False)
    said = capsys.readouterr().out
    assert set(line["metrics"]) == declared_for(cell)
    read = {k: v["value"] for k, v in line["metrics"].items()}
    nothing = {"collective_ms_per_step", "collective_exposed_ms_per_step"}
    assert all(v > 0 for k, v in read.items() if k not in nothing)
    assert "left out of the line" not in said
    for name, by_hand in BY_HAND.items():
        want = by_hand[CELLS.index(cell)]
        assert (name in read) == (want is not None), name
        if want is not None:
            assert read[name] == pytest.approx(
                want, rel=WITHIN.get(name, 0.03)), name
    # forward + backward + optimizer + unscoped + collectives = busy time
    (note,) = [x for x in said.splitlines() if x.startswith("bench: scopes:")]
    assert abs(float(note.rsplit(": ", 1)[1].rstrip("%"))) < 0.01
    assert all(v < 100 for k, v in read.items() if k.endswith("_roofline"))
    assert len(line["breakdown"]["device_ops"]) == 10
    assert "walked in" in said


def test_a_record_written_before_the_scopes_reads_the_old_metrics(capsys):
    """A record of the parent's worker holds labels and no scopes: the 20
    metrics PR 35 declared read as they did (the experts' roofline by name
    alone), the by-scope metrics are left out of the line and said so, and
    the breakdown names operations as it named them."""
    import run
    cell = "trinity-spmd-1chip-ep8share-8k"
    new = run.reduce(files.cell(cell), chip_record(cell), 1, False)
    rec = chip_record(cell)
    for dev in rec["traced"]["trace"]["devices"]:
        del dev["scopes"]
    capsys.readouterr()
    old = run.reduce(files.cell(cell), rec, 1, False)
    said = capsys.readouterr().out
    by_scope = set(BY_HAND) & declared_for(cell)
    assert set(old["metrics"]) == declared_for(cell) - by_scope
    assert all(old["metrics"][k] == new["metrics"][k] for k in old["metrics"])
    for name in by_scope:
        assert f"{name}: declared for this cell and nothing to read" in said
    assert "bench: scopes:" not in said
    assert old["device"] == new["device"]
    assert old["breakdown"]["idle_gaps"] == new["breakdown"]["idle_gaps"]
    assert " | " not in old["breakdown"]["device_ops"][0][0]


def test_the_recordings_of_earlier_prs_read_bit_for_bit():
    """The four traces recorded before PR 36, through the reduction of
    run.py: every metric PR 35 declared, the device's busy time and the
    idle gaps read what ``jax.profiler.ProfileData`` and the parent's
    readers read (data/parent_readings.json: the parent's tree, to the last
    bit). But one: the experts' roofline counts a grouped product only under
    the scope ``experts`` now, and the recording of PR 34 kept no op_name."""
    import run
    want = files.load_json(os.path.join(DATA, "parent_readings.json"))
    shared = want.pop("record")
    for name, readings in want.items():
        rec = {**shared, "traced": {
            "steps": readings.pop("steps"), "stamps": [41.0, 41.16],
            "trace": xplane.summarize_file(
                os.path.join(DATA, name + ".xplane.pb.gz"))}}
        ctx = run.context(files.cell("lm-spmd-1chip"), rec)
        got = {"device": run.traced_device(ctx),
               "idle_gaps": run.breakdown(ctx)["idle_gaps"]}
        for metric in set(readings) - set(got):
            spec, read = files.layer_metric(metric)
            got[metric] = read(ctx, spec)
        if name.startswith("lfm2"):
            for metric in ("moe_experts_roofline", "ep4_moe_experts_roofline"):
                assert readings.pop(metric) > 0 and got.pop(metric) is None
        assert json.loads(json.dumps(got)) == readings, name


def test_a_reader_that_finds_nothing_leaves_its_metric_out(capsys):
    import run
    rec = chip_record("lm-spmd-1chip")
    rec["kernel_costs"] = {}
    metrics = run.reduce(files.cell("lm-spmd-1chip"), rec, 1,
                         False)["metrics"]
    assert "attn_kernel_roofline" not in metrics    # no cost from shapes
    assert "attn_kernel_ms_per_step" in metrics
    assert "attn_kernel_roofline: declared for this cell and nothing to " \
        "read" in capsys.readouterr().out
