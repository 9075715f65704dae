"""BENCHMARK.json against the builder's contract, and every file a cell
names found by its name."""

import os
import re

import files

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_contract_shape():
    b = files.benchmark_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(x["why"]) <= 200 for x in b["configs"] + b["workloads"])
    assert 2 <= len(b["workloads"]) <= 24
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in b["workloads"]} == \
        {c["name"] for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e
        # a metric that exists only in some cells lists them (the driver
        # wants every other metric on every cell's traced line)
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in b["workloads"]}
        assert m.get("workloads", True)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_reduced_names_no_width():
    for c in files.benchmark_json()["configs"]:
        spec = files.load_json(files.config_path(c["name"]))
        for key in c["reduced"]:
            assert key in spec and key in spec["published"]
            # a width: a size, a key ending in _dim or _rank, a head size;
            # the catalog's num_hidden_layers is a depth
            assert not re.search(
                r"(_dim$|_rank$|embd|inner|intermediate|head|expansion|"
                r"per_tok|(hidden|latent|state|proj\w*)_(size|width)$)", key)


def test_every_file_resolves():
    b = files.benchmark_json()
    for w in b["workloads"]:
        spec = files.load_json(files.config_path(w["config"]))
        traffic = files.load_json(files.traffic_path(w["traffic"]))
        assert spec["sample"] and "rehearsal" in spec
        base = os.path.splitext(files.config_path(w["config"]))[0]
        assert os.path.exists(base + ".py")
        assert os.path.exists(f"{base}.{traffic['mode']}.py")
        assert os.path.exists(os.path.join(
            files.HERE, "reference", f"{w['config']}.py"))
    for m in b["per_layer"]:
        spec, read = files.layer_metric(m["name"])
        assert callable(read) and spec["doc"]
    assert files.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_every_metric_file_is_declared():
    """No metric is read by hand and not declared: a file in
    ``layer_metrics/`` is a line of BENCHMARK.json's ``per_layer``, and a
    metric of only some cells lists them (a later PR's cell is refused
    where a metric that moves its end-to-end metric has no list)."""
    folder = os.path.join(files.HERE, "layer_metrics")
    declared = {m["name"]: m for m in files.benchmark_json()["per_layer"]}
    assert {f[:-5] for f in os.listdir(folder) if f.endswith(".json")} == \
        set(declared)
    assert {f[:-3] for f in os.listdir(folder) if f.endswith(".py")} <= \
        set(declared)
    for name, m in declared.items():
        if files.layer_metric(name)[0]["reader"] == "trace_scopes":
            assert m["workloads"] and m["source"] == "device_trace"
            assert (m["unit"], m["better"], m["moves"]) == \
                ("ms", "lower", "step_ms_p50")


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(SystemExit):
        files.peaks("TPU v9 imaginary")
