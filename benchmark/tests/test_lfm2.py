"""``lfm2-8b-a1b`` and its cell: the file against the catalog, the operations
counted from shapes against a count by hand, and what the cell's checks read
of two wrong models, three dropped leaves, half of the batch left out and a
state left unchanged on the CPU at the rehearsal's widths. The program against the plain reference in float32 and bfloat16,
piece by piece, is ``tests/test_lfm2_lm.py``; the rehearsal of the cell
itself is a case of ``test_run.py``; the traced line of the cell is read
here from one step its own program left on the v5e
(``data/lfm2-spmd-1chip-ep4share-8k.1step.xplane.pb.gz``)."""

import json

import pytest

import files
import lfm2_defects

CONFIG, TRAFFIC = lfm2_defects.CONFIG, lfm2_defects.TRAFFIC
CELL = "lfm2-spmd-1chip-ep4share-8k"


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_four_cuts():
    spec = files.load_json(files.config_path(CONFIG))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-8B-A1B")
    assert spec["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if spec[k] != v}
    cuts = {"num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size"}
    assert changed == cuts == set(spec["reduced"])
    assert spec["published"] == {k: row["config"][k] for k in cuts}
    assert spec["router_outputs"] == row["config"]["num_experts"]
    for key in ("assumed", "what_the_cut_distorts", "deployment",
                "memory_analysis", "rehearsal", "departures"):
        assert key in spec
    assert {"tied_head", "norm_placement", "qk_norm", "router_bias_rate",
            "route_eps"} <= set(spec["assumed"])
    declared = next(c for c in files.benchmark_json()["configs"]
                    if c["name"] == CONFIG)
    assert set(declared["reduced"]) == cuts
    assert declared["source"] == row["source_url"]
    cell = files.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)


def test_the_cut_is_a_dense_conv_layer_and_one_whole_period():
    _, cfg = cell_config()
    assert [(k.mixer, k.experts, k.window, k.rope) for k in cfg.layers] == [
        ("conv", False, 0, True), ("attention", True, 0, True),
        ("conv", True, 0, True), ("conv", True, 0, True),
        ("conv", True, 0, True)]
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k) == (32, 8, 4)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.conv_kernel) == (7168, 1792, 3)
    assert cfg.vocab_size == 16384 == 65536 // 4
    assert (cfg.route_eps, cfg.norm, cfg.tie_embeddings, cfg.qk_norm) == (
        1e-6, "pre", True, True)
    assert cfg.remat == "block" and cfg.remat_barrier


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # a conv mixer 16.78 M matmul parameters (hidden x 3 hidden and hidden
    # x hidden), the attention mixer 10.49 M (q, o 4.19 M each, k and v
    # 1.05 M each); the dense FFN 44.04 M; an expert layer's router 0.07 M
    # and 4 * 8 / 32 = one held expert a token, 11.01 M; the tied head 33.55
    # M; visible pairs 33,558,528, 12 * 2048 operations a pair
    by_hand = 6 * (4 * 16.777216e6 + 10.485760e6 + 44.040192e6 + 4 * (
        0.065536e6 + 11.010048e6) + 33.554432e6) \
        + 12 * 2048 * 33558528 / 8192
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    assert model.flops_per_sample(cfg) / 1e9 == pytest.approx(1.298, abs=1e-3)
    costs = model.kernel_costs(cfg, 2)
    assert costs["head64_attn_kernel"]["flops"] == pytest.approx(
        2 * 12 * 2048 * 33558528)
    assert costs["head64_attn_kernel"]["bytes"] == \
        6 * 2 * 8192 * (2048 + 512) * 2
    assert costs["ep4_moe_experts"]["flops"] == pytest.approx(
        4 * 9 * 2 * 2048 * 1792 * 16384)


@pytest.mark.parametrize("defect, limits", [
    ("float8", {"logits", "first_moment"}),
    ("conv_looks_ahead", {"loss", "first_moment"}),
    # a leaf whose gradient is missing on one side
    ("conv_w_dropped", {"first_moment"}),
    ("conv_in_dropped", {"first_moment"}),
    ("experts_dropped", {"first_moment"}),
    # a step that summed one of its two rows, and one that left adamw's
    # state as init made it
    ("half_of_the_batch", {"first_moment"}),
    ("state_unchanged", {"first_moment"}),
])
def test_a_wrong_model_fails_the_cells_checks(defect, limits):
    found = lfm2_defects.readings(lfm2_defects.cell_checks(
        5, lfm2_defects.DEFECTS[defect])[2])
    lfm2_defects.say(defect, found)
    failed = {k for k, (v, band) in found.items() if v > band}
    assert limits <= failed, found


def test_the_traced_line_has_every_metric_of_the_cell(capsys):
    """One step of the cell's own program as the v5e's profiler recorded it
    (my chip run, PR 36; cut by make_fixture.py with every operation's
    op_name), reduced with the record that run left: the costs the
    configuration counts from shapes are the record's, every per-layer
    metric declared for the cell is on the line, and the three that this
    cell brought read what the ledger's PR 35 line reads (35.94 ms, 23.30%,
    55.82% over 8 steps). Every grouped product lies under the scope
    ``experts``: the roofline reads by name and scope what the name alone
    read."""
    import run
    import trace_ops
    from test_run import chip_record, declared_for
    model, cfg = cell_config()
    rec = chip_record(CELL)
    assert json.loads(json.dumps(model.kernel_costs(cfg, 2))) == \
        rec["kernel_costs"]
    line = run.reduce(files.cell(CELL), rec, 1, False)
    assert "left out of the line" not in capsys.readouterr().out
    assert set(line["metrics"]) == declared_for(CELL)
    read = {k: v["value"] for k, v in line["metrics"].items()}
    assert read["head64_attn_kernel_ms_per_step"] == pytest.approx(35.9,
                                                                   abs=0.3)
    assert read["head64_attn_kernel_roofline"] == pytest.approx(23.3, abs=0.3)
    assert read["ep4_moe_experts_roofline"] == pytest.approx(55.8, abs=0.8)
    spec, _ = files.layer_metric("ep4_moe_experts_roofline")
    ctx = {"record": rec}
    by_name = {k: v for k, v in spec.items() if k != "scope"}
    assert trace_ops.per_step_ms(ctx, {**spec, "what": "self"}) == \
        trace_ops.per_step_ms(ctx, {**by_name, "what": "self"})
    assert trace_ops.per_step_ms(ctx, {
        **spec, "what": "self", "scope": "moe_combine"}) == [0.0]
    assert 0 < read["mfu_pct"] < 100
