"""``qwen3-next-80b-a3b`` and its cell: the file against the catalog, the
operations counted from shapes against a count by hand, the cell's checks at
the rehearsal's widths on the CPU, and what they read of the planted defects
(``qwen3next_defects.py``). The program against the plain reference piece by
piece is ``tests/test_qwen3next_lm.py``; the traced line of the cell is read
here from one step its own program left on the v5e
(``data/qwen3next-spmd-1chip-ep32share-8k.scoped.1step.xplane.pb.gz``)."""

import json
import os

import pytest

import files
import qwen3next_defects as defects

CONFIG, TRAFFIC = defects.CONFIG, defects.TRAFFIC
CELL = "qwen3next-spmd-1chip-ep32share-8k"
NEW_METRICS = {"gdn_mixer_ms_per_step", "gdn_scan_ms_per_step",
               "gdn_scan_roofline", "h256_attn_kernel_ms_per_step",
               "h256_attn_kernel_roofline", "e512_moe_experts_roofline"}
PAIRS = 8192 * 8193 // 2        # visible (query, key) pairs of a causal row


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_three_cuts():
    spec = files.load_json(files.config_path(CONFIG))
    # the catalog's entry for Qwen3-Next-80B-A3B-Instruct, as published
    from test_run import DATA
    row = files.load_json(os.path.join(DATA,
                                       "qwen3-next-80b-a3b.catalog.json"))
    assert spec["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if spec[k] != v}
    cuts = {"num_hidden_layers", "num_experts", "vocab_size"}
    assert changed == cuts == set(spec["reduced"])
    assert spec["published"] == {k: row["config"][k] for k in cuts}
    assert spec["router_outputs"] == row["config"]["num_experts"]
    assert spec["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert spec["layers_taken"] == list(range(4))
    for key in ("assumed", "what_the_cut_distorts", "deployment",
                "memory_analysis", "rehearsal", "departures"):
        assert key in spec
    assert {"zero_centred_norms", "l2norm_eps", "chunk",
            "gdn_initialisation", "layouts", "mtp_module",
            "router_aux_loss_coef", "router", "optimizer",
            "sequence_and_tokens", "routers_on_a_share"} <= set(
                spec["assumed"])
    declared = next(c for c in files.benchmark_json()["configs"]
                    if c["name"] == CONFIG)
    assert set(declared["reduced"]) == cuts
    assert declared["source"] == row["source_url"]
    cell = files.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)


def test_the_new_metrics_list_the_cell_and_nothing_accepted_lost_one():
    per_layer = {m["name"]: m for m in files.benchmark_json()["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        spec, read = files.layer_metric(name)
        assert callable(read) and "doc" in spec
    for name in ("fwd_ms_per_step", "bwd_ms_per_step", "unscoped_ms_per_step",
                 "optimizer_ms_per_step", "head_loss_ms_per_step",
                 "recompute_ms_per_step", "rope_ms_per_step",
                 "moe_routed_ms_per_step"):
        assert per_layer[name]["workloads"][-1] == CELL
    cells = files.benchmark_json()["workloads"]
    assert len(cells) == 9 and [c["chips"] for c in cells].count(4) == 1


def test_the_cut_is_one_period_of_the_pattern():
    _, cfg = cell_config()
    assert [(k.mixer, k.experts) for k in cfg.layers] == [
        ("gdn", True)] * 3 + [("attention", True)]
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.rope_dim, cfg.gdn_key_heads, cfg.gdn_value_heads,
            cfg.gdn_key_dim, cfg.gdn_value_dim, cfg.conv_kernel) == (
        2048, 16, 2, 256, 64, 16, 32, 128, 128, 4)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_ff_expert,
            cfg.shared_width, cfg.route_score, cfg.route_eps,
            cfg.shared_gate) == (512, 16, 10, 512, 512, "softmax", 0.0,
                                 True)
    assert cfg.vocab_size == 18992 == 151936 // 8
    assert (cfg.rope_theta, cfg.zero_centred_norms, cfg.qk_norm,
            cfg.attn_gate, cfg.tie_embeddings, cfg.router_bias_rate) == (
        1e7, True, True, True, False, 0.0)
    assert cfg.remat == "block" and cfg.remat_barrier


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # a delta-rule layer's three projections 33.686 M (2048 x 12,352 +
    # 4096 x 2048), three of them; the attention layer's five matrices
    # 27.263 M; every layer's router 1.049 M, shared expert 3.146 M and its
    # gate 2,048, and 10 x 16 / 512 of a held expert's 3.146 M; the untied
    # head 38.896 M; attention 4 x 16 x 256 operations a visible pair; the
    # rule's products 5.330 M a token and layer forward
    assert model.gdn_params(cfg) == 33_685_504
    assert model.attention_params(cfg) == 27_262_976
    assert model.scan_flops_per_token(cfg) == pytest.approx(
        (16 * 4 * 64 * 64 * 128 + 32 * (2 * 64 ** 3 / 3 + 2 * 64 * 64 * 256
                                         + 6 * 64 * 128 * 128
                                         + 2 * 64 * 64 * 128)) / 64)
    by_hand = 6 * (3 * 33_685_504 + 27_262_976 + 4 * (
        1_048_576 + 3_145_728 + 2048 + 10 * 16 / 512 * 3_145_728)
        + 38_895_616) + 3 * 16384 * PAIRS / 8192 \
        + 3 * 3 * model.scan_flops_per_token(cfg)
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    # 459 M operations a token forward: the three delta-rule mixers 48% of
    # them, the attention layer's mixer 26%, the head 17%
    forward = model.flops_per_sample(cfg) / 3
    assert forward / 1e6 == pytest.approx(459.0, abs=0.5)
    assert 3 * (2 * 33_685_504 + model.scan_flops_per_token(cfg)) \
        / forward == pytest.approx(0.475, abs=0.005)


def test_kernel_costs_by_hand():
    model, cfg = cell_config()
    costs = model.kernel_costs(cfg, 2)
    # one layer, 16 heads of 256: 3.30 T operations, 16.7 ms at 197 TFLOP/s
    assert costs["h256_attn"]["flops"] == 2 * 3 * 4 * 16 * 256 * PAIRS
    assert costs["h256_attn"]["flops"] / 197e12 * 1e3 == pytest.approx(
        16.74, abs=0.01)
    assert costs["h256_attn"]["bytes"] == 6 * 2 * 8192 * (4096 + 512) * 2
    # 5,120 held assignments a layer under an even router, four layers
    assert costs["e512_moe_experts"]["flops"] == pytest.approx(
        4 * 3 * 3 * 2 * 2048 * 512 * 5120)
    assert costs["e512_moe_experts"]["bytes"] == pytest.approx(
        4 * 2 * (3 * 16 * 3 * 2048 * 512 + 3 * 5120 * (2 * 2048 + 3 * 512)))
    # the rule: three layers, 2 rows, forward and twice that backward; q and
    # k in bf16 at the key heads, v bf16, g and beta fp32, o fp32, and 128
    # chunk states of 32 x 128 x 128 in fp32 written and read
    assert costs["gdn_scan"]["flops"] == pytest.approx(
        3 * 3 * 2 * 8192 * model.scan_flops_per_token(cfg))
    assert costs["gdn_scan"]["bytes"] == 3 * 3 * 2 * (
        8192 * (2 * 2 * 2048 + 2 * 4096 + 2 * 4 * 32 + 4 * 4096)
        + 2 * 4 * 128 * 4096 * 128)


def test_the_cells_checks_pass_at_the_rehearsals_widths():
    checks = defects.cell_checks(5)
    assert all(check["ok"] for check in checks.values()), checks
    err = checks["reference"]["error"]
    assert len(err["gdn_mixer_by_layer"]) == 3
    assert len(err["choices_off_by_layer"]) == 4
    assert 0 < err["gdn_mixer"] < 1e-5      # float32 on both sides
    assert 0 < err["gdn_scan"] < 1e-5
    leaves = checks["step_grad"]["error"]["by_leaf"]
    for leaf in ("['embed']", "['lm_head']", "['gdn_layers']['gdn_qkvz']",
                 "['gdn_layers']['gdn_A_log']",
                 "['gdn_layers']['shared_gate']",
                 "['layers']['wgate']", "['layers']['q_norm']"):
        assert 0 < leaves[leaf] < 1e-3


# what each planted defect must fail at the rehearsal's widths (float32, 256
# tokens); on the chip at the published widths: PERF.md section 4
@pytest.mark.parametrize("defect, limits", [
    ("float8", {"logits", "gdn_mixer", "gdn_scan"}),
    ("qk_not_normed", {"logits", "gdn_mixer", "gdn_scan"}),
    ("value_head_by_remainder", {"logits", "gdn_mixer", "gdn_scan"}),
    ("delta_from_the_undecayed_state", {"logits", "gdn_mixer", "gdn_scan"}),
    ("beta_left_out_of_the_write", {"logits", "gdn_mixer", "gdn_scan"}),
    ("gate_before_the_norm", {"logits", "gdn_mixer"}),
    ("rotation_on_the_whole_head", {"logits"}),
    ("sigmoid_routing", {"choices_off", "logits"}),
    ("shared_gate_left_out", {"logits"}),
])
def test_a_planted_defect_fails_the_forward_check(defect, limits):
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS[defect], with_step_grad=False))
    defects.say(defect, found)
    assert limits <= defects.failed(found), found
    if limits <= {"logits", "choices_off"}:
        # a defect outside the mixer leaves the rule's two checks alone
        assert not {"gdn_mixer", "gdn_scan"} & defects.failed(found)


@pytest.mark.parametrize("defect, limit", [
    ("half_of_the_batch", "first_moment"),
    ("step_routes_by_sigmoid", "first_moment"),
])
def test_a_planted_defect_of_the_step_fails_its_check(defect, limit):
    found = defects.readings(defects.cell_checks(5, defects.DEFECTS[defect]))
    defects.say(defect, found)
    assert limit in defects.failed(found), found
    # the forward pass is the program's own: the reference check passes
    assert not {"choices_off", "logits", "gdn_mixer", "gdn_scan",
                "loss"} & defects.failed(found)


def test_what_the_cells_checks_do_not_tell():
    """Written down as untold (PERF.md section 4): the inverse cut to five
    factors leaves out the powers 32 to 63 of a strictly lower 64 x 64
    matrix whose entries are a write strength times a decayed product of
    unit keys; here they round to nothing."""
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS["solve_cut_short"], with_step_grad=False))
    defects.say("solve_cut_short", found)
    assert not defects.failed(found)


def test_the_traced_line_has_every_metric_of_the_cell(capsys):
    """One step of the cell's own program as the v5e's profiler recorded it
    (measured on one v5e chip; cut by make_fixture.py with every operation's
    op_name), reduced with the record that run left: the costs the
    configuration counts from shapes are the record's, every per-layer
    metric declared for the cell is on the line, the new scopes are in the
    trace, no share of a roofline passes 100%, and the delta-rule mixers
    are the largest of the cell's scope metrics."""
    import run
    from test_run import chip_record, declared_for
    model, cfg = cell_config()
    rec = chip_record(CELL)
    assert json.loads(json.dumps(model.kernel_costs(cfg, 2))) == \
        rec["kernel_costs"]
    line = run.reduce(files.cell(CELL), rec, 1, False)
    assert "left out of the line" not in capsys.readouterr().out
    assert set(line["metrics"]) == declared_for(CELL) >= NEW_METRICS
    read = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(read[name] > 0 for name in NEW_METRICS)
    assert all(0 < v < 100 for k, v in read.items()
               if k.endswith("_roofline") or k == "mfu_pct")
    # the rule inside the mixer, the mixer inside the step
    assert read["gdn_scan_ms_per_step"] < read["gdn_mixer_ms_per_step"] \
        < read["fwd_ms_per_step"] + read["bwd_ms_per_step"]
    scoped = {"gdn_mixer_ms_per_step", "head_loss_ms_per_step",
              "recompute_ms_per_step", "rope_ms_per_step",
              "moe_routed_ms_per_step", "optimizer_ms_per_step"}
    assert max(scoped, key=read.get) == "gdn_mixer_ms_per_step"
    (dev,) = rec["traced"]["trace"]["devices"]
    for scope in ("gdn_mixer", "gdn_conv", "gdn_scan", "gdn_gate_norm",
                  "rope", "qk_norm", "attn_gate", "shared_expert"):
        assert any("/%s/" % scope in "/" + name.replace("(", "/").replace(
            ")", "/") + "/" for name in dev["scopes"]), scope
