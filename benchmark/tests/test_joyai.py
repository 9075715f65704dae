"""``joyai-llm-flash`` and its cell: the file against the catalog, the
operations counted from shapes against a count by hand, the cell's checks at
the rehearsal's widths on the CPU, and what they read of the planted defects
(``joyai_defects.py``). The program against the plain reference piece by
piece is ``tests/test_joyai_lm.py``; the traced line of the cell is read here
from one step its own program left on the v5e
(``data/joyai-spmd-1chip-ep32share-8k.scoped.1step.xplane.pb.gz``)."""

import json

import pytest

import files
import joyai_defects as defects

CONFIG, TRAFFIC = defects.CONFIG, defects.TRAFFIC
CELL = "joyai-spmd-1chip-ep32share-8k"
NEW_METRICS = {"mla_mixer_ms_per_step", "mla_latent_ms_per_step",
               "mla_attn_kernel_ms_per_step", "mla_attn_kernel_roofline",
               "mtp_ms_per_step", "ep32_moe_experts_roofline"}
PAIRS = 8192 * 8193 // 2        # visible (query, key) pairs of a causal row


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_three_cuts():
    spec = files.load_json(files.config_path(CONFIG))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    assert spec["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if spec[k] != v}
    cuts = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert changed == cuts == set(spec["reduced"])
    assert spec["published"] == {k: row["config"][k] for k in cuts}
    assert spec["router_outputs"] == row["config"]["n_routed_experts"]
    assert spec["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert spec["layers_taken"] == list(range(5))
    for key in ("assumed", "what_the_cut_distorts", "deployment",
                "memory_analysis", "rehearsal", "departures"):
        assert key in spec
    assert {"mtp_module", "mtp_hidden_state", "mtp_halves_order",
            "mtp_weight", "router_bias_rate", "route_eps", "norm_placement",
            "initialisation", "optimizer", "sequence_and_tokens",
            "settled_start", "routers_on_a_share"} <= set(spec["assumed"])
    declared = next(c for c in files.benchmark_json()["configs"]
                    if c["name"] == CONFIG)
    assert set(declared["reduced"]) == cuts
    assert declared["source"] == row["source_url"]
    cell = files.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    # no width in the cut, nor in the rehearsal's shadow of it
    assert not {k for k in cuts if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"}


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
    assert len(cells) == 8 and [c["chips"] for c in cells].count(4) == 1


def test_the_cut_is_the_dense_layer_four_expert_layers_and_the_module():
    _, cfg = cell_config()
    assert [(k.mixer, k.experts) for k in cfg.layers] == [
        ("mla", False)] + [("mla", True)] * 4
    assert (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.d_ff) == (
        2048, 32, 1536, 512, 128, 64, 128, 7168)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_ff_expert,
            cfg.shared_width, cfg.expert_ffn, cfg.route_scale) == (
        256, 8, 8, 768, 768, "swiglu", 2.5)
    assert cfg.vocab_size == 16160 == 129280 // 8
    assert (cfg.mtp_depth, cfg.mtp_weight, cfg.rope_theta, cfg.route_eps,
            cfg.norm, cfg.tie_embeddings, cfg.positions) == (
        1, 0.1, 32e6, 1e-20, "pre", False, "rope")
    assert cfg.remat == "block" and cfg.remat_barrier


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # a layer's latent attention 26.345 M matmul parameters (3.146 + 9.437 +
    # 1.180 + 4.194 + 8.389), six of them with the module's block; the dense
    # FFN 44.040 M; an expert layer's router 0.524 M, the shared expert 4.719
    # M and 8 * 8 / 256 of a held expert's 4.719 M, five of them; the module's
    # proj 8.389 M; the untied head 33.096 M TWICE; attention 2 x 32 x (192 +
    # 128) operations a visible pair, six layers
    assert model.latent_params(cfg) == 26_345_472
    by_hand = 6 * (6 * 26.345472e6 + 44.040192e6 + 5 * (
        0.524288e6 + 4.718592e6 + 0.25 * 4.718592e6) + 8.388608e6
        + 2 * 33.095680e6) + 3 * 20480 * 6 * PAIRS / 8192
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    # 1,120 M operations a token forward, the attention cores 503 M of them
    assert model.flops_per_sample(cfg) / 3e9 == pytest.approx(1.121, abs=1e-3)
    assert 20480 * 6 * PAIRS / 8192 / 1e6 == pytest.approx(503.4, abs=0.1)


def test_kernel_costs_by_hand():
    model, cfg = cell_config()
    costs = model.kernel_costs(cfg, 2)
    # 2.06 T operations a row and layer; least 125.6 ms a step at 197 TFLOP/s
    assert costs["mla_attn"]["flops"] == 2 * 3 * 20480 * 6 * PAIRS
    assert 3 * 20480 * PAIRS / 1e12 == pytest.approx(2.062, abs=1e-3)
    assert costs["mla_attn"]["flops"] / 197e12 * 1e3 == pytest.approx(
        125.6, abs=0.1)
    # q, k at 192 and v, o at 128, three passes each, bfloat16
    assert costs["mla_attn"]["bytes"] == 6 * 3 * 2 * 8192 * 32 * 2 * (
        192 + 192 + 128 + 128)
    # 4,096 held assignments a layer under an even router, five expert layers
    assert costs["ep32_moe_experts"]["flops"] == pytest.approx(
        5 * 3 * 3 * 2 * 2048 * 768 * 4096)
    assert costs["ep32_moe_experts"]["flops"] / 197e12 * 1e3 == \
        pytest.approx(2.94, abs=0.01)
    assert costs["ep32_moe_experts"]["bytes"] == pytest.approx(
        5 * 2 * (3 * 8 * 3 * 2048 * 768 + 3 * 4096 * (2 * 2048 + 3 * 768)))


def test_the_cells_checks_pass_at_the_rehearsals_widths():
    checks = defects.cell_checks(5)
    assert all(check["ok"] for check in checks.values()), checks
    err = checks["reference"]["error"]
    assert len(err["mla_mixer_by_layer"]) == 6
    assert len(err["choices_off_by_layer"]) == 5
    assert 0 < err["mla_mixer"] < 1e-5      # float32 on both sides
    assert 0 < err["mtp_logits"] < 1e-5
    leaves = checks["step_grad"]["error"]["by_leaf"]
    for leaf in ("['embed']", "['lm_head']", "['mtp']['proj']",
                 "['mtp']['enorm']", "['mtp']['wq_b']",
                 "['mla_layers']['wkv_a']"):
        assert 0 < leaves[leaf] < 1e-4


# what each planted defect must fail at the rehearsal's widths (float32, 256
# tokens); on the chip at the published widths: PERF.md section 4
@pytest.mark.parametrize("defect, limits", [
    ("float8", {"logits", "mtp_logits", "mla_mixer"}),
    ("scale_by_the_unrotated_width", {"logits", "mla_mixer"}),
    ("rotation_on_the_whole_head", {"logits", "mla_mixer"}),
    ("a_key_a_head", {"logits", "mla_mixer"}),
    ("kv_norm_left_out", {"logits", "mla_mixer"}),
    ("q_norm_left_out", {"logits", "mla_mixer"}),
    ("halves_without_the_permutation", {"logits", "mla_mixer"}),
    ("mtp_fed_the_same_token", {"mtp_logits", "loss_mtp"}),
    ("mtp_scores_the_next_token", {"loss_mtp"}),
    ("last_position_not_masked", {"loss_mtp"}),
])
def test_a_planted_defect_fails_the_forward_check(defect, limits):
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS[defect], with_step_grad=False, settled=False))
    defects.say(defect, found)
    assert limits <= defects.failed(found), found
    if defect.startswith(("mtp_", "last_")):
        # the module's defects leave the main head alone
        assert not {"logits", "loss_main", "mla_mixer"} & defects.failed(
            found)


@pytest.mark.parametrize("defect", ["mtp_scores_the_next_token",
                                    "mtp_weight_one"])
def test_a_planted_defect_of_the_gradient_fails_step_grad(defect):
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS[defect], settled=False))
    defects.say(defect, found)
    assert "first_moment" in defects.failed(found), found


@pytest.mark.parametrize("defect", ["embedding_from_the_main_term_alone",
                                    "mtp_after_the_final_norm"])
def test_what_the_cells_checks_do_not_tell(defect):
    """Written down as untold (PERF.md section 4): the readings move, by
    less than the limits the chip's rounding needs. With the seeded weights
    every norm's scale is 1, so a second RMSNorm in front of the module
    changes nothing but the gradient of that scale, at a tenth of the
    weight; the embedding's second look-up is a tenth of its gradient.
    ``tests/test_joyai_lm.py`` holds both on the CPU, with scales off 1."""
    clean = defects.readings(defects.cell_checks(5, settled=False))
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS[defect], settled=False))
    defects.say(defect, found)
    assert not defects.failed(found)
    assert found["first_moment"][0] > 100 * clean["first_moment"][0]


def test_the_traced_line_has_every_metric_of_the_cell(capsys):
    """One step of the cell's own program as the v5e's profiler recorded it
    (my chip run, PR 41; cut by make_fixture.py with every operation's
    op_name), reduced with the record that run left: the costs the
    configuration counts from shapes are the record's, every per-layer
    metric declared for the cell is on the line, the new scopes are in the
    trace, no share of a roofline passes 100%, and the new mixer is the
    largest of the cell's scope metrics."""
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
    # the kernel inside the mixer, the mixer the largest scope of the step
    assert read["mla_attn_kernel_ms_per_step"] \
        < read["mla_mixer_ms_per_step"] \
        < read["fwd_ms_per_step"] + read["bwd_ms_per_step"]
    scoped = {"mla_mixer_ms_per_step", "mla_latent_ms_per_step",
              "mtp_ms_per_step", "head_loss_ms_per_step",
              "recompute_ms_per_step", "rope_ms_per_step",
              "moe_routed_ms_per_step", "optimizer_ms_per_step"}
    assert max(scoped, key=read.get) == "mla_mixer_ms_per_step"
    assert read["mla_mixer_ms_per_step"] > 0.6 * (
        read["fwd_ms_per_step"] + read["bwd_ms_per_step"])
    (dev,) = rec["traced"]["trace"]["devices"]
    for scope in ("mla_mixer", "mla_q", "mla_kv", "attn_latent", "rope",
                  "mtp", "mtp_proj"):
        assert any("/%s/" % scope in "/" + name.replace("(", "/").replace(
            ")", "/") + "/" for name in dev["scopes"]), scope
    # the module's head and loss keep their names inside it
    assert any("(mtp)/head" in name for name in dev["scopes"])
    assert any("(mtp)/loss" in name for name in dev["scopes"])
