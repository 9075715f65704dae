"""``nemotron-3-nano-30b-a3b`` and its cell: the file against the catalog, the
operations counted from shapes against a count by hand, the cell's checks at
the rehearsal's widths on the CPU, and what they read of the planted
defects (``nemotron3_defects.py``). The program against the plain reference
piece by piece is ``tests/test_nemotron_lm.py``; the traced line of the cell
is read here from one step its own program left on the v5e
(``data/nemotron3-spmd-1chip-ep16share-8k.scoped.1step.xplane.pb.gz``)."""

import json

import jax.numpy as jnp
import pytest

import files
import nemotron3_defects as defects

CONFIG, TRAFFIC = defects.CONFIG, defects.TRAFFIC
CELL = "nemotron3-spmd-1chip-ep16share-8k"
NEW_METRICS = {"mamba_mixer_ms_per_step", "ssm_scan_ms_per_step",
               "ssm_scan_roofline", "relu2_moe_experts_roofline",
               "kv2_attn_kernel_ms_per_step", "kv2_attn_kernel_roofline"}


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_three_cuts():
    spec = files.load_json(files.config_path(CONFIG))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert spec["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if spec[k] != v}
    cuts = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert changed == cuts == set(spec["reduced"])
    assert spec["published"] == {k: row["config"][k] for k in cuts}
    assert spec["router_outputs"] == row["config"]["n_routed_experts"]
    assert spec["layers_taken"] == list(range(7))
    for key in ("assumed", "what_the_cut_distorts", "deployment",
                "memory_analysis", "rehearsal", "departures"):
        assert key in spec
    assert {"inner_width", "no_rotation", "router_bias_rate", "weight_decay",
            "route_eps", "mamba_mixer"} <= set(spec["assumed"])
    declared = next(c for c in files.benchmark_json()["configs"]
                    if c["name"] == CONFIG)
    assert set(declared["reduced"]) == cuts
    assert declared["source"] == row["source_url"]
    cell = files.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    # no width in the cut
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
                 "recompute_ms_per_step", "moe_routed_ms_per_step"):
        assert per_layer[name]["workloads"][-1] == CELL
    assert CELL not in per_layer["rope_ms_per_step"]["workloads"]


def test_the_cut_is_the_first_seven_letters_of_the_published_pattern():
    _, cfg = cell_config()
    assert "".join({"mamba2": "M", "none": "E", "attention": "*"}[k.mixer]
                   for k in cfg.layers) == "MEMEM*E"
    assert all((k.experts, k.rope, k.window) == (
        True if k.mixer == "none" else None, False, 0) for k in cfg.layers)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (
        2688, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_chunk, cfg.conv_kernel) == (64, 64, 128, 8, 128, 4)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k, cfg.d_ff_expert,
            cfg.shared_width, cfg.expert_ffn, cfg.route_scale) == (
        128, 8, 6, 1856, 3712, "relu2", 2.5)
    assert cfg.vocab_size == 16384 == 131072 // 8
    assert (cfg.route_eps, cfg.norm, cfg.tie_embeddings, cfg.positions) == (
        1e-20, "pre", False, "none")
    assert cfg.remat == "block" and cfg.remat_barrier


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # a state-space layer 38.707 M matmul parameters (2688 x 10304 and 4096
    # x 2688) and the scan's 3.408 M operations a token forward (C B^T once
    # a group 0.262 M, three products a head 3.146 M); the attention layer
    # 23.396 M; an expert layer's router 0.344 M, the shared expert 19.956
    # M and 6 * 8 / 128 of a held expert's 9.978 M; the untied head 44.040
    # M; visible pairs 33,558,528, 12 * 4096 operations a pair
    scan = 8 * 2 * 128 * 128 + 64 * (2 * 128 * 64 + 4 * 64 * 128)
    assert model.scan_flops_per_token(cfg) == scan == 3_407_872
    by_hand = 6 * (3 * 38.707200e6 + 23.396352e6 + 3 * (
        0.344064e6 + 19.955712e6 + 0.375 * 9.977856e6) + 44.040192e6) \
        + 3 * 3 * scan + 12 * 4096 * 33558528 / 8192
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    assert model.flops_per_sample(cfg) / 1e9 == pytest.approx(1.766, abs=1e-3)


def test_kernel_costs_by_hand():
    model, cfg = cell_config()
    costs = model.kernel_costs(cfg, 2)
    assert costs["ssm_scan"]["flops"] == 3 * 3 * 2 * 8192 * 3_407_872
    # X, B, C in bf16, dt in fp32, y in fp32 a token; 64 chunk states of 64
    # x 64 x 128 fp32 written and read; three times for both passes
    assert costs["ssm_scan"]["bytes"] == 3 * 3 * 2 * (
        8192 * (8192 + 4096 + 256 + 16384) + 2 * 4 * 64 * 4096 * 128)
    assert costs["relu2_moe_experts"]["flops"] == pytest.approx(
        3 * 2 * 3 * 2 * 2688 * 1856 * 6144)
    assert costs["relu2_moe_experts"]["bytes"] == pytest.approx(
        3 * 2 * (3 * 8 * 2 * 2688 * 1856 + 3 * 6144 * 2 * (2688 + 1856)))
    assert costs["kv2_attn"]["flops"] == pytest.approx(
        2 * 12 * 4096 * 33558528)
    assert costs["kv2_attn"]["bytes"] == 6 * 2 * 8192 * (4096 + 256) * 2


def test_the_cells_checks_pass_at_the_rehearsals_widths():
    checks = defects.cell_checks(5)
    assert all(check["ok"] for check in checks.values()), checks
    err = checks["reference"]["error"]
    assert len(err["ssm_mixer_by_layer"]) == 3
    assert 0 < err["ssm_mixer"] < 1e-5      # float32 on both sides
    assert len(err["ssm_scan_by_layer"]) == 3
    assert 0 < err["ssm_scan"] < 1e-5
    leaves = checks["step_grad"]["error"]["by_leaf"]
    for leaf in ("ssm_A_log", "ssm_dt_bias", "ssm_D", "ssm_conv_b"):
        assert 0 < leaves["['mamba_mixers']['%s']" % leaf] < 1e-4


@pytest.mark.parametrize("defect, limits", [
    ("float8", {"logits", "ssm_mixer", "ssm_scan", "first_moment"}),
    ("dt_without_softplus", {"ssm_mixer"}),
    ("decay_on_the_input_too", {"ssm_mixer", "ssm_scan"}),
    ("heads_to_groups_by_remainder", {"ssm_mixer", "ssm_scan"}),
    ("relu_without_the_square", {"logits"}),
    ("shared_expert_at_the_routed_width", {"logits"}),
    ("skip_left_out", {"ssm_mixer", "ssm_scan"}),
    ("norm_before_the_gate", {"ssm_mixer"}),
    ("conv_bias_left_out", {"ssm_mixer"}),
    ("rotation_switched_on", {"logits"}),
    # a leaf whose gradient is missing on one side
    ("A_log_dropped", {"first_moment"}),
    ("dt_bias_dropped", {"first_moment"}),
    ("conv_b_dropped", {"first_moment"}),
    # a step that summed one of its two rows, and one that left adamw's
    # state as init made it
    ("half_of_the_batch", {"first_moment"}),
    ("state_unchanged", {"first_moment"}),
    # IN THE PROGRAM: the step routes with no selection bias where the
    # forward-only program routes by the settled one
    ("step_routes_without_the_bias", {"flips_step_vs_forward"}),
])
def test_a_planted_defect_fails_the_cells_checks(defect, limits):
    found = defects.readings(defects.cell_checks(
        5, defects.DEFECTS[defect]))
    defects.say(defect, found)
    assert limits <= defects.failed(found), found


# the scan at the published chunk, state and head size in bfloat16, 8 heads
# of one group over 512 tokens: what the CPU can run of the chip's program
PUBLISHED_SCAN = dict(ssm_chunk=128, ssm_state=128, ssm_head_dim=64,
                      ssm_heads=8, ssm_groups=1, max_seq=512,
                      dtype=jnp.bfloat16)


def test_sums_kept_in_bfloat16_fail_the_scans_own_limit():
    """The stated precision is bfloat16 operands summed in float32. At the
    published chunk and state (contractions of 128) the program passes
    ``ssm_scan``, and the same products with a running sum kept in
    bfloat16 do not: the check comes out not correct. ``ssm_mixer``, the
    whole mixer against the float32 recurrence, has the operands' own
    rounding in it and moves far less."""
    sound = defects.scan_readings(5, **PUBLISHED_SCAN)
    defects.say("none", sound)
    summed = defects.scan_readings(5, defects.DEFECTS["sums_in_bfloat16"],
                                   **PUBLISHED_SCAN)
    defects.say("sums_in_bfloat16", summed)
    assert not defects.failed(sound)
    assert "ssm_scan" in defects.failed(summed)
    assert summed["ssm_scan"][0] > 3 * sound["ssm_scan"][0]
    assert summed["ssm_mixer"][0] / sound["ssm_mixer"][0] \
        < summed["ssm_scan"][0] / sound["ssm_scan"][0]


@pytest.mark.parametrize("defect", ["products_return_bfloat16",
                                    "sums_in_bfloat16"])
def test_the_scans_products_in_bfloat16_fail_the_rehearsals_checks(defect):
    """Planted in the PROGRAM: at the rehearsal's widths the program
    computes in float32 and reads 5e-8 in ``ssm_scan``; with the scan's
    four products in bfloat16 it reads ten thousand times that, and the
    defect is taken off again."""
    found = defects.readings(defects.cell_checks(5, defects.DEFECTS[defect]))
    defects.say(defect, found)
    assert found["ssm_scan"][0] > 1e-4
    from horovod_tpu.parallel import ssd
    assert ssd.jnp is jnp


def test_the_traced_line_has_every_metric_of_the_cell(capsys):
    """One step of the cell's own program as the v5e's profiler recorded it
    (my chip run, PR 39; cut by make_fixture.py with every operation's
    op_name), reduced with the record that run left: the costs the
    configuration counts from shapes are the record's, every per-layer
    metric declared for the cell is on the line, the four new scopes are
    in the trace, and no share of a roofline passes 100%."""
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
    assert all(0 < v <= 100 for k, v in read.items()
               if k.endswith("_roofline") or k == "mfu_pct")
    # the scan inside the mixer, the mixer inside the step
    assert read["ssm_scan_ms_per_step"] < read["mamba_mixer_ms_per_step"] \
        < read["fwd_ms_per_step"] + read["bwd_ms_per_step"]
    assert read["ssm_scan_ms_per_step"] == pytest.approx(90.5, abs=1.0)
    assert read["ssm_scan_roofline"] == pytest.approx(12.3, abs=0.3)
    assert read["kv2_attn_kernel_roofline"] == pytest.approx(47.6, abs=0.5)
    (dev,) = rec["traced"]["trace"]["devices"]
    for scope in ("mamba_mixer", "ssm_conv", "ssm_scan", "ssm_gate_norm"):
        assert any("/%s/" % scope in name + "/" for name in dev["scopes"])
    assert not any("rope" in name for name in dev["scopes"])
