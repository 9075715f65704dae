"""``trinity-mini`` and its cell: the file against the catalog, the
operations counted from shapes against a count by hand, and what the cell's
checks read of three wrong models and two dropped leaves on the CPU at the rehearsal's widths. The
program against the plain reference in float32, piece by piece, is
``tests/test_trinity_lm.py``; the rehearsal of the cell itself is a case of
``test_run.py``."""

import json

import pytest

import files
import trinity_defects

CONFIG, TRAFFIC = trinity_defects.CONFIG, trinity_defects.TRAFFIC


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_four_cuts():
    spec = files.load_json(files.config_path(CONFIG))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Mini")
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
    declared = next(c for c in files.benchmark_json()["configs"]
                    if c["name"] == CONFIG)
    assert set(declared["reduced"]) == cuts


def test_the_cut_is_the_first_five_layers_of_the_published_list():
    _, cfg = cell_config()
    assert [(k.window, k.rope, k.experts) for k in cfg.layers] == [
        (2048, True, False), (2048, True, True), (2048, True, True),
        (0, False, True), (2048, True, True)]
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.n_experts, cfg.held, cfg.moe_top_k) == (128, 8, 8)
    assert cfg.vocab_size == 25024 == 200192 // 8


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # attention 27.26 M matmul parameters a layer (q, gate, o 8.39 M each,
    # k and v 1.05 M each); the dense FFN 37.75 M; an expert layer's router
    # 0.26 M, shared expert 6.29 M and 8 * 8 / 128 = half a held expert a
    # token, 3.15 M; the head 51.25 M; visible pairs 14,681,088 a window
    # layer and 33,558,528 the full one, 12 * 4096 operations a pair
    by_hand = 6 * (5 * 27.262976e6 + 37.748736e6 + 4 * (
        0.262144e6 + 6.291456e6 + 0.5 * 6.291456e6) + 51.249152e6) \
        + 12 * 4096 * (4 * 14681088 + 33558528) / 8192
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    assert model.flops_per_sample(cfg) / 1e9 == pytest.approx(2.138, abs=1e-3)
    costs = model.kernel_costs(cfg, 1)
    assert costs["attn_kernel"]["flops"] == pytest.approx(
        12 * 4096 * (4 * 14681088 + 33558528))
    assert costs["attn_kernel"]["bytes"] == 5 * 6 * 8192 * (4096 + 512) * 2
    assert costs["moe_experts"]["flops"] == pytest.approx(
        4 * 9 * 2 * 2048 * 1024 * 4096)


@pytest.mark.parametrize("defect, limits", [
    ("float8", {"logits", "first_moment"}),
    # (hardly a token keeps all its choices, so ``logits`` has none to read)
    ("no_window", {"choices_off", "loss", "first_moment"}),
    ("rope_everywhere", {"logits", "first_moment"}),
    # a leaf whose gradient is missing on one side
    ("q_norm_dropped", {"first_moment"}),
    ("experts_dropped", {"first_moment"}),
])
def test_a_wrong_model_fails_the_cells_checks(defect, limits):
    found = trinity_defects.readings(trinity_defects.cell_checks(
        5, trinity_defects.DEFECTS[defect])[2])
    trinity_defects.say(defect, found)
    failed = {k for k, (v, band) in found.items() if v > band}
    assert limits <= failed, found
