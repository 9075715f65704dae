"""Operations per sample, from shapes, against numbers worked by hand."""

import dataclasses

import pytest

import files


@pytest.mark.parametrize("depth,gflop", [(4, 1.93), (5, 2.25), (6, 2.58)])
def test_lm_flops_per_token(depth, gflop):
    model = files.config_module("cerebras-gpt-1.3b")
    spec = files.load_json(files.config_path("cerebras-gpt-1.3b"))
    traffic = files.load_json(files.traffic_path("spmd-1chip-4x2048"))
    cfg = dataclasses.replace(
        model.transformer_config(spec, traffic, False), n_layers=depth)
    # by hand: 50.33 M matmul parameters a layer (4 * 2048^2 + 2 * 2048 *
    # 8192), 102.93 M in the tied head, 6 operations a parameter a token;
    # attention 6 * 2048 * 2048 = 25.17 M a layer a token (causal half)
    by_hand = 6 * (depth * 50.331648e6 + 102.926336e6) + depth * 25.165824e6
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    assert model.flops_per_sample(cfg) / 1e9 == pytest.approx(gflop, abs=5e-3)


def test_lm_attention_kernel_cost():
    model = files.config_module("cerebras-gpt-1.3b")
    spec = files.load_json(files.config_path("cerebras-gpt-1.3b"))
    traffic = files.load_json(files.traffic_path("spmd-1chip-4x2048"))
    cfg = model.transformer_config(spec, traffic, False)
    cost = model.kernel_costs(cfg, 4)["attn_kernel"]
    # 4 layers * 8192 tokens * 25.17 M; 12 passes over 4 * 2048 * 2048 bf16
    assert cost["flops"] == pytest.approx(4 * 8192 * 25.165824e6)
    assert cost["bytes"] == 4 * 12 * 4 * 2048 * 2048 * 2


def test_resnet_flops_per_image():
    model = files.config_module("resnet50-v1.5")
    spec = files.load_json(files.config_path("resnet50-v1.5"))
    shapes = model.conv_shapes(spec)
    assert len(shapes) == 53 + 1         # 53 convolutions and the classifier
    forward_macs = sum(p * k * ci * co for p, k, ci, co in shapes)
    assert forward_macs / 1e9 == pytest.approx(4.09, abs=0.01)   # v1.5
    assert model.flops_per_sample(spec) / 1e9 == pytest.approx(24.5, abs=0.1)
