"""``ouro-2.6b`` and its cell: the operations counted from shapes against a
count by hand and against XLA's own, the plain reference against the
program in float32, and what the cell's two checks read of the program and
of four wrong models on the CPU at the rehearsal's widths (the counts that
``configs/ouro-2.6b.py`` TOLERANCE and ``ouro-2.6b.spmd.py`` GRAD_TOLERANCE
quote). The rehearsal of the cell itself is a case of ``test_run.py``."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

import files
import ouro_defects
from horovod_tpu.common import scopes as program
from horovod_tpu.models import transformer

CONFIG, TRAFFIC = ouro_defects.CONFIG, ouro_defects.TRAFFIC


def cell_config(rehearse=False):
    model = files.config_module(CONFIG)
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    return model, model.transformer_config(spec, traffic, rehearse)


def test_the_file_keeps_every_published_number_but_the_depth():
    import json
    spec = files.load_json(files.config_path(CONFIG))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert spec["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if spec[k] != v}
    assert changed == {"num_hidden_layers"} == set(spec["reduced"])
    assert spec["published"] == {"num_hidden_layers": 48}
    assert spec["total_ut_steps"] == 4 and spec["num_hidden_layers"] == 6
    for key in ("assumed", "what_the_cut_distorts", "deployment",
                "memory_analysis", "rehearsal", "departures"):
        assert key in spec


def test_flops_per_token_by_hand():
    model, cfg = cell_config()
    # 51.38 M matmul parameters a layer application (4 * 2048^2 + 3 * 2048
    # * 5632), 24 of them; 100.66 M in each of four heads; attention 6 *
    # 4096 * 2048 = 50.33 M a layer application a token (causal half)
    by_hand = 6 * (24 * 51.380224e6 + 4 * 100.663296e6) + 24 * 50.331648e6
    assert model.flops_per_sample(cfg) == pytest.approx(by_hand, rel=1e-9)
    assert model.flops_per_sample(cfg) / 1e9 == pytest.approx(11.02, abs=5e-3)
    cost = model.kernel_costs(cfg, 1)["attn_kernel"]
    assert cost["flops"] == pytest.approx(24 * 4096 * 50.331648e6)
    assert cost["bytes"] == 24 * 12 * 4096 * 2048 * 2


@pytest.mark.parametrize("depth", [1, 2])
def test_flops_against_xlas_own_count(depth):
    """XLA's cost analysis of the plain reference's loss, forward and
    backward, compiled (not run) on the CPU at the published widths and one
    row of 4,096 tokens. The reference is unrolled (XLA counts a loop's
    body once, so the program's scans cannot be asked) and its attention
    covers the whole square, so the causal half is doubled here. XLA reads
    0.3% more: norms, RoPE, softmax, the loss."""
    model, cfg = cell_config()
    cfg = dataclasses.replace(cfg, n_layers=depth)
    reference = files.reference_module(CONFIG)
    params = jax.eval_shape(
        lambda: transformer.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, cfg.max_seq), jnp.int32)
    cost = jax.jit(lambda p, x, y: reference.grads(
        model.to_reference(p), x, y, cfg.n_loops)).lower(
        params, tok, tok).compile().cost_analysis()
    attention = (cfg.n_loops * cfg.n_layers * 3
                 * (4 * cfg.max_seq * cfg.d_model) / 2)
    ours = (model.flops_per_sample(cfg) + attention) * cfg.max_seq
    assert ours < cost["flops"] < 1.01 * ours


def test_the_program_in_float32_is_the_reference():
    model, cfg = cell_config(rehearse=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = model.make_params(cfg, 3)
    check = model.reference_check(
        cfg, params, files.reference_module(CONFIG), 3,
        jax.jit(lambda p, x, y: transformer.lean_lm_loss(p, x, y, cfg)))
    assert check["ok"], check
    assert check["error"]["logits"] < 1e-4
    assert check["error"]["exit_p"] < 1e-5 and check["error"]["loss"] < 1e-5


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_program_passes(seed):
    _, _, checks = ouro_defects.cell_checks(seed)
    ouro_defects.say(f"program, seed {seed}", ouro_defects.readings(checks))
    assert checks["reference"]["ok"] and checks["loop_grad"]["ok"], checks


@pytest.mark.parametrize("name", list(ouro_defects.DEFECTS))
def test_what_a_wrong_model_reads(name):
    worst = []
    for seed in (1, 2, 3):
        _, _, checks = ouro_defects.cell_checks(
            seed, ouro_defects.DEFECTS[name])
        found = ouro_defects.readings(checks)
        ouro_defects.say(f"{name}, seed {seed}", found)
        assert not (checks["reference"]["ok"] and checks["loop_grad"]["ok"])
        worst.append(max(v / b for v, b in found.values()))
    assert min(worst) > 3


LOOPED = "jit(train_step)/transpose(jvp())/loop/while/body/closed_call/"


@pytest.mark.parametrize("name,scope_names,reads,leaves", [
    ("head_loss_ms_per_step", (program.HEAD, program.LOSS),
     [LOOPED + "checkpoint/rematted_computation/head/btd,vd->btv/dot_general",
      LOOPED + "checkpoint/loss/exp"],
     [LOOPED + "layers/while/body/closed_call/checkpoint/attn/header/mul",
      # 0.13 ms of the gate (PR 28) is not the exits' heads and losses
      "jit(train_step)/jvp()/loop/while/body/closed_call/exit_gate/mul"]),
    ("rope_ms_per_step", (program.ROPE,),
     [LOOPED + "layers/while/body/closed_call/checkpoint/attn/rope/mul",
      "jit(train_step)/jvp()/rope/cos"],
     [LOOPED + "layers/while/body/closed_call/checkpoint/attn/ropes/mul"]),
    ("recompute_ms_per_step", (),
     [LOOPED + "checkpoint/rematted_computation/head/btd,vd->btv/dot_general"],
     [LOOPED + "checkpoint/head/btd,vd->btv/dot_general"]),
])
def test_the_metric_files_read_the_loops_scopes(name, scope_names, reads, leaves):
    """The files that read the looped model's scopes (PR 28; declared for
    its cell since PR 36, the exits under ``head_loss_ms_per_step``): they
    quote the program's scope names and read the op_names jax writes under
    the loop and under remat."""
    spec, _ = files.layer_metric(name)
    assert spec["reader"] == "trace_scopes" and spec["doc"]
    (pattern,) = spec["match"]
    for scope in scope_names:
        assert scope in pattern
    for op_name in reads:
        assert re.search(pattern, op_name), op_name
    for op_name in leaves:
        assert not re.search(pattern, op_name), op_name
