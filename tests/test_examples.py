"""Smoke tests for examples/ — run each example end-to-end (tiny settings) in
a subprocess, CI-style (reference: examples are exercised by the buildkite
pipeline, gen-pipeline.sh:163).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _run(args, timeout=420):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    })
    return subprocess.run([sys.executable] + args, env=env, timeout=timeout,
                          capture_output=True, text=True)


def test_mnist_mlp_example():
    r = _run([os.path.join(EXAMPLES, "mnist_mlp.py"), "--epochs", "1",
              "--batch-size", "512"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "loss=" in r.stdout, r.stdout


# Tier-1 budget (ISSUE 9 satellite): the two ResNet50 benchmark
# examples are big-bench subprocesses (78s + 45s measured) — the slow
# marker's other named category. The examples subsystem keeps mnist,
# transformer_lm x3, scaling, elastic and the tpurun CLI run in tier-1;
# ResNet training itself stays covered in-process
# (test_pallas_kernels.py::test_resnet_fused_bn_variant_trains).
@pytest.mark.slow
def test_resnet_benchmark_example_spmd():
    r = _run([os.path.join(EXAMPLES, "resnet50_synthetic_benchmark.py"),
              "--batch-size", "2", "--num-iters", "2", "--num-warmup", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Total img/sec" in r.stdout, r.stdout


@pytest.mark.slow
def test_resnet_benchmark_example_eager():
    r = _run([os.path.join(EXAMPLES, "resnet50_synthetic_benchmark.py"),
              "--mode", "eager", "--batch-size", "2", "--num-iters", "2",
              "--num-warmup", "2", "--fp16-allreduce"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "Img/sec per worker" in r.stdout, r.stdout


def test_elastic_example_single_process():
    r = _run([os.path.join(EXAMPLES, "elastic_synthetic.py"),
              "--total-batches", "20", "--batch-size", "16"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done:" in r.stdout, r.stdout


def test_scaling_benchmark_example():
    r = _run([os.path.join(EXAMPLES, "scaling_benchmark.py"),
              "--sizes", "1,2", "--bytes", "1048576", "--iters", "2",
              "--batch-per-chip", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.splitlines() if l.startswith("{")]
    assert len(lines) == 4, r.stdout
    import json
    recs = [json.loads(l) for l in lines]
    assert {rec["bench"] for rec in recs} == {"allreduce",
                                             "weak_scaling_train"}


@pytest.mark.integration
def test_mnist_under_tpurun_cli():
    """Genuine CLI end-to-end: `tpurun -np 2 python examples/mnist_mlp.py`
    (the reference's keystone `horovodrun -np 2` pattern, SURVEY §4)."""
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
    })
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, os.path.join(EXAMPLES, "mnist_mlp.py"),
         "--epochs", "1", "--batch-size", "1024"],
        env=env, timeout=420, capture_output=True, text=True)
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-2000:])
    assert "size=2" in r.stdout, r.stdout


def test_api_docs_in_sync(tmp_path):
    """docs/api.md must match what tools/gen_api_docs.py generates (the
    docstring-driven reference the README links)."""
    import shutil
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    committed = os.path.join(repo, "docs", "api.md")
    with open(committed) as f:
        before = f.read()
    # run the generator against a scratch copy of the repo's docs dir
    work = tmp_path / "repo"
    work.mkdir()
    (work / "docs").mkdir()
    # the generator writes relative to its own location's parent/docs
    (work / "tools").mkdir()
    shutil.copy(os.path.join(repo, "tools", "gen_api_docs.py"),
                work / "tools" / "gen_api_docs.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run([sys.executable, str(work / "tools" / "gen_api_docs.py")],
                   check=True, env=env, capture_output=True)
    with open(work / "docs" / "api.md") as f:
        regenerated = f.read()
    assert regenerated == before, \
        "docs/api.md is stale — run python tools/gen_api_docs.py"


@pytest.mark.parametrize("attention", ["ring", "flash"])
def test_transformer_lm_example_spmd(attention):
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mesh", "data=2", "--d-model", "32", "--n-layers", "1",
              "--n-heads", "4", "--d-ff", "64", "--vocab", "128",
              "--seq", "32", "--batch", "4", "--steps", "2",
              "--attention", attention])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tokens_per_sec" in r.stdout, r.stdout
    # the layer's 8,256 of the 12,384 gradient values are summed inside
    # the backward scan (ln1, ln2, four d x d, two d x d_ff; embed, ln_f)
    assert "'grad_reduce_in_backward_share': 0.6667" in r.stdout, r.stdout
    # which kernel the local attention call takes is said where that call
    # is flash_attention_local's: off the TPU, none
    assert ("'attn_kernel': {'kernel': 'materialized'" in r.stdout) == (
        attention == "flash"), r.stdout


def test_transformer_lm_example_conv_pattern():
    """A conv/attention sparse-expert decoder from the example's flags (the
    kind of benchmark/configs/lfm2-8b-a1b.json): one dense conv layer, then
    attention, conv, conv, conv with routed experts of which a half is held,
    over data=2; the layers by kind of mixer and the routing are reported
    with the loss."""
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mesh", "data=2", "--d-model", "32", "--n-layers", "5",
              "--n-heads", "4", "--kv-heads", "2", "--qk-norm", "--d-ff",
              "48", "--vocab", "128", "--seq", "32", "--batch", "4",
              "--steps", "2", "--attention", "flash", "--positions", "rope",
              "--rope-theta", "1e6", "--ffn", "swiglu", "--norm-eps", "1e-5",
              "--remat", "block", "--pattern", "caccc", "--dense-layers",
              "1", "--experts", "8", "--top-k", "2", "--expert-ff", "16",
              "--experts-held", "4", "--router-bias-rate", "0.001"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "'layers_by_mixer': {'attention': 1, 'conv': 4}" in r.stdout, \
        r.stdout
    assert "'dropped': 0.0" in r.stdout and "held_share" in r.stdout
    # (64 live rows: under a chunk, so the row sums are chunks)
    assert "'row_sum_form': 'chunks'" in r.stdout, r.stdout
    assert "'head_size': '8'" in r.stdout, r.stdout


def test_transformer_lm_example_state_space_pattern():
    """A state-space/attention sparse-expert decoder of layers of ONE
    sublayer from the example's flags (the kind of
    benchmark/configs/nemotron-3-nano-30b-a3b.json): the layers by kind of
    mixer and the form of the scan (off the TPU the jax.numpy one) are
    reported with the loss."""
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mesh", "data=2", "--d-model", "32", "--n-layers", "7",
              "--n-heads", "4", "--kv-heads", "2", "--vocab", "128",
              "--seq", "32", "--batch", "4", "--steps", "2", "--attention",
              "flash", "--positions", "none", "--ffn", "swiglu",
              "--norm-eps", "1e-5", "--remat", "block", "--untied-head",
              "--pattern", "MEMEM*E", "--ssm-heads", "4", "--ssm-head-dim",
              "8", "--ssm-state", "16", "--ssm-groups", "2", "--ssm-chunk",
              "16", "--experts", "8", "--top-k", "2", "--expert-ff", "16",
              "--expert-ffn", "relu2", "--shared-experts", "1",
              "--shared-ff", "32", "--experts-held", "4",
              "--router-bias-rate", "0.001"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "'layers_by_mixer': {'attention': 1, 'mamba2': 3, 'none': 3}" \
        in r.stdout, r.stdout
    assert ("'scan_kernel': {'form': 'chunked', 'chunk': '16', "
            "'heads_per_block': '0'}") in r.stdout, r.stdout


def test_transformer_lm_example_looped():
    """The looped decoder from the example's flags: RoPE, SwiGLU, sandwich
    norms, an untied head and four passes under remat, the sequence split
    over the two devices _run gives; the exit gate's share of every pass
    is reported with the loss."""
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mesh", "seq=2", "--d-model", "32",
              "--n-layers", "2", "--n-heads", "4", "--d-ff", "48",
              "--vocab", "128", "--seq", "32", "--batch", "4", "--steps",
              "2", "--positions", "rope", "--rope-theta", "1e6", "--ffn",
              "swiglu", "--norm", "sandwich", "--untied-head", "--n-loops",
              "4", "--remat", "block"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tokens_per_sec" in r.stdout and "exit_share" in r.stdout
    # shared layers are summed once, after the pass loop
    assert "'grad_reduce_in_backward_share': 0.0" in r.stdout, r.stdout


def test_transformer_lm_example_pp_refuses_the_loop_by_name():
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mode", "pp", "--stages", "2", "--d-model", "32",
              "--n-layers", "2", "--n-heads", "4", "--d-ff", "64",
              "--vocab", "128", "--seq", "32", "--batch", "4", "--steps",
              "1", "--n-loops", "4"])
    assert r.returncode != 0
    assert "make_pp_train_step" in r.stderr and "n_loops=4" in r.stderr


def test_transformer_lm_example_eager():
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mode", "eager", "--d-model", "32", "--n-layers", "1",
              "--n-heads", "4", "--d-ff", "64", "--vocab", "128",
              "--seq", "32", "--batch", "4", "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tokens_per_sec" in r.stdout, r.stdout


def test_transformer_lm_example_pp():
    r = _run([os.path.join(EXAMPLES, "transformer_lm.py"),
              "--mode", "pp", "--stages", "2", "--n-micro", "4",
              "--d-model", "32", "--n-layers", "2",
              "--n-heads", "4", "--d-ff", "64", "--vocab", "128",
              "--seq", "32", "--batch", "4", "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "tokens_per_sec" in r.stdout, r.stdout


def test_sparse_embedding_example():
    r = _run([os.path.join(EXAMPLES, "sparse_embedding.py"),
              "--steps", "10", "--vocab", "5000"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sparse" in r.stdout and "saved" in r.stdout, r.stdout
