"""The SPMD step compiled HERE for a described TPU v5e:2x2 (no chip
attached, nothing runs): what libtpu's own compiler makes of the step's
gradient all-reduces with the options ``make_train_step`` hands it, and
whether the attention kernels fit as ``parallel/flash_attention.py`` builds
them (an over-large block fails the compile: ``RESOURCE_EXHAUSTED ...
memory space vmem``).

The compiler is the installed libtpu's, so a wrong option name fails these
tests loudly, and an upgrade that stops overlapping shows here before a
chip is asked. Every call that loads libtpu sits in a fixture of this one
file (one xdist worker loads it; see the on-chip-measurement guide): never
at import, never in a second test file.
"""

import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models import transformer as tfm

# lm-spmd-4chip-dp's own program but for its depth (2 of the cell's 4
# layers): the widths of benchmark/configs/cerebras-gpt-1.3b.json, bf16
# compute, the splash kernels, 4 rows of 2048 tokens a chip, adamw
CFG = tfm.TransformerConfig(vocab_size=50257, d_model=2048, n_heads=16,
                            n_layers=2, d_ff=8192, max_seq=2048,
                            dtype=jnp.bfloat16, attention="flash")
ROWS_PER_CHIP = 4
# ouro-spmd-1chip-loop4's own program but for its depth (2 of the cell's 6
# layers): the widths of benchmark/configs/ouro-2.6b.json, four passes, one
# row of 4096 tokens under remat="block"
LOOPED = tfm.TransformerConfig(
    vocab_size=49152, d_model=2048, n_heads=16, n_layers=2, d_ff=5632,
    max_seq=4096, dtype=jnp.bfloat16, attention="flash", remat="block",
    positions="rope", rope_theta=1e6, ffn="swiglu", norm="sandwich",
    norm_eps=1e-6, tie_embeddings=False, n_loops=4)
# trinity-spmd-1chip-ep8share-8k's own program but for its depth (1 dense
# + 2 of the cell's 4 expert layers, a window layer and the full one): the
# widths of benchmark/configs/trinity-mini.json, 32 query heads over 4 KV
# heads of 128, window 2048, 8 of 128 experts held, top 8, one row of 8192
# tokens under remat="block"
_KIND = tfm.LayerKind
SPARSE = tfm.TransformerConfig(
    vocab_size=25024, d_model=2048, n_heads=32, n_kv_heads=4, head_size=128,
    n_layers=3, d_ff=6144, max_seq=8192, dtype=jnp.bfloat16,
    attention="flash", remat="block", positions="rope", rope_theta=1e4,
    ffn="swiglu", norm="sandwich", norm_eps=1e-5, tie_embeddings=False,
    qk_norm=True, attn_gate=True, embed_scale=2048 ** 0.5,
    layers=(_KIND(2048, True, False), _KIND(2048, True, True),
            _KIND(0, False, True)),
    n_experts=128, moe_top_k=8, d_ff_expert=1024, n_shared_experts=1,
    route_scale=2.826, experts_held=8, router_bias_rate=1e-3)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def pallas_branch(monkeypatch):
    """Attention asks ``jax.default_backend()`` whether to take its Pallas
    kernels; the process is held to the CPU, the compile is for the TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compiled_step(topo, shape, cfg, rows_per_chip=ROWS_PER_CHIP):
    """Scheduled HLO text of ``make_train_step`` over the described chips."""
    mesh = Mesh(np.array(topo.devices[:int(np.prod(shape))]).reshape(shape),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    opt = optax.adamw(3e-4)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))

    def on_mesh(tree):
        return jax.tree_util.tree_map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, tfm.param_specs(cfg))

    params = on_mesh(shapes)
    adam, *rest = jax.eval_shape(opt.init, shapes)
    state = (adam._replace(
        count=jax.ShapeDtypeStruct((), jnp.int32,
                                   sharding=NamedSharding(mesh, P())),
        mu=on_mesh(adam.mu), nu=on_mesh(adam.nu)), *rest)
    tok = jax.ShapeDtypeStruct(
        (rows_per_chip * shape[0], cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS)))
    step = tfm.make_train_step(mesh, cfg, opt)
    return step.lower(params, state, tok, tok).compile().as_text()


def _computations(text):
    """name -> lines of every computation of an HLO module's text."""
    found, name = {}, None
    for line in text.split("\n"):
        head = (re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
                if line.rstrip().endswith("{") else None)
        if head:
            name = head[1]
            found[name] = []
        elif line.rstrip() == "}":
            name = None
        elif name:
            found[name].append(line)
    return found


def test_data4_layer_all_reduces_are_asynchronous_inside_the_backward_loop(
        topo, pallas_branch):
    """``data=4``: the options compile, and in the backward loop's body
    every matrix leaf's all-reduce is an asynchronous start/done pair with
    a matmul or the stacking between them; the embedding's runs beside the
    layers' optimizer update."""
    text = _compiled_step(topo, (4, 1, 1), CFG)
    assert "splash_mha" in text         # the cell's kernels, not the ring
    bodies = {name: lines for name, lines in _computations(text).items()
              if any("transpose(jvp(layers))/while/body" in line
                     and "%async-collective-done" in line for line in lines)}
    # (where the compiler peels an iteration into the entry computation,
    # the loop's body is the other one)
    bodies = {name: lines for name, lines in bodies.items()
              if not name.startswith("main")} or bodies
    assert len(bodies) == 1, list(bodies)
    (body,) = bodies.values()
    started = [i for i, line in enumerate(body)
               if re.search(r"%async-collective-start[.\d]* = ", line)]
    done = [i for i, line in enumerate(body)
            if re.search(r"%async-collective-done[.\d]* = ", line)]
    # wq, wk, wv, wo, w1, w2 (the two norms' 8 kB may stay synchronous)
    assert len(started) >= 6 and len(started) == len(done)
    for a, b in zip(started, done):
        between = [line for line in body[a + 1:b] if " fusion(" in line]
        assert between, "a start/done pair with no compute between them"
    # and no gradient leaf crosses chips in anything narrower than float32
    grads = [line for line in text.split("\n")
             if "grad_reduce/psum" in line and re.search(
                 r"%(all-reduce|async-collective-start)[.\d]* = ", line)]
    assert grads and not [g for g in grads if re.search(
        r"= \(?(bf16|f16)\[", g)]
    main = next(lines for name, lines in _computations(text).items()
                if name.startswith("main"))
    embed = [i for i, line in enumerate(main)
             if re.search(r"%async-collective-(start|done)[.\d]* = ", line)
             and f"f32[{CFG.vocab_size},{CFG.d_model}]" in line]
    assert len(embed) >= 2
    assert any(" fusion(" in line for line in main[embed[0] + 1:embed[-1]])


def test_data4_without_the_options_overlaps_nothing(topo, pallas_branch,
                                                    monkeypatch):
    """What the options are for: libtpu's defaults leave every gradient
    all-reduce synchronous (and combine a layer's into ops that wait for
    the iteration's last gradient)."""
    monkeypatch.setattr(tfm, "_TPU_OVERLAP_OPTIONS", {})
    text = _compiled_step(topo, (4, 1, 1), CFG)
    assert "async-collective-start" not in text
    assert "all-reduce-start" not in text
    assert " all-reduce(" in text


@pytest.mark.parametrize("cfg, rows", [(CFG, ROWS_PER_CHIP), (LOOPED, 1)],
                         ids=["plain-4x2048", "looped-1x4096-remat"])
def test_the_attention_kernels_fit_and_the_backward_is_one_kernel(
        topo, pallas_branch, cfg, rows):
    """Whether a geometry fits the chip's VMEM is the compiler's word, not
    an estimate: both LM cells' steps (depth 2) compile for the v5e with the
    splash kernels at the blocks ``splash_geometry`` chose, under
    recomputation too; the backward is the fused dkv kernel alone, and the
    stock flash kernel is in neither program."""
    text = _compiled_step(topo, (1, 1, 1), cfg, rows)
    calls = set(re.findall(r"%((?:splash|flash)\w*?)(?:\.\d+)? = ", text))
    assert {"splash_mha_fwd_residuals",
            "splash_mha_dkv_no_residuals"} <= calls, calls
    assert not [c for c in calls
                if c.startswith("splash_mha_dq") or "flash" in c], calls


def test_the_sparse_expert_step_compiles_with_its_kernels(topo,
                                                         pallas_branch):
    """The new cell's step (depth 1 + 2) for the v5e: the window layers and
    the full layer both through splash's MQA form (a KV head and its group
    of 8 query heads a call: K and V are not repeated), forward and the ONE
    fused backward kernel under recomputation, inside scoped VMEM; the held
    experts' products through the megablox kernels; no stock flash kernel,
    no dq kernel, no ragged-dot fallback."""
    text = _compiled_step(topo, (1, 1, 1), SPARSE, 1)
    calls = set(re.findall(r"%((?:splash|flash|gmm|tgmm|ragged)[\w\-]*?)"
                           r"(?:\.\d+)? = ", text))
    assert {"splash_mqa_fwd_residuals", "splash_mqa_dkv_no_residuals",
            "gmm", "tgmm"} <= calls, calls
    assert not [c for c in calls if "_dq" in c or "flash" in c
                or "mha" in c or "ragged" in c], calls
    # K and V reach the kernels with their 4 heads, never as 32
    kv = re.findall(r"%splash_mqa_fwd_residuals[.\d]* = .*", text)
    assert kv and all("bf16[4,8192,128]" in line or
                      "bf16[1,4,8192,128]" in line for line in kv), kv[0][:400]


@pytest.mark.parametrize("window", [2048, 0], ids=["band", "causal"])
def test_the_grouped_attention_call_alone_fits(topo, pallas_branch, window):
    """1 x 32/4 x 8192 x 128 under ``jax.checkpoint``, the banded and the
    causal call of the sparse-expert cell by themselves."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import flash_attention as fa

    @jax.checkpoint
    def attn(q, k, v):
        return fa.flash_attention_local(q, k, v, layout="bhtk",
                                        under_remat=True, window=window)

    chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16, sharding=chip)
    text = jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert "splash_mqa_dkv_no_residuals" in text
    assert "splash_mqa_dq" not in text and "splash_mha" not in text


@pytest.mark.parametrize("rows, t, d, causal, remat", [
    (4, 2048, 128, False, False),       # the geometry no cell runs
    (1, 4096, 128, False, True),
    (4, 2048, 256, True, False),        # ... and a head no cell has
    (1, 4096, 256, True, True),
], ids=["full-4x2048", "full-1x4096-remat", "head256-4x2048",
        "head256-1x4096-remat"])
def test_the_attention_call_alone_fits(topo, pallas_branch, rows, t, d,
                                       causal, remat):
    """Forward and backward of ``flash_attention_local`` by itself, for
    what ``splash_geometry`` answers where no cell's step would show a
    block too large for the chip's VMEM."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import flash_attention as fa

    def attn(q, k, v):
        return fa.flash_attention_local(q, k, v, causal=causal,
                                        layout="bhtk", under_remat=remat)

    body = jax.checkpoint(attn) if remat else attn
    x = jax.ShapeDtypeStruct((rows, 16, t, d), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    text = jax.jit(jax.grad(
        lambda q, k, v: body(q, k, v).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(x, x, x).compile().as_text()
    assert "splash_mha_dkv_no_residuals" in text
    assert "splash_mha_dq" not in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_row_sum_as_a_one_hot_product_fits(topo, dtype):
    """Form (f) of ``tools/grouped_matmul_sweep.py --mode rows`` at the
    sparse-expert cell's shape (10,240 rows of 2,048 into 8,192 tokens):
    measured on the chip (PERF.md section 6, PR 33) and not shipped; the
    megablox ``tgmm`` over tiles of 256 tokens at (512, 256, 1024), float32
    rows as two bfloat16 parts side by side."""
    import importlib.util
    from jax.sharding import SingleDeviceSharding
    spec = importlib.util.spec_from_file_location(
        "grouped_matmul_sweep", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "grouped_matmul_sweep.py"))
    sweep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sweep)
    chip = SingleDeviceSharding(topo.devices[0])
    text = jax.jit(lambda rows, token, n_live: sweep.onehot_tgmm(False)(
        rows, token, n_live, 8192)).lower(
            jax.ShapeDtypeStruct((10240, 2048), dtype, sharding=chip),
            jax.ShapeDtypeStruct((10240,), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    ).compile().as_text()
    assert re.search(r"%tgmm[.\d]* = ", text)


# -- the conv/attention sparse-expert cell (ISSUE 34) ------------------------

def test_the_head_64_attention_call_alone_fits(topo, pallas_branch):
    """2 x 32/8 x 8192 x 64 under ``jax.checkpoint``: heads half as wide as
    the kernel's 128 lanes through splash's MQA form at the causal blocks,
    forward and the ONE fused backward kernel."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import flash_attention as fa

    @jax.checkpoint
    def attn(q, k, v):
        return fa.flash_attention_local(q, k, v, layout="bhtk",
                                        under_remat=True)

    chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16, sharding=chip)
    text = jax.jit(jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert "splash_mqa_fwd_residuals" in text
    assert "splash_mqa_dkv_no_residuals" in text
    assert "splash_mqa_dq" not in text and "splash_mha" not in text
    # K and V reach the kernel with their 8 heads of 64
    assert re.search(r"splash_mqa_dkv_no_residuals[.\d]* = .*"
                     r"bf16\[(2,)?8,8192,64\]", text)


def test_the_grouped_products_at_1792_fit_with_a_tiling_a_call(
        topo, pallas_branch):
    """``grouped_swiglu`` over the cell's buffer (40,960 rows of 2,048, 8
    held experts of 1,792), forward and backward: the megablox kernels at
    ``moe.gmm_tiles`` (896 where 1024 does not divide), every call its own;
    no ragged-dot fallback."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import moe
    chip = SingleDeviceSharding(topo.devices[0])

    def spec(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    text = jax.jit(jax.grad(
        lambda rows, sizes, wg, wu, wd: moe.grouped_swiglu(
            rows, sizes, wg, wu, wd).astype(jnp.float32).sum(),
        (0, 2, 3, 4))).lower(
            spec(40960, 2048), spec(8, dtype=jnp.int32),
            spec(8, 2048, 1792), spec(8, 2048, 1792), spec(8, 1792, 2048)
    ).compile().as_text()
    calls = re.findall(r"%((?:gmm|tgmm|ragged)[\w\-]*?)(?:\.\d+)? = ", text)
    # (the last product's forward is dead under a sum: 2 + 3 of the rows')
    assert calls.count("gmm") >= 5 and calls.count("tgmm") == 3, calls
    assert not [c for c in calls if "ragged" in c], calls


def test_the_conv_attention_cells_step_and_the_memory_it_states(
        topo, pallas_branch):
    """``lfm2-spmd-1chip-ep4share-8k``'s own program, whole (1 dense + 4
    expert layers, 2 rows of 8,192 tokens, ``remat="block"``) for the v5e:
    the attention kernel's forward TWICE (the one attention layer's
    recomputation is real: ``remat_barrier``) and its fused backward once,
    the grouped products through megablox, the row sums as gathers, and the
    compiler's ``memory_analysis`` as ``benchmark/configs/lfm2-8b-a1b.json`` states it,
    under the 14.4 GB (90% of the chip) a cell may reach."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [p for p in (os.path.join(bench, "readers"), bench)
                    if p not in sys.path]
    import files
    model = files.config_module("lfm2-8b-a1b")
    spec = files.load_json(files.config_path("lfm2-8b-a1b"))
    traffic = files.load_json(files.traffic_path(
        files.cell("lfm2-spmd-1chip-ep4share-8k")["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    opt = model.optimizer()
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    held = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=held), tree)

    tok = jax.ShapeDtypeStruct(
        (traffic["rows_per_chip"], cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS)))
    compiled = tfm.make_train_step(mesh, cfg, opt).lower(
        on_mesh(shapes), on_mesh(jax.eval_shape(opt.init, shapes)), tok,
        tok).compile()
    text = compiled.as_text()
    calls = re.findall(r"%((?:splash|flash|gmm|tgmm|ragged)[\w\-]*?)"
                       r"(?:\.\d+)? = ", text)
    assert calls.count("splash_mqa_fwd_residuals") == 2, calls
    assert calls.count("splash_mqa_dkv_no_residuals") == 1, calls
    assert "gmm" in calls and "tgmm" in calls
    assert not [c for c in calls if "_dq" in c or "flash" in c
                or "mha" in c or "ragged" in c], calls
    # the buffer fits the loads (moe.topk_buffer_rows): a conditional a
    # pass in each of the two expert stacks' scans, the grouped products
    # over the tight buffer's 20,480 rows in one branch and over the wide
    # one's 40,960 in the other
    assert len(re.findall(r" conditional\(", text)) == 4
    for rows in (20480, 40960):
        assert re.search(r"%%gmm[.\d]* = bf16\[%d,1792\]" % rows, text), rows
    # a quarter of the experts held: the row sums are gathers of the 2 x
    # 8,192 tokens' rows, one a choice (moe.row_sum_form), out of slabs of
    # at most 96 MiB (moe.NEAR_BYTES): the tight buffer is one (80 MiB, no
    # copy), the wide one two halves sliced out of it; and nothing is
    # scattered under the routed experts' scopes on either pass
    moved = re.findall(r" (gather|scatter)\(.*op_name=\"[^\"]*moe_"
                       r"(combine|dispatch)", text)
    assert moved and {kind for kind, _ in moved} == {"gather"}, moved
    for scope in (r"/moe_combine/", r"transpose\(jvp\(moe_dispatch\)\)/"):
        assert re.search(r"bf16\[16384,2048\]\S* gather\(.*" + scope
                         + "gather", text), scope
        assert re.search(r"bf16\[20480,2048\]\S* dynamic-slice\(.*"
                         r"branch_0_fun/.*" + scope, text), scope
        assert not re.search(r"bf16\[20480,2048\]\S* dynamic-slice\(.*"
                             r"branch_1_fun/.*" + scope, text)
    found = compiled.memory_analysis()
    stated = spec["memory_analysis"]["rows_%d" % traffic["rows_per_chip"]]
    live = found.argument_size_in_bytes + found.temp_size_in_bytes
    assert live < 14.4e9
    assert stated["parameters"] == 507_820_288 == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert live / 1e9 == pytest.approx(stated["live_gb"], rel=0.02)


def test_the_state_space_cells_step_and_the_memory_it_states(
        topo, pallas_branch):
    """``nemotron3-spmd-1chip-ep16share-8k``'s own program, whole (MEMEM*E:
    three Mamba-2 layers, one attention layer, three routed-expert layers, 2
    rows of 8,192 tokens, ``remat="block"``) for the v5e: the attention
    kernel at 32 query over 2 KV heads of 128, forward TWICE (its
    recomputation is real: ``remat_barrier``) and its fused backward once;
    the relu2 experts' grouped products through megablox at 1856, a width
    no multiple of 128 divides, with no ragged-dot fallback; the scan's
    kernels in place of a loop over 64 chunks; and the compiler's
    ``memory_analysis``, under the 14.4 GB (90% of the chip) a cell may
    reach and under what ``benchmark/configs/nemotron-3-nano-30b-a3b.json``
    states of the parent's program."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [p for p in (os.path.join(bench, "readers"), bench)
                    if p not in sys.path]
    import files
    config, cell = "nemotron-3-nano-30b-a3b", \
        "nemotron3-spmd-1chip-ep16share-8k"
    model = files.load_module(os.path.join(
        bench, "configs", config + ".py"), "bench_config_nemotron3")
    spec = files.load_json(files.config_path(config))
    traffic = files.load_json(files.traffic_path(files.cell(cell)["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    opt = model.optimizer()
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    held = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=held), tree)

    tok = jax.ShapeDtypeStruct(
        (traffic["rows_per_chip"], cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS)))
    compiled = tfm.make_train_step(mesh, cfg, opt).lower(
        on_mesh(shapes), on_mesh(jax.eval_shape(opt.init, shapes)), tok,
        tok).compile()
    text = compiled.as_text()
    calls = re.findall(r"%((?:splash|flash|gmm|tgmm|ragged)[\w\-]*?)"
                       r"(?:\.\d+)? = ", text)
    assert calls.count("splash_mqa_fwd_residuals") == 2, calls
    assert calls.count("splash_mqa_dkv_no_residuals") == 1, calls
    assert "gmm" in calls and "tgmm" in calls
    assert not [c for c in calls if "_dq" in c or "flash" in c
                or "mha" in c or "ragged" in c], calls
    # the buffer fits the loads: a conditional a pass in each of the three
    # expert layers' runs, the two grouped products over the tight buffer's
    # 7,680 rows in one branch and over the wide one's 15,360 in the other
    assert len(re.findall(r" conditional\(", text)) == 6
    for rows in (7680, 15360):
        assert re.search(r"%%gmm[.\d]* = bf16\[%d,1856\]" % rows, text), rows
    # the scan runs as its kernels (parallel/ssd.py scan_form), all under
    # ssm_scan: each of the three layers' forward twice, the first writing y
    # alone and the recomputation keeping the chunks' incoming states, and
    # the written backward once; no loop over the chunks is XLA's, and
    # nothing of a chunk's [128, 128] in float32 reaches HBM under the scope
    scans = re.findall(r"%(ssd_scan\w*?)(?:\.\d+)? = [^\n]*custom-call\("
                       r"[^\n]*op_name=\"([^\"]*)\"", text)
    assert sorted(name for name, _ in scans) == [
        "ssd_scan_bwd"] * 3 + ["ssd_scan_fwd"] * 3 + [
        "ssd_scan_fwd_states"] * 3, scans
    for name, op_name in scans:
        assert "/mamba_mixer/ssm_scan/" in op_name, op_name
        assert ("rematted_computation" in op_name) == (
            name == "ssd_scan_fwd_states"), op_name
    assert not re.search(r"while\(.*op_name=\"[^\"]*ssm_scan", text)
    assert not re.search(r"f32\[[\d,]*128,128\][^\n]*op_name=\"[^\"]*"
                         r"ssm_scan", text)
    found = compiled.memory_analysis()
    stated = spec["memory_analysis"]["rows_%d" % traffic["rows_per_chip"]]
    live = found.argument_size_in_bytes + found.temp_size_in_bytes
    assert live < 14.4e9
    assert stated["parameters"] == 528_093_120 == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    # the JSON's statement is the PARENT's program (PR 39, the scan in
    # jax.numpy: 12.511 GB) and a benchmark PR's to correct (PERF.md section
    # 7); with the kernels the scan's [128, 128] temporaries and autodiff's
    # residuals of them are gone
    assert stated["live_gb"] == 12.511
    assert live / 1e9 == pytest.approx(LIVE_GB_WITH_THE_SCANS_KERNELS,
                                       rel=0.02)


LIVE_GB_WITH_THE_SCANS_KERNELS = 10.864


def test_the_latent_attention_cells_step_and_the_memory_it_states(
        topo, pallas_branch):
    """``joyai-spmd-1chip-ep32share-8k``'s own program, whole (a dense
    layer, four expert layers as one scan, the multi-token-prediction
    module; latent attention in all six; 2 rows of 8,192 tokens,
    ``remat="block"``) for the v5e: the attention kernel at q and k heads of
    192 beside v heads of 128 through splash's MHA form, in each of the
    three scans forward TWICE (the recomputation is real) and the fused
    backward once, which is the compiler's word that the chosen blocks fit
    VMEM at this pair of head sizes; the experts' grouped products through
    megablox at 768 with no ragged-dot fallback; and the compiler's
    ``memory_analysis`` under the 14.4 GB (90% of the chip) a cell may reach
    and within 2% of what ``benchmark/configs/joyai-llm-flash.json``
    states."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path[:0] = [p for p in (os.path.join(bench, "readers"), bench)
                    if p not in sys.path]
    import files
    config, cell = "joyai-llm-flash", "joyai-spmd-1chip-ep32share-8k"
    model = files.load_module(os.path.join(
        bench, "configs", config + ".py"), "bench_config_joyai")
    spec = files.load_json(files.config_path(config))
    traffic = files.load_json(files.traffic_path(files.cell(cell)["traffic"]))
    cfg = model.transformer_config(spec, traffic, False)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1, 1),
                (tfm.DATA_AXIS, tfm.SEQ_AXIS, tfm.TENSOR_AXIS))
    opt = model.optimizer()
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    held = NamedSharding(mesh, P())

    def on_mesh(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=held), tree)

    tok = jax.ShapeDtypeStruct(
        (traffic["rows_per_chip"], cfg.max_seq), jnp.int32,
        sharding=NamedSharding(mesh, P(tfm.DATA_AXIS, tfm.SEQ_AXIS)))
    compiled = tfm.make_train_step(mesh, cfg, opt).lower(
        on_mesh(shapes), on_mesh(jax.eval_shape(opt.init, shapes)), tok,
        tok).compile()
    text = compiled.as_text()
    calls = re.findall(r"%((?:splash|flash|gmm|tgmm|ragged)[\w\-]*?)"
                       r"(?:\.\d+)? = ", text)
    # the dense layer's run, the expert layers' scan and the module's block
    assert calls.count("splash_mha_fwd_residuals") == 6, calls
    assert calls.count("splash_mha_dkv_no_residuals") == 3, calls
    assert "gmm" in calls and "tgmm" in calls
    assert not [c for c in calls if "_dq" in c or "flash" in c
                or "mqa" in c or "ragged" in c], calls
    # q and k at 192, v and the result at 128, under attn_latent
    assert re.search(r"%splash_mha_fwd_residuals[.\d]* = [^\n]*"
                     r"bf16\[2,32,8192,128\]", text)
    assert re.search(
        r"operand_layout_constraints=\{[^\n]*bf16\[2,32,8192,192\]\{3,2,1,0\},"
        r" bf16\[2,32,8192,192\]\{3,2,1,0\}, bf16\[2,32,8192,128\]", text)
    kernel = "mla_mixer/attn_latent/vmap(jit(_splash_attention))/splash_mha_"
    assert "jvp(layers)/while/body/closed_call/" + kernel in text
    # the module's block runs its own kernels, under mtp
    assert "jvp(mtp)/while/body/closed_call/" + kernel in text
    # the tight buffer's 5,120 rows at the experts' width of 768
    assert re.search(r"%gmm[.\d]* = bf16\[5120,768\]", text)
    found = compiled.memory_analysis()
    stated = spec["memory_analysis"]["rows_%d" % traffic["rows_per_chip"]]
    live = found.argument_size_in_bytes + found.temp_size_in_bytes
    assert live < 14.4e9
    assert stated["parameters"] == 491_697_408 == sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert live / 1e9 == pytest.approx(stated["live_gb"], rel=0.02)
    assert found.temp_size_in_bytes / 1e9 == pytest.approx(
        stated["temporaries_gb"], rel=0.02)


@pytest.mark.parametrize("rows, remat", [(2, True), (1, False)],
                         ids=["the-cells-rows-remat", "the-checks-one-row"])
def test_the_scan_call_alone_fits(topo, pallas_branch, rows, remat):
    """Forward and backward of ``ssd_chunked`` by itself at the state-space
    cell's shapes (8,192 tokens, 64 heads of 64 over 8 groups, state 128,
    chunk 128, bfloat16): whether the kernels' blocks and scratch fit the
    chip's VMEM is the compiler's word, before any chip is asked."""
    from jax.sharding import SingleDeviceSharding
    from horovod_tpu.parallel import ssd
    t, h, p, g, n, chunk = 8192, 64, 64, 8, 128, 128
    assert ssd.scan_form((rows, t, h, p), (rows, t, g, n), chunk) == {
        "form": "kernel", "chunk": "128", "heads_per_block": "8"}
    one = SingleDeviceSharding(topo.devices[0])

    def of(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def scan(*args):
        return ssd.ssd_chunked(*args, chunk)

    body = jax.checkpoint(scan) if remat else scan
    text = jax.jit(jax.value_and_grad(lambda *args: body(*args).sum(),
                                      range(6))).lower(
        of((rows, t, h, p), jnp.bfloat16), of((rows, t, h), jnp.float32),
        of((h,), jnp.float32), of((rows, t, g, n), jnp.bfloat16),
        of((rows, t, g, n), jnp.bfloat16), of((h,), jnp.float32)
    ).compile().as_text()
    calls = re.findall(r"%(ssd_scan\w*?)(?:\.\d+)? = [^\n]*custom-call\(",
                       text)
    assert sorted(calls) == ["ssd_scan_bwd"] + ["ssd_scan_fwd"] * remat + [
        "ssd_scan_fwd_states"], calls
