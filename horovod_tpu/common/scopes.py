"""Names inside the compiled programs: the ``jax.named_scope`` names the
product writes and the names of its jitted programs.

A scope is metadata of the lowered program. It adds no operation and no
host work, and there is no switch: every operation traced under it carries
the name in its ``op_name``. jax writes the pass itself:
``jit(train_step)/shard_map/jvp(layers)/while/body/closed_call/attn/
dot_general`` in the forward pass and ``jit(train_step)/shard_map/
transpose(jvp(layers))/...`` in the backward pass: the scopes open where
``value_and_grad`` is applied (inside the step's ``shard_map`` since PR 29)
move inside the parentheses; differentiated through a ``shard_map`` from
outside they stay behind it, ``transpose(jvp())/shard_map/layers/...``. On the
TPU the profiler keeps each executed operation's ``op_name`` as the
``tf_op`` stat of the event's metadata; ``benchmark/scopes.py``,
``benchmark/readers/trace_scopes.py`` and their metric files read these
names, and ``docs/observability.md`` lists them ("Reading a device trace
by scope"). A fusion has one ``op_name``, its root's. The benchmark's
worker keeps every executed operation's ``op_name`` in its record, and the
traced line carries the by-scope metrics (PR 36).

jax leaves metadata out of the persistent compilation cache's key, so a
program cached before a scope was written is served without it. The
program's name IS part of the key: a change to what the scopes cover that
must show in traces takes a new program name with it.

The host's side of the same trace: :func:`host_span` writes a span of the
product's host code on the profiler's clock, the one the device lines are
on (``HOST_SPANS``; ``docs/observability.md``, "Host spans on the
profiler's clock").
"""

from __future__ import annotations

import jax
import optax

# models/transformer.py: the one block every step builder runs
# (make_train_step, the pipeline's stages, MoE-EP's segments)
EMBED = "embed"         # _embed: the token lookup and its cast
LOOP = "loop"           # _run_passes: the lax.scan over the passes (n_loops > 1)
LAYERS = "layers"       # the lax.scan over the stacked layers (_run_passes,
#                         a pipeline stage)
ATTN = "attn"           # _attn_sublayer: rmsnorms, projections, attention, residual
ROPE = "rope"           # the cos/sin tables; inside attn, q and k rotated
FFN = "ffn"             # _block: rmsnorms, dense or MoE branch, residual
HEAD = "head"           # _final_norm: every pass's final rmsnorm; _head: logits einsum
LOSS = "loss"           # _lean_xent, both rules of its custom_vjp
EXIT_GATE = "exit_gate"  # gate logit, exit distribution, entropy (n_loops > 1)
# inside attn, where the configuration has them (cfg.layers: a per-layer
# pattern; cfg.qk_norm; cfg.attn_gate)
ATTN_WINDOW = "attn_window"     # the attention call of a sliding-window layer
ATTN_FULL = "attn_full"         # ... of a causal full-attention layer
QK_NORM = "qk_norm"             # q and k RMSNormed over the head
ATTN_GATE = "attn_gate"         # the gate's projection, sigmoid and product
# a layer whose mixer is the gated short convolution (LayerKind.mixer "conv")
CONV_MIXER = "conv_mixer"       # where such a layer has attn: rmsnorm, the two
#                                 projections, the residual
SHORT_CONV = "short_conv"       # inside it: the two gates and the taps
# a layer whose mixer is the Mamba-2 state-space mixer (LayerKind.mixer
# "mamba2"; models/transformer.py _mamba_mix, parallel/ssd.py)
MAMBA_MIXER = "mamba_mixer"     # where such a layer has attn: rmsnorm, the
#                                 products with ssm_in and ssm_out, dt's
#                                 softplus, the residual
SSM_CONV = "ssm_conv"           # inside it: the taps, their bias, the SiLU
SSM_SCAN = "ssm_scan"           # ... everything of ssd_chunked
SSM_GATE_NORM = "ssm_gate_norm"  # ... the gate, then the norm over groups
# a layer whose mixer is latent attention (LayerKind.mixer "mla";
# models/transformer.py _mla_mix)
MLA_MIXER = "mla_mixer"         # where such a layer has attn: rmsnorm, the
#                                 low-rank projections, the kernel, the
#                                 output projection, the residual
MLA_Q = "mla_q"                 # inside it: wq_a, its norm, wq_b, q put
#                                 together again after the rotation
MLA_KV = "mla_kv"               # ... wkv_a, its norm, wkv_b, the one
#                                 rotated key broadcast over the heads and
#                                 joined to each head's own part
ATTN_LATENT = "attn_latent"     # ... the attention call (q/k heads of
#                                 qk_nope_dim + qk_rope_dim, v heads of
#                                 v_head_dim); 'rope' stands beside these
# the multi-token-prediction module (cfg.mtp_depth; _mtp_module): its
# block's scopes, 'head' and 'loss' keep their names inside it
MTP = "mtp"                     # the whole module
MTP_PROJ = "mtp_proj"           # inside it: the next token's embedding, the
#                                 two norms, the product with proj
# inside ffn, the routed-expert layer (parallel/moe.py topk_*)
ROUTER = "router"               # fp32 scores, top-k, weights, counts
MOE_DISPATCH = "moe_dispatch"   # the sort by expert and the gather
EXPERTS = "experts"             # the held experts' grouped matmuls (three of
#                                 a SwiGLU, two around relu(.)^2)
SHARED_EXPERT = "shared_expert"  # the expert every token takes
MOE_COMBINE = "moe_combine"     # weighted scatter-add back to the tokens
# optimizer.py and the step builders
OPTIMIZER = "optimizer"         # inner.update + optax.apply_updates
DECOMPRESS = "decompress"       # eager apply program: what precedes them
GRAD_REDUCE = "grad_reduce"     # the cross-replica gradient reduction:
#   make_train_step's psums (inside the backward scan and after it) and
#   distributed()'s

# jitted programs, as the trace's ``XLA Modules`` line shows them (jit_<name>)
TRAIN_STEP = "train_step"               # make_train_step
APPLY_UPDATE = "hvd_apply_update"       # DistributedEagerOptimizer._apply_fn
APPLY_DELTA = "hvd_apply_delta"         # the Adasum delta optimizer's twin


def apply_update(optimizer, grads, opt_state, params):
    """``optimizer.update`` + ``optax.apply_updates`` under the
    ``optimizer`` scope; returns ``(params, opt_state)``."""
    with jax.named_scope(OPTIMIZER):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state


# host spans: the product's host code on the profiler's clock, ``hvd.<name>``
# on the calling thread's line of the trace's ``/host:CPU`` plane
# optimizer.py: DistributedEagerOptimizer.update_and_apply (the sharded
# and the delta-Adasum twins write the outer span only)
OPT_UPDATE_AND_APPLY = "opt.update_and_apply"   # entry to the return of the
#                                                 unawaited outputs
OPT_FLATTEN = "opt.flatten"             # tree_flatten(grads), _sparse_ks
OPT_REDUCE = "opt.reduce"               # _reduce_async; no world of one
OPT_APPLY_LOOKUP = "opt.apply_lookup"   # _apply_fn: the key and the cache
OPT_APPLY_DISPATCH = "opt.apply_dispatch"   # the call of hvd_apply_update
# core/engine.py, core/replay.py: where the other stacks time themselves
ENGINE_GROUPED_ALLREDUCE = "engine.grouped_allreduce"   # the caller's thread
ENGINE_DISPATCH = "engine.dispatch"     # Engine._dispatch: XLA_DISPATCH
ENGINE_COMPILE_DISPATCH = "engine.compile_dispatch"     # ... a fresh builder
REPLAY_LAUNCH = "replay.launch"         # StepReplay._launch
ENGINE_WAIT = "engine.wait"             # the waits that count host_blocks
ENGINE_FETCH = "engine.fetch"           # the read that counts host_fetches
HOST_SPANS = (
    OPT_UPDATE_AND_APPLY, OPT_FLATTEN, OPT_REDUCE, OPT_APPLY_LOOKUP,
    OPT_APPLY_DISPATCH, ENGINE_GROUPED_ALLREDUCE, ENGINE_DISPATCH,
    ENGINE_COMPILE_DISPATCH, REPLAY_LAUNCH, ENGINE_WAIT, ENGINE_FETCH)


def host_span(name: str):
    """A context manager that writes the span ``hvd.<name>`` (``name`` one
    of ``HOST_SPANS``) into a running ``jax.profiler`` trace. With no
    profiler session it costs the TraceMe's own check, 0.6 us; there is no
    switch, no other clock and no other consumer."""
    return jax.profiler.TraceAnnotation("hvd." + name)
