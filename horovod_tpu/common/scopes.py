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
by scope"). A fusion has one ``op_name``, its root's.

jax leaves metadata out of the persistent compilation cache's key, so a
program cached before a scope was written is served without it. The
program's name IS part of the key: a change to what the scopes cover that
must show in traces takes a new program name with it.
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
# inside ffn, the routed-expert layer (parallel/moe.py topk_*)
ROUTER = "router"               # fp32 scores, top-k, weights, counts
MOE_DISPATCH = "moe_dispatch"   # the sort by expert and the gather
EXPERTS = "experts"             # the held experts' three grouped matmuls
SHARED_EXPERT = "shared_expert"  # the SwiGLU every token takes
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

