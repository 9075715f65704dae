"""``cerebras-gpt-1.3b`` as the program runs it: sizes from the json beside
this file, weights from a seed on the device, operations from shapes, and
the comparison with the plain reference.

The step itself (which entry point of the program trains this model) is in
``cerebras-gpt-1.3b.<mode>.py``, one file per mode of a traffic mix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from horovod_tpu.models.transformer import (TransformerConfig, forward_block,
                                            init_params, param_specs)

LR = 3e-4       # examples/transformer_lm.py, chip_smoke.py

# Worst error the comparison with the float32 reference allows: the logits'
# largest error as a share of the largest reference logit, the loss's error
# as a share of the reference loss. Measured on the v5e at the published
# widths, one row of 2048 tokens, 14 runs, 14 seeds (my chip runs, PR
# 22): logits 7.4e-3 to 1.02e-2, loss 1.7e-7 to 3.2e-5. The program
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in float32,
# through 4 layers and the head; the bounds are 2.5 and 6 times the worst
# seen. A model that computed in an 8-bit float (3 bits of mantissa, 32 times
# the rounding error) would miss them by an order of magnitude.
TOLERANCE = {"logits": 2.5e-2, "loss": 2e-4}


def sizes(spec: dict, rehearse: bool) -> dict:
    return {**spec, **spec["rehearsal"]} if rehearse else spec


def transformer_config(spec, traffic, rehearse: bool) -> TransformerConfig:
    s = sizes(spec, rehearse)
    t = sizes(traffic, rehearse)
    if t["seq_len"] > s["n_positions"]:
        raise ValueError(f"sequence {t['seq_len']} exceeds n_positions "
                         f"{s['n_positions']}")
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["n_embd"], n_heads=s["n_head"],
        n_layers=s["n_layer"], d_ff=s["n_inner"], max_seq=t["seq_len"],
        dtype=jnp.bfloat16, attention="flash", remat=t["remat"])


def make_params(cfg: TransformerConfig, seed: int, shardings=None):
    """fp32 parameters on the device, in one jitted call from the seed."""
    make = jax.jit(lambda key: init_params(key, cfg), out_shardings=shardings)
    return make(jax.random.PRNGKey(seed))


def flops_per_sample(cfg: TransformerConfig) -> float:
    """Operations the forward and backward passes need for ONE token.

    Matrix multiplications: 2 operations a parameter a token forward, twice
    that backward, over the four attention projections and the two MLP
    matrices of every layer and the output head (the tied embedding used
    as a matrix; the look-up is no multiplication). Attention: scores and
    weighted values are 2 * 2 * T * d_model operations a token a layer
    forward over the whole square; a causal model needs half of it, and
    backward twice forward. Recomputation is not counted."""
    d, f, layers = cfg.d_model, cfg.d_ff, cfg.n_layers
    matmul_params = layers * (4 * d * d + 2 * d * f) + cfg.vocab_size * d
    attention = layers * 3 * (4 * cfg.max_seq * d) / 2
    return 6.0 * matmul_params + attention


def kernel_costs(cfg: TransformerConfig, rows: int) -> dict:
    """What the attention kernels of ONE step on one chip must do, from
    shapes: operations as in :func:`flops_per_sample` (6 causal-half
    matmuls of T x T x head a head a row a layer; the backward's
    recomputation of the scores is not counted, so the fused splash
    backward, 5 matmuls for the 4 counted, can reach 4/5 of its compute
    roofline at best), bytes as q, k, v, o read or
    written once forward and q, k, v, o, do read and dq, dk, dv written
    once backward (12 passes over [rows, heads, T, head] in bfloat16)."""
    t, layers = cfg.max_seq, cfg.n_layers
    flops = layers * rows * 3 * (4 * t * t * cfg.d_model) / 2
    nbytes = layers * 12 * rows * t * cfg.d_model * 2
    return {"attn_kernel": {"flops": flops, "bytes": nbytes}}


def to_reference(params) -> dict:
    """The program's parameters (layers stacked on a leading axis) as the
    plain reference takes them."""
    n = params["layers"]["ln1"].shape[0]
    return {"embed": params["embed"], "ln_f": params["ln_f"],
            "layers": [{k: v[i] for k, v in params["layers"].items()}
                       for i in range(n)]}


def reference_check(cfg: TransformerConfig, params, reference, seed: int,
                    loss_fn) -> dict:
    """The program's logits and loss on one row of ``max_seq`` tokens
    against the float32 reference, same weights. ``loss_fn(params, inputs,
    targets)`` is the loss the mode's train step differentiates."""
    tok = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(1, cfg.max_seq + 1)).astype(np.int32)
    inputs, targets = jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])

    # the tokens are arguments, not constants of the programs: another seed
    # must find the same programs in the compilation cache
    @jax.jit
    def ref(params, inputs, targets):
        with jax.default_matmul_precision("highest"):
            logits = reference.forward(to_reference(params), inputs)
            return logits, reference.loss(logits, targets)

    want_logits, want_loss = ref(params, inputs, targets)
    got_logits = jax.jit(lambda p, x: forward_block(p, x, cfg))(params,
                                                                inputs)
    got_loss = loss_fn(params, inputs, targets)
    scale = float(jnp.max(jnp.abs(want_logits)))
    err = {"logits": float(jnp.max(jnp.abs(
               got_logits.astype(jnp.float32) - want_logits))) / scale,
           "loss": abs(float(got_loss) - float(want_loss))
           / abs(float(want_loss))}
    return {"ok": all(err[k] <= TOLERANCE[k] for k in err), "error": err,
            "tolerance": TOLERANCE}


def param_shardings(cfg: TransformerConfig, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(cfg))
