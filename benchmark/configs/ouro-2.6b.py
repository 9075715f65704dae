"""``ouro-2.6b`` as the program runs it: sizes from the json beside this
file, weights from a seed on the device, operations from shapes, and the
comparison with the plain reference (``reference/ouro-2.6b.py``).

The step itself (which entry point of the program trains this model) is in
``ouro-2.6b.<mode>.py``, one file per mode of a traffic mix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding

from horovod_tpu.models.transformer import (TransformerConfig, forward_exits,
                                            init_params, param_specs)

LR = 3e-4           # examples/transformer_lm.py, the other LM configuration
# adamw's rate climbs to LR over this many steps. The 20 steps of a 10 s
# window are steps 18 to 37 of the run (6 of warm-up, 8 of calibration, 2 to
# settle and loop_grad's one before them). On the v5e, one seed each (PR 28):
# the loss fell over the window with one step of warm-up, which is the rate
# constant at 3e-4 (sums of the first and last 4 steps 36.2 -> 31.0), and
# with 20, 50 and 200 steps (38.1 -> 33.5, 39.4 -> 30.4, 42.4 -> 34.4), so
# loss_fell needs no warm-up; but at the full rate single steps jump by 1
# to 2 either way, and with 200 steps each batch of the pool falls step over
# step. It stays as the margin of a check that one run in hundreds must not
# fail; with 200 steps it held in 14 runs of 14.
WARMUP_STEPS = 200

# Worst error the comparison with the float32 reference allows, on one row
# of the timed length, each set between two readings on the v5e at the
# published widths (PR 28): what the program read over 22 seeds, and what the
# same checks read when the reference's norms, attention, FFN and head round
# to an 8-bit float (float8_e4m3fn; ``benchmark/tests/ouro_defects.py <seed>
# float8`` on the chip; the test suite does the same at the rehearsal's
# widths).
# ``logits``: the largest error of any of the four exits as a share of the
# largest reference logit of that exit; it grows pass by pass (1.3e-2,
# 1.7e-2, 2.4e-2, 3.5e-2 in one run). Program 3.0e-2 to 4.6e-2, 8-bit
# float 2.1e-1 and 2.3e-1 (two seeds).
# ``exit_p``: the largest error of any p_t, a probability. Program 9.5e-3 to
# 1.5e-2, 8-bit float 7.9e-2 and 9.8e-2.
# ``loss``: share of the reference's loss. Rounding averages out over 4,096
# tokens (program 1.0e-6 to 3.6e-5, 8-bit float 3.6e-5, 3.9e-5; at the rehearsal's
# 128 tokens up to 2.7e-4), so this one is no test of precision: it is set
# where another model lands (RoPE left off: 2e-3 and more).
# CPU counts at the rehearsal's widths: tests/test_looped_lm.py,
# benchmark/tests/test_ouro.py.
TOLERANCE = {"logits": 1e-1, "exit_p": 3e-2, "loss": 1e-3}


def sizes(spec: dict, rehearse: bool) -> dict:
    return {**spec, **spec["rehearsal"]} if rehearse else spec


def transformer_config(spec, traffic, rehearse: bool) -> TransformerConfig:
    s = sizes(spec, rehearse)
    t = sizes(traffic, rehearse)
    if t["seq_len"] > s["max_position_embeddings"]:
        raise ValueError(f"sequence {t['seq_len']} exceeds "
                         f"max_position_embeddings")
    if (s["num_key_value_heads"] != s["num_attention_heads"]
            or s["head_dim"] * s["num_attention_heads"] != s["hidden_size"]
            or s["hidden_act"] != "silu" or s["tie_word_embeddings"]):
        raise ValueError("the block of models/transformer.py has no field "
                         "for this: KV heads, head size, activation, tying")
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], n_layers=s["num_hidden_layers"],
        d_ff=s["intermediate_size"], max_seq=t["seq_len"],
        dtype=jnp.bfloat16, attention="flash", remat=t["remat"],
        positions="rope", rope_theta=float(s["rope_theta"]), ffn="swiglu",
        norm="sandwich", norm_eps=s["rms_norm_eps"], tie_embeddings=False,
        n_loops=s["total_ut_steps"],
        exit_entropy_weight=spec["assumed"]["exit_entropy_weight"])


def optimizer():
    return optax.adamw(optax.linear_schedule(0.0, LR, WARMUP_STEPS))


def make_params(cfg: TransformerConfig, seed: int, shardings=None):
    """fp32 parameters on the device, in one jitted call from the seed."""
    make = jax.jit(lambda key: init_params(key, cfg), out_shardings=shardings)
    return make(jax.random.PRNGKey(seed))


def flops_per_sample(cfg: TransformerConfig) -> float:
    """Operations the forward and backward passes need for ONE token: what
    the objective needs, no recomputation.

    Matrix multiplications: 2 operations a parameter a token forward, twice
    that backward; every pass applies the four attention projections and
    the three SwiGLU matrices of every layer and ends in the head (untied:
    the embedding is a look-up, no multiplication; the gate's 2048 weights
    are left out). Attention: scores and weighted values are 2 * 2 * T *
    d_model operations a token a layer application forward over the whole
    square; a causal model needs half of it, and backward twice forward."""
    d, f = cfg.d_model, cfg.d_ff
    applications = cfg.n_loops * cfg.n_layers
    matmul_params = (applications * (4 * d * d + 3 * d * f)
                     + cfg.n_loops * cfg.vocab_size * d)
    attention = applications * 3 * (4 * cfg.max_seq * d) / 2
    return 6.0 * matmul_params + attention


def kernel_costs(cfg: TransformerConfig, rows: int) -> dict:
    """What the attention kernels of ONE step on one chip must do, from
    shapes, whichever kernel the program picks: every layer application's
    6 causal-half matmuls of T x T x head a head a row (the forward that
    remat="block" runs again in the backward is not counted, nor the
    backward's own recomputation of the scores), and q, k, v, o read or
    written once forward and q, k, v, o, do read and dq, dk, dv written
    once backward (12 passes over [rows, heads, T, head] in bfloat16)."""
    t, applications = cfg.max_seq, cfg.n_loops * cfg.n_layers
    flops = applications * rows * 3 * (4 * t * t * cfg.d_model) / 2
    nbytes = applications * 12 * rows * t * cfg.d_model * 2
    return {"attn_kernel": {"flops": flops, "bytes": nbytes}}


def to_reference(params) -> dict:
    """The program's parameters (layers stacked on a leading axis) as the
    plain reference takes them."""
    n = params["layers"]["ln1"].shape[0]
    return {**params, "layers": [{k: v[i] for k, v in params["layers"].items()}
                                 for i in range(n)]}


def from_reference(weights) -> dict:
    """A tree in the reference's form (its gradient), stacked as the
    program's."""
    return {**weights, "layers": {
        k: jnp.stack([lw[k] for lw in weights["layers"]])
        for k in weights["layers"][0]}}


def seeded_row(cfg: TransformerConfig, seed: int, length: int):
    """(inputs, targets) [1, length]: a row a comparison is made on."""
    tok = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, size=(1, length + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


def run_quickly_built(fn, *args):
    """``fn(*args)`` through a program the compiler spends little on: the
    reference's unrolled float32 graphs take it six minutes at its usual
    effort and 20 s at the least (v5e compiler, PR 28), and they run once."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"exec_time_optimization_effort": -1.0})(*args)


def reference_check(cfg: TransformerConfig, params, reference, seed: int,
                    loss_fn) -> dict:
    """The program against the float32 reference on one row of ``max_seq``
    tokens, same weights: the logits of all four exits, p_1 .. p_4 and the
    loss. ``loss_fn(params, inputs, targets)`` is the loss the mode's
    train step differentiates. The comparison is made on the device: no
    array of vocabulary width leaves it."""
    # the tokens are arguments, not constants of the programs: another seed
    # must find the same programs in the compilation cache
    inputs, targets = seeded_row(cfg, seed, cfg.max_seq)
    got_logits, got_p = jax.jit(
        lambda p, x: forward_exits(p, x, cfg))(params, inputs)
    got_loss = loss_fn(params, inputs, targets)

    def errors(params, inputs, targets, got_logits, got_p, got_loss):
        with jax.default_matmul_precision("highest"):
            want_logits, want_p = reference.forward(
                to_reference(params), inputs, cfg.n_loops)
            want_loss = reference.objective(
                jnp.stack([reference.cross_entropy(logits, targets)
                           for logits in want_logits]),
                want_p, cfg.exit_entropy_weight)
        by_exit = jnp.stack([
            jnp.max(jnp.abs(got.astype(jnp.float32) - want))
            / jnp.max(jnp.abs(want))
            for got, want in zip(got_logits, want_logits)])
        return {"logits_by_exit": by_exit, "logits": jnp.max(by_exit),
                "exit_p": jnp.max(jnp.abs(got_p - want_p)),
                "loss": jnp.abs(got_loss - want_loss) / jnp.abs(want_loss),
                "loss_program": got_loss, "loss_reference": want_loss,
                "exit_share_reference": jnp.mean(want_p, axis=(1, 2))}

    found = run_quickly_built(errors, params, inputs, targets, got_logits,
                              got_p, got_loss)
    found = {k: np.asarray(v).tolist() for k, v in found.items()}
    return {"ok": all(found[k] <= TOLERANCE[k] for k in TOLERANCE),
            "error": found, "tolerance": TOLERANCE}


def param_shardings(cfg: TransformerConfig, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(cfg))
