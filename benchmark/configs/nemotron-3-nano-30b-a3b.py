"""``nemotron-3-nano-30b-a3b`` as the program runs it: sizes from the json
beside this file, weights from a seed on the device, operations from shapes,
and the comparison with the plain reference
(``reference/nemotron-3-nano-30b-a3b.py``).

The step itself (which entry point of the program trains this model) is in
``nemotron-3-nano-30b-a3b.<mode>.py``, one file per mode of a traffic mix.
"""

from __future__ import annotations

import os
import types

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.transformer import (SSM_DT_INIT, LayerKind,
                                            TransformerConfig, layer_rows,
                                            mamba_mix)
from horovod_tpu.parallel.ssd import ssd_chunked

import files

# what the conv/attention cell's file already has and this one needs as it
# is: the weights from a seed, the trees between the program's stacks and
# the reference's list of layers, the seeded rows, the quickly built
# programs, the choices compared as sets
_lfm2 = files.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lfm2-8b-a1b.py"), "bench_config_lfm2")
(sizes, optimizer, make_params, visible_pairs, to_reference, from_reference,
 expert_stacks, router_bias, without_bias, seeded_rows, built_quickly,
 run_quickly_built, same_choices, param_shardings) = (
    getattr(_lfm2, name) for name in (
        "sizes", "optimizer", "make_params", "visible_pairs", "to_reference",
        "from_reference", "expert_stacks", "router_bias", "without_bias",
        "seeded_rows", "built_quickly", "run_quickly_built", "same_choices",
        "param_shardings"))

# Worst error the comparison with the float32 reference allows, on the
# cell's own two rows of the timed length (2 x 8,192 tokens), each set
# between two readings on the v5e at the published widths (PR 39; PERF.md
# section 4 has them all): what the program read over twenty seeds (the
# scan's own limit: over nine), and what the same checks read when the
# reference's norms, scans, mixers, experts and head round to an 8-bit float
# (float8_e4m3fn; ``benchmark/tests/nemotron3_defects.py <seed> float8`` on
# the chip, two seeds; the second with the scan's result rounded too). The
# reference TAKES the program's choices of expert (``reference.forward``'s
# ``given``) and makes its own beside them, as in the conv/attention cell
# (configs/lfm2-8b-a1b.py TOLERANCE has why); here a token routed otherwise
# differs by a whole expert, and the state-space layers hand that on to
# EVERY later token of the row. Left to its own choices all the way
# (``free_logits``, under no limit) it reads 6.3e-2 to 7.6e-2 over the 80%
# of the tokens whose 18 choices all agree.
# ``choices_off``: the share of the 2 x T x 6 x 3 choices of expert that are
# not the reference's own (as sets, token by token and layer by layer).
# Program 1.00e-2 to 1.09e-2 (0.7e-2 in the first expert layer, 1.3e-2 in
# the third), 8-bit float 8.3e-2 and 8.9e-2.
# ``logits``: over ALL tokens, the error that 999 in 1,000 of them stay
# under (a token's error is its largest over the vocabulary), as a share of
# the largest reference logit. Program 0.98e-2 to 1.13e-2 (worst token
# 1.2e-2), 8-bit float 8.9e-2 and 9.8e-2; rotation switched on in the one
# attention layer 5.4e-2, the nearest wrong model.
# ``ssm_mixer``: per state-space layer, the program's WHOLE mixer
# (``mamba_mix``) against the reference's float32 recurrence on the SAME
# normed input, the reference's own: relative L2 over a row's outputs, the
# worst layer and row. Program 5.06e-3 to 5.25e-3, every layer and seed
# within 4%; 8-bit float 3.8e-2 and 4.6e-2; the decay put on the input too
# 2.3e-1, heads read their group by remainder 3.8e-1. Most of the reading
# is the rounding of X, B and C to bfloat16, which is as stated, so it sits
# a quarter above the largest reading and tells a wrong mixer, not how the
# scan sums: a running sum kept in bfloat16 reads 6.7e-3 to 6.9e-3 here.
# ``ssm_scan``: per state-space layer, the program's scan ALONE
# (``ssd_chunked``) against the reference's recurrence on the SAME operands
# already rounded to bfloat16 (:func:`mixer_errors`), so the operands'
# rounding cancels and what is left is what the chunked form rounds inside
# and how it sums: bfloat16 operands summed in float32 is what the
# configuration states. Program 1.21e-3 to 1.38e-3 over nine seeds (the
# first or the second layer the worst, the third 1.06e-3 to 1.2e-3); every
# product summed in bfloat16, one term at a time (``sums_in_bfloat16``),
# 5.75e-3 to 6.28e-3 on three of those seeds; 8-bit float 2.65e-2. The
# limit is 1.8 times the largest reading and under half the smallest of
# the bfloat16 sums. NOT TOLD, by this or any
# limit: the products RETURNING bfloat16 (float32 sums inside the MXU, the
# result rounded: 1.24e-3 to 1.32e-3, 3% over the program on each seed,
# inside the seeds' own spread):
# three of the four results are rounded to bfloat16 as the next product's
# operand anyway.
# ``loss``: share of the reference's loss. Rounding averages out over
# 16,384 tokens: program 1.3e-6 to 1.93e-5 over twenty seeds, 8-bit float
# 1.16e-4 on one seed and 9.4e-6 on another, so precision moves it no more
# than a seed does and this limit tells none; it sits at three times the
# largest reading, under where another model lands (relu without its square
# 3.6e-4, the taps' bias left out 6.8e-4, the shared expert at the routed
# width 1.3e-3).
# ``counts_off``, ``dropped``: exact, 0.
TOLERANCE = {"choices_off": 2.5e-2, "logits": 2.5e-2, "ssm_mixer": 6.5e-3,
             "ssm_scan": 2.5e-3, "loss": 6e-5, "counts_off": 0, "dropped": 0}

MIXER_OF = {"M": LayerKind(mixer="mamba2", experts=None, rope=False),
            "E": LayerKind(mixer="none", experts=True, rope=False),
            "*": LayerKind(mixer="attention", experts=None, rope=False)}


def layer_kinds(s: dict) -> tuple:
    """One LayerKind a layer from the letters ``layers_taken`` of the
    published ``hybrid_override_pattern``: "M" the Mamba-2 mixer alone, "E"
    the routed-expert FFN alone, "*" attention alone, which rotates
    nothing."""
    taken = s["layers_taken"]
    if len(taken) != s["num_hidden_layers"]:
        raise ValueError("layers_taken names another depth than "
                         "num_hidden_layers")
    return tuple(MIXER_OF[s["hybrid_override_pattern"][at]] for at in taken)


def transformer_config(spec, traffic, rehearse: bool) -> TransformerConfig:
    s = sizes(spec, rehearse)
    t = sizes(traffic, rehearse)
    if t["seq_len"] > s["max_position_embeddings"]:
        raise ValueError(f"sequence {t['seq_len']} exceeds "
                         f"max_position_embeddings")
    if (s["attention_bias"] or s["mlp_bias"] or s["mamba_proj_bias"]
            or s["use_bias"] or s["tie_word_embeddings"]
            or s["mlp_hidden_act"] != "relu2" or s["mamba_hidden_act"]
            != "silu" or s["n_group"] != 1 or s["topk_group"] != 1
            or s["n_shared_experts"] != 1 or not s["use_conv_bias"]
            or {"min": s["time_step_min"], "max": s["time_step_max"],
                "floor": s["time_step_floor"]} != SSM_DT_INIT):
        raise ValueError("the block of models/transformer.py has no field "
                         "for this: a bias on a projection, a tied head, "
                         "experts that are not relu2, a mixer that is not "
                         "silu, routing limited to groups of experts, "
                         "several shared experts, taps without a bias, "
                         "another range of initial step sizes")
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], head_size=s["head_dim"],
        n_layers=s["num_hidden_layers"], max_seq=t["seq_len"],
        # (the rehearsal computes in float32: configs/lfm2-8b-a1b.py)
        dtype=jnp.float32 if rehearse else jnp.bfloat16, attention="flash",
        remat=t["remat"], remat_barrier=True,
        # no rotation and no other position signal: ``assumed.no_rotation``
        positions="none", ffn="swiglu", norm="pre", norm_eps=s["norm_eps"],
        tie_embeddings=False, layers=layer_kinds(s),
        conv_kernel=s["conv_kernel"], ssm_heads=s["mamba_num_heads"],
        ssm_head_dim=s["mamba_head_dim"], ssm_state=s["ssm_state_size"],
        ssm_groups=s["n_groups"], ssm_chunk=s["chunk_size"],
        n_experts=s["router_outputs"], moe_top_k=s["num_experts_per_tok"],
        d_ff_expert=s["moe_intermediate_size"], expert_ffn="relu2",
        n_shared_experts=s["n_shared_experts"],
        d_ff_shared=s["moe_shared_expert_intermediate_size"],
        route_scale=float(s["routed_scaling_factor"]),
        route_norm=s["norm_topk_prob"], route_eps=s["route_eps"],
        experts_held=s["n_routed_experts"], first_expert=s["first_expert"],
        router_bias_rate=s["router_bias_rate"])


def with_groups(reference, cfg: TransformerConfig):
    """The reference's module with the configuration's groups of B and C
    bound into ``grads``, for a caller that passes it none (the
    conv/attention cell's ``step_grad``, which this cell runs as it is)."""
    return types.SimpleNamespace(**{
        **vars(reference),
        "grads": lambda *args: reference.grads(*args, cfg.ssm_groups)})


def mixer_errors(reference, cfg: TransformerConfig, x, want, lw) -> tuple:
    """One state-space layer with the leaves ``lw`` on the float32 normed
    input ``x [B, T, D]``, as relative L2 errors: ``(ssm_mixer, ssm_scan)``
    of TOLERANCE. The first: the program's whole mixer against ``want``,
    the reference's ``mamba(x, lw)``. The second: the program's scan ALONE,
    ``ssd_chunked`` on the reference's own operands rounded to the compute
    dtype as ``mamba_mix`` rounds them (X, B, C; the step sizes stay
    float32), against the reference's recurrence on the SAME rounded
    operands in float32: what is left is what the chunked form rounds
    inside and how it sums."""
    def off(got, want):
        return jnp.sqrt(jnp.sum(jnp.square(got.astype(jnp.float32) - want))
                        / jnp.sum(jnp.square(want)))

    with jax.default_matmul_precision("highest"):
        _, xs, dt, b, c = reference.scan_operands(x, lw, cfg.ssm_groups)
        xs, b, c = (v.astype(cfg.dtype) for v in (xs, b, c))
        want_y = reference.scan(xs.astype(jnp.float32), dt, b.astype(
            jnp.float32), c.astype(jnp.float32), lw)
    return (off(mamba_mix(x.astype(cfg.dtype), lw, cfg=cfg), want),
            off(ssd_chunked(xs, dt, -jnp.exp(lw["ssm_A_log"]), b, c,
                            lw["ssm_D"], cfg.ssm_chunk), want_y))


def scan_flops_per_token(cfg: TransformerConfig) -> float:
    """Operations of ``ssd_chunked``'s four products for ONE token of one
    state-space layer, forward: a chunk of ``L`` tokens computes ``C B^T``
    once a GROUP (2 L L N; the heads of a group share it), and a head the
    scores times ``X`` (2 L L P), the chunk's state (2 L P N) and the
    carried state read out (2 L N P)."""
    ln, p, n = cfg.ssm_chunk, cfg.ssm_head_dim, cfg.ssm_state
    return (cfg.ssm_groups * 2 * ln * ln * n
            + cfg.ssm_heads * (2 * ln * ln * p + 4 * ln * p * n)) / ln


def flops_per_sample(cfg: TransformerConfig) -> float:
    """Operations the forward and backward passes need for ONE token: what
    the objective needs of THIS chip, no recomputation.

    Matrix multiplications: 2 operations a parameter a token forward, twice
    that backward. A state-space layer: its two projections (hidden x (2 H P
    + 2 G N + H) and H P x hidden) and the chunked scan's four products
    (:func:`scan_flops_per_token`; the taps, the decays, the gate and the
    norm are elementwise and not counted); an attention layer: q and o
    (hidden x heads x head each) and k, v (hidden x KV heads x head); an
    expert layer: the router, the shared expert's two matrices and of the
    routed experts the HELD share only, ``top_k * held / experts``
    assignments a token on average, two matrices each; the untied head over
    the vocabulary slice (the embedding is a look-up). Attention: 4 * heads
    * head operations a VISIBLE (query, key) pair forward, the causal half,
    and backward twice forward."""
    d, hk = cfg.d_model, cfg.n_heads * cfg.head_dim
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    per_kind = {
        "mamba2": d * (2 * inner + 2 * gn + cfg.ssm_heads) + inner * d,
        "attention": 2 * d * hk + 2 * d * cfg.kv_heads * cfg.head_dim,
        "none": d * cfg.n_experts + 2 * d * cfg.shared_width
        + cfg.moe_top_k * cfg.held / cfg.n_experts * 2 * d * cfg.d_ff_expert}
    matmul_params = cfg.vocab_size * d + sum(
        per_kind[kind.mixer] for kind in cfg.layers)
    n_of = {m: sum(kind.mixer == m for kind in cfg.layers) for m in per_kind}
    attention = 3 * 4 * hk * n_of["attention"] * visible_pairs(cfg) \
        / cfg.max_seq
    scan = 3 * n_of["mamba2"] * scan_flops_per_token(cfg)
    return 6.0 * matmul_params + attention + scan


def kernel_costs(cfg: TransformerConfig, rows: int) -> dict:
    """What three kernels of ONE step on one chip must do, from shapes,
    whichever kernel the program picks and no recomputation counted.

    ``ssm_scan``, the state-space layers' chunked scans: the four products
    (:func:`scan_flops_per_token`) forward and twice that backward; forward
    ``X``, ``B`` and ``C`` read in bfloat16, ``dt`` in float32, ``y``
    written in float32, and a chunk's state (float32, heads x head x state)
    written and read once each; backward the same read again and as much
    written for their cotangents: three times the forward's bytes.

    ``relu2_moe_experts``, the held experts' two grouped matrix products in
    the expert layers: forward and two backward products each, over the
    EXPECTED held assignments (``rows * T * top_k * held / experts`` rows:
    an even router's); the held experts' weights read forward, read backward
    and their gradient written, the rows' activations read or written once a
    product's operand or result, all in bfloat16.

    ``kv2_attn``, the attention layers' calls: 4 * heads * head operations a
    visible pair forward and twice that backward; q and o read or written
    once forward and q, o, do, dq once backward (6 passes over [rows,
    heads, T, head] in bfloat16), k and v once forward and k, v, dk, dv
    once backward (6 passes over the KV heads)."""
    t = cfg.max_seq
    n_of = {m: sum(kind.mixer == m for kind in cfg.layers)
            for m in ("mamba2", "attention", "none")}
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    scan_flops = 3 * n_of["mamba2"] * rows * t * scan_flops_per_token(cfg)
    scan_bytes = 3 * n_of["mamba2"] * rows * (
        t * (2 * inner + 2 * 2 * gn + 4 * cfg.ssm_heads + 4 * inner)
        + 2 * 4 * (t // cfg.ssm_chunk) * inner * cfg.ssm_state)
    d, f = cfg.d_model, cfg.d_ff_expert
    held_rows = rows * t * cfg.moe_top_k * cfg.held / cfg.n_experts
    moe_flops = n_of["none"] * 2 * 3 * 2 * d * f * held_rows
    moe_bytes = n_of["none"] * 2 * (
        3 * cfg.held * 2 * d * f + 3 * held_rows * (2 * d + 2 * f))
    hk, kv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    attn_flops = rows * 3 * 4 * hk * n_of["attention"] * visible_pairs(cfg)
    attn_bytes = n_of["attention"] * 6 * rows * t * (hk + kv) * 2
    return {"ssm_scan": {"flops": scan_flops, "bytes": scan_bytes},
            "relu2_moe_experts": {"flops": moe_flops, "bytes": moe_bytes},
            "kv2_attn": {"flops": attn_flops, "bytes": attn_bytes}}


def reference_check(cfg: TransformerConfig, params, reference, seed: int,
                    rows: int, forward, loss_fn) -> tuple:
    """``(check, the reference's own choices [L, rows, T, k])``: the program
    against the float32 reference on the cell's own ``rows`` rows of
    ``max_seq`` tokens, same weights, the same share of the experts and the
    program's choices of expert (see TOLERANCE): the reference's own choices
    against them, the logits of every token, every state-space layer's
    mixer on the reference's input to it, the loss, and the counts the bias
    update is made from. ``forward(params, inputs)`` is the program's
    ``forward_routes``, jitted, and runs ALL the rows at once;
    ``loss_fn(params, inputs, targets)`` the loss the mode's train step
    differentiates. The REFERENCE takes the rows one at a time, through one
    program built for one row (PERF.md section 7 (f)). Beside the limits,
    under none (``free_*``): what the reference reads when it is LEFT to its
    own choices all the way, over the tokens whose choices all agree.
    Nothing of vocabulary width leaves the device."""
    inputs, targets = seeded_rows(cfg, seed, rows, cfg.max_seq)
    got_logits, got_routes = forward(params, inputs)
    got_loss = float(loss_fn(params, inputs, targets))
    k, n_layers = cfg.moe_top_k, len(got_routes.counts)
    mixers = [at for kind, at in zip(cfg.layers, layer_rows(cfg))
              if kind.mixer == "mamba2"]

    def given_them(params, inputs, targets, got_logits, got_expert):
        """One row: (each token's largest error over the vocabulary [T],
        the largest reference logit, the reference's own choices [L, T, k],
        the sum of the reference's losses, every state-space layer's
        :func:`mixer_errors` on the reference's input [M layers, 2])."""
        probes = []
        with jax.default_matmul_precision("highest"):
            want_logits, want_choices = reference.forward(
                to_reference(params, cfg), inputs, cfg.first_expert,
                top_k=cfg.moe_top_k, given=list(got_expert), probes=probes,
                groups=cfg.ssm_groups)
            nll = reference.cross_entropy(want_logits, targets)
        off = [jnp.stack(mixer_errors(reference, cfg, x, want, {
            name: leaf[row] for name, leaf in params[stack].items()}))
            for (stack, row), (x, want) in zip(mixers, probes)]
        err = jnp.max(jnp.abs(got_logits.astype(jnp.float32) - want_logits),
                      axis=-1)
        return (err[0], jnp.max(jnp.abs(want_logits)),
                jnp.stack(want_choices)[:, 0], jnp.sum(nll), jnp.stack(off))

    def left_free(params, inputs, got_logits, got_expert):
        """One row: (each token's largest error [T], nan where a choice of
        the token's differs; the largest reference logit)."""
        with jax.default_matmul_precision("highest"):
            free_logits, free_choices = reference.forward(
                to_reference(params, cfg), inputs, cfg.first_expert,
                top_k=cfg.moe_top_k, groups=cfg.ssm_groups)
        agreed = jnp.all(same_choices(
            got_expert, jnp.stack(free_choices)) == k, axis=0)
        err = jnp.max(jnp.abs(got_logits.astype(jnp.float32) - free_logits),
                      axis=-1)
        return (jnp.where(agreed, err, jnp.nan)[0],
                jnp.max(jnp.abs(free_logits)))

    def row(r):
        return (inputs[r:r + 1], targets[r:r + 1], got_logits[r:r + 1],
                got_routes.expert[:, r:r + 1])

    given_built = built_quickly(given_them, params, *row(0))
    free_built = built_quickly(left_free, params, *row(0)[:1], *row(0)[2:])
    by_row, free_by_row = [], []
    for r in range(rows):
        tok, tgt, logits, expert = row(r)
        by_row.append(jax.device_get(given_built(params, tok, tgt, logits,
                                                 expert)))
        free_by_row.append(jax.device_get(free_built(params, tok, logits,
                                                     expert)))
    del given_built, free_built
    err = np.stack([x[0] for x in by_row])                      # [rows, T]
    top = max(float(x[1]) for x in by_row)
    own = np.stack([x[2] for x in by_row], axis=1)      # [L, rows, T, k]
    want_loss = sum(float(x[3]) for x in by_row) / inputs.size
    mixer_off = np.stack([x[4] for x in by_row])    # [rows, M layers, 2]
    got_expert = np.asarray(got_routes.expert)
    got_counts = np.asarray(got_routes.counts)
    same = (got_expert[..., :, None] == own[..., None, :]).any(-1).sum(-1)
    flipped = (k - same).sum(axis=(1, 2))                       # [L]
    want_counts = np.stack([np.bincount(c.ravel(), minlength=cfg.n_experts)
                            for c in own])
    free_err = np.stack([x[0] for x in free_by_row])
    free_top = max(float(x[1]) for x in free_by_row)
    found = {
        "choices_off": float((k - same).sum() / same.size / k),
        "choices_off_by_layer": (flipped / (same.size / n_layers)
                                 / k).tolist(),
        "tokens_agreed": float((same == k).all(axis=0).mean()),
        "logits": float(np.quantile(err, 0.999) / top),
        "logits_worst": float(err.max() / top),
        "ssm_mixer": float(mixer_off[..., 0].max()),
        "ssm_mixer_by_layer": mixer_off[..., 0].max(axis=0).tolist(),
        "ssm_scan": float(mixer_off[..., 1].max()),
        "ssm_scan_by_layer": mixer_off[..., 1].max(axis=0).tolist(),
        "loss": abs(got_loss - want_loss) / abs(want_loss),
        "loss_program": got_loss, "loss_reference": want_loss,
        # a flipped choice takes one assignment from an expert and gives
        # one to another
        "counts_off": int(np.maximum(np.abs(
            got_counts - want_counts).sum(axis=1) - 2 * flipped, 0).max()),
        "dropped": int(np.abs(inputs.size * k
                              - got_counts.sum(axis=1)).max()),
        "held_share": (got_counts[
            :, cfg.first_expert:cfg.first_expert + cfg.held].sum(axis=1)
            / (inputs.size * k)).tolist(),
        "free_tokens_agreed": float(np.mean(~np.isnan(free_err))),
        # (None: no token's choices all agree, as under a wrong model)
        "free_logits": float(np.nanquantile(free_err, 0.999) / free_top)
        if not np.isnan(free_err).all() else None}
    return {"ok": all(found[name] <= TOLERANCE[name] for name in TOLERANCE),
            "error": found, "tolerance": TOLERANCE,
            "tokens": int(inputs.size)}, own
