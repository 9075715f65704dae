"""``qwen3-next-80b-a3b`` as the program runs it: sizes from the json beside
this file, weights from a seed on the device, operations from shapes, and
the comparison with the plain reference (``reference/qwen3-next-80b-a3b.py``).

The step itself (which entry point of the program trains this model) is in
``qwen3-next-80b-a3b.<mode>.py``, one file per mode of a traffic mix.
"""

from __future__ import annotations

import os
import types

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.transformer import (LayerKind, TransformerConfig,
                                            _gdn_mix, layer_rows)
from horovod_tpu.parallel.gated_delta import CHUNK, gated_delta_chunked

import files

# what the conv/attention cell's file already has and this one needs as it
# is: the sizes, the optimizer, the weights from a seed, the seeded rows, the
# quickly built programs, the selection bias left out of a tree
_lfm2 = files.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lfm2-8b-a1b.py"), "bench_config_lfm2")
(sizes, optimizer, make_params, visible_pairs, expert_stacks, router_bias,
 without_bias, seeded_rows, built_quickly, run_quickly_built,
 param_shardings) = (
    getattr(_lfm2, name) for name in (
        "sizes", "optimizer", "make_params", "visible_pairs",
        "expert_stacks", "router_bias", "without_bias", "seeded_rows",
        "built_quickly", "run_quickly_built", "param_shardings"))

# Worst error the comparison with the float32 reference allows, on the
# cell's own two rows of the timed length (2 x 8,192 tokens), each set
# between two readings on the v5e at the published widths (PERF.md
# section 4 has them all): what the program read over fourteen seeds
# (twelve runs of the cell and two ``benchmark/tests/qwen3next_defects.py
# forward <seed> none``; the limits were set from the first four, and the
# ten after them were read against the limits: the ranges include them), and
# what the same checks read when the reference's norms, mixers (the
# recurrence among them), experts and head round to an 8-bit
# float (float8_e4m3fn; ``qwen3next_defects.py forward <seed> float8``);
# where a planted defect reads nearer the program than the 8-bit reference
# does, the limit sits between the program and IT. The reference TAKES the
# program's choices of expert (``reference.forward``'s ``given``) and makes
# its own beside them, as in the conv/attention cell (configs/lfm2-8b-a1b.py
# TOLERANCE has why); a delta-rule layer hands a token routed otherwise on
# to every later token of its row.
# ``choices_off``: the share of the 2 x T x 10 x 4 choices of expert that
# are not the reference's own (as sets, token by token and layer by layer).
# Program 1.99e-2 to 2.06e-2 (1.0e-2 in the first layer, 2.7e-2 in the
# fourth: softmax over 512 outputs, ten chosen, near-ties at the tenth);
# 8-bit float 4.64e-1; the rotation on the whole head 2.25e-2, sigmoid
# scores 3.54e-2. Half again above the largest reading.
# ``logits``: over ALL tokens, the error that 999 in 1,000 of them stay
# under (a token's error is its largest over the vocabulary), as a share of
# the largest reference logit; the worst token is reported beside it.
# Program 3.47e-2 to 4.06e-2 (worst token 4.8e-2 to 7.5e-2); 8-bit float
# 6.95e-1; the nearest wrong model, the rotation over the whole head of 256,
# 7.03e-2: the limit sits between the program and it. About twice the other
# LM cells' reading: the attention layer adds nothing measurable to it and
# the three delta-rule layers about a third of its square (the witnesses of
# ``qwen3next_defects.py``); the rest is not split (PERF.md section 7).
# ``gdn_mixer``: per delta-rule layer, the program's WHOLE mixer
# (``_gdn_mix``) against the reference's float32 recurrence on the SAME
# normed input, the reference's own: relative L2 over a row's outputs, the
# worst layer and row. Program 6.76e-3 to 6.85e-3, every layer and seed
# within 1%; 8-bit float 2.66e-1; the delta read from the undecayed state
# 1.1e-1, the nearest defect. Most of it is the rounding of the operands to
# bfloat16, which is as stated: half again above the largest reading.
# ``gdn_scan``: per delta-rule layer, the program's chunked rule ALONE
# (``gated_delta_chunked``) against the reference's recurrence on the SAME
# operands already rounded to bfloat16 (q, k and v as the taps leave them;
# g and beta stay float32: :func:`mixer_errors`), so the operands' rounding
# cancels and what is left is what the chunked form rounds inside (the
# inverse, u, w, the state and the scores as bfloat16 operands) and how it
# sums. Program 2.88e-3 to 3.38e-3; 8-bit float 1.36e-1; the nearest
# defect 1.07e-1. 1.8 times the largest reading.
# ``loss``: share of the reference's loss. Rounding averages out over
# 16,384 tokens: program 1.6e-6 to 3.9e-5, 8-bit float 1.22e-3; it sits at
# 2.6 times the largest reading and tells the gate before the norm (5.2e-4)
# and value heads read by remainder (1.2e-4) on its own.
# ``counts_off``, ``dropped``: exact, 0.
TOLERANCE = {"choices_off": 3e-2, "logits": 5.5e-2, "gdn_mixer": 1e-2,
             "gdn_scan": 6e-3, "loss": 1e-4, "counts_off": 0, "dropped": 0}


def layer_kinds(s: dict) -> tuple:
    """One LayerKind a layer of ``layers_taken``: every
    ``full_attention_interval``-th layer (counted from 1) gated attention,
    the others the gated delta rule; every layer with the routed experts
    (``decoder_sparse_step`` 1, no ``mlp_only_layers``)."""
    taken = s["layers_taken"]
    if len(taken) != s["num_hidden_layers"]:
        raise ValueError("layers_taken names another depth than "
                         "num_hidden_layers")
    every = s["full_attention_interval"]
    return tuple(LayerKind(experts=True, mixer="attention"
                           if (at + 1) % every == 0 else "gdn")
                 for at in taken)


def transformer_config(spec, traffic, rehearse: bool) -> TransformerConfig:
    s = sizes(spec, rehearse)
    t = sizes(traffic, rehearse)
    if t["seq_len"] > s["max_position_embeddings"]:
        raise ValueError(f"sequence {t['seq_len']} exceeds "
                         f"max_position_embeddings")
    if (s["hidden_act"] != "silu" or s["decoder_sparse_step"] != 1
            or s["mlp_only_layers"] or s["use_sliding_window"]
            or s["rope_scaling"] is not None or s["tie_word_embeddings"]
            or not s["norm_topk_prob"]
            or s["linear_num_value_heads"] % s["linear_num_key_heads"]):
        raise ValueError("the block of models/transformer.py has no field "
                         "for this: experts that are not silu, dense "
                         "layers between the expert layers, a sliding "
                         "window, scaled rotation, a tied head, weights "
                         "not normalised over the chosen experts, value "
                         "heads that do not share key heads evenly")
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"],
        n_kv_heads=s["num_key_value_heads"], head_size=s["head_dim"],
        n_layers=s["num_hidden_layers"], d_ff=s["intermediate_size"],
        max_seq=t["seq_len"],
        # (the rehearsal computes in float32: configs/lfm2-8b-a1b.py)
        dtype=jnp.float32 if rehearse else jnp.bfloat16, attention="flash",
        remat=t["remat"], remat_barrier=True, positions="rope",
        rope_theta=float(s["rope_theta"]),
        rope_dim=int(s["head_dim"] * s["partial_rotary_factor"]),
        ffn="swiglu", norm="pre", norm_eps=s["rms_norm_eps"],
        zero_centred_norms=True, tie_embeddings=False, qk_norm=True,
        attn_gate=True, layers=layer_kinds(s),
        conv_kernel=s["linear_conv_kernel_dim"],
        gdn_key_heads=s["linear_num_key_heads"],
        gdn_value_heads=s["linear_num_value_heads"],
        gdn_key_dim=s["linear_key_head_dim"],
        gdn_value_dim=s["linear_value_head_dim"],
        n_experts=s["router_outputs"], moe_top_k=s["num_experts_per_tok"],
        d_ff_expert=s["moe_intermediate_size"], n_shared_experts=1,
        d_ff_shared=s["shared_expert_intermediate_size"], shared_gate=True,
        route_score="softmax", route_norm=s["norm_topk_prob"],
        route_eps=s["route_eps"], experts_held=s["num_experts"],
        first_expert=s["first_expert"], router_bias_rate=0.0)


def with_key_heads(reference, cfg: TransformerConfig):
    """The reference's module with the configuration's key heads bound into
    ``grads``, for a caller that passes it none (the conv/attention cell's
    ``step_grad``, which this cell runs as it is)."""
    return types.SimpleNamespace(**{
        **vars(reference),
        "grads": lambda *args: reference.grads(
            *args, key_heads=cfg.gdn_key_heads)})


def gdn_params(cfg: TransformerConfig) -> int:
    """Parameters of one delta-rule layer's mixer that a token multiplies:
    the three projections."""
    qk = cfg.gdn_key_heads * cfg.gdn_key_dim
    vv = cfg.gdn_value_heads * cfg.gdn_value_dim
    return cfg.d_model * (2 * qk + 2 * vv + 2 * cfg.gdn_value_heads) \
        + vv * cfg.d_model


def attention_params(cfg: TransformerConfig) -> int:
    """... of one gated attention layer's mixer: q, its gate and o (hidden x
    heads x head each), k and v (hidden x KV heads x head)."""
    hk, kv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return 3 * cfg.d_model * hk + 2 * cfg.d_model * kv


def scan_flops_per_token(cfg: TransformerConfig) -> float:
    """Operations of ``gated_delta_chunked``'s products for ONE token of one
    delta-rule layer, forward. A chunk of ``L`` tokens computes, a KEY head,
    ``K K^T`` and ``Q K^T`` (2 L L dk each); a VALUE head the solve's least
    work (the unit lower triangular inverse, L^3 / 3 multiply-adds), ``u``
    and ``w`` (2 L L dv and 2 L L dk), what the state gives each token and
    its output (2 L dk dv each), the chunk's state (2 L dk dv) and the
    scores times the written values (2 L L dv)."""
    ln, dk, dv = CHUNK, cfg.gdn_key_dim, cfg.gdn_value_dim
    per_key_head = 2 * 2 * ln * ln * dk
    per_value_head = (2 * ln ** 3 / 3 + 2 * ln * ln * (dv + dk)
                      + 3 * 2 * ln * dk * dv + 2 * ln * ln * dv)
    return (cfg.gdn_key_heads * per_key_head
            + cfg.gdn_value_heads * per_value_head) / ln


def n_of(cfg: TransformerConfig, mixer: str) -> int:
    return sum(kind.mixer == mixer for kind in cfg.layers)


def flops_per_sample(cfg: TransformerConfig) -> float:
    """Operations the forward and backward passes need for ONE token: what
    the objective needs of THIS chip, no recomputation.

    Matrix multiplications: 2 operations a parameter a token forward, twice
    that backward. A delta-rule layer: its three projections
    (:func:`gdn_params`) and the chunked rule's products
    (:func:`scan_flops_per_token`; the taps, the norms, the decays and the
    gate are elementwise and not counted); an attention layer: its five
    matrices (:func:`attention_params`); every layer's router, the shared
    expert's three matrices and its gate, and of the routed experts the
    HELD share only, ``top_k * held / experts`` assignments a token on
    average, three matrices each; the untied head over the vocabulary slice
    (the embedding is a look-up). Attention: 4 * heads * head operations a
    VISIBLE (query, key) pair forward, the causal half, and backward twice
    forward."""
    d = cfg.d_model
    expert_layer = (d * cfg.n_experts + 3 * d * cfg.shared_width + d
                    + cfg.moe_top_k * cfg.held / cfg.n_experts
                    * 3 * d * cfg.d_ff_expert)
    matmul_params = cfg.vocab_size * d + len(cfg.layers) * expert_layer \
        + n_of(cfg, "gdn") * gdn_params(cfg) \
        + n_of(cfg, "attention") * attention_params(cfg)
    attention = 3 * 4 * cfg.n_heads * cfg.head_dim * n_of(
        cfg, "attention") * visible_pairs(cfg) / cfg.max_seq
    scan = 3 * n_of(cfg, "gdn") * scan_flops_per_token(cfg)
    return 6.0 * matmul_params + attention + scan


def kernel_costs(cfg: TransformerConfig, rows: int) -> dict:
    """What three kernels of ONE step on one chip must do, from shapes,
    whichever kernel the program picks and no recomputation counted.

    ``gdn_scan``, the delta-rule layers' chunked rules: the products
    (:func:`scan_flops_per_token`) forward and twice that backward; forward
    q and k (key heads) and v read in bfloat16, g and beta in float32, o
    written in float32, and a chunk's incoming state (float32, value heads
    x dk x dv) written and read once each; backward the same read again and
    as much written for their cotangents: three times the forward's bytes.

    ``h256_attn``, the attention layers' calls at heads of 256: 4 * heads *
    head operations a visible pair forward and twice that backward; q and o
    read or written once forward and q, o, do, dq once backward (6 passes
    over [rows, heads, T, head] in bfloat16), k and v once forward and k,
    v, dk, dv once backward (6 passes over the KV heads).

    ``e512_moe_experts``, the held experts' three grouped matrix products:
    forward and two backward products each, over the EXPECTED held
    assignments (``rows * T * top_k * held / experts`` rows: an even
    router's); the held experts' weights read forward, read backward and
    their gradient written, the rows' activations read or written once a
    product's operand or result, all in bfloat16."""
    t = cfg.max_seq
    n_gdn = n_of(cfg, "gdn")
    qk = cfg.gdn_key_heads * cfg.gdn_key_dim
    vv = cfg.gdn_value_heads * cfg.gdn_value_dim
    scan_flops = 3 * n_gdn * rows * t * scan_flops_per_token(cfg)
    scan_bytes = 3 * n_gdn * rows * (
        t * (2 * 2 * qk + 2 * vv + 2 * 4 * cfg.gdn_value_heads + 4 * vv)
        + 2 * 4 * (t // CHUNK) * vv * cfg.gdn_key_dim)
    hk, kv = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    n_attention = n_of(cfg, "attention")
    attn_flops = rows * 3 * 4 * hk * n_attention * visible_pairs(cfg)
    attn_bytes = n_attention * 6 * rows * t * (hk + kv) * 2
    d, f = cfg.d_model, cfg.d_ff_expert
    held_rows = rows * t * cfg.moe_top_k * cfg.held / cfg.n_experts
    n_expert_layers = len(cfg.layers)
    moe_flops = n_expert_layers * 3 * 3 * 2 * d * f * held_rows
    moe_bytes = n_expert_layers * 2 * (
        3 * cfg.held * 3 * d * f + 3 * held_rows * (2 * d + 3 * f))
    return {"gdn_scan": {"flops": scan_flops, "bytes": scan_bytes},
            "h256_attn": {"flops": attn_flops, "bytes": attn_bytes},
            "e512_moe_experts": {"flops": moe_flops, "bytes": moe_bytes}}


# -- the published layout ----------------------------------------------------

def qkvz_published(cfg: TransformerConfig) -> np.ndarray:
    """Where the program's ``gdn_qkvz`` columns (``[q | k | v | z]``) stand
    in the PUBLISHED ``in_proj_qkvz``, grouped per key head ``h``: ``[q_h |
    k_h | v of its value heads | z of its value heads]``. The published
    column ``i`` is the program's ``qkvz_published(cfg)[i]``."""
    hk, dk, dv = cfg.gdn_key_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    r, qk = cfg.gdn_value_heads // hk, hk * dk
    vv = cfg.gdn_value_heads * dv
    return np.concatenate([np.concatenate([
        h * dk + np.arange(dk), qk + h * dk + np.arange(dk),
        2 * qk + h * r * dv + np.arange(r * dv),
        2 * qk + vv + h * r * dv + np.arange(r * dv)]) for h in range(hk)])


def ba_published(cfg: TransformerConfig) -> np.ndarray:
    """... of ``gdn_ba`` (``[b | a]``) in the published ``in_proj_ba``,
    per key head ``[b | a]`` of its value heads."""
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    r = hv // hk
    return np.concatenate([np.concatenate([h * r + np.arange(r),
                                           hv + h * r + np.arange(r)])
                           for h in range(hk)])


MOE_LEAVES = ("ln2", "router", "ewg", "ewu", "ewd", "shared_wg",
              "shared_wu", "shared_wd", "shared_gate")


def layer_to_reference(lw: dict, cfg: TransformerConfig) -> dict:
    """One layer's leaves as the reference takes them: the published names
    and columns; the selection bias (0, and no part of the model) left
    out."""
    out = {k: lw[k] for k in ("ln1",) + MOE_LEAVES}
    d = cfg.d_model
    if "gdn_qkvz" in lw:
        out.update(in_proj_qkvz=lw["gdn_qkvz"][:, qkvz_published(cfg)],
                   in_proj_ba=lw["gdn_ba"][:, ba_published(cfg)],
                   conv_w=lw["gdn_conv_w"], A_log=lw["gdn_A_log"],
                   dt_bias=lw["gdn_dt_bias"], norm=lw["gdn_norm"],
                   out_proj=lw["gdn_out"])
    else:
        out.update(q_proj=jnp.stack([lw["wq"], lw["wgate"]], axis=2
                                    ).reshape(d, -1),
                   k_proj=lw["wk"].reshape(d, -1),
                   v_proj=lw["wv"].reshape(d, -1),
                   o_proj=lw["wo"].reshape(-1, d),
                   q_norm=lw["q_norm"], k_norm=lw["k_norm"])
    return out


def layer_from_reference(lw: dict, cfg: TransformerConfig) -> dict:
    """The inverse of :func:`layer_to_reference` (a gradient's layer),
    without the selection bias."""
    out = {k: lw[k] for k in ("ln1",) + MOE_LEAVES}
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if "in_proj_qkvz" in lw:
        out.update(gdn_qkvz=lw["in_proj_qkvz"][:, np.argsort(
                       qkvz_published(cfg))],
                   gdn_ba=lw["in_proj_ba"][:, np.argsort(ba_published(cfg))],
                   gdn_conv_w=lw["conv_w"], gdn_A_log=lw["A_log"],
                   gdn_dt_bias=lw["dt_bias"], gdn_norm=lw["norm"],
                   gdn_out=lw["out_proj"])
    else:
        both = lw["q_proj"].reshape(d, h, 2, dh)
        out.update(wq=both[:, :, 0], wgate=both[:, :, 1],
                   wk=lw["k_proj"].reshape(d, hkv, dh),
                   wv=lw["v_proj"].reshape(d, hkv, dh),
                   wo=lw["o_proj"].reshape(h, dh, d),
                   q_norm=lw["q_norm"], k_norm=lw["k_norm"])
    return out


def to_reference(params, cfg: TransformerConfig) -> dict:
    """The program's parameters (every kind of layer's leaves stacked on a
    leading axis of a stack of its own) as the plain reference takes them:
    one dict a layer in the order they run, in the published layout."""
    stacks = {stack for stack, _ in layer_rows(cfg)}
    out = {k: v for k, v in params.items() if k not in stacks}
    out["layers"] = [layer_to_reference(
        {k: v[row] for k, v in params[stack].items()}, cfg)
        for stack, row in layer_rows(cfg)]
    return out


def from_reference(weights, cfg: TransformerConfig) -> dict:
    """A tree in the reference's form (its gradient), stacked as the
    program's, without the selection bias."""
    out = {k: v for k, v in weights.items() if k != "layers"}
    by_stack = {}
    for lw, (stack, _) in zip(weights["layers"], layer_rows(cfg)):
        by_stack.setdefault(stack, []).append(layer_from_reference(lw, cfg))
    for stack, found in by_stack.items():
        out[stack] = {k: jnp.stack([lw[k] for lw in found])
                      for k in found[0]}
    return out


def mixer_errors(reference, cfg: TransformerConfig, x, want, lp,
                 lw) -> tuple:
    """One delta-rule layer, the program's leaves ``lp`` and the same in
    the reference's form ``lw``, on the float32 normed input ``x [B, T,
    D]``, as relative L2 errors: ``(gdn_mixer, gdn_scan)`` of TOLERANCE.
    The first: the program's whole mixer against ``want``, the reference's
    ``gdn(x, lw)``. The second: the program's rule ALONE,
    ``gated_delta_chunked`` on the reference's own operands rounded to the
    compute dtype as ``_gdn_mix`` rounds them (q, k and v as the taps leave
    them; g and beta stay float32), against the reference's recurrence on
    the SAME rounded operands in float32."""
    def off(got, want):
        return jnp.sqrt(jnp.sum(jnp.square(got.astype(jnp.float32) - want))
                        / jnp.sum(jnp.square(want)))

    with jax.default_matmul_precision("highest"):
        q, k, v, _, g, beta = reference.gdn_operands(x, lw,
                                                     cfg.gdn_key_heads)
        q, k, v = (a.astype(cfg.dtype) for a in (q, k, v))
        want_o = reference.recurrence(*(a.astype(jnp.float32)
                                        for a in (q, k, v)), g, beta)
    return (off(_gdn_mix(x.astype(cfg.dtype), lp, cfg=cfg), want),
            off(gated_delta_chunked(q, k, v, g, beta), want_o))


def reference_check(cfg: TransformerConfig, params, reference, seed: int,
                    rows: int, forward, loss_fn) -> tuple:
    """``(check, the reference's own choices [L, rows, T, k])``: the program
    against the float32 reference on the cell's own ``rows`` rows of
    ``max_seq`` tokens, same weights, the same share of the experts and the
    program's choices of expert (see TOLERANCE): the reference's own choices
    against them, the logits of every token, every delta-rule layer's mixer
    and rule on the reference's input to it, the loss, and the counts.
    ``forward(params, inputs)`` is the program's ``forward_routes``, jitted,
    and runs ALL the rows at once; ``loss_fn(params, inputs, targets)`` the
    loss the mode's train step differentiates. The REFERENCE takes the rows
    one at a time, through one program built for one row (PERF.md section 7
    (f)). Nothing of vocabulary width leaves the device."""
    inputs, targets = seeded_rows(cfg, seed, rows, cfg.max_seq)
    got_logits, got_routes = forward(params, inputs)
    got_loss = float(loss_fn(params, inputs, targets))
    k, n_layers = cfg.moe_top_k, len(got_routes.counts)
    mixers = [at for kind, at in zip(cfg.layers, layer_rows(cfg))
              if kind.mixer == "gdn"]

    def given_them(params, inputs, targets, got_logits, got_expert):
        """One row: (each token's largest error over the vocabulary [T],
        the largest reference logit, the reference's own choices [L, T, k],
        the sum of the reference's losses, every delta-rule layer's
        :func:`mixer_errors` on the reference's input [G layers, 2])."""
        probes = []
        weights = to_reference(params, cfg)
        with jax.default_matmul_precision("highest"):
            want_logits, want_choices = reference.forward(
                weights, inputs, cfg.first_expert, top_k=cfg.moe_top_k,
                given=list(got_expert), probes=probes,
                key_heads=cfg.gdn_key_heads)
            nll = reference.cross_entropy(want_logits, targets)
        by_layer = [lw for lw, (stack, _) in zip(weights["layers"],
                                                 layer_rows(cfg))
                    if "in_proj_qkvz" in lw]
        off = [jnp.stack(mixer_errors(reference, cfg, x, want, {
            name: leaf[row] for name, leaf in params[stack].items()}, lw))
            for (stack, row), (x, want), lw in zip(mixers, probes,
                                                    by_layer)]
        err = jnp.max(jnp.abs(got_logits.astype(jnp.float32) - want_logits),
                      axis=-1)
        return (err[0], jnp.max(jnp.abs(want_logits)),
                jnp.stack(want_choices)[:, 0], jnp.sum(nll), jnp.stack(off))

    def row(r):
        return (inputs[r:r + 1], targets[r:r + 1], got_logits[r:r + 1],
                got_routes.expert[:, r:r + 1])

    given_built = built_quickly(given_them, params, *row(0))
    by_row = [jax.device_get(given_built(params, *row(r)))
              for r in range(rows)]
    del given_built
    err = np.stack([x[0] for x in by_row])                      # [rows, T]
    top = max(float(x[1]) for x in by_row)
    own = np.stack([x[2] for x in by_row], axis=1)      # [L, rows, T, k]
    want_loss = sum(float(x[3]) for x in by_row) / inputs.size
    mixer_off = np.stack([x[4] for x in by_row])    # [rows, G layers, 2]
    got_expert = np.asarray(got_routes.expert)
    got_counts = np.asarray(got_routes.counts)
    same = (got_expert[..., :, None] == own[..., None, :]).any(-1).sum(-1)
    flipped = (k - same).sum(axis=(1, 2))                       # [L]
    want_counts = np.stack([np.bincount(c.ravel(), minlength=cfg.n_experts)
                            for c in own])
    found = {
        "choices_off": float((k - same).sum() / same.size / k),
        "choices_off_by_layer": (flipped / (same.size / n_layers)
                                 / k).tolist(),
        "tokens_agreed": float((same == k).all(axis=0).mean()),
        "logits": float(np.quantile(err, 0.999) / top),
        "logits_worst": float(err.max() / top),
        "gdn_mixer": float(mixer_off[..., 0].max()),
        "gdn_mixer_by_layer": mixer_off[..., 0].max(axis=0).tolist(),
        "gdn_scan": float(mixer_off[..., 1].max()),
        "gdn_scan_by_layer": mixer_off[..., 1].max(axis=0).tolist(),
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
            / (inputs.size * k)).tolist()}
    return {"ok": all(found[name] <= TOLERANCE[name] for name in TOLERANCE),
            "error": found, "tolerance": TOLERANCE,
            "tokens": int(inputs.size)}, own
