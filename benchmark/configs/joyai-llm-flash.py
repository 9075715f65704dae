"""``joyai-llm-flash`` as the program runs it: sizes from the json beside this
file, weights from a seed on the device, operations from shapes, and the
comparison with the plain reference (``reference/joyai-llm-flash.py``).

The step itself (which entry point of the program trains this model) is in
``joyai-llm-flash.<mode>.py``, one file per mode of a traffic mix.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models.transformer import (MTP, LayerKind,
                                            TransformerConfig, _mla_mix,
                                            _rope_tables, layer_rows)

import files

# what the conv/attention cell's file already has and this one needs as it
# is: the sizes, the optimizer, the weights from a seed, the seeded rows, the
# quickly built programs
_lfm2 = files.load_module(os.path.join(os.path.dirname(os.path.abspath(
    __file__)), "lfm2-8b-a1b.py"), "bench_config_lfm2")
(sizes, optimizer, make_params, visible_pairs, seeded_rows, built_quickly,
 run_quickly_built, param_shardings) = (
    getattr(_lfm2, name) for name in (
        "sizes", "optimizer", "make_params", "visible_pairs", "seeded_rows",
        "built_quickly", "run_quickly_built", "param_shardings"))

# Worst error the comparison with the float32 reference allows, on the
# cell's own two rows of the timed length (2 x 8,192 tokens), each set
# between two readings on the v5e at the published widths (PR 41; PERF.md
# section 4 has them all): what the program read over its seeds (seven when
# the limits were set: six runs of the cell and one of ``benchmark/tests/
# joyai_defects.py <seed> none``; four more were read against them after:
# the ranges here are of all eleven), and what the same checks read when
# the reference's norms, attention, FFNs and heads round to an 8-bit float
# (float8_e4m3fn; ``joyai_defects.py <seed> float8`` on the chip); where a
# planted defect reads nearer the program than the 8-bit reference does (the
# q norm left out, the nearest wrong model), the limit sits between the
# program and IT. The reference TAKES the program's choices of expert
# (``reference.forward``'s ``given``) and makes its own beside them, as in
# the conv/attention cell (configs/lfm2-8b-a1b.py TOLERANCE has why).
# ``choices_off``: the share of the 2 x T x 8 x 5 choices of expert (the
# module's block's among them) that are not the reference's own (as sets).
# Program 1.64e-2 to 1.75e-2 (1.3e-2 in the first expert layer, 2.1e-2 in the
# fourth); 8-bit float 1.15e-1; the q norm left out 2.54e-2.
# ``logits``, ``mtp_logits``: of the main head and of the module's, over ALL
# tokens, the error that 999 in 1,000 of them stay under (a token's error is
# its largest over the vocabulary), as a share of the head's largest
# reference logit; the worst token is reported beside each. Program 1.71e-2
# to 1.84e-2 (worst token 2.29e-2) and 1.34e-2 to 1.56e-2 (1.75e-2); 8-bit
# float 1.38e-1 and 1.04e-1; the q norm left out 5.8e-2 and 4.0e-2.
# ``mla_mixer``: per layer (the module's block the sixth), the program's
# WHOLE mixer (``_mla_mix``) against the reference's float32 latent attention
# on the SAME normed input, the reference's own: relative L2 over a row's
# outputs, the worst layer and row. Program 6.78e-3 to 6.89e-3, the dense
# layer's on every seed (its input is the normed embedding; the other five
# read 3.4e-3 to 3.9e-3); 8-bit float 4.6e-2; the q norm left out 2.1e-2, the
# KV norm 5.4e-2, every other defect of the attention 2.2e-1 and more.
# ``loss_main``, ``loss_mtp``: each term as a share of the reference's, apart.
# Rounding averages out over 16,384 tokens: program 2.0e-6 to 2.4e-5 and
# 1.9e-6 to 2.4e-5; 8-bit float 8.4e-6 and 1.9e-4. With uniform random tokens
# every target's cross-entropy is ln 16,160 give or take its sampling noise,
# so these limits tell a wrong model by little: a module scored against the
# wrong token reads 7.6e-4 in ``loss_mtp``, its last position left unmasked
# 1.4e-4, the rotation on the whole head 1.1e-3 in ``loss_main``
# (``step_grad`` tells the first by far: 3.2). They sit at a little over
# three times the largest reading.
# ``counts_off``, ``dropped``: exact, 0.
TOLERANCE = {"choices_off": 2.5e-2, "logits": 3e-2, "mtp_logits": 3e-2,
             "mla_mixer": 1.2e-2, "loss_main": 8e-5, "loss_mtp": 8e-5,
             "counts_off": 0, "dropped": 0}


def layer_kinds(s: dict) -> tuple:
    """One LayerKind a layer: latent attention throughout, the first
    ``first_k_dense_replace`` with the dense FFN, every ``moe_layer_freq``-th
    after them (1: all) with the routed experts."""
    if s["moe_layer_freq"] != 1:
        raise ValueError("moe_layer_freq other than 1: dense layers between "
                         "the expert layers have no place in layers_taken")
    if len(s["layers_taken"]) != s["num_hidden_layers"]:
        raise ValueError("layers_taken names another depth than "
                         "num_hidden_layers")
    return tuple(LayerKind(mixer="mla",
                           experts=at >= s["first_k_dense_replace"])
                 for at in s["layers_taken"])


def transformer_config(spec, traffic, rehearse: bool) -> TransformerConfig:
    s = sizes(spec, rehearse)
    t = sizes(traffic, rehearse)
    if t["seq_len"] > s["max_position_embeddings"]:
        raise ValueError(f"sequence {t['seq_len']} exceeds "
                         f"max_position_embeddings")
    if (s["attention_bias"] or s["tie_word_embeddings"]
            or s["rope_scaling"] is not None or not s["rope_interleave"]
            or s["hidden_act"] != "silu" or s["scoring_func"] != "sigmoid"
            or s["topk_method"] != "noaux_tc" or s["n_group"] != 1
            or s["topk_group"] != 1 or s["num_nextn_predict_layers"] != 1
            or s["num_key_value_heads"] != s["num_attention_heads"]
            or s["qk_head_dim"] != s["qk_nope_head_dim"]
            + s["qk_rope_head_dim"]):
        raise ValueError("the block of models/transformer.py has no field "
                         "for this: a bias on a projection, a tied head, "
                         "scaled rotation, experts that are not silu, "
                         "scores that are not sigmoid, routing limited to "
                         "groups of experts, another number of "
                         "multi-token-prediction modules than one, fewer "
                         "KV heads than query heads under latent attention")
    return TransformerConfig(
        vocab_size=s["vocab_size"], d_model=s["hidden_size"],
        n_heads=s["num_attention_heads"], n_layers=s["num_hidden_layers"],
        d_ff=s["intermediate_size"], max_seq=t["seq_len"],
        # (the rehearsal computes in float32: configs/lfm2-8b-a1b.py)
        dtype=jnp.float32 if rehearse else jnp.bfloat16, attention="flash",
        remat=t["remat"], remat_barrier=True, positions="rope",
        rope_theta=float(s["rope_theta"]), ffn="swiglu", norm="pre",
        norm_eps=s["rms_norm_eps"], tie_embeddings=False,
        layers=layer_kinds(s), q_lora_rank=s["q_lora_rank"],
        kv_lora_rank=s["kv_lora_rank"], qk_nope_dim=s["qk_nope_head_dim"],
        qk_rope_dim=s["qk_rope_head_dim"], v_head_dim=s["v_head_dim"],
        n_experts=s["router_outputs"], moe_top_k=s["num_experts_per_tok"],
        d_ff_expert=s["moe_intermediate_size"],
        n_shared_experts=s["n_shared_experts"],
        route_scale=float(s["routed_scaling_factor"]),
        route_norm=s["norm_topk_prob"], route_eps=s["route_eps"],
        experts_held=s["n_routed_experts"], first_expert=s["first_expert"],
        router_bias_rate=s["router_bias_rate"],
        mtp_depth=s["num_nextn_predict_layers"], mtp_weight=s["mtp_weight"])


def latent_params(cfg: TransformerConfig) -> int:
    """Parameters of one layer's latent attention that a token multiplies."""
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return (d * cfg.q_lora_rank + cfg.q_lora_rank * h * (dn + dr)
            + d * (cfg.kv_lora_rank + dr) + cfg.kv_lora_rank * h * (dn + dv)
            + h * dv * d)


def attention_flops_per_pair(cfg: TransformerConfig) -> int:
    """Operations of one layer's attention core for ONE visible (query, key)
    pair, forward, at the PUBLISHED head sizes: the scores over ``qk_nope_dim
    + qk_rope_dim`` and the weighted values over ``v_head_dim``, every
    head."""
    return 2 * cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim
                              + cfg.v_head_dim)


def flops_per_sample(cfg: TransformerConfig) -> float:
    """Operations the forward and backward passes need for ONE token: what
    the objective needs of THIS chip, no recomputation.

    Matrix multiplications: 2 operations a parameter a token forward, twice
    that backward. Every layer's latent attention (its five matrices:
    :func:`latent_params`), the multi-token-prediction module's block among
    them; the dense layers' three SwiGLU matrices; in an expert layer (the
    module's too) the router, the shared expert's three matrices and of the
    routed experts the HELD share only, ``top_k * held / experts``
    assignments a token on average, three matrices each; the module's
    ``proj`` (2 hidden x hidden); the untied head over the vocabulary slice
    TWICE, once a head (the embedding's two uses are look-ups). Attention:
    :func:`attention_flops_per_pair` a VISIBLE pair forward, the causal half,
    in every layer and the module's block, backward twice forward."""
    d = cfg.d_model
    expert_layer = (d * cfg.n_experts + 3 * d * cfg.shared_width
                    + cfg.moe_top_k * cfg.held / cfg.n_experts
                    * 3 * d * cfg.d_ff_expert)
    kinds = cfg.layers + (cfg.layers[-1],) * cfg.mtp_depth
    matmul_params = (1 + cfg.mtp_depth) * cfg.vocab_size * d \
        + cfg.mtp_depth * 2 * d * d
    for kind in kinds:
        matmul_params += latent_params(cfg) + (
            expert_layer if kind.experts else 3 * d * cfg.d_ff)
    attention = 3 * attention_flops_per_pair(cfg) * len(kinds) \
        * visible_pairs(cfg) / cfg.max_seq
    return 6.0 * matmul_params + attention


def kernel_costs(cfg: TransformerConfig, rows: int) -> dict:
    """What two kernels of ONE step on one chip must do, from shapes,
    whichever kernel the program picks, no recomputation and no padding
    counted.

    ``mla_attn``, the attention calls of every layer and of the
    multi-token-prediction module's block, at the PUBLISHED head sizes (a
    kernel that lays a head of 192 out on 256 lanes does more, and reads a
    smaller share for it): :func:`attention_flops_per_pair` a visible pair
    forward and twice that backward; q and k (heads of ``qk_nope_dim +
    qk_rope_dim``) and v and o (heads of ``v_head_dim``) read or written
    once forward and each with its cotangent once backward: 3 passes over
    each of the four in bfloat16.

    ``ep32_moe_experts``, the held experts' three grouped matrix products in
    the expert layers (the module's too): forward and two backward products
    each, over the EXPECTED held assignments (``rows * T * top_k * held /
    experts`` rows: an even router's); the held experts' weights read
    forward, read backward and their gradient written, the rows' activations
    read or written once a product's operand or result, all in bfloat16."""
    t = cfg.max_seq
    kinds = cfg.layers + (cfg.layers[-1],) * cfg.mtp_depth
    attn_flops = rows * 3 * attention_flops_per_pair(cfg) * len(kinds) \
        * visible_pairs(cfg)
    attn_bytes = len(kinds) * 3 * rows * t * cfg.n_heads * 2 * (
        2 * (cfg.qk_nope_dim + cfg.qk_rope_dim) + 2 * cfg.v_head_dim)
    n_expert_layers = sum(bool(kind.experts) for kind in kinds)
    d, f = cfg.d_model, cfg.d_ff_expert
    held_rows = rows * t * cfg.moe_top_k * cfg.held / cfg.n_experts
    moe_flops = n_expert_layers * 3 * 3 * 2 * d * f * held_rows
    moe_bytes = n_expert_layers * 2 * (
        3 * cfg.held * 3 * d * f + 3 * held_rows * (2 * d + 3 * f))
    return {"mla_attn": {"flops": attn_flops, "bytes": attn_bytes},
            "ep32_moe_experts": {"flops": moe_flops, "bytes": moe_bytes}}


def pairs_side_by_side(cfg: TransformerConfig):
    """Where the program's rotated columns stand in the PUBLISHED order.
    The program rotates halves (``_rope``: column ``i`` with column ``i +
    dr / 2``), the model is published with the pairs side by side
    (``rope_interleave``: ``2i`` with ``2i + 1``), so the published column
    ``2i`` is the program's ``i`` and ``2i + 1`` its ``i + dr / 2``: the
    scores do not change under one permutation of q's and k's columns."""
    half = cfg.qk_rope_dim // 2
    return np.stack([np.arange(half), half + np.arange(half)], 1).ravel()


def _rotated_columns(lw: dict, cfg: TransformerConfig, order) -> dict:
    """One layer's leaves with the rotated columns of ``wq_b`` and ``wkv_a``
    taken in ``order``."""
    dn, rkv = cfg.qk_nope_dim, cfg.kv_lora_rank
    return {**lw,
            "wq_b": jnp.concatenate([lw["wq_b"][..., :dn],
                                     lw["wq_b"][..., dn:][..., order]], -1),
            "wkv_a": jnp.concatenate([lw["wkv_a"][..., :rkv],
                                      lw["wkv_a"][..., rkv:][..., order]],
                                     -1)}


MODULE_LEAVES = ("enorm", "hnorm", "proj", "ln_f")


def to_reference(params, cfg: TransformerConfig) -> dict:
    """The program's parameters (every kind of layer's leaves stacked on a
    leading axis of a stack of its own, the module's under ``mtp``) as the
    plain reference takes them: one dict a layer in the order they run, the
    module's block a dict of its own, the rotated columns in the published
    order (:func:`pairs_side_by_side`)."""
    order = pairs_side_by_side(cfg)
    stacks = {stack for stack, _ in layer_rows(cfg)} | {MTP}
    out = {k: v for k, v in params.items() if k not in stacks}
    out["layers"] = [_rotated_columns(
        {k: v[row] for k, v in params[stack].items()}, cfg, order)
        for stack, row in layer_rows(cfg)]
    mp = {k: v[0] for k, v in params[MTP].items()}
    out["mtp"] = {**{k: mp[k] for k in MODULE_LEAVES},
                  "block": _rotated_columns(
                      {k: v for k, v in mp.items()
                       if k not in MODULE_LEAVES}, cfg, order)}
    return out


def from_reference(weights, cfg: TransformerConfig) -> dict:
    """A tree in the reference's form (its gradient), stacked as the
    program's, the rotated columns back in the program's order."""
    back = np.argsort(pairs_side_by_side(cfg))
    out = {k: v for k, v in weights.items() if k not in ("layers", "mtp")}
    by_stack = {}
    for lw, (stack, _) in zip(weights["layers"], layer_rows(cfg)):
        by_stack.setdefault(stack, []).append(
            _rotated_columns(lw, cfg, back))
    for stack, found in by_stack.items():
        out[stack] = {k: jnp.stack([lw[k] for lw in found])
                      for k in found[0]}
    mw = weights["mtp"]
    out[MTP] = {k: v[None] for k, v in {
        **{k: mw[k] for k in MODULE_LEAVES},
        **_rotated_columns(mw["block"], cfg, back)}.items()}
    return out


def expert_rows(cfg: TransformerConfig) -> list:
    """``(stack, row)`` of every expert layer in the order they run, the
    multi-token-prediction module's block (``params["mtp"]``, row 0) the
    last."""
    return [at for kind, at in zip(cfg.layers, layer_rows(cfg))
            if kind.experts] + [(MTP, 0)] * cfg.mtp_depth


def expert_stacks(cfg: TransformerConfig) -> set:
    return {stack for stack, _ in expert_rows(cfg)}


def router_bias(params, cfg: TransformerConfig):
    """[expert layers in the order they run, experts]: every expert
    layer's selection bias."""
    return jnp.stack([params[stack]["router_bias"][row]
                      for stack, row in expert_rows(cfg)])


def without_bias(tree, cfg: TransformerConfig) -> dict:
    """``tree`` (parameters, a gradient, a moment) less the selection bias,
    whose gradient is 0 on both sides."""
    stacks = expert_stacks(cfg)
    return {k: ({n: v for n, v in leaves.items() if n != "router_bias"}
                if k in stacks else leaves) for k, leaves in tree.items()}


def mixer_rows(cfg: TransformerConfig) -> list:
    """``(stack, row)`` of every latent-attention mixer in the order the
    reference probes them: the layers', then the module's block's."""
    return layer_rows(cfg) + [(MTP, 0)] * cfg.mtp_depth


def mixer_error(cfg: TransformerConfig, x, want, lp):
    """The program's whole mixer with one layer's leaves ``lp`` on the
    float32 normed input ``x [B, T, D]`` against ``want``, the reference's
    latent attention of the same input: relative L2."""
    got = _mla_mix(x.astype(cfg.dtype), lp, cfg=cfg,
                   rope=_rope_tables(cfg, x.shape[1], None))
    return jnp.sqrt(jnp.sum(jnp.square(got.astype(jnp.float32) - want))
                    / jnp.sum(jnp.square(want)))


def reference_check(cfg: TransformerConfig, params, reference, seed: int,
                    rows: int, forward, loss_terms) -> tuple:
    """``(check, the reference's own choices [L, rows, T, k])``: the program
    against the float32 reference on the cell's own ``rows`` rows of
    ``max_seq`` tokens, same weights, the same share of the experts and the
    program's choices of expert (see TOLERANCE): the reference's own choices
    against them, both heads' logits of every token, every layer's mixer on
    the reference's input to it, the two loss terms, and the counts the bias
    update is made from. ``forward(params, inputs, targets)`` is the
    program's ``forward_heads``, jitted, and runs ALL the rows at once;
    ``loss_terms(params, inputs, targets)`` the two terms of the loss the
    mode's train step differentiates. The REFERENCE takes the rows one at a
    time, through one program built for one row (PERF.md section 7 (f)).
    Nothing of vocabulary width leaves the device."""
    inputs, targets = seeded_rows(cfg, seed, rows, cfg.max_seq)
    (got_logits, got_mtp), got_routes = forward(params, inputs, targets)
    got_main, got_mtp_loss = (float(x) for x in loss_terms(params, inputs,
                                                           targets))
    k, n_layers = cfg.moe_top_k, len(got_routes.counts)
    mixers = mixer_rows(cfg)

    def given_them(params, inputs, targets, got_logits, got_mtp, got_expert):
        """One row: (each head's largest error over the vocabulary of every
        token [2, T], each head's largest reference logit [2], the
        reference's own choices [L, T, k], the sums of the reference's two
        losses [2], every mixer's :func:`mixer_error` [layers])."""
        probes = []
        with jax.default_matmul_precision("highest"):
            want_logits, want_mtp, want_choices = reference.forward(
                to_reference(params, cfg), inputs, targets, cfg.first_expert,
                top_k=cfg.moe_top_k, given=list(got_expert), probes=probes)
            nll = jnp.stack([
                jnp.sum(reference.cross_entropy(want_logits, targets)),
                jnp.sum(reference.mtp_cross_entropy(want_mtp, targets))])
        off = jnp.stack([mixer_error(cfg, x, want, {
            name: leaf[row] for name, leaf in params[stack].items()
            if name not in MODULE_LEAVES})
            for (stack, row), (x, want) in zip(mixers, probes)])
        err = jnp.stack([
            jnp.max(jnp.abs(got.astype(jnp.float32) - want), axis=-1)[0]
            for got, want in ((got_logits, want_logits),
                              (got_mtp, want_mtp))])
        top = jnp.stack([jnp.max(jnp.abs(want_logits)),
                         jnp.max(jnp.abs(want_mtp))])
        return err, top, jnp.stack(want_choices)[:, 0], nll, off

    def row(r):
        return (inputs[r:r + 1], targets[r:r + 1], got_logits[r:r + 1],
                got_mtp[r:r + 1], got_routes.expert[:, r:r + 1])

    given_built = built_quickly(given_them, params, *row(0))
    by_row = [jax.device_get(given_built(params, *row(r)))
              for r in range(rows)]
    del given_built
    err = np.stack([x[0] for x in by_row], axis=1)          # [2, rows, T]
    top = np.max([x[1] for x in by_row], axis=0)            # [2]
    own = np.stack([x[2] for x in by_row], axis=1)      # [L, rows, T, k]
    want_main, want_mtp_loss = np.sum([x[3] for x in by_row], axis=0) \
        / (inputs.size, rows * (cfg.max_seq - 1))
    mixer_off = np.stack([x[4] for x in by_row])        # [rows, layers]
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
        "logits": float(np.quantile(err[0], 0.999) / top[0]),
        "logits_worst": float(err[0].max() / top[0]),
        "mtp_logits": float(np.quantile(err[1], 0.999) / top[1]),
        "mtp_logits_worst": float(err[1].max() / top[1]),
        "mla_mixer": float(mixer_off.max()),
        "mla_mixer_by_layer": mixer_off.max(axis=0).tolist(),
        "loss_main": abs(got_main - want_main) / abs(want_main),
        "loss_mtp": abs(got_mtp_loss - want_mtp_loss) / abs(want_mtp_loss),
        "loss_program": [got_main, got_mtp_loss],
        "loss_reference": [float(want_main), float(want_mtp_loss)],
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
