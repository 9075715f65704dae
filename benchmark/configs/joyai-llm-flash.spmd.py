"""The latent-attention sparse-expert decoder with its
multi-token-prediction module through the SPMD product: ``make_train_step``
over a mesh, as ``examples/transformer_lm.py --mode spmd`` runs it. One
process drives every chip of the mesh; the engine takes no part. The step
returns, beside the loss ``CE + mtp_weight * CE_mtp``, the experts' counts
(the module's block's the last row) and the module's term, and carries the
routers' selection bias in the parameters (a leaf of the stack of expert
layers and of the module); on a share of the experts the routing weights take
no gradient (``make_train_step``'s docstring).

The job is the conv/attention cell's (``lfm2-8b-a1b.spmd.py``: the pool on
the host, the routers' bias settled before anything is timed, ``reference``
and ``step_grad``) written out again for a model whose forward pass needs the
targets too (the module embeds the next token) and whose loss has two terms.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (forward_heads, lm_loss_terms,
                                            make_train_step,
                                            router_bias_step)
from horovod_tpu.parallel.mesh import training_mesh

import files
from job import Job

HERE = os.path.dirname(os.path.abspath(__file__))
# the seeded projections of a tree, as the four-chip cell's mesh_step
# takes them
project = files.load_module(os.path.join(
    HERE, "cerebras-gpt-1.3b.spmd.py"), "bench_step_lm").project

# ``step_grad`` as ``lfm2-8b-a1b.spmd.py`` describes it: adamw's first moment
# after the window's OWN program took its first step against 0.1 times the
# reference's float32 gradient of ``CE + 0.1 CE_mtp`` on the same rows (every
# layer, every block of attention rows and both exits recomputed), both
# projected on 8 seeded directions a leaf, EVERY leaf: the module's under
# ``mtp``, and the embedding's and the head's, which take both terms. A leaf
# that is missing on one side, or a state the step left unchanged, reads the
# largest of 8 standard normal draws: 1.4 to 2.8. Read on the v5e at the
# published widths (PR 41; PERF.md section 4): ``first_moment`` 2.3e-1 to
# 3.5e-1 over the seeds, the worst leaf on EVERY seed a held expert's
# (``ewg``, ``ewu`` or ``ewd``, the module's block's or the expert layers':
# an expert's gradient is a sum over about 512 tokens, and the step chooses
# otherwise than the forward-only program for one or two of them: the
# sparse-expert cell's finding, PERF.md section 4), every other leaf 1.3e-1
# and under, the median leaf 5e-2. So a reading over a tenth is the flips',
# not rounding's. The limit sits between the largest reading and 1 with the
# more room above the reading: twice above it.
# ``flips_step_vs_forward``, half the difference of the step's counts and
# the forward-only program's over the choices: 1.78e-3 to 1.90e-3 over the
# seeds (five routed layers, each handing a flipped token on to every later
# token of its row through attention). It compares two programs of the
# product, so no wrong REFERENCE moves it; a step that routes without the
# settled bias reads 2.8e-1 in the state-space cell. The limit is three
# times the largest reading.
GRAD_TOLERANCE = {"first_moment": 0.7, "bias": 0.0,
                  "flips_step_vs_forward": 6e-3}
BIAS_SURE_AT_LEAST = 0.25


def build(model, spec, traffic, env) -> Job:
    cfg = model.transformer_config(spec, traffic, env.rehearse)
    t = model.sizes(traffic, env.rehearse)
    axes = {"data": 1, "seq": 1, "tensor": 1, **traffic["mesh"]}
    mesh = training_mesh(axes, jax.devices()[:env.chips])
    rows = t["rows_per_chip"] * axes["data"]
    opt = model.optimizer()
    train_step = make_train_step(mesh, cfg, opt)
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    shardings = model.param_shardings(cfg, mesh)
    # the forward pass alone: both heads' logits, every expert layer's
    # choices and counts (the module's block's last)
    forward = jax.jit(lambda p, x, y: forward_heads(p, x, y, cfg))
    # the pool stays on the host: the loop puts each batch one step ahead
    pool = np.random.RandomState(env.seed).randint(
        0, cfg.vocab_size, size=(t["pool_batches"], rows, cfg.max_seq + 1)
    ).astype(np.int32)

    def settled(params):
        """The traffic's ``router_settling``: the selection bias as the
        step's own rule leaves it, at the model's own rate, after
        ``router_settling_passes`` forward passes over the pool."""
        stacks = model.expert_stacks(cfg)
        bias = {s: {"router_bias": params[s]["router_bias"]} for s in stacks}
        move = jax.jit(lambda bias, counts: router_bias_step(bias, counts,
                                                             cfg))

        def with_bias(bias):
            return {**params, **{s: {**params[s], **bias[s]}
                                 for s in stacks}}

        for i in range(t["router_settling_passes"]):
            tok = pool[i % len(pool)]
            bias = move(bias, forward(with_bias(bias), tok[:, :-1],
                                      tok[:, 1:])[1].counts)
        return with_bias(bias)

    def init():
        # one item, replaced in place: step_grad lets the window's program
        # take a step, which gives the state's buffers away
        params = settled(model.make_params(cfg, env.seed, shardings))
        return [(params, jax.jit(opt.init)(params))]

    def batch(i):
        tok = pool[i % len(pool)]
        return jax.device_put((tok[:, :-1], tok[:, 1:]), tok_sh)

    def step(state, inputs_targets):
        params, opt_state, loss, stats = train_step(*state[0],
                                                    *inputs_targets)
        state[0] = (params, opt_state)
        state[1:] = [stats]     # what the step returns beside the loss
        return state, loss

    def step_grad(state, own):
        """See GRAD_TOLERANCE. ``own [L, rows, T, k]``: the reference's own
        choices on the same rows (``model.reference_check``'s), the
        module's block's the last."""
        params = state[0][0]
        batch_ = jax.device_put(
            model.seeded_rows(cfg, env.seed, rows, cfg.max_seq), tok_sh)
        key = jax.random.PRNGKey(env.seed)
        taken = forward(params, *batch_)[1]
        forward_counts = np.asarray(taken.counts)

        # (the key is an argument, not a constant of the program: another
        # seed must find the same program in the compilation cache.) The
        # program returns the projections AND the gradient they were taken
        # of, which is dropped: that is the form that was held against the
        # whole trees on the host (GRAD_TOLERANCE). With the reference's
        # own choices and its bias update among the results in place of the
        # tree, this compiler built a program that read every leaf 0.2 to
        # 0.5 off, from the same rows and the same choices (PERF.md section
        # 6 (4)); the own choices are ``model.reference_check``'s
        def reference_side(params, inputs, targets, key, given):
            with jax.default_matmul_precision("highest"):
                grads = env.reference.grads(
                    model.to_reference(params, cfg), inputs, targets,
                    cfg.first_expert, jax.checkpoint, cfg.moe_top_k, given)
            tree = model.without_bias(jax.tree_util.tree_map(
                lambda g: 0.1 * g, model.from_reference(grads, cfg)), cfg)
            return project(tree, key), tree

        (want, want_length), tree = model.run_quickly_built(
            reference_side, params, *batch_, key, list(taken.expert))
        del tree
        before = model.router_bias(params, cfg)
        want_bias = jnp.stack([
            env.reference.bias_update(bias, jnp.asarray(chosen),
                                      cfg.router_bias_rate)
            for bias, chosen in zip(before, own)])
        want_counts = jnp.stack([env.reference.counts(
            jnp.asarray(chosen), cfg.n_experts) for chosen in own])
        want = np.asarray(want, np.float64)
        state[0] = (params, jax.jit(opt.init)(params))
        state, _ = step(state, batch_)
        moment = model.without_bias(
            optax.tree_utils.tree_get(state[0][1], "mu"), cfg)
        got, length = project(moment, key)
        # what a direction reads of a vector of the longer side's length:
        # its components are uniform on [-1, 1), of mean square 1/3
        scale = np.maximum(np.asarray(length, np.float64), np.asarray(
            want_length, np.float64))[:, None] / np.sqrt(3.0)
        # (a leaf of zeros on both sides, the routers' on a share, reads 0)
        by_leaf = np.max(np.abs(np.asarray(got, np.float64) - want)
                         / np.where(scale > 0, scale, 1.0), axis=1)
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(moment)]
        counts = np.asarray(state[1]["expert_counts"], np.float64)
        want_counts = np.asarray(want_counts, np.float64)
        sure = np.abs(want_counts - want_counts.mean(axis=1, keepdims=True)) \
            > np.abs(counts - want_counts)
        bias_off = np.asarray(model.router_bias(state[0][0], cfg)) \
            != np.asarray(want_bias)
        err = {"first_moment": float(by_leaf.max()),
               "bias": float((bias_off & sure).sum()),
               "bias_sure": float(sure.mean()),
               "bias_off": float(bias_off.mean()),
               # a choice that differs moves two counts by one
               "flips_step_vs_forward": float(
                   np.abs(counts - forward_counts).sum() / 2
                   / (counts.shape[0] * batch_[0].size * cfg.moe_top_k)),
               "by_leaf": dict(zip(names, by_leaf.tolist()))}
        return {"ok": all(err[k] <= GRAD_TOLERANCE[k]
                          for k in GRAD_TOLERANCE)
                and err["bias_sure"] >= BIAS_SURE_AT_LEAST,
                "error": err, "tolerance": GRAD_TOLERANCE,
                "tokens": int(batch_[0].size)}

    def reference_checks(state, with_step_grad=True):
        # the float32 reference has the chip without adamw's two moments:
        # before the first step they are the zeros opt.init makes of them
        # again in step_grad, where the cell's own step then takes that step.
        # ``with_step_grad`` False (benchmark/tests/joyai_defects.py): the
        # forward pass's check alone
        params = state[0][0]
        state[0] = (params, None)
        reference, own = model.reference_check(
            cfg, params, env.reference, env.seed, rows, forward,
            jax.jit(lambda p, x, y: lm_loss_terms(p, x, y, cfg)))
        if not with_step_grad:
            return {"reference": reference}
        return {"reference": reference, "step_grad": step_grad(state, own)}

    return Job(samples_per_step=rows * cfg.max_seq,
               flops_per_sample=model.flops_per_sample(cfg),
               init=init, batch=batch, step=step,
               reference_checks=reference_checks,
               kernel_costs=model.kernel_costs(cfg, t["rows_per_chip"]))
