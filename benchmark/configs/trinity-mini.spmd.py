"""The sparse-expert decoder through the SPMD product: ``make_train_step``
over a mesh, as ``examples/transformer_lm.py --mode spmd`` runs it. One
process drives every chip of the mesh; the engine takes no part. The step
returns the experts' counts beside the loss and carries the routers' selection
bias in the parameters; on a share of the experts the routing weights take no
gradient (``make_train_step``'s docstring).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (forward_routes, make_spmd_loss,
                                            make_train_step)
from horovod_tpu.parallel.mesh import training_mesh
from horovod_tpu.parallel.moe import router_bias_update

import files
from job import Job

HERE = os.path.dirname(os.path.abspath(__file__))
# the seeded projections of a tree, as the four-chip cell's mesh_step
# takes them
project = files.load_module(os.path.join(
    HERE, "cerebras-gpt-1.3b.spmd.py"), "bench_step_lm").project

# ``step_grad``, the looped cell's ``loop_grad`` on this model: adamw's first
# moment after the first step is 0.1 times the gradient that step applied,
# whatever the rate. The window's OWN program takes that step, on a seeded
# row of the timed 8,192 tokens; the reference's float32 gradient of the
# same row (every layer, every block of attention rows and the exit
# recomputed: ``wrap=jax.checkpoint``, no number changes), times 0.1, is
# what the moment must be. Both are projected on 8 seeded directions a leaf;
# the error is the worst difference over leaves and directions as a share of
# what a direction reads of a vector of the longer side's length (a leaf off
# by a share e of its length reads about 1.4 e; a leaf that is missing on
# one side, or a state the step left unchanged, reads the largest of 8
# standard normal draws: 1.4 on average, under 0.8 once in 80 runs, and the
# driver makes a dozen runs a check). Every leaf but the selection bias,
# whose gradient is 0 on both sides; the routers' is 0 on both sides too (a
# share: the routing weights are constants of its backward pass), and reads
# 0.
# What the limit has to leave room for is not arithmetic. The held experts'
# leaves read 1.9e-1 to 4.7e-1 where every other leaf reads under 1.4e-1:
# an expert sees 512 tokens, any two programs of this model choose
# differently for a token in a hundred (the step against the float32
# reference: ``choices_off``, 1.1 to 1.6%; the step against the
# forward-only program of the same bfloat16 model, by their counts alone at
# least 0.15 to 0.26%, reported as ``flips_step_vs_forward``; on the CPU in
# bfloat16 too), and a token that chooses otherwise adds or removes a whole
# term of a sum of 512 incoherent ones: 12 of 512 move it by 0.15 of its
# length, which reads 0.21. Giving the reference the forward-only program's
# choices left the reading where it was (3.8e-1, one run): the step's own
# choices are not that program's either, and the step does not return them.
# The bias after that step against the REFERENCE's update
# (``reference.bias_update`` of the reference's own choices), on every
# expert whose count is further from the layer's mean in the reference than
# the program's count is from the reference's: there the sign of ``mean(c)
# - c_e`` cannot depend on a flipped choice, and the two must agree to the
# bit (``bias``: experts that do not, 0). ``bias_sure`` is the share of the
# experts so held (86 to 92%); the others sit within a few assignments of
# the mean, where a flipped choice decides the sign.
# On the v5e at the published widths (PR 32; PERF.md section 4), the program
# over its seeds, then the reference in an 8-bit float: ``first_moment``
# 1.9e-1 to 4.7e-1 against 2.6 to 3.3: the limit is below where a missing
# leaf reads, 1.7 times the program's worst and a third of the 8-bit
# reading.
GRAD_TOLERANCE = {"first_moment": 0.8, "bias": 0.0}
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
    # the forward pass alone, with every layer's choices and counts
    forward = jax.jit(lambda p, x: forward_routes(p, x, cfg))
    # the pool stays on the host: the loop puts each batch one step ahead
    pool = np.random.RandomState(env.seed).randint(
        0, cfg.vocab_size, size=(t["pool_batches"], rows, cfg.max_seq + 1)
    ).astype(np.int32)

    def settled(params):
        """The traffic's ``router_settling``: the selection bias as the
        step's own rule leaves it, at the model's own rate, after
        ``router_settling_passes`` forward passes over the pool."""
        move = jax.jit(router_bias_update)
        layers = dict(params["layers"])
        for i in range(t["router_settling_passes"]):
            counts = forward({**params, "layers": layers},
                             pool[i % len(pool)][:, :-1])[1].counts
            layers["router_bias"] = move(layers["router_bias"], counts,
                                         cfg.router_bias_rate)
        return {**params, "layers": layers}

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

    def without_bias(tree):
        return {**tree, "layers": {k: v for k, v in tree["layers"].items()
                                   if k != "router_bias"}}

    def step_grad(state):
        """See GRAD_TOLERANCE."""
        params = state[0][0]
        row = jax.device_put(model.seeded_row(cfg, env.seed, cfg.max_seq),
                             tok_sh)
        key = jax.random.PRNGKey(env.seed)
        kinds = model.reference_kinds(cfg)
        forward_counts = np.asarray(forward(params, row[0])[1].counts)

        # (the key is an argument, not a constant of the program: another
        # seed must find the same program in the compilation cache)
        def reference_side(params, inputs, targets, key):
            with jax.default_matmul_precision("highest"):
                weights = model.to_reference(params)
                grads = env.reference.grads(
                    weights, inputs, targets, kinds, cfg.first_expert,
                    jax.checkpoint, cfg.moe_top_k)
                _, choices = env.reference.forward(
                    weights, inputs, kinds, cfg.first_expert,
                    top_k=cfg.moe_top_k)
            routed = [lw for lw in weights["layers"] if "router" in lw]
            return project(without_bias(jax.tree_util.tree_map(
                lambda g: 0.1 * g, model.from_reference(grads, params))),
                key), jnp.stack([
                    env.reference.bias_update(lw["router_bias"], chosen,
                                              cfg.router_bias_rate)
                    for lw, chosen in zip(routed, choices)]), jnp.stack([
                        env.reference.counts(chosen, cfg.n_experts)
                        for chosen in choices])

        (want, want_length), want_bias, want_counts = \
            model.run_quickly_built(reference_side, params, *row, key)
        want = np.asarray(want, np.float64)
        state[0] = (params, jax.jit(opt.init)(params))
        state, _ = step(state, row)
        moment = without_bias(optax.tree_utils.tree_get(state[0][1], "mu"))
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
        bias_off = np.asarray(state[0][0]["layers"]["router_bias"]) \
            != np.asarray(want_bias)
        err = {"first_moment": float(by_leaf.max()),
               "bias": float((bias_off & sure).sum()),
               "bias_sure": float(sure.mean()),
               "bias_off": float(bias_off.mean()),
               # a choice that differs moves two counts by one
               "flips_step_vs_forward": float(
                   np.abs(counts - forward_counts).sum() / 2
                   / (counts.shape[0] * row[0].size * cfg.moe_top_k)),
               "by_leaf": dict(zip(names, by_leaf.tolist()))}
        return {"ok": all(err[k] <= GRAD_TOLERANCE[k]
                          for k in GRAD_TOLERANCE)
                and err["bias_sure"] >= BIAS_SURE_AT_LEAST,
                "error": err, "tolerance": GRAD_TOLERANCE,
                "tokens": row[0].shape[1]}

    def reference_checks(state):
        # the float32 reference has the chip without adamw's two moments:
        # before the first step they are the zeros opt.init makes of them
        # again in step_grad, where the cell's own step then takes that step
        params = state[0][0]
        state[0] = (params, None)
        return {"reference": model.reference_check(
                    cfg, params, env.reference, env.seed, forward,
                    jax.jit(make_spmd_loss(mesh, cfg))),
                "step_grad": step_grad(state)}

    return Job(samples_per_step=rows * cfg.max_seq,
               flops_per_sample=model.flops_per_sample(cfg),
               init=init, batch=batch, step=step,
               reference_checks=reference_checks,
               kernel_costs=model.kernel_costs(cfg, t["rows_per_chip"]))
