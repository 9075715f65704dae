"""The looped decoder through the SPMD product: ``make_train_step`` over a
mesh, as ``examples/transformer_lm.py --mode spmd`` runs it. One process
drives every chip of the mesh; the engine takes no part.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import make_spmd_loss, make_train_step
from horovod_tpu.parallel.mesh import training_mesh

import files
from job import Job

# the seeded projections of a tree, as the four-chip cell's mesh_step
# takes them
project = files.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "cerebras-gpt-1.3b.spmd.py"), "bench_step_lm").project

# ``loop_grad``: adamw's first moment after the first step is 0.1 times the
# gradient that step applied, whatever the rate. The cell's own jitted step
# takes that step on a seeded row of GRAD_TOKENS tokens; the reference's
# float32 gradient of the same row, times 0.1, is what the moment must be.
# 2,048 and not the timed 4,096: the reference's float32 backward, even with
# its layers recomputed, needs 10.2 GB at 4,096 positions and 5.9 GB at 2,048
# beside 2 GB of weights (v5e compiler, PR 28), and the cell's peak_hbm_gb
# is to be the training step's. At 2,048 under remat="block" the call goes
# to the same stock splash kernels, one fused backward, at the same block
# sizes as at 4,096 (parallel/flash_attention.py splash_geometry, PR 31).
# Both are projected on 8 seeded directions a leaf; the error is the worst
# difference over leaves and directions as a share of what a direction
# reads of a vector of the longer side's length (a leaf off by a share e of
# its length reads about 1.4 e, a leaf that is missing on one side 1 to
# 2). On the v5e at the published widths (PR 28): the program 4.5e-2 to
# 8.5e-2 over 22 seeds, against the reference in an 8-bit float 2.8, 3.0.
# CPU counts at the rehearsal's widths (tests/test_looped_lm.py,
# benchmark/tests/test_ouro.py): the program 6.0e-2 to 8.6e-2; a gradient
# through one pass of four 0.75 to 3.7, the exit distribution held constant
# in the loss 0.75, RoPE left off 2.4 to 10, an 8-bit float 2.9 to 11. The
# limit is 2.4 times the program's worst on the chip and a fourth of the
# least wrong gradient: no tighter, because the driver draws fresh seeds for
# every run.
GRAD_TOLERANCE = {"first_moment": 2e-1}
GRAD_TOKENS = 2048


def leaves_to_compare(tree) -> dict:
    """The tree with the exit gate's weight and bias as one leaf: the bias
    is one number, a sum over all tokens of terms of both signs, and what is
    left of it after cancellation has no digits to compare."""
    gate = tree["exit_gate"]
    return {**tree, "exit_gate": jnp.concatenate([gate["w"], gate["b"][None]])}


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
    # the pool stays on the host: the loop puts each batch one step ahead
    pool = np.random.RandomState(env.seed).randint(
        0, cfg.vocab_size, size=(t["pool_batches"], rows, cfg.max_seq + 1)
    ).astype(np.int32)

    def init():
        # one item, replaced in place: loop_grad lets the window's program
        # take a step, which gives the state's buffers away
        params = model.make_params(cfg, env.seed, shardings)
        return [(params, jax.jit(opt.init)(params))]

    def batch(i):
        tok = pool[i % len(pool)]
        return jax.device_put((tok[:, :-1], tok[:, 1:]), tok_sh)

    def step(state, inputs_targets):
        params, opt_state, loss = train_step(*state[0], *inputs_targets)
        state[0] = (params, opt_state)
        return state, loss

    def loop_grad(state):
        """See GRAD_TOLERANCE. The reference's backward recomputes each of
        its layers and exits (``wrap=jax.checkpoint``: no number changes),
        so that GRAD_TOKENS positions in float32 fit beside the weights."""
        params = state[0][0]
        row = jax.device_put(model.seeded_row(
            cfg, env.seed, min(GRAD_TOKENS, cfg.max_seq)), tok_sh)
        key = jax.random.PRNGKey(env.seed)

        def reference_moment(params, inputs, targets):
            with jax.default_matmul_precision("highest"):
                grads = env.reference.grads(
                    model.to_reference(params), inputs, targets,
                    cfg.n_loops, cfg.exit_entropy_weight,
                    wrap=jax.checkpoint)
            return project(leaves_to_compare(jax.tree_util.tree_map(
                lambda g: 0.1 * g, model.from_reference(grads))), key)

        want, want_length = model.run_quickly_built(
            reference_moment, params, *row)
        want = np.asarray(want, np.float64)
        state[0] = (params, jax.jit(opt.init)(params))
        state, _ = step(state, row)
        moment = leaves_to_compare(
            optax.tree_utils.tree_get(state[0][1], "mu"))
        got, length = project(moment, key)
        # what a direction reads of a vector of the longer side's length:
        # its components are uniform on [-1, 1), of mean square 1/3
        scale = np.maximum(np.asarray(length, np.float64), np.asarray(
            want_length, np.float64))[:, None] / np.sqrt(3.0)
        by_leaf = np.max(np.abs(np.asarray(got, np.float64) - want) / scale,
                         axis=1)
        names = [jax.tree_util.keystr(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(moment)]
        err = {"first_moment": float(by_leaf.max()),
               "by_leaf": dict(zip(names, by_leaf.tolist()))}
        return {"ok": err["first_moment"] <= GRAD_TOLERANCE["first_moment"],
                "error": err, "tolerance": GRAD_TOLERANCE,
                "tokens": row[0].shape[1]}

    def reference_checks(state):
        # the float32 reference has the chip without adamw's two moments:
        # before the first step they are the zeros opt.init makes of them
        # again in loop_grad, where the cell's own step then takes that step
        params = state[0][0]
        state[0] = (params, None)
        return {"reference": model.reference_check(
                    cfg, params, env.reference, env.seed,
                    jax.jit(make_spmd_loss(mesh, cfg))),
                "loop_grad": loop_grad(state)}

    return Job(samples_per_step=rows * cfg.max_seq,
               flops_per_sample=model.flops_per_sample(cfg),
               init=init, batch=batch, step=step,
               reference_checks=reference_checks,
               kernel_costs=model.kernel_costs(cfg, t["rows_per_chip"]))
