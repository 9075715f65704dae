"""The decoder through the SPMD product: ``make_train_step`` over a mesh,
as ``examples/transformer_lm.py --mode spmd`` runs it. One process drives
every chip of the mesh; the engine takes no part.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import make_spmd_loss, make_train_step
from horovod_tpu.parallel.mesh import training_mesh

from job import Job

PROJECTIONS = 8
# Worst difference the step over the mesh may show against the same rows
# taken one at a time on one chip. ``loss``: share of the one-chip loss;
# the smoke's data=4 step read 6.2e-7 on the v5e (PR 21), and its band is
# this one. ``first_moment``: adamw's first moment after the first step is
# 0.1 times the gradient the step applied, so the mean over the rows of
# the one-chip moments is what the mesh must hold. Both sides are projected
# on PROJECTIONS seeded directions a leaf; the error is the worst
# difference over leaves and directions as a share of what a direction
# reads of a vector of the moment's length, so a leaf that is off by a
# share e of its length reads about 1.4 e (the largest of 8 normal draws).
# Rows taken alone and several at a time differ in where bfloat16 rounds
# and in the order of float32 sums: 4.0e-3 to 6.8e-3 (forced-CPU count at
# the rehearsal's widths, 1 and 4 host devices, 6 seeds); on the v5e not
# measured (PR 22's chip budget was spent; more tokens a row average the
# rounding down). A gradient that was not summed over the chips reads 2.4 to
# 2.6, one summed over two chips of four 1.7 to 2.1, a sum where the mean
# belongs 1.6 to 2.3, the right mean with the embedding's rows one place
# off 1.3 to 2.8 (forced-CPU counts, benchmark/tests/test_mesh_check.py).
# The bound is seven times the rounding and a 26th of the least of these.
# A mean taken in bfloat16 reads 7.7e-3 to 8.6e-3 and passes: this comparison
# cannot see it.
MESH_TOLERANCE = {"loss": 1e-4, "first_moment": 5e-2}


def on_chip(tree, device):
    """The copy of a replicated tree that ``device`` holds: its own
    buffers, nothing moved."""
    def pick(x):
        for shard in x.addressable_shards:
            if shard.device == device and shard.data.shape == x.shape:
                return shard.data
        raise ValueError(f"no whole copy of {x.shape} on {device}: the "
                         f"check is written for a state that is replicated")
    return jax.tree_util.tree_map(pick, tree)


@jax.jit
def project(moment, key):
    """[leaves, PROJECTIONS] inner products with seeded directions, and
    each leaf's length."""
    rows, lengths = [], []
    for i, x in enumerate(jax.tree_util.tree_leaves(moment)):
        keys = jax.random.split(jax.random.fold_in(key, i), PROJECTIONS)
        rows.append(jnp.stack([jnp.sum(x * jax.random.uniform(
            k, x.shape, x.dtype, -1.0, 1.0)) for k in keys]))
        lengths.append(jnp.sqrt(jnp.sum(x * x)))
    return jnp.stack(rows), jnp.stack(lengths)


def build(model, spec, traffic, env) -> Job:
    cfg = model.transformer_config(spec, traffic, env.rehearse)
    t = model.sizes(traffic, env.rehearse)
    axes = {"data": 1, "seq": 1, "tensor": 1, **traffic["mesh"]}
    mesh = training_mesh(axes, jax.devices()[:env.chips])
    rows = t["rows_per_chip"] * axes["data"]
    opt = optax.adamw(model.LR)
    train_step = make_train_step(mesh, cfg, opt)
    tok_sh = NamedSharding(mesh, P("data", "seq"))
    # the pool stays on the host: the loop puts each batch one step ahead
    pool = np.random.RandomState(env.seed).randint(
        0, cfg.vocab_size, size=(t["pool_batches"], rows, cfg.max_seq + 1)
    ).astype(np.int32)

    def init():
        params = model.make_params(cfg, env.seed,
                                   model.param_shardings(cfg, mesh))
        return params, jax.jit(opt.init)(params)

    def batch(i):
        tok = pool[i % len(pool)]
        return jax.device_put((tok[:, :-1], tok[:, 1:]), tok_sh)

    def step(state, inputs_targets):
        params, opt_state, loss = train_step(*state, *inputs_targets)
        return (params, opt_state), loss

    def one_chip():
        return training_mesh({"data": 1, "seq": 1, "tensor": 1},
                             jax.devices()[:1])

    def reference_checks(state):
        # on one chip whatever the mesh: the same two programs in every
        # cell of this configuration, and no kernel to partition
        one = one_chip()
        params = jax.device_put(state[0], model.param_shardings(cfg, one))
        return {"reference": model.reference_check(
            cfg, params, env.reference, env.seed,
            jax.jit(make_spmd_loss(one, cfg)))}

    def mesh_check(state):
        """The window's own program takes the first step, over the mesh, on
        the pool's first batch; before it, ``make_train_step`` on one chip
        takes each row of that batch alone from the same state. The mesh's
        loss must be the mean of the rows' losses and its first moment the
        mean of theirs (MESH_TOLERANCE): a gradient summed over the wrong
        chips, scaled wrongly or put together in the wrong order is not."""
        one = one_chip()
        chip = one.devices.flat[0]
        one_step = make_train_step(one, cfg, opt)
        key = jax.random.PRNGKey(env.seed)

        @jax.jit
        def row_moment(params, opt_state, inputs, targets):
            _, after, loss = one_step(params, opt_state, inputs, targets)
            return optax.tree_utils.tree_get(after, "mu"), loss

        alone = on_chip(state, chip)
        row_sh = NamedSharding(one, P("data", "seq"))
        want, want_loss = 0.0, 0.0
        for r in range(rows):
            tok = pool[0][r:r + 1]
            moment, loss = row_moment(*alone, *jax.device_put(
                (tok[:, :-1], tok[:, 1:]), row_sh))
            want = want + np.asarray(project(moment, key)[0], np.float64)
            want_loss += float(loss)
        del alone, moment       # the mesh's step donates these buffers
        want, want_loss = want / rows, want_loss / rows

        state, loss = step(state, batch(0))
        got, length = project(on_chip(
            optax.tree_utils.tree_get(state[1], "mu"), chip), key)
        # what a direction reads of a vector of this length: its
        # components are uniform on [-1, 1), of mean square 1/3
        scale = np.asarray(length, np.float64)[:, None] / np.sqrt(3.0)
        err = {"loss": abs(float(loss) - want_loss) / abs(want_loss),
               "first_moment": float(np.max(
                   np.abs(np.asarray(got, np.float64) - want) / scale))}
        return state, {
            "ok": all(err[k] <= MESH_TOLERANCE[k] for k in err),
            "error": err, "tolerance": MESH_TOLERANCE, "rows": rows}

    return Job(samples_per_step=rows * cfg.max_seq,
               flops_per_sample=model.flops_per_sample(cfg),
               init=init, batch=batch, step=step,
               reference_checks=reference_checks, mesh_check=mesh_check,
               kernel_costs=model.kernel_costs(cfg, t["rows_per_chip"]))
