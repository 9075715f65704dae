"""What the four-chip SPMD cell's two checks of the gradient sum refuse:
``mesh_check`` (one step over the mesh against the rows taken alone on
one chip) and ``replicas_equal`` (every chip's copy of the state, bit for
bit). On four forced host devices, at the rehearsal's widths, the step
file is given train steps whose gradient sum is wrong in four ways (and
one that is only coarser, which passes); each
is ``make_train_step``'s own on a mesh of one chip, so the one-chip side
of the comparison stays right. The readings are the forced-CPU counts that
the step file's MESH_TOLERANCE quotes."""

import os

import jax
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import files
import worker
from horovod_tpu.models import transformer
from job import Env

CONFIG, TRAFFIC = "cerebras-gpt-1.3b", "spmd-4chip-dp-4x2048"


def train_step_with(reduce):
    """``make_train_step`` with the gradient taken on each chip's rows and
    put together by ``reduce(gradients, chips)``; the outputs are declared
    replicated and nothing checks it, as in the program."""
    def make(mesh, cfg, optimizer):
        n = mesh.shape["data"]
        specs = transformer.param_specs(cfg)

        def body(params, inputs, targets):
            def local(p):
                total, count, _ = transformer._local_loss(
                    p, inputs, targets, cfg)
                return total / count
            loss, grads = jax.value_and_grad(local)(params)
            return lax.pmean(loss, "data"), reduce(grads, n)

        grads_of = jax.shard_map(
            body, mesh=mesh, in_specs=(specs, P("data"), P("data")),
            out_specs=(P(), specs), check_vma=False)

        def step(params, opt_state, inputs, targets):
            loss, grads = grads_of(params, inputs, targets)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1))
    return make


def each(f):
    return lambda grads, n: jax.tree_util.tree_map(lambda g: f(g, n), grads)


def rolled(grads, n):
    """The right mean, the embedding's rows one place off on every chip
    alike (a gather put together in the wrong order): only on a mesh."""
    grads = each(lambda g, n: lax.pmean(g, "data"))(grads, n)
    if n > 1:
        grads["embed"] = jax.numpy.roll(grads["embed"], 1, axis=0)
    return grads


REDUCTIONS = {
    "mean": each(lambda g, n: lax.pmean(g, "data")),
    "unsummed": each(lambda g, n: g),
    "pairs": each(lambda g, n: lax.psum(
        g, "data", axis_index_groups=[[0, 1], [2, 3]]) / 2 if n == 4 else g),
    "sum": each(lambda g, n: lax.psum(g, "data")),
    "rolled": rolled,
    # what the checks cannot see: the mean taken in bfloat16 is within the
    # band that rounding between batch shapes needs
    "bfloat16": each(lambda g, n: lax.pmean(
        g.astype(jax.numpy.bfloat16), "data").astype(g.dtype))}
# (mesh_check passes, replicas_equal passes)
EXPECTED = {"mean": (True, True), "unsummed": (False, False),
            "pairs": (False, False), "sum": (False, True),
            "rolled": (False, True), "bfloat16": (True, True)}


def build(seed, make_train_step=None):
    spec = files.load_json(files.config_path(CONFIG))
    traffic = files.load_json(files.traffic_path(TRAFFIC))
    step_file = os.path.splitext(files.config_path(CONFIG))[0] + ".spmd.py"
    module = files.load_module(step_file, "bench_step_under_test")
    if make_train_step is not None:
        module.make_train_step = make_train_step
    env = Env(seed=seed, chips=4, rehearse=True,
              reference=files.reference_module(CONFIG))
    return module.build(files.config_module(CONFIG), spec, traffic, env)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_program_passes(seed):
    job = build(seed)
    state, check = job.mesh_check(job.init())
    assert check["ok"], check
    for i in range(3):
        state, _ = job.step(state, job.batch(i))
    assert worker.replicas_equal(state)["ok"]


@pytest.mark.parametrize("kind", list(REDUCTIONS))
def test_what_a_gradient_sum_reads(kind):
    readings = []
    for seed in (1, 2, 3):
        job = build(seed, train_step_with(REDUCTIONS[kind]))
        state, check = job.mesh_check(job.init())
        for i in range(3):
            state, _ = job.step(state, job.batch(i))
        equal = worker.replicas_equal(state)
        readings.append(check["error"]["first_moment"])
        assert (check["ok"], equal["ok"]) == EXPECTED[kind], (check, equal)
        assert check["error"]["loss"] <= check["tolerance"]["loss"]
    print(f"{kind}: first_moment {min(readings):.3g} to {max(readings):.3g}")


def test_one_chip_has_nothing_to_compare():
    """``replicas_equal`` does not pass for want of copies."""
    import jax.numpy as jnp
    assert not worker.replicas_equal({"x": jnp.ones(3)})["ok"]
