"""Expert-parallel MoE tests: the EP-sharded layer (all-to-all dispatch over
4 expert shards) must match the single-shard reference bit-for-bit given the
same expert weights, gradients must flow, and capacity overflow must drop
tokens to zero (Switch semantics)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.moe import MoEParams, init_moe, moe_layer_p

E, D, F = 8, 16, 32
N_SHARD = 4


def _mesh(n=N_SHARD):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("expert",))


def _params(seed=0):
    """Full (unsharded) params with E experts."""
    return init_moe(jax.random.PRNGKey(seed), D, F, E, n_expert_shards=1)


def _shard_params(full: MoEParams, n=N_SHARD):
    e_local = E // n
    return [MoEParams(full.router,
                      full.w_in[i * e_local:(i + 1) * e_local],
                      full.w_out[i * e_local:(i + 1) * e_local])
            for i in range(n)]


def test_ep_matches_single_shard():
    full = _params()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, D).astype(np.float32))
    ref, aux_ref = moe_layer_p(x, full, "none", 1, capacity_factor=8.0)

    mesh = _mesh()
    shards = _shard_params(full)
    w_in = jnp.stack([s.w_in for s in shards])    # [n, E/n, D, F]
    w_out = jnp.stack([s.w_out for s in shards])

    def body(x, router, w_in, w_out):
        p = MoEParams(router, w_in[0], w_out[0])
        y, aux = moe_layer_p(x, p, "expert", N_SHARD, capacity_factor=8.0)
        return y, aux

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(), P("expert"), P("expert")),
        out_specs=(P(), P()), check_vma=False))
    y, aux = fn(x, full.router, w_in, w_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


def test_gradients_flow_through_dispatch():
    full = _params(seed=1)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(32, D).astype(np.float32))

    def loss(params, x):
        y, aux = moe_layer_p(x, params, "none", 1, capacity_factor=8.0)
        return jnp.sum(y ** 2) + 0.01 * aux

    g = jax.grad(loss)(full, x)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()
    # router receives gradient through the gate
    assert float(jnp.abs(g.router).sum()) > 0


def test_capacity_overflow_drops_tokens():
    """With capacity 1 and many tokens on one expert, overflow outputs are
    exactly zero (residual carries them)."""
    full = _params(seed=2)
    # tokens engineered to route identically: identical inputs
    x = jnp.tile(jnp.asarray(np.random.RandomState(3).randn(1, D),
                             jnp.float32), (16, 1))
    y, _ = moe_layer_p(x, full, "none", 1, capacity_factor=1.0 / 16 * E)
    # capacity = ceil(16 * (E/16) / E) = 1 → only the first token survives
    nz = np.flatnonzero(np.abs(np.asarray(y)).sum(axis=1) > 1e-9)
    assert len(nz) == 1 and nz[0] == 0, nz


# ---------------------------------------------------------------------------
# MoE-EP through the engine alltoall (ISSUE 17): the capacity-routed
# train step in models/transformer.py riding engine.grouped_alltoall
# ---------------------------------------------------------------------------

def _moe_ep_fixture():
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import (
        TransformerConfig, init_params, make_moe_ep_train_step,
        moe_ep_partition)
    hvd.init()
    eng = hvd._engine()
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq=16,
                            dtype=jnp.float32, attention="flash",
                            use_moe=True, n_experts=4,
                            moe_capacity_factor=2.0)
    opt = optax.sgd(0.1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    shared, expert = moe_ep_partition(
        params, eng.backend.rank(), eng.backend.size(), cfg)
    step = make_moe_ep_train_step(eng, cfg, opt)
    st = (shared, expert, opt.init({"shared": shared, "expert": expert}))
    rng = np.random.RandomState(0)
    tok = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
    return eng, step, st, tok, tgt


def test_moe_ep_engine_step_learns():
    """The engine-alltoall MoE step trains: loss decreases over a few
    steps and both the shared and the expert leaves actually move."""
    eng, step, st, tok, tgt = _moe_ep_fixture()
    eng.replay.invalidate_all("test isolation")
    w1_before = np.asarray(st[1]["w1"]).copy()
    embed_before = np.asarray(st[0]["embed"]).copy()
    losses = []
    for _ in range(5):
        *st, loss = step(*st, tok, tgt)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses
    assert not np.array_equal(np.asarray(st[1]["w1"]), w1_before), \
        "expert weights never updated"
    assert not np.array_equal(np.asarray(st[0]["embed"]), embed_before), \
        "shared weights never updated"


def test_moe_ep_routing_metrics_populate():
    """Per-expert dispatch accounting rides the PR 5 skew machinery:
    hvd_tpu_moe_expert_tokens_total counts by expert index and the
    per-layer hvd_tpu_moe_dispatch_skew gauge lands at >= 1 (max/mean)."""
    from horovod_tpu.metrics import registry
    eng, step, st, tok, tgt = _moe_ep_fixture()
    eng.replay.invalidate_all("test isolation")
    snap0 = registry().snapshot()
    *st, _ = step(*st, tok, tgt)
    snap = registry().snapshot()

    def rows(s, name):
        ent = s.get("counters", {}).get(name) or \
            s.get("gauges", {}).get(name)
        return dict((tuple(sorted(l.items())), v)
                    for l, v in (ent or {}).get("values", []))

    tok_rows = rows(snap, "hvd_tpu_moe_expert_tokens_total")
    base_rows = rows(snap0, "hvd_tpu_moe_expert_tokens_total")
    delta = sum(tok_rows.values()) - sum(base_rows.values())
    # every routed token is counted once per layer (pre-capacity)
    assert delta == 2 * 16 * 2, delta       # B*T tokens x L layers
    skew = rows(snap, "hvd_tpu_moe_dispatch_skew")
    assert any(dict(k).get("layer") == "0" for k in skew), skew
    assert all(v >= 1.0 for v in skew.values())


def test_moe_ep_step_is_bitwise_deterministic():
    """Same params, same batch, fresh replay state: the whole loss
    trajectory repeats bitwise (the engine transport introduces no
    nondeterminism — the PP x MoE acceptance bar, size-1 face)."""
    def trajectory():
        eng, step, st, tok, tgt = _moe_ep_fixture()
        eng.replay.invalidate_all("test isolation")
        out = []
        for _ in range(4):
            *st, loss = step(*st, tok, tgt)
            out.append(float(loss))
        return out
    assert trajectory() == trajectory()
