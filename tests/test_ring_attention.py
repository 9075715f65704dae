"""Ring attention vs single-device attention: numerics must match exactly
(modulo fp accumulation order), including causal masking across block
boundaries and the backward pass."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel.ring_attention import ring_attention_p, local_attention


def _mesh_seq(n=4):
    import numpy as _np
    devs = jax.devices()[:n]
    return jax.sharding.Mesh(_np.array(devs), ("seq",))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_local(causal):
    mesh = _mesh_seq(4)
    B, T, H, D = 2, 16, 4, 8  # T global; 4 per block... T_local = 4
    rng = np.random.RandomState(0)
    q = rng.randn(B, T, H, D).astype(np.float32) * 0.3
    k = rng.randn(B, T, H, D).astype(np.float32) * 0.3
    v = rng.randn(B, T, H, D).astype(np.float32)

    ref = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=causal))

    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", 4, causal=causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    sh = NamedSharding(mesh, P(None, "seq"))
    out = np.asarray(fn(jax.device_put(q, sh), jax.device_put(k, sh),
                        jax.device_put(v, sh)))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_matches_local_bf16():
    """VERDICT r3 item 3 'done' bar: flash-inner-kernel ring attention
    matches the materialized reference at bf16 tolerance on the virtual
    mesh."""
    mesh = _mesh_seq(4)
    B, T, H, D = 2, 32, 2, 16
    rng = np.random.RandomState(2)
    q = (rng.randn(B, T, H, D) * 0.3).astype(jnp.bfloat16)
    k = (rng.randn(B, T, H, D) * 0.3).astype(jnp.bfloat16)
    v = rng.randn(B, T, H, D).astype(jnp.bfloat16)
    ref = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=True)
                     .astype(jnp.float32))
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", 4, causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    sh = NamedSharding(mesh, P(None, "seq"))
    out = np.asarray(fn(jax.device_put(q, sh), jax.device_put(k, sh),
                        jax.device_put(v, sh)).astype(jnp.float32))
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


def test_ring_attention_no_full_score_block():
    """VERDICT r3 item 3: the per-ring-step kernel must NOT materialize the
    [.., T_local, T_local] score block — the compiled program may only hold
    [.., T_local, chunk] slabs. Asserted on the optimized HLO of a
    T_local=2048 forward (chunk=512), where a materialized block would
    appear as a 2048x2048 buffer."""
    mesh = _mesh_seq(4)
    B, T_local, H, D = 1, 2048, 1, 64
    T = 4 * T_local
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", 4, causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    sh = NamedSharding(mesh, P(None, "seq"))
    arg = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=sh)
    txt = fn.lower(arg, arg, arg).compile().as_text()
    assert "2048,2048" not in txt, \
        "compiled ring attention materializes a T_local x T_local buffer"
    from horovod_tpu.parallel.ring_attention import _chunk_len
    c = _chunk_len(T_local)
    assert f"2048,{c}" in txt or f"{c},2048" in txt  # the chunked slab
    # fully-masked future blocks are skipped by a REAL runtime conditional
    # (half the causal ring's matmuls on average), not masked-and-computed
    assert "conditional" in txt


@pytest.mark.parametrize("n", [2, 4])
def test_zigzag_matches_local(n):
    """Zig-zag (load-balanced causal) layout: sharding the zigzag-permuted
    sequence contiguously and un-permuting the output must reproduce the
    reference exactly — the layout changes the schedule, not the math."""
    from horovod_tpu.parallel.ring_attention import zigzag_indices
    mesh = _mesh_seq(n)
    B, T, H, D = 2, 8 * n, 2, 8
    rng = np.random.RandomState(3)
    q = rng.randn(B, T, H, D).astype(np.float32) * 0.3
    k = rng.randn(B, T, H, D).astype(np.float32) * 0.3
    v = rng.randn(B, T, H, D).astype(np.float32)
    ref = np.asarray(local_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal=True))
    idx, inv = zigzag_indices(T, n)
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", n, causal=True,
                                         layout="zigzag"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
    sh = NamedSharding(mesh, P(None, "seq"))
    out_zig = fn(*(jax.device_put(jnp.take(x, idx, axis=1), sh)
                   for x in (jnp.asarray(q), jnp.asarray(k),
                             jnp.asarray(v))))
    out = np.asarray(jnp.take(out_zig, inv, axis=1))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_zigzag_grad_matches():
    from horovod_tpu.parallel.ring_attention import zigzag_indices
    n = 4
    mesh = _mesh_seq(n)
    B, T, H, D = 1, 16, 2, 4
    rng = np.random.RandomState(4)
    q = rng.randn(B, T, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, T, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, T, H, D).astype(np.float32)
    idx, inv = zigzag_indices(T, n)

    def loss_local(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    gref = jax.grad(loss_local, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", n, causal=True,
                                         layout="zigzag"),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"))

    def loss_ring(q, k, v):
        # loss through zigzag layout: permute in, attention, un-permute out
        out = ring(jnp.take(q, idx, axis=1), jnp.take(k, idx, axis=1),
                   jnp.take(v, idx, axis=1))
        return jnp.sum(jnp.take(out, inv, axis=1) ** 2)

    sh = NamedSharding(mesh, P(None))
    g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
        jax.device_put(jnp.asarray(q), sh), jax.device_put(jnp.asarray(k), sh),
        jax.device_put(jnp.asarray(v), sh))
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_zigzag_schedule_is_balanced(n):
    """The point of zig-zag: every rank executes the SAME amount of segment
    work at every ring step (off-diagonal steps: exactly 2 FULL halves;
    diagonal step: 1 FULL + 2 DIAG), so no rank straggles the ring. The
    compiled switch branches are driven by exactly this arithmetic
    (zigzag_pair_kinds), so asserting on it asserts the runtime schedule."""
    from horovod_tpu.parallel.ring_attention import (
        zigzag_pair_kinds, KIND_EMPTY, KIND_DIAG, KIND_FULL)
    cost = {KIND_EMPTY: 0.0, KIND_DIAG: 0.5, KIND_FULL: 1.0}
    for t in range(n):
        per_rank = []
        for r in range(n):
            owner = (r - t) % n
            kinds = zigzag_pair_kinds(r, owner, n)
            # (lo,hi) must be statically empty — never compiled into work
            assert kinds[("lo", "hi")] == KIND_EMPTY
            assert kinds[("hi", "lo")] == KIND_FULL
            per_rank.append(sum(cost[k] for k in kinds.values()))
        assert max(per_rank) == min(per_rank), \
            f"step {t}: unbalanced work {per_rank}"
        assert per_rank[0] == 2.0  # 2 full-equivalents per step per rank
    # and the contiguous schedule is NOT balanced (the problem zigzag fixes)
    from horovod_tpu.parallel.ring_attention import _kind  # noqa: F401
    contig = [sum(1.0 if (r - t) % n < r else (0.5 if (r - t) % n == r
                                              else 0.0)
                  for t in range(n)) for r in range(n)]
    assert max(contig) > 1.5 * min(contig)


def test_force_ring_single_device():
    """force_ring=True drives the generic ring path (switch kinds, merge,
    identity ppermute) on one device — the route the single-chip bench uses
    to measure the multi-chip kernels honestly."""
    B, T, H, D = 2, 16, 2, 8
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    mesh = _mesh_seq(1)
    for layout in ("contiguous", "zigzag"):
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention_p(q, k, v, "seq", 1, causal=True,
                                             layout=layout, force_ring=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        sh = NamedSharding(mesh, P(None, "seq"))
        out = np.asarray(fn(jax.device_put(q, sh), jax.device_put(k, sh),
                            jax.device_put(v, sh)))
        ref = np.asarray(local_attention(q, k, v, causal=True))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_matches():
    mesh = _mesh_seq(4)
    B, T, H, D = 1, 8, 2, 4
    rng = np.random.RandomState(1)
    q = rng.randn(B, T, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, T, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, T, H, D).astype(np.float32)

    def loss_local(q, k, v):
        return jnp.sum(local_attention(q, k, v, causal=True) ** 2)

    gref = jax.grad(loss_local, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                                   jnp.asarray(v))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention_p(q, k, v, "seq", 4, causal=True),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"))

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    sh = NamedSharding(mesh, P(None, "seq"))
    g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh))
    for a, b in zip(g, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-4)
