"""Adasum: scale-invariant gradient reduction.

TPU-native re-design of the reference's vector-halving distance-doubling (VHDD)
algorithm (horovod/common/ops/adasum/adasum.h:194-336): log2(n) levels of
pairwise exchange; at each level partners combine their vectors with

    adasum(a, b) = (1 - dot(a,b) / (2*|a|^2)) * a + (1 - dot(a,b) / (2*|b|^2)) * b

(the coefficient triple dot/|a|^2/|b|^2 is the 3-vector the reference
allreduces per tensor, adasum.h:338-398). Instead of MPI point-to-point
send/recv we exchange whole vectors with ``lax.ppermute`` along the mesh axis —
XLA lowers the pairwise permutation onto ICI neighbor links. Reduction order
is made rank-symmetric so both partners compute bit-identical results.

Requires a power-of-2 group size, like the reference
(horovod/common/util.py num_rank_is_power_2 gate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def adasum_combine(a, b):
    """Pairwise Adasum of two same-shape vectors; accumulations in fp32
    (adasum.h does fp64/fp32 accumulation for fp16 inputs).

    With HOROVOD_ADASUM_PALLAS=1 the fused Pallas kernel
    (ops/pallas_kernels.py) is used instead — measured on a v5e it wins for
    ~1M-element tensors (30.0 vs 37.8 ms incl. dispatch) and loses at 16M
    (377 vs 320 ms), so the XLA-fused lax version stays the default."""
    from .pallas_kernels import adasum_pallas_enabled, adasum_combine_pallas
    if adasum_pallas_enabled():
        return adasum_combine_pallas(a, b)
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    dot = jnp.sum(af * bf)
    na = jnp.sum(af * af)
    nb = jnp.sum(bf * bf)
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(na == 0, 1.0, na)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(nb == 0, 1.0, nb)))
    out = ca * af + cb * bf
    return out.astype(a.dtype)


def adasum_p(x, axis_name: str, axis_size: int):
    """In-SPMD Adasum allreduce over ``axis_name`` (power-of-2 size).

    Distance-doubling recursion: level d pairs rank r with r XOR d
    (adasum.h:194-336's neighbor schedule).
    """
    if axis_size & (axis_size - 1):
        raise ValueError(f"Adasum requires a power-of-2 size, got {axis_size}")
    d = 1
    while d < axis_size:
        perm = [(r, r ^ d) for r in range(axis_size)]
        other = lax.ppermute(x, axis_name, perm)
        x = adasum_combine(x, other)
        d *= 2
    return x


def adasum_combine_sharded(a, b, axis_name: str, groups):
    """Pairwise Adasum where the logical vector is *sharded* across
    ``groups`` along ``axis_name``: dot/|a|²/|b|² are computed on the local
    shard and psum'd over the group so the coefficients correspond to the
    full vector (the reference allreduces the 3-vector over the reduction
    communicator, adasum.h:338-398)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    triple = jnp.stack([jnp.sum(af * bf), jnp.sum(af * af),
                        jnp.sum(bf * bf)])
    dot, na, nb = lax.psum(triple, axis_name, axis_index_groups=groups)
    ca = jnp.where(na == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(na == 0, 1.0, na)))
    cb = jnp.where(nb == 0, 0.0, 1.0 - dot / (2.0 * jnp.where(nb == 0, 1.0, nb)))
    return (ca * af + cb * bf).astype(a.dtype)


def hierarchical_adasum_p(x, axis_name: str, local_size: int, axis_size: int):
    """Hierarchical Adasum over a 1-D axis factored as (cross, local).

    TPU-native rebuild of AdasumGpuAllreduceOp (adasum_gpu_operations.cc:
    157-255): reduce-scatter a *sum* within each local (node) group, run the
    VHDD recursion across nodes on the scattered shards — with the
    coefficient triples psum'd over the local group so they reflect the full
    node vector (start_level=local_size in the reference's flat-rank
    formulation, :249-255) — then all-gather the shards back locally. The
    1/local_size prescale matches the frontend divisor logic for
    hierarchical Adasum (torch/mpi_ops.py:79-103): the node's contribution
    is the *mean* of its ranks' tensors.
    """
    cross = axis_size // local_size
    if cross & (cross - 1):
        raise ValueError(
            f"hierarchical Adasum requires a power-of-2 cross size, got "
            f"{cross} (= {axis_size}/{local_size})")
    if local_size == 1:
        return adasum_p(x, axis_name, axis_size)
    local_groups = [[c * local_size + l for l in range(local_size)]
                    for c in range(cross)]
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % local_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    flat = flat / local_size
    shard = lax.psum_scatter(flat, axis_name, scatter_dimension=0, tiled=True,
                             axis_index_groups=local_groups)
    d = 1
    while d < cross:
        perm = [(c * local_size + l, (c ^ d) * local_size + l)
                for c in range(cross) for l in range(local_size)]
        other = lax.ppermute(shard, axis_name, perm)
        shard = adasum_combine_sharded(shard, other, axis_name, local_groups)
        d *= 2
    out = lax.all_gather(shard, axis_name, axis=0, tiled=True,
                         axis_index_groups=local_groups)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)


def build_adasum(mesh: Mesh, axis: str, prescale_factor: float = 1.0,
                 postscale_factor: float = 1.0,
                 local_size: int = 0):
    """Stacked Adasum builder for the eager engine: (n, *s) -> (n, *s).

    Pre/postscale factors match the reference Adasum path, where scaling (e.g.
    1/local_size before a hierarchical Adasum) is applied around the VHDD
    recursion (torch/mpi_ops.py:79-103 divisor logic).
    """
    n = mesh.shape[axis]

    def body(x):  # (1, *s) block in, replicated out (see build_allreduce)
        v = x[0]
        if prescale_factor != 1.0:
            v = v * prescale_factor
        if local_size > 1:
            v = hierarchical_adasum_p(v, axis, local_size, n)
        else:
            v = adasum_p(v, axis, n)
        if postscale_factor != 1.0:
            v = v * postscale_factor
        return v

    # check_vma=False: the VHDD recursion is rank-symmetric, so every rank
    # ends with the identical combined vector — replicated by construction,
    # but not statically inferrable through ppermute.
    fn = shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=P(),
                   check_vma=False)
    return jax.jit(fn)


def adasum_allreduce_handle(engine, tensor, name=None, prescale_factor=1.0,
                            postscale_factor=1.0):
    """Engine entry point for op=Adasum on the eager path."""
    x = jnp.asarray(tensor)
    sub = engine._consume_substitute()
    engine._m_account("adasum", [x])
    # Adasum's per-tensor coefficient recursion cannot ride the packed
    # replay program — mark the step unreplayable (core/replay.py).
    engine._replay.observe("adasum", sub, [x], name)
    name = engine._register(name, "adasum", x.nbytes)
    from ..core.engine import _join_meta_row
    engine._join_sync("adasum", [_join_meta_row(x, 0)], skip=sub)
    engine._debug_check(name, "adasum", [x], wildcard=sub)
    mesh = engine.backend.group_mesh
    # Hierarchical variant (local mean -> cross VHDD -> local gather,
    # adasum_gpu_operations.cc:157-255) when the topology supports it and
    # HOROVOD_HIERARCHICAL_ALLREDUCE is on, like the reference's automatic
    # NCCL-hierarchical Adasum on multi-GPU nodes.
    local = 0
    if engine.config.hierarchical_allreduce and engine._hierarchical_ok():
        ls = engine.backend.local_size()
        cross = engine.backend.size() // ls
        if ls > 1 and cross >= 1 and (cross & (cross - 1)) == 0:
            local = ls
    fn = engine._builder(("adasum", prescale_factor, postscale_factor, local),
                         lambda: build_adasum(mesh, engine._axis(),
                                              prescale_factor,
                                              postscale_factor,
                                              local_size=local))
    from ..core.engine import _translate_failure
    engine._count_dispatch()
    out = _translate_failure(lambda: fn(engine.backend.to_global(x)))
    return engine._single(name, out, kind="adasum")


def adasum_reference(vectors):
    """NumPy reference of the VHDD recursion, used by tests the same way the
    reference's test_adasum_pytorch.py compares against a NumPy formula."""
    import numpy as np

    def combine(a, b):
        a = a.astype(np.float64)
        b = b.astype(np.float64)
        dot = float(np.sum(a * b))
        na = float(np.sum(a * a))
        nb = float(np.sum(b * b))
        ca = 0.0 if na == 0 else 1.0 - dot / (2 * na)
        cb = 0.0 if nb == 0 else 1.0 - dot / (2 * nb)
        return ca * a + cb * b

    vecs = [np.asarray(v) for v in vectors]
    n = len(vecs)
    assert n & (n - 1) == 0, "power of 2 required"
    d = 1
    while d < n:
        vecs = [combine(vecs[r], vecs[r ^ d]) for r in range(n)]
        d *= 2
    return vecs[0]
