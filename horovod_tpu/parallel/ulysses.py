"""Ulysses-style (all-to-all) sequence parallelism.

The second canonical long-context strategy next to ring attention
(SURVEY.md §5: "Ulysses-style head/sequence alltoall via
jax.lax.all_to_all"; the reference's transport primitive is its first-class
alltoall, operations.cc:951): instead of rotating K/V blocks around a ring,
one all-to-all re-shards the activations from sequence-sharded to
head-sharded, every device runs *full-sequence* attention on its head
slice, and a second all-to-all restores sequence sharding.

Trade-off vs ring attention: 2 all-to-alls of the (q,k,v / o) activations
per attention call — O(T·H·D/n) bytes each — versus n−1 ppermute rotations
of K/V; Ulysses needs ``n_heads % axis_size == 0`` but runs the whole
softmax locally (no online-softmax recombination), which XLA fuses into one
flash-style kernel. On ICI meshes both ride neighbor links; pick per model
shape (many heads + moderate T → Ulysses; few heads or extreme T → ring).
"""

from __future__ import annotations

from jax import lax

from .flash_attention import flash_attention_local


def ulysses_attention_p(q, k, v, axis_name: str, axis_size: int,
                        causal: bool = True):
    """All-to-all sequence-parallel attention over ``axis_name``.

    Args:
      q, k, v: local blocks ``[B, T_local, H, D]`` — the global sequence is
        the concatenation of blocks in axis order, exactly like
        :func:`~horovod_tpu.parallel.ring_attention.ring_attention_p`
        (drop-in interchangeable).
      causal: causal mask over global positions.

    Returns the local output block ``[B, T_local, H, D]``.
    """
    n = axis_size
    if n == 1:
        return flash_attention_local(q, k, v, causal=causal)
    heads = q.shape[2]
    if heads % n != 0:
        raise ValueError(
            f"ulysses attention needs n_heads ({heads}) divisible by the "
            f"sequence axis size ({n}); use ring attention otherwise")

    def seq_to_heads(x):
        # [B, T/n, H, D] -> [B, T, H/n, D]: every device trades its local
        # sequence block of the other head groups for the full sequence of
        # its own head group — one fused all-to-all on ICI.
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    # full-sequence attention on this device's head slice; the global causal
    # mask is now an ordinary local causal mask — and the compute is a
    # plain single-shard attention, so it takes the tuned Pallas
    # flash/splash kernel on TPU (materialized fallback elsewhere / for
    # 128-unaligned lengths)
    oh = flash_attention_local(qh, kh, vh, causal=causal)
    return heads_to_seq(oh)
