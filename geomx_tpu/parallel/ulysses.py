"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy next to ring attention (absent from the
reference — SURVEY.md §2.3 lists no SP/CP anywhere; this is a TPU-design
addition): instead of rotating K/V blocks around a ring, one all-to-all
re-shards the activations from sequence-sharded to head-sharded, every
device runs *dense* attention over the full sequence for its slice of
heads, and a second all-to-all restores sequence sharding.

Trade-off vs the ring: 2 collectives total instead of ``sp`` neighbor
hops (better for small ``sp`` over fast ICI all-to-alls; requires the
per-shard head count to divide by ``sp``), and the full sequence's K/V
for one head group must fit on a device.  Use inside ``shard_map`` over a mesh with
an ``sp`` axis, q/k/v pre-sharded on their sequence dimension.
"""

from __future__ import annotations

import jax
from jax import lax

from geomx_tpu.parallel.ring_attention import (
    dense_attention, fast_dense_attention)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    causal: bool = True,
    fast: bool = False,
) -> jax.Array:
    """Exact attention via head↔sequence all-to-all re-sharding.

    Shapes (per device): q/k/v ``[B, T_local, H, D]`` with the global
    sequence laid out contiguously by sp rank (same contract as
    ring_attention).  Returns ``[B, T_local, H, D]`` in q.dtype.
    """
    P = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % P != 0:
        raise ValueError(
            f"ulysses_attention needs the per-shard head count ({H} heads "
            f"visible inside shard_map) divisible by the '{axis_name}' "
            f"axis size ({P}); use ring_attention otherwise")

    def seq_to_heads(x):  # [B, T/P, H, D] -> [B, T, H/P, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):  # [B, T, H/P, D] -> [B, T/P, H, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    attn = fast_dense_attention if fast else dense_attention
    o = attn(seq_to_heads(q), seq_to_heads(k), seq_to_heads(v),
             causal=causal)
    return heads_to_seq(o).astype(q.dtype)
