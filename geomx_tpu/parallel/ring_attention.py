"""Ring attention: exact long-context attention over a sequence-parallel axis.

Absent from the reference (SURVEY.md §2.3: no SP/CP/ring-attention
anywhere); this is a TPU-design addition mandated by the build plan —
long sequences shard over the ``sp`` mesh axis, K/V blocks rotate around
the ring via ``lax.ppermute`` (neighbor hops over ICI), and each device
accumulates its queries' attention online (flash-attention-style running
max/denominator), so the full sequence never materializes on one chip.

Use inside ``shard_map`` over a mesh with an ``sp`` axis; q/k/v arrive
pre-sharded on their sequence dimension.  Computation runs in float32
accumulators with bf16-friendly inputs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _block_attn(q, k, v, bias, fast: bool = False):
    """One (Q-block, KV-block) partial attention.

    q: [B, Tq, H, D]; k/v: [B, Tk, H, D]; bias: [Tq, Tk] additive.
    Returns (scores_max [B,Tq,H], exp_sum [B,Tq,H], out [B,Tq,H,D]).

    ``fast`` keeps the two matmuls in the input dtype (bf16 on TPU →
    MXU-native passes) with float32 accumulation; the online-softmax
    statistics stay float32 either way.  False = all-fp32 reference.
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    if fast:
        s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                       preferred_element_type=jnp.float32) * scale
    else:
        s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    s = s + bias[None, :, None, :]
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    if fast:
        o = jnp.einsum("bqhk,bkhd->bqhd", p.astype(q.dtype), v,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m, l, o


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
    axis_size: Optional[int] = None,
    causal: bool = True,
    fast=False,
) -> jax.Array:
    """Exact attention with K/V ring rotation over ``axis_name``.

    Shapes (per device): q/k/v [B, T_local, H, D].  Global sequence =
    axis_size * T_local, laid out contiguously by sp rank.  Returns
    [B, T_local, H, D] in q.dtype.  ``fast`` = bf16 MXU matmuls with
    fp32 accumulation in each block (see _block_attn); accumulation
    across ring hops is float32 either way.  ``fast="flash"`` runs each
    hop's block through the fused pallas kernel
    (``ops/block_attention.flash_block_attention``): no HBM-materialized
    score/prob tensors, same semantics (on-chip wants D a multiple of
    128; off-chip use TPU interpret mode).
    """
    if axis_size is None:
        axis_size = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    neg = jnp.float32(-1e30)

    q_pos = my * T + jnp.arange(T)  # global positions of my queries

    def bias_for(src_idx):
        """Additive causal bias between my Q block and the KV block that
        originated on sp-rank ``src_idx``."""
        if not causal:
            return jnp.zeros((T, T), jnp.float32)
        k_pos = src_idx * T + jnp.arange(T)
        return jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0, neg)

    # online-softmax accumulators (float32)
    m0 = jnp.full((B, T, H), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, T, H), jnp.float32)
    o0 = jnp.zeros((B, T, H, D), jnp.float32)

    # receive from the next rank: after i hops we hold the block that
    # originated at (my + i) mod axis_size
    perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]

    def step(i, carry):
        k_blk, v_blk, m, l, o = carry
        src = (my + i) % axis_size
        if fast == "flash":
            from geomx_tpu.ops.block_attention import flash_block_attention

            offs = jnp.stack([my * T, src * T]).astype(jnp.int32)
            bm, bl, bo = flash_block_attention(q, k_blk, v_blk, offs,
                                               causal)
        else:
            bm, bl, bo = _block_attn(q, k_blk, v_blk, bias_for(src),
                                     fast=fast)
        new_m = jnp.maximum(m, bm)
        # guard fully-masked blocks (bm = -inf everywhere for that row)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - new_m, neg))
        beta = jnp.exp(jnp.where(jnp.isfinite(bm), bm - new_m, neg))
        l = l * alpha + bl * beta
        o = o * alpha[..., None] + bo * beta[..., None]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, new_m, l, o

    _, _, m, l, o = lax.fori_loop(0, axis_size, step, (k, v, m0, l0, o0))
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.astype(q.dtype)


def dense_attention(q, k, v, causal: bool = True) -> jax.Array:
    """Single-device reference implementation (for tests and the tp-only
    path): identical math, full sequence materialized."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def fast_dense_attention(q, k, v, causal: bool = True) -> jax.Array:
    """MXU-friendly dense attention: matmuls stay in the input dtype
    (bf16 on TPU) with float32 accumulation (``preferred_element_type``),
    softmax in float32, probabilities cast back to bf16 for the PV
    matmul.  ``dense_attention`` above upcasts q/k/v to fp32 *before*
    the einsums, which forces fp32 MXU passes.  Numerics:
    identical reduction tree, only the QK/PV multiply operands are bf16;
    max abs diff vs the fp32 path is ~1e-2 on unit-scale inputs, well
    inside bf16 training tolerance."""
    scale = jnp.float32(1.0 / np.sqrt(q.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bqhk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)
