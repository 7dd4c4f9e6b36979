"""Pallas flash-style block attention with online-softmax stats.

The ring-attention hot loop (``parallel/ring_attention.py``) computes one
(Q-block, KV-block) partial attention per ICI hop.  Its einsum form
materializes the [B, Tq, H, Tk] score/prob tensors in HBM between the
two matmuls on every hop — O(B·H·Tq·Tk) traffic that grows quadratically
with the per-device sequence.  This kernel fuses QK^T → mask → online
softmax → PV inside VMEM, so HBM traffic drops to the O(B·H·T·D) tensor
reads/writes, with both matmuls on the MXU in the input dtype
(bf16-friendly) and float32 accumulation.

Semantics are IDENTICAL to ``ring_attention._block_attn`` with its
``bias_for`` causal bias (fully-masked rows produce m = -1e30 and junk
l/o that the ring's merge wipes via beta → 0 — same contract), so the
kernel drops into the ring as ``fast="flash"`` with no change to the
merge.  The backward pass recomputes the block through the einsum
reference and takes its exact VJP (standard flash remat trade: no
stored probs, ~1 extra block forward in bwd).

Positions arrive as runtime offsets (scalar-prefetch): ``q_off``/
``k_off`` are the global indices of the blocks' first tokens, so ONE
kernel serves every ring hop — diagonal (causal triangle), below-
diagonal (fully visible) and above-diagonal (fully masked) — without
data-dependent control flow.

Correctness coverage runs on CPU via pallas TPU interpret mode
(tests/test_block_attention.py) and, compiled by Mosaic, on the chip
(chip_smoke.py); on-chip the lane dim wants head_dim a multiple of 128
(the flagship's is 128).

Ref for the role this plays: the reference's fused 16:1 packing kernels
(gradient_compression-inl.h:40-139) are its example of hot-loop kernel
discipline; this is ours for the SP attention path (no reference
counterpart — GeoMX has no attention at all).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _block_attn_ref(q, k, v, offs, causal: bool):
    """Einsum reference (bit-compatible with ring_attention._block_attn
    fast mode + bias_for): the primal definition the VJP differentiates."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = jnp.float32(1.0 / np.sqrt(D))
    s = jnp.einsum("bqhd,bkhd->bqhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = offs[0] + jnp.arange(Tq)
        k_pos = offs[1] + jnp.arange(Tk)
        vis = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(vis[None, :, None, :], s, _NEG)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqhk,bkhd->bqhd", p.astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o.astype(jnp.float32)


def _kernel(offs_ref, q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, *,
            scale: float, causal: bool, bq: int, Tk: int):
    iq = pl.program_id(2)
    q = q_ref[...]    # [bq, D]
    kk = k_ref[...]   # [Tk, D]
    vv = v_ref[...]
    s = lax.dot_general(q, kk, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    if causal:
        q_pos = (offs_ref[0] + iq * bq
                 + lax.broadcasted_iota(jnp.int32, (bq, Tk), 0))
        k_pos = offs_ref[1] + lax.broadcasted_iota(jnp.int32, (bq, Tk), 1)
        s = jnp.where(q_pos >= k_pos, s, jnp.float32(_NEG))
    m = jnp.max(s, axis=1, keepdims=True)   # [bq, 1]
    p = jnp.exp(s - m)
    m_ref[...] = m
    l_ref[...] = jnp.sum(p, axis=1, keepdims=True)
    o_ref[...] = lax.dot_general(
        p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _pick_bq(Tq: int) -> int:
    """Q-block rows per grid step.  Default ladder prefers the largest
    tile that divides Tq; ``GEOMX_FLASH_BLOCK_Q`` (a tile found by
    a sweep on the chip) overrides when it divides Tq — tile choice is a pure performance knob, semantics are
    offset-driven and identical for every bq."""
    import os

    override = os.environ.get("GEOMX_FLASH_BLOCK_Q")
    if override:
        try:
            bq = int(override)
        except ValueError:
            bq = 0  # malformed value: fall through to the ladder —
            #         never kill a training step over a perf knob
        if 0 < bq <= Tq and Tq % bq == 0:
            return bq
    for cand in (256, 128, 64, 32, 16, 8):
        if Tq % cand == 0:
            return min(cand, Tq)
    return Tq


def _flash_fwd_impl(q, k, v, offs, causal: bool):
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq = _pick_bq(Tq)
    grid = (B, H, Tq // bq)
    kernel = functools.partial(
        _kernel, scale=1.0 / np.sqrt(D), causal=causal, bq=bq, Tk=Tk)
    # Mosaic tiles the LAST TWO block dims ((8, 128) for f32), so the
    # kernel blocks over [B, H, T, D]: (T, D) trail and the per-head
    # block is a squeezed leading dim.  Blocking the caller's
    # [B, T, H, D] layout directly would put a size-1 head block in the
    # sublane dim, which the TPU lowering refuses.  The row statistics
    # keep a trailing unit dim for the same reason.
    qh, kh, vh = (x.swapaxes(1, 2) for x in (q, k, v))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        # index_map gets the scalar-prefetch ref appended to grid indices
        in_specs=[
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, i, offs: (b, h, i, 0)),
            pl.BlockSpec((None, None, Tk, D),
                         lambda b, h, i, offs: (b, h, 0, 0)),
            pl.BlockSpec((None, None, Tk, D),
                         lambda b, h, i, offs: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, 1),
                         lambda b, h, i, offs: (b, h, i, 0)),
            pl.BlockSpec((None, None, bq, 1),
                         lambda b, h, i, offs: (b, h, i, 0)),
            pl.BlockSpec((None, None, bq, D),
                         lambda b, h, i, offs: (b, h, i, 0)),
        ],
    )
    m, l, o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Tq, D), jnp.float32),
        ],
    )(offs, qh, kh, vh)
    return (m[..., 0].swapaxes(1, 2), l[..., 0].swapaxes(1, 2),
            o.swapaxes(1, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def flash_block_attention(q, k, v, offs, causal: bool = True):
    """One fused (Q-block, KV-block) partial attention.

    ``q`` [B, Tq, H, D]; ``k``/``v`` [B, Tk, H, D]; ``offs`` int32 [2] =
    (global index of q's first token, global index of k's first token).
    Returns ``(m [B,Tq,H], l [B,Tq,H], o [B,Tq,H,D])`` float32 — the
    unnormalized online-softmax partials ring_attention merges.
    """
    return _flash_fwd_impl(q, k, v, offs, causal)


def _vjp_fwd(q, k, v, offs, causal: bool):
    return _flash_fwd_impl(q, k, v, offs, causal), (q, k, v, offs)


def _vjp_bwd(causal: bool, res, cots):
    q, k, v, offs = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _block_attn_ref(q_, k_, v_, offs, causal),
        q, k, v)
    dq, dk, dv = vjp(cots)
    return dq, dk, dv, None


flash_block_attention.defvjp(_vjp_fwd, _vjp_bwd)
