"""Quantized gradient all-reduce over the party mesh (ICI).

TPU-native addition beyond the reference (which compresses only the
WAN tier): the intra-slice gradient all-reduce is the party's largest
ICI payload, and an int8 block-quantized reduce-scatter + all-gather
cuts its bytes ~4x at bf16/f32 precision loss bounded per 256-element
block.  Pattern follows the public EQuARX design (PAPERS.md: EQuARX —
quantize, exchange, dequantize-accumulate partial sums exactly, then
re-quantize once for the broadcast leg), re-expressed with
``shard_map`` + ``all_to_all``/``all_gather`` so XLA schedules the
collectives on ICI like any other.

Two exact-arithmetic properties make this safe:
- partial sums are accumulated in f32 AFTER dequantization (only the
  wire is int8; no int overflow, no accumulation drift), and
- each element is quantized at most twice end-to-end (once per leg),
  so the error is <= 2 * block_absmax / 254 — the caller can keep a
  residual (error feedback) if the optimizer needs it tighter.

Usage: call ``quantized_psum_mean(x, axis_name, axis_size)`` inside a
``shard_map`` over the reduce axis (each device passes its full-length
local vector), or use ``make_party_step_quantized(grad_fn, mesh)`` as
a drop-in for ``dp.make_party_step``.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

BLOCK = 256  # quantization block (VPU-lane friendly; per-block scale)


def _quantize_blocks(x: jnp.ndarray):
    """x [n] f32 -> (q int8 [n], scale f32 [n/BLOCK]).  n % BLOCK == 0."""
    blocks = x.reshape(-1, BLOCK)
    absmax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale[:, 0]


def _dequantize_blocks(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return (q.reshape(-1, BLOCK).astype(jnp.float32)
            * scale[:, None]).reshape(-1)


def quantized_psum_mean(x: jnp.ndarray, axis_name: str,
                        axis_size: int) -> jnp.ndarray:
    """Mean-reduce a flat f32 vector across ``axis_name`` with int8
    wire traffic (call INSIDE shard_map; every device holds its own
    full-length local vector).

    reduce-scatter leg: quantize locally, ``all_to_all`` so device d
    receives shard d of every peer, dequantize and sum in f32.
    broadcast leg: re-quantize the summed shard, ``all_gather``,
    dequantize.  Wire bytes ~ 2 * n * (1 + 4/BLOCK) vs 2 * 4n for the
    fp32 ring — ~3.9x less."""
    n = x.shape[0]
    # pad to axis_size * BLOCK so every shard is block-aligned
    chunk = ((n + axis_size * BLOCK - 1) // (axis_size * BLOCK)) * BLOCK
    pad = chunk * axis_size - n
    xp = jnp.pad(x, (0, pad))
    q, s = _quantize_blocks(xp)
    # shape as [axis_size, chunk] / [axis_size, chunk/BLOCK]: leading
    # axis is the exchange axis for all_to_all
    q = q.reshape(axis_size, chunk)
    s = s.reshape(axis_size, chunk // BLOCK)
    # after all_to_all: [axis_size(peer), chunk] — peer p's quantized
    # shard-of-mine
    q_peers = jax.lax.all_to_all(q, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
    s_peers = jax.lax.all_to_all(s, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
    # exact f32 accumulation of dequantized peer shards
    part = jax.vmap(_dequantize_blocks)(q_peers, s_peers)
    shard_sum = jnp.sum(part, axis=0) / float(axis_size)   # mean
    # broadcast leg: one more quantization, gather all shards
    q2, s2 = _quantize_blocks(shard_sum)
    q_all = jax.lax.all_gather(q2, axis_name, axis=0)      # [P, chunk]
    s_all = jax.lax.all_gather(s2, axis_name, axis=0)
    full = jax.vmap(_dequantize_blocks)(q_all, s_all).reshape(-1)
    return full[:n]


def quantized_psum_mean_ef(x: jnp.ndarray, residual: jnp.ndarray,
                           axis_name: str, axis_size: int):
    """:func:`quantized_psum_mean` with EQuARX-style error feedback:
    returns ``(mean, new_residual)``.

    Each participant folds its residual into this round's contribution
    BEFORE quantizing and keeps the quantization error it just incurred
    for the next round, so the systematic part of the int8 error (e.g.
    sub-threshold components of a block whose absmax is dominated by
    one large element quantize to exactly 0 every round) accumulates in
    the residual until it crosses the quantization step instead of
    being lost forever — the property that makes the quantized rung
    accuracy-neutral over a training run rather than merely bounded per
    round.

    Residual domain: the SUM each contribution enters with weight 1
    (``mean * axis_size``).  Two terms are captured:

    - leg 1 (reduce-scatter): ``(x + r) - dequant(quant(x + r))`` —
      the participant's own full-length quantization error;
    - leg 2 (broadcast): the re-quantization error of the shard this
      device owns, scaled by ``axis_size`` because the shard sum it
      distorts lands in the output with weight ``axis_size`` relative
      to a single contribution — held by the shard owner alone (one
      compensator per error, never double-counted).

    The caller threads ``new_residual`` back in next round (zeros to
    start).  Without it this function degrades exactly to
    :func:`quantized_psum_mean` applied to ``x + residual``."""
    x_adj = x + residual
    n = x.shape[0]
    chunk = ((n + axis_size * BLOCK - 1) // (axis_size * BLOCK)) * BLOCK
    pad = chunk * axis_size - n
    xp = jnp.pad(x_adj, (0, pad))
    q, s = _quantize_blocks(xp)
    # leg-1 error feedback: what the int8 wire just lost of OUR vector
    leg1 = xp - _dequantize_blocks(q, s)
    q = q.reshape(axis_size, chunk)
    s = s.reshape(axis_size, chunk // BLOCK)
    q_peers = jax.lax.all_to_all(q, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
    s_peers = jax.lax.all_to_all(s, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
    part = jax.vmap(_dequantize_blocks)(q_peers, s_peers)
    shard_sum = jnp.sum(part, axis=0) / float(axis_size)   # mean
    q2, s2 = _quantize_blocks(shard_sum)
    # leg-2 error feedback: the re-quantization error of the shard WE
    # own (mean domain; every peer receives it, we alone compensate)
    err2 = shard_sum - _dequantize_blocks(q2, s2)
    d = jax.lax.axis_index(axis_name)
    leg2 = jax.lax.dynamic_update_slice(
        jnp.zeros_like(xp), err2 * float(axis_size), (d * chunk,))
    q_all = jax.lax.all_gather(q2, axis_name, axis=0)
    s_all = jax.lax.all_gather(s2, axis_name, axis=0)
    full = jax.vmap(_dequantize_blocks)(q_all, s_all).reshape(-1)
    return full[:n], (leg1 + leg2)[:n]


def make_party_step_quantized(grad_fn: Callable, mesh: Mesh) -> Callable:
    """:func:`geomx_tpu.parallel.dp.make_party_step` with the gradients
    reduced by :func:`quantized_psum_mean` instead of the fp32
    all-reduce.  ``grad_fn(params, x, y) -> (loss, acc, grads)``;
    loss/acc are mean-reduced exactly (scalars are free), gradients
    ride the int8 wire."""
    from geomx_tpu.parallel.dp import make_party_step

    def reduce_grads(grads, axis, n_dev):
        flat, treedef = jax.tree_util.tree_flatten(grads)
        sizes = [int(np.prod(g.shape)) for g in flat]
        cat = jnp.concatenate([g.reshape(-1).astype(jnp.float32)
                               for g in flat])
        red = quantized_psum_mean(cat, axis, n_dev)
        out = []
        off = 0
        for g, sz in zip(flat, sizes):
            out.append(red[off:off + sz].reshape(g.shape))
            off += sz
        return jax.tree_util.tree_unflatten(treedef, out)

    return make_party_step(grad_fn, mesh, reduce_grads=reduce_grads)
