"""Sliding-window causal attention with grouped-query heads: query ``t``
sees the keys ``s`` with ``t - window < s <= t``, ``window`` keys
itself included (HF's ``sliding_window`` counts them so).

Three Pallas kernels, tiled ``tile`` x ``tile`` (512 x 512 at a 512
window), named so that the trace finds them apart from the full layers'
flash calls:

* ``window_attn_fwd``: flash-style attention of a k/v head's group of q
  heads, the online softmax in float32; writes ``o`` and each row's
  log-sum-exp;
* ``window_attn_bwd_dq`` / ``window_attn_bwd_dkv``: the backward pass, q
  side and k/v side (a k/v head's gradients summed over its group).

Every kernel's grid runs over the q (or kv) tiles and, for each, only
the ``reach + 1`` kv (or q) tiles that the window meets (:func:`reach`):
at a 512 window and 512 tiles a q tile meets its own kv tile and the one
before, so a sequence of 8,192 computes 31 of the 136 causal tiles
(:func:`tiles`).  The mask inside a tile is built from iotas; no [T, T]
mask or bias exists anywhere.  Matmuls take the inputs' dtype with
float32 accumulation.

Off the chip the kernels run under the TPU interpreter
(``tests/test_laguna.py``); the rehearsal and the threaded tests take
``parallel/ring_attention.py``'s dense attention with its ``window``
mask instead (``models/transformer.py``, ``attn_impl``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512
NEG = -1e30         # finite: exp() of a masked score under it is 0
# a grid step holds a group's q tile, o tile and float32 accumulator
# (9 heads x 512 x 128: 1.2 + 1.2 + 2.4 MB, double-buffered): past the
# default scoped VMEM
VMEM_BYTES = 64 * 2 ** 20
TRANS_B = (((1,), (1,)), ((), ()))   # a @ b^T
NN = (((1,), (0,)), ((), ()))        # a plain a @ b


def reach(window: int, tile: int, n: int) -> int:
    """The kv tiles before a q tile's own that its window meets (of
    ``n`` tiles in all)."""
    return min(-(-(window - 1) // tile), n - 1)


def tiles(T: int, window: int, tile: int):
    """(tiles the kernels compute, causal tiles) of one sequence of
    ``T`` positions: the grid's, from shapes alone."""
    n = T // tile
    r = reach(window, tile, n)
    return sum(min(i, r) + 1 for i in range(n)), n * (n + 1) // 2


def pairs(T: int, window: int) -> int:
    """(query, key) pairs in the window, a sequence: ``sum_t min(t + 1,
    window)``."""
    w = min(window, T)
    return w * (w + 1) // 2 + (T - w) * w


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def _visible(q0, k0, shape, window: int):
    """The pairs of a tile whose first query and key are ``q0`` and
    ``k0`` that the window lets through."""
    rows = q0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    return (cols <= rows) & (cols > rows - window)


def _probs(q, k, lse, ok, scale):
    """A head's probabilities on a tile from its row log-sum-exp."""
    s = lax.dot_general(q, k, TRANS_B,
                        preferred_element_type=jnp.float32) * scale
    return jnp.where(ok, jnp.exp(s - lse), 0.0)


# ---------------------------------------------------------------------------
# window_attn_fwd
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, window: int, reach: int, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)
    group, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    kt = i - reach + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    @pl.when(kt >= 0)
    def _tile():
        ok = _visible(i * bq, kt * bk, (bq, bk), window)
        k, v = k_ref[...], v_ref[...]
        for h in range(group):
            s = lax.dot_general(q_ref[h], k, TRANS_B,
                                preferred_element_type=jnp.float32) * scale
            m = m_ref[:, h:h + 1]
            m_new = jnp.maximum(m, jnp.max(jnp.where(ok, s, NEG), axis=1,
                                           keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[:, h:h + 1] = alpha * l_ref[:, h:h + 1] + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + lax.dot_general(
                p.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
            m_ref[:, h:h + 1] = m_new

    @pl.when(j == reach)
    def _done():
        l = l_ref[...]
        for h in range(group):
            o_ref[h] = (acc_ref[h] / l[:, h:h + 1]).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _fwd(q, k, v, window: int, tile: int):
    """``q`` [B, H, T, D], ``k``/``v`` [B, G, T, D] -> (o [B, H, T, D],
    lse [B, G, T, H / G] float32)."""
    B, H, T, D = q.shape
    G = k.shape[1]
    group, n = H // G, T // tile
    r = reach(window, tile, n)
    kernel = functools.partial(_fwd_kernel, window=window, reach=r,
                               scale=float(1.0 / np.sqrt(D)))
    heads_at = lambda b, g, i, j: (b, g, i, 0)                  # noqa: E731
    keys_at = lambda b, g, i, j: (                              # noqa: E731
        b, g, jnp.maximum(i - r + j, 0), 0)
    f32 = jnp.float32
    return pl.pallas_call(
        kernel, name="window_attn_fwd", grid=(B, G, n, r + 1),
        in_specs=[pl.BlockSpec((None, group, tile, D), heads_at),
                  pl.BlockSpec((None, None, tile, D), keys_at),
                  pl.BlockSpec((None, None, tile, D), keys_at)],
        out_specs=[pl.BlockSpec((None, group, tile, D), heads_at),
                   pl.BlockSpec((None, None, tile, group), heads_at)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, G, T, group), f32)],
        scratch_shapes=[pltpu.VMEM((group, tile, D), f32),
                        pltpu.VMEM((tile, group), f32),
                        pltpu.VMEM((tile, group), f32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
    )(q, k, v)


# ---------------------------------------------------------------------------
# window_attn_bwd: the q side and the k/v side
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, window: int, reach: int, scale: float):
    i, j = pl.program_id(2), pl.program_id(3)
    group, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    kt = i - reach + j

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    @pl.when(kt >= 0)
    def _tile():
        ok = _visible(i * bq, kt * bk, (bq, bk), window)
        k, v = k_ref[...], v_ref[...]
        lse, delta = lse_ref[...], delta_ref[...]
        for h in range(group):
            p = _probs(q_ref[h], k, lse[:, h:h + 1], ok, scale)
            dp = lax.dot_general(do_ref[h], v, TRANS_B,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, h:h + 1])
            dq_acc[h] += lax.dot_general(
                ds.astype(k.dtype), k, NN,
                preferred_element_type=jnp.float32) * scale

    @pl.when(j == reach)
    def _done():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, window: int, reach: int,
                scale: float, nq: int):
    j, jj = pl.program_id(2), pl.program_id(3)
    group, bq, _ = q_ref.shape
    bk = k_ref.shape[0]
    qt = j + jj

    @pl.when(jj == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(qt < nq)
    def _tile():
        ok = _visible(qt * bq, j * bk, (bq, bk), window)
        k, v = k_ref[...], v_ref[...]
        lse, delta = lse_ref[...], delta_ref[...]
        for h in range(group):
            q, do = q_ref[h], do_ref[h]
            p = _probs(q, k, lse[:, h:h + 1], ok, scale)
            dv_acc[...] += lax.dot_general(
                p.T.astype(do.dtype), do, NN,
                preferred_element_type=jnp.float32)
            dp = lax.dot_general(do, v, TRANS_B,
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, h:h + 1])
            dk_acc[...] += lax.dot_general(
                ds.T.astype(q.dtype), q, NN,
                preferred_element_type=jnp.float32) * scale

    @pl.when(jj == reach)
    def _done():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(q, k, v, do, lse, delta, window: int, tile: int):
    B, H, T, D = q.shape
    G = k.shape[1]
    group, n = H // G, T // tile
    r = reach(window, tile, n)
    scale = float(1.0 / np.sqrt(D))
    f32 = jnp.float32
    semantics = _params("parallel", "parallel", "parallel", "arbitrary")

    def specs(qt, kt):
        """In-specs from the grid's (q tile, kv tile) functions."""
        heads_at = lambda b, g, a, c: (b, g, qt(a, c), 0)      # noqa: E731
        keys_at = lambda b, g, a, c: (b, g, kt(a, c), 0)       # noqa: E731
        return [pl.BlockSpec((None, group, tile, D), heads_at),
                pl.BlockSpec((None, None, tile, D), keys_at),
                pl.BlockSpec((None, None, tile, D), keys_at),
                pl.BlockSpec((None, group, tile, D), heads_at),
                pl.BlockSpec((None, None, tile, group), heads_at),
                pl.BlockSpec((None, None, tile, group), heads_at)]

    # q side: grid (b, g, q tile i, the window's kv tiles)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, window=window, reach=r, scale=scale),
        name="window_attn_bwd_dq", grid=(B, G, n, r + 1),
        in_specs=specs(lambda i, j: i,
                       lambda i, j: jnp.maximum(i - r + j, 0)),
        out_specs=pl.BlockSpec((None, group, tile, D),
                               lambda b, g, i, j: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((group, tile, D), f32)],
        compiler_params=semantics,
    )(q, k, v, do, lse, delta)
    # k/v side: grid (b, g, kv tile j, the q tiles whose window meets it)
    kv_at = pl.BlockSpec((None, None, tile, D),
                         lambda b, g, j, jj: (b, g, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, window=window, reach=r, scale=scale,
                          nq=n),
        name="window_attn_bwd_dkv", grid=(B, G, n, r + 1),
        in_specs=specs(lambda j, jj: jnp.minimum(j + jj, n - 1),
                       lambda j, jj: j),
        out_specs=[kv_at, kv_at],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((tile, D), f32),
                        pltpu.VMEM((tile, D), f32)],
        compiler_params=semantics,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(q, k, v, window, tile):
    return _fwd(q, k, v, window, tile)[0]


def _attend_fwd(q, k, v, window, tile):
    o, lse = _fwd(q, k, v, window, tile)
    return o, (q, k, v, o, lse)


def _attend_bwd(window, tile, res, do):
    q, k, v, o, lse = res
    B, H, T, _ = q.shape
    G = k.shape[1]
    do = do.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    delta = delta.reshape(B, G, H // G, T).swapaxes(2, 3)
    return _bwd(q, k, v, do, lse, delta, window, tile)


_attend.defvjp(_attend_fwd, _attend_bwd)


def window_attention(q, k, v, *, window: int, tile: int = TILE):
    """``q`` [B, T, H, D], ``k``/``v`` [B, T, G, D] (q head ``h`` reads
    k/v head ``h // (H / G)``) -> ``o`` [B, T, H, D]: each query over the
    ``window`` keys ending at its own position, softmax scale ``D^-1/2``;
    ``tile`` is cut to ``T``, which it must divide."""
    T = q.shape[1]
    tile = min(tile, T)
    if T % tile:
        raise ValueError(f"{T} positions are not whole tiles of {tile}")
    o = _attend(*(x.swapaxes(1, 2) for x in (q, k, v)), window, tile)
    return o.swapaxes(1, 2)
