"""Kimi Delta Attention: the gated delta rule with a per-channel decay,
in its chunked form.

Per head, with a state ``S`` of K x V (zero before the first position),
a log decay ``g_t`` <= 0 per key channel and a step ``beta_t``::

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

A token at a time that is T dependent steps.  The chunked form does the
work inside a chunk of C positions as matrix products and carries the
state from chunk to chunk, T / C dependent steps.  With ``G`` the log
decay cumulated inside the chunk, ``S_0`` the state the chunk starts
from and ``u_i = beta_i (v_i - S'_i^T k_i)`` the rank-one updates::

    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc)   (j < i)
    B_ij = sum_c q_ic k_jc exp(G_ic - G_jc)   (j <= i)
    M = (I + diag(beta) A)^-1 diag(beta)      (a C x C triangular system)
    W = M (k * exp(G)),  U = M v
    V_new = U - W S_0                         (the u_i, stacked)
    O = (q * exp(G)) S_0 + B V_new
    S_C = diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T V_new

**The trap.**  The factored form ``(k * exp(G)) (k * exp(-G))^T`` of
``A`` overflows in float32 once ``G`` passes -88, which decays of a few
units a position reach inside a chunk.  Every exponent taken here is of
a difference that is <= 0: a chunk is halved down to single positions,
and the pairs of a later half's row i with the earlier half's column j
take both factors relative to the later half's first row r (``exp(G_i
- r) exp(r - G_j)``, each <= 1), a reference point inside every block
of every size (:func:`_pair_products`).  An underflow to 0 is the right answer to float32's
precision; an overflow is not.

This is the algorithm in ``jax.numpy``: the chunk-parallel
products batched over the chunks of a block and the heads, a
``lax.scan`` over the chunks for the state, a block of chunks at a time
under ``jax.checkpoint``, differentiated by jax (the triangular inverse
by hand: ``d(M^-1) = -M^-T dM M^-T``, so that its row recursion keeps
nothing).  Any backend.  TPU kernels for the state's pass from chunk
to chunk (a backward pass of their own, eight heads a grid step) were
written, compiled for the v5e and measured against this scan at the
cell's shape, lost at every block size, and are not here: PERF.md
section 6 (PR 37).

**What a layer's checkpoint keeps.**  The scan over blocks names two of
its values (``jax.ad_checkpoint.checkpoint_name``): the state a block
starts from (:data:`STATE_NAME`, float32) and the block's output
(:data:`OUT_NAME`).  Under a plain ``jax.checkpoint`` of the layer
around it the names mean nothing and the layer's backward pass runs the
whole scan forward again only to get those two back, then each block
once more inside the backward scan: three runs of the chunk-parallel
products.  A checkpoint whose policy is ``save_only_these_names(*
KEPT_NAMES)`` (``models/transformer.py`` ``make_apply`` under ``remat``)
keeps them from the first forward pass, ``named_bytes`` a layer (at
[1, 8192, 32, 128] in bfloat16 67 MB of states and 67 MB of output),
and the scan runs twice: forward, and a block at a time in the backward
scan, whose own ``jax.checkpoint`` stays inside the named body so that a
block's 4 GB a layer of chunk products are still never kept (PERF.md
section 6, PR 38).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

SUB = 16        # rows of the triangular system inverted by substitution


def _precision(dtype):
    return (lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def _inverse_rows(low):
    """``(I + low)^-1`` for strictly lower ``low`` [..., c, c] of a few
    rows, by forward substitution, row by row, unrolled."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    rows = []
    for i in range(c):
        row = jnp.broadcast_to(eye[i], low.shape[:-2] + (c,))
        if i:
            row = row - jnp.einsum("...j,...jk->...k", low[..., i, :i],
                                   jnp.stack(rows, axis=-2),
                                   precision=lax.Precision.HIGHEST)
        rows.append(row)
    return jnp.stack(rows, axis=-2)


def _inverse(low):
    c = low.shape[-1]
    s = min(SUB, c)
    lead = low.shape[:-2]

    def blocks(size):
        n = c // size
        return low.reshape(lead + (n, size, n, size))

    b = blocks(s)
    inv = _inverse_rows(jnp.stack([b[..., i, :, i, :] for i in range(c // s)],
                                  axis=-3))
    hi = lax.Precision.HIGHEST
    while s < c:
        # [[a, 0], [l, d]]^-1 = [[a^-1, 0], [-d^-1 l a^-1, d^-1]]
        b = blocks(s)
        pairs = c // s // 2
        below = jnp.stack([b[..., 2 * p + 1, :, 2 * p, :]
                           for p in range(pairs)], axis=-3)
        inv = inv.reshape(lead + (pairs, 2, s, s))
        a, d = inv[..., 0, :, :], inv[..., 1, :, :]
        corner = -jnp.matmul(jnp.matmul(d, below, precision=hi), a,
                             precision=hi)
        inv = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([corner, d], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


@jax.custom_vjp
def unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low`` [..., C, C] strictly lower
    triangular, C a power of two times :data:`SUB` (or less than it),
    float32: sub-blocks by forward substitution, then merged two by two
    with matrix products.  A sum of powers of ``low`` would cancel
    catastrophically where the keys of a chunk are alike."""
    return _inverse(low)


def _inverse_fwd(low):
    inv = _inverse(low)
    return inv, inv


def _inverse_bwd(inv, d_inv):
    hi = lax.Precision.HIGHEST
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, d_inv, precision=hi), t,
                        precision=hi),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _pair_products(q, k, G, dtype):
    """``A`` (strictly lower) and ``B`` (lower, with the diagonal) of a
    chunk, [..., C, C] float32, from q, k [..., C, K] and the cumulated
    log decay ``G`` [..., C, K] float32; no exponent above 0.

    By halving: a block of b positions is its two halves' blocks on the
    diagonal and, below them, the pairs of a later row i with an earlier
    column j, whose decay ``exp(G_i - G_j)`` factors through the later
    half's first row r as ``exp(G_i - r) exp(r - G_j)``, each <= 1: one
    matrix product a block.  From single positions (A 0, B ``q_i .
    k_i``) up to the chunk, log2 C levels."""
    lead, (C, K) = q.shape[:-2], q.shape[-2:]
    prec = _precision(dtype)
    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    B = jnp.sum(qf * kf, axis=-1)[..., None, None]      # [., C, 1, 1]
    A = jnp.zeros_like(B)
    half = 1
    while half < C:
        n = C // (2 * half)
        halves = lambda x: x.reshape(lead + (n, 2, half, K))  # noqa: E731
        Gh, qh, kh = halves(G), halves(qf), halves(kf)
        ref = Gh[..., 1, :1, :]
        rows = jnp.exp(Gh[..., 1, :, :] - ref)
        cols = (kh[..., 0, :, :] * jnp.exp(ref - Gh[..., 0, :, :])
                ).astype(dtype)
        below = lambda x: jnp.einsum(                   # noqa: E731
            "...ik,...jk->...ij", (x[..., 1, :, :] * rows).astype(dtype),
            cols, precision=prec, preferred_element_type=jnp.float32)

        def merge(diag, corner):
            d = diag.reshape(lead + (n, 2, half, half))
            first, second = d[..., 0, :, :], d[..., 1, :, :]
            return jnp.concatenate([
                jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
                jnp.concatenate([corner, second], axis=-1)], axis=-2)

        A, B = merge(A, below(kh)), merge(B, below(qh))
        half *= 2
    return A[..., 0, :, :], B[..., 0, :, :]


def _state_scan(S0, W, U, k_out, keep):
    """The chunks' states from ``S0`` [B, H, V, K] float32 (TRANSPOSED:
    value channels first, so that the decay is a broadcast along the
    last dimension): W, k_out [B, H, N, C, K], U float32 [B, H, N, C,
    V], keep float32 [B, H, N, 1, K] -> (the state each chunk starts
    from [B, H, N, V, K] and V_new [B, H, N, C, V], both in W's dtype,
    the state after the last chunk, float32).  What is left dependent in
    the chunked form: for each chunk in turn ``V_new = U - W S`` and ``S
    <- diag(keep) S + K_out^T V_new``, a ``lax.scan`` differentiated by
    jax."""
    dtype = W.dtype
    mm = functools.partial(jnp.einsum, precision=_precision(dtype),
                           preferred_element_type=jnp.float32)

    def step(S, xs):
        W_n, U_n, k_out_n, keep_n = xs
        v_new = U_n - mm("bhck,bhvk->bhcv", W_n, S.astype(dtype))
        S_next = keep_n * S + mm("bhcv,bhck->bhvk", v_new.astype(dtype),
                                 k_out_n)
        return S_next, (S.astype(dtype), v_new.astype(dtype))

    chunks_first = lambda x: jnp.moveaxis(x, 2, 0)   # noqa: E731
    S_end, (S, v_new) = lax.scan(
        step, S0, tuple(map(chunks_first, (W, U, k_out, keep))))
    return jnp.moveaxis(S, 0, 2), jnp.moveaxis(v_new, 0, 2), S_end


def _block(S0, q, k, v, g, beta):
    """A block of N chunks from the state ``S0`` [B, H, V, K] float32
    (transposed: value channels first): q, k [B, H, N, C, K], v [B, H,
    N, C, V], g float32 like k, beta float32 [B, H, N, C] -> (o [B, H,
    N, C, V], the state after the block, the most negative cumulated log
    decay)."""
    dtype = v.dtype
    mm = functools.partial(jnp.einsum, precision=_precision(dtype),
                           preferred_element_type=jnp.float32)
    G = jnp.cumsum(g, axis=-2)
    A, Bm = _pair_products(q, k, G, dtype)
    M = (unit_lower_inverse(beta[..., :, None] * A)
         * beta[..., None, :]).astype(dtype)
    kf = k.astype(jnp.float32)
    last = G[..., -1:, :]
    k_in = (kf * jnp.exp(G)).astype(dtype)           # into the chunk
    k_out = (kf * jnp.exp(last - G)).astype(dtype)   # on to its end
    q_in = (q.astype(jnp.float32) * jnp.exp(G)).astype(dtype)
    W = mm("...ij,...jk->...ik", M, k_in).astype(dtype)
    U = mm("...ij,...jv->...iv", M, v)
    S, v_new, S_end = _state_scan(S0, W, U, k_out, jnp.exp(last))
    o = (mm("...ck,...vk->...cv", q_in, S)
         + mm("...ij,...jv->...iv", Bm.astype(dtype), v_new))
    return o.astype(dtype), S_end, jnp.min(G)


BLOCK_CHUNKS = 4    # chunks of a block: the chunk-parallel products of
#                     one block are alive at a time, and the backward
#                     pass keeps one state a block and computes the
#                     block again (jax.checkpoint); 4 by a sweep at the
#                     cell's shape on the v5e (PERF.md section 6, PR 37)

# what the scan over blocks names for a checkpoint around it to keep
STATE_NAME = "kda_state"    # the state a block starts from, float32
OUT_NAME = "kda_out"        # a block's output
KEPT_NAMES = (STATE_NAME, OUT_NAME)


def chunk_kda(q, k, v, g, beta, chunk: int = 64):
    """The gated delta rule over ``q``, ``k`` [B, T, H, K] (the caller's
    normalisation and scale already in them), ``v`` [B, T, H, V], the
    log decay ``g`` [B, T, H, K] (float32, <= 0) and the step ``beta``
    [B, T, H] (float32): ``(o [B, T, H, V]`` in v's dtype, ``stats)``.
    Matrix products run in v's dtype with float32 sums (float32 at
    ``highest``); decays, the triangular system and the carried state
    are float32.  ``stats``: ``log_decay_min``, the most negative log
    decay cumulated inside any chunk (float32 scalar, no gradient), how
    near the naive factored form would be to overflow (-88); ``chunks``
    and ``state_bytes``, the float32 states the backward pass is handed
    (one a block of :data:`BLOCK_CHUNKS` chunks, head and sequence), and
    ``named_bytes``, those states and the output, what a checkpoint
    around the scan keeps if its policy saves :data:`KEPT_NAMES`; all
    three from shapes."""
    B, T, H, K = k.shape
    V = v.shape[-1]
    per = min(BLOCK_CHUNKS, -(-T // chunk))
    pad = -T % (chunk * per)
    if pad:
        # a padded position decays nothing, writes nothing (beta 0, k 0)
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (x.ndim - 2)) for x in (q, k, v, g, beta))
    blocks = (T + pad) // (chunk * per)

    def blocked(x):      # [B, T, H, ...] -> [blocks, B, H, per, C, ...]
        x = x.reshape((B, blocks, per, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 4, 1), 2, 0)

    block = jax.checkpoint(_block)

    def body(S, xs):
        o, S, low = block(checkpoint_name(S, STATE_NAME), *xs)
        return S, (checkpoint_name(o, OUT_NAME), low)

    with jax.named_scope("kda"):
        _, (o, low) = lax.scan(
            body, jnp.zeros((B, H, V, K), jnp.float32),
            (blocked(q), blocked(k), blocked(v),
             blocked(g.astype(jnp.float32)),
             blocked(beta.astype(jnp.float32))))
    state_bytes = blocks * B * H * K * V * 4
    named_bytes = state_bytes + o.size * o.dtype.itemsize
    # [blocks, B, H, per, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 4).reshape(
        B, blocks * per * chunk, H, V)[:, :T]
    stats = {"log_decay_min": lax.stop_gradient(jnp.min(low)),
             "chunks": blocks * per,
             "state_bytes": state_bytes, "named_bytes": named_bytes}
    return o, stats
