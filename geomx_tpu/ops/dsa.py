"""Learned sparse attention (DeepSeek Sparse Attention): a lightning
indexer scores every earlier key for each query, each query attends to
the ``topk`` keys it scored highest, and the indexer is trained by a KL
term of its own.

With ``x`` the (detached) normed stream, J indexer heads of width E,
the indexer's score of key ``s`` for query ``t`` (``s <= t``) is::

    I_ts = sum_j w_tj relu(q_tj . k_s)

where ``w`` comes in already scaled (``models/transformer.py``: J^-1/2
E^-1/2).  ``S_t`` is the ``min(t + 1, topk)`` positions ``s <= t`` of
largest ``I_ts``, ties to the lower position (``lax.top_k``'s order),
and the attention of every head is over ``S_t`` alone.  The indexer's
loss is ``sum_t KL(p_t || softmax(I_t,S_t))`` with ``p_t`` the heads'
mean attention over ``S_t`` under stop-gradient; since ``p`` is a
constant of the backward pass the program carries its cross-entropy
``H(p_t, softmax(I_t,S_t))`` = KL + H(p_t), whose gradient is the
KL's: ``dL/dI_ts = softmax(I_t,S_t)_s - p_ts`` on the chosen pairs.

Three Pallas kernels, tiled ``tile`` x ``tile`` (the config's 512 q and
kv chunks), named so that the trace finds them:

* ``dsa_index``: for a tile of queries, the scores against every
  earlier key (kept in VMEM as int32 keys of the same order as the
  floats, ``-0 = +0``) and an EXACT selection: the ``topk``-th largest
  key ``tau_t`` by bisection over its 32 bits, and where keys tie at
  ``tau_t`` the last tied position ``sigma_t`` that still fits, by
  bisection over positions.  The chosen set is ``key > tau`` or ``key
  == tau and s <= sigma``, which is ``lax.top_k``'s.  Also the chosen
  keys of each row in each kv tile, from which a tile that no query of
  a q tile chose is skipped by the attention kernels.
* ``dsa_attn_fwd``: flash-style attention of all heads over the chosen
  set, the scores computed again by the same code (bit for bit what the
  selection saw), with the online log-sum-exp of the indexer's scores
  over the chosen set and the cross-entropy above.  Each head's row
  statistics (running max, sum and the sum against the indexer's
  scores) live in tiles of their own, ``[H, tile, 128]`` with the value
  in every lane: no two heads share a state word, so the heads' softmax
  chains do not wait on each other's stores, and the broadcast to a
  score tile is a repeat of whole vregs, not a lane permute.  They are
  gathered into ``[tile, H]`` once a q tile, when the row is done.  A
  score off the chosen set is masked once, to ``MASKED`` (below every
  running max), whose ``exp`` is 0.
* ``dsa_attn_bwd_dq`` / ``dsa_attn_bwd_dkv``: the backward pass, q side
  and k/v side, each with the indexer's gradient on its side.

Nothing ``[T, topk, heads, dim]`` is gathered: work is dense over the
tiles that hold a chosen key, masked to the chosen pairs.  Scores,
softmax, the threshold and the KL are float32; matmuls take the inputs'
dtype with float32 accumulation.

What a layer's checkpoint keeps (``KEPT_NAMES``): the selection
(``tau``, ``sigma``, the tiles visited) and what the forward kernel
returned that the backward kernels read (``o``, the heads' ``lse`` and
the indexer's ``lsei``), :func:`named_bytes` a layer (136.5 MB at [1,
16384, 32, 128] in bfloat16).  The forward kernel runs on values
without a gradient and :func:`_attend` takes its outputs as inputs, so
with those kept nothing in the backward pass's recompute of the layer
needs a kernel call: it neither selects nor attends again, and the
forward kernel runs once a layer (PERF.md section 6).  ``ce``
is not kept: the backward pass does not read it.

Off the chip the kernels run under the TPU interpreter
(``pltpu.force_tpu_interpret_mode()``: ``tests/test_dsa.py``), which runs
a kernel op by op, so that every kernel computes the scores to the same
bits as Mosaic's code does on the chip; its callbacks deadlock against
dispatches from other threads and ``jax.checkpoint`` refuses them.  The
rehearsal and the threaded tests run the same equations as plain XLA
(:func:`dsa_attention_xla`: the scores once, ``lax.top_k`` over their
keys, every pair masked).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 512
NEG = -1e30         # finite: exp() of a masked score under it is 0
# a score off the chosen set, below NEG: a row's running max starts at
# NEG and stays there until a chosen score, exp(MASKED - max) is 0
MASKED = 2 * NEG
LANES = 128         # the forward's row statistics fill a vreg's lanes
INT_MIN = np.int32(np.iinfo(np.int32).min)
TAU_NAME, SIGMA_NAME, VISITED_NAME = "dsa_tau", "dsa_sigma", "dsa_visited"
O_NAME, LSE_NAME, LSEI_NAME = "dsa_o", "dsa_lse", "dsa_lsei"
KEPT_NAMES = (TAU_NAME, SIGMA_NAME, VISITED_NAME, O_NAME, LSE_NAME,
              LSEI_NAME)
# the kernels hold a tile of all heads at once (and dsa_index a q
# tile's keys against the whole sequence): past the default scoped VMEM
VMEM_BYTES = 100 * 2 ** 20
TRANS_B = (((1,), (1,)), ((), ()))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def order_key(x):
    """float32 -> int32 of the same order, ``-0`` and ``+0`` one value
    (a score of zero is common: every relu of a key at 0, times weights
    of either sign)."""
    b = jnp.where(x == 0, 0, lax.bitcast_convert_type(x, jnp.int32))
    return jnp.where(b < 0, b ^ np.int32(0x7FFFFFFF), b)


def _scores(qi_ref, ki, w, heads: int):
    """The indexer's scores of a tile, ``[bq, bk]`` float32, from
    ``qi_ref`` [J, bq, E], ``ki`` [bk, E] and ``w`` [bq, J]: the one
    definition every kernel computes, so that all see the same bits."""
    acc = None
    for j in range(heads):
        r = lax.dot_general(qi_ref[j], ki, TRANS_B,
                            preferred_element_type=jnp.float32)
        term = w[:, j:j + 1] * jnp.maximum(r, 0.0)
        acc = term if acc is None else acc + term
    return acc


def _chosen(scores, tau, sigma, q0, k0):
    """The chosen pairs of a tile whose first query and key are ``q0``
    and ``k0``: causal, and ``key > tau`` or tied at it and at or before
    ``sigma``."""
    shape = scores.shape
    rows = q0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    key = order_key(scores)
    return (cols <= rows) & ((key > tau) | ((key == tau) & (cols <= sigma)))


# ---------------------------------------------------------------------------
# dsa_index: the scores and the exact selection
# ---------------------------------------------------------------------------

def _index_kernel(qi_ref, ki_ref, w_ref, tau_ref, sigma_ref, cnt_ref,
                  keys_ref, *, topk: int, heads: int):
    i = pl.program_id(1)
    nk, bq, tile = keys_ref.shape
    T = nk * tile
    w = w_ref[...]
    rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, tile), 0)
    row = i * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)

    def cols(c):
        return c * tile + lax.broadcasted_iota(jnp.int32, (bq, tile), 1)

    def fill(c, carry):
        key = order_key(_scores(qi_ref, ki_ref[c], w, heads))
        # a later key sorts below every score (INT_MIN is a NaN's key)
        keys_ref[c] = jnp.where(cols(c) <= rows, key, INT_MIN)
        return carry

    lax.fori_loop(0, i + 1, fill, 0)

    def count(pred):
        def body(c, acc):
            return acc + jnp.sum(jnp.where(pred(keys_ref[c], c), 1.0, 0.0),
                                 axis=1, keepdims=True)
        return lax.fori_loop(0, i + 1, body,
                             jnp.zeros((bq, 1), jnp.float32))

    # a tile whose every row has topk keys or fewer takes them all
    tau_ref[...] = jnp.full((bq, 1), INT_MIN, jnp.int32)
    sigma_ref[...] = jnp.full((bq, 1), T, jnp.int32)

    @pl.when((i + 1) * bq > topk)
    def _search():
        # the topk-th largest key, a bit at a time from the top: the
        # largest value with at least topk keys at or above it
        ans = jnp.full((bq, 1), INT_MIN, jnp.int32)
        for bit in range(31, -1, -1):
            cand = ans ^ np.array(1 << bit, np.uint32).view(np.int32)
            ans = jnp.where(count(lambda kk, c: kk >= cand) >= topk,
                            cand, ans)
        # of the keys tied at it, the first ``need`` by position: the
        # largest a with fewer than ``need`` ties before position a
        need = topk - count(lambda kk, c: kk > ans)
        a = jnp.zeros((bq, 1), jnp.int32)
        for bit in range(T.bit_length() - 1, -1, -1):
            cand = a + (1 << bit)
            ties = count(lambda kk, c: (kk == ans) & (cols(c) < cand))
            a = jnp.where((cand <= T) & (ties < need), cand, a)
        few = row + 1 <= topk
        tau_ref[...] = jnp.where(few, INT_MIN, ans)
        sigma_ref[...] = jnp.where(few, T, a)

    tau, sigma = tau_ref[...], sigma_ref[...]
    cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)
    for c in range(nk):
        @pl.when(c <= i)
        def _(c=c):
            kk = keys_ref[c]
            ch = (cols(c) <= rows) & ((kk > tau)
                                      | ((kk == tau) & (cols(c) <= sigma)))
            cnt_ref[:, c:c + 1] = jnp.sum(jnp.where(ch, 1.0, 0.0), axis=1,
                                          keepdims=True)


def select(qi, ki, w, *, topk: int, tile: int = TILE):
    """``qi`` [B, J, T, E], ``ki`` [B, T, E], ``w`` [B, T, J] float32 ->
    (``tau``, ``sigma`` int32 [B, T, 1], the chosen keys of each row in
    each kv tile, float32 [B, T, T / tile])."""
    B, J, T, E = qi.shape
    nk = T // tile
    kernel = functools.partial(_index_kernel, topk=topk, heads=J)
    return pl.pallas_call(
        kernel, name="dsa_index", grid=(B, nk),
        in_specs=[
            pl.BlockSpec((None, J, tile, E), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((None, nk, tile, E), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((None, tile, J), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, tile, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, tile, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, tile, nk), lambda b, i: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, T, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, T, nk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nk, tile, tile), jnp.int32)],
        compiler_params=_params("parallel", "parallel"),
    )(qi, ki.reshape(B, nk, tile, E), w)


# ---------------------------------------------------------------------------
# dsa_attn_fwd
# ---------------------------------------------------------------------------

def _wide(x, n: int):
    """A row statistic held in ``LANES`` equal lanes, ``[rows, LANES]``,
    as ``[rows, n]``: repeated, or cut where ``n`` is narrower (the
    tests' tiles and heads)."""
    return x[:, :n] if n <= LANES else pltpu.repeat(x, n // LANES, 1)


def _fwd_kernel(vis_ref, q_ref, k_ref, v_ref, qi_ref, ki_ref, w_ref,
                tau_ref, sigma_ref, o_ref, lse_ref, lsei_ref, ce_ref,
                n_ref, acc_ref, m_ref, l_ref, pi_ref, mi_ref, li_ref, *,
                heads: int, scale: float):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    H, bq, D = q_ref.shape
    group = H // k_ref.shape[0]
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        m_ref[...] = jnp.full(m_ref.shape, NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        pi_ref[...] = jnp.zeros(pi_ref.shape, jnp.float32)
        mi_ref[...] = jnp.full(mi_ref.shape, NEG, jnp.float32)
        li_ref[...] = jnp.zeros(li_ref.shape, jnp.float32)
        n_ref[...] = jnp.zeros(n_ref.shape, jnp.float32)

    @pl.when((j <= i) & (vis_ref[(b * nq + i) * nk + j] > 0))
    def _tile():
        idx = _scores(qi_ref, ki_ref[...], w_ref[...], heads)
        ch = _chosen(idx, tau_ref[...], sigma_ref[...], i * bq, j * bk)
        n_ref[...] += jnp.sum(jnp.where(ch, 1.0, 0.0), axis=1, keepdims=True)
        # the indexer's softmax over the chosen set, online
        si = jnp.where(ch, idx, MASKED)
        mi = mi_ref[...]
        mi_new = jnp.maximum(mi, jnp.max(si, axis=1, keepdims=True))
        li_ref[...] = li_ref[...] * jnp.exp(mi - mi_new) + jnp.sum(
            jnp.exp(si - _wide(mi_new, bk)), axis=1, keepdims=True)
        mi_ref[...] = mi_new
        for h in range(H):
            g = h // group
            s = lax.dot_general(q_ref[h], k_ref[g], TRANS_B,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(ch, s, MASKED)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - _wide(m_new, bk))
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            pi_ref[h] = alpha * pi_ref[h] + jnp.sum(p * idx, axis=1,
                                                    keepdims=True)
            acc_ref[h] = _wide(alpha, D) * acc_ref[h] + lax.dot_general(
                p.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == i)
    def _done():
        lane = lax.broadcasted_iota(jnp.int32, (1, H), 1)

        def by_head(ref):
            """A lane-replicated ``[H, bq, LANES]`` statistic as ``[bq,
            H]``."""
            out = jnp.zeros(lse_ref.shape, jnp.float32)
            for h in range(H):
                out = jnp.where(lane == h, _wide(ref[h], H), out)
            return out

        for h in range(H):
            o_ref[h] = (acc_ref[h] / _wide(l_ref[h], D)).astype(o_ref.dtype)
        l = by_head(l_ref)
        lse_ref[...] = by_head(m_ref) + jnp.log(l)
        lsei = mi_ref[:, :1] + jnp.log(li_ref[:, :1])
        lsei_ref[...] = lsei
        # H(p, softmax(I)) = lse(I) - sum_s p_s I_s, p the heads' mean
        ce_ref[...] = lsei - jnp.sum(by_head(pi_ref) / l, axis=1,
                                     keepdims=True) / H


def _fwd(q, k, v, qi, ki, w, tau, sigma, visited, tile):
    """``q`` [B, H, T, D], ``k``/``v`` [B, G, T, D], ``qi`` [B, J, T, E],
    ``ki`` [B, T, E], ``w`` [B, T, J] -> (o [B, H, T, D], lse [B, T, H],
    lse of the indexer's chosen scores [B, T, 1], the cross-entropy [B,
    T, 1], chosen keys attended a row [B, T, 1])."""
    B, H, T, D = q.shape
    G, J, E = k.shape[1], qi.shape[1], qi.shape[-1]
    n = T // tile
    kernel = functools.partial(_fwd_kernel, heads=J,
                               scale=float(1.0 / np.sqrt(D)))
    rows = lambda b, i, j, vis: (b, i, 0)                     # noqa: E731
    heads_at = lambda b, i, j, vis: (b, 0, i, 0)              # noqa: E731
    keys_at = lambda b, i, j, vis: (b, 0, jnp.minimum(j, i), 0)  # noqa: E731
    vec = lambda width: pl.BlockSpec((None, tile, width), rows)  # noqa: E731
    f32 = jnp.float32
    return pl.pallas_call(
        kernel, name="dsa_attn_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n, n),
            in_specs=[
                pl.BlockSpec((None, H, tile, D), heads_at),
                pl.BlockSpec((None, G, tile, D), keys_at),
                pl.BlockSpec((None, G, tile, D), keys_at),
                pl.BlockSpec((None, J, tile, E), heads_at),
                pl.BlockSpec((None, tile, E), lambda b, i, j, vis: (
                    b, jnp.minimum(j, i), 0)),
                vec(J), vec(1), vec(1),
            ],
            out_specs=[pl.BlockSpec((None, H, tile, D), heads_at),
                       vec(H), vec(1), vec(1), vec(1)],
            scratch_shapes=[pltpu.VMEM((H, tile, D), f32),
                            pltpu.VMEM((H, tile, LANES), f32),
                            pltpu.VMEM((H, tile, LANES), f32),
                            pltpu.VMEM((H, tile, LANES), f32),
                            pltpu.VMEM((tile, LANES), f32),
                            pltpu.VMEM((tile, LANES), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
                   jax.ShapeDtypeStruct((B, T, H), f32),
                   jax.ShapeDtypeStruct((B, T, 1), f32),
                   jax.ShapeDtypeStruct((B, T, 1), f32),
                   jax.ShapeDtypeStruct((B, T, 1), f32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
    )(visited, q, k, v, qi, ki, w, tau, sigma)


# ---------------------------------------------------------------------------
# dsa_attn_bwd: the q side and the k/v side
# ---------------------------------------------------------------------------

def _tile_grads(q_ref, k_ref, v_ref, do_ref, qi_ref, ki, w, tau, sigma,
                lse, delta, lsei, gce, q0, k0, *, heads, scale, on_head):
    """What both backward kernels compute of a tile: the chosen pairs,
    each head's probabilities ``a`` and score gradient ``ds`` (handed to
    ``on_head(h, g, a, ds)``), and the indexer's score gradient ``dI =
    gce (softmax(I) - p)`` on the chosen pairs."""
    H = q_ref.shape[0]
    group = H // k_ref.shape[0]
    idx = _scores(qi_ref, ki, w, heads)
    ch = _chosen(idx, tau, sigma, q0, k0)
    p = None
    for h in range(H):
        g = h // group
        s = lax.dot_general(q_ref[h], k_ref[g], TRANS_B,
                            preferred_element_type=jnp.float32) * scale
        a = jnp.where(ch, jnp.exp(s - lse[:, h:h + 1]), 0.0)
        dp = lax.dot_general(do_ref[h], v_ref[g], TRANS_B,
                             preferred_element_type=jnp.float32)
        on_head(h, g, a, a * (dp - delta[:, h:h + 1]))
        p = a if p is None else p + a
    soft = jnp.where(ch, jnp.exp(idx - lsei), 0.0)
    return gce * (soft - p / H)


def _relu_grads(qi_ref, ki, w, d_idx, heads, on_head):
    """For each indexer head j: ``relu(q_j k^T)`` and the score gradient
    through it, ``dI w_j 1[q_j k > 0]``, handed to ``on_head``."""
    for j in range(heads):
        r = lax.dot_general(qi_ref[j], ki, TRANS_B,
                            preferred_element_type=jnp.float32)
        on_head(j, jnp.maximum(r, 0.0),
                jnp.where(r > 0, d_idx * w[:, j:j + 1], 0.0))


def _dq_kernel(vis_ref, q_ref, k_ref, v_ref, do_ref, qi_ref, ki_ref, w_ref,
               tau_ref, sigma_ref, lse_ref, delta_ref, lsei_ref, gce_ref,
               dq_ref, dqi_ref, dw_ref, dq_acc, dqi_acc, dw_acc, *,
               heads: int, scale: float):
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    H, bq, _ = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)
        dqi_acc[...] = jnp.zeros(dqi_acc.shape, jnp.float32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, jnp.float32)

    @pl.when((j <= i) & (vis_ref[(b * nq + i) * nk + j] > 0))
    def _tile():
        kk, ki, w = k_ref, ki_ref[...], w_ref[...]

        def head(h, g, a, ds):
            dq_acc[h] += lax.dot_general(
                ds.astype(kk.dtype), kk[g], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        d_idx = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, qi_ref, ki, w, tau_ref[...],
            sigma_ref[...], lse_ref[...], delta_ref[...], lsei_ref[...],
            gce_ref[...], i * bq, j * bk, heads=heads, scale=scale,
            on_head=head)

        def index_head(jj, relu, dr):
            dw_acc[:, jj:jj + 1] += jnp.sum(d_idx * relu, axis=1,
                                            keepdims=True)
            dqi_acc[jj] += lax.dot_general(
                dr.astype(ki.dtype), ki, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        _relu_grads(qi_ref, ki, w, d_idx, heads, index_head)

    @pl.when(j == i)
    def _done():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)
        dqi_ref[...] = dqi_acc[...]
        dw_ref[...] = dw_acc[...]


def _dkv_kernel(vis_ref, q_ref, k_ref, v_ref, do_ref, qi_ref, ki_ref, w_ref,
                tau_ref, sigma_ref, lse_ref, delta_ref, lsei_ref, gce_ref,
                dk_ref, dv_ref, dki_ref, dk_acc, dv_acc, dki_acc, *,
                heads: int, scale: float):
    b, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk, nq = pl.num_programs(1), pl.num_programs(2)
    H, bq, _ = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
        dki_acc[...] = jnp.zeros(dki_acc.shape, jnp.float32)

    @pl.when((i >= j) & (vis_ref[(b * nq + i) * nk + j] > 0))
    def _tile():
        ki, w = ki_ref[...], w_ref[...]

        def head(h, g, a, ds):
            dv_acc[g] += lax.dot_general(
                a.T.astype(do_ref.dtype), do_ref[h],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dk_acc[g] += lax.dot_general(
                ds.T.astype(q_ref.dtype), q_ref[h],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

        d_idx = _tile_grads(
            q_ref, k_ref, v_ref, do_ref, qi_ref, ki, w, tau_ref[...],
            sigma_ref[...], lse_ref[...], delta_ref[...], lsei_ref[...],
            gce_ref[...], i * bq, j * bk, heads=heads, scale=scale,
            on_head=head)

        def index_head(jj, relu, dr):
            dki_acc[...] += lax.dot_general(
                dr.T.astype(qi_ref.dtype), qi_ref[jj],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        _relu_grads(qi_ref, ki, w, d_idx, heads, index_head)

    @pl.when(i == nq - 1)
    def _done():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dki_ref[...] = dki_acc[...]


def _bwd(q, k, v, do, qi, ki, w, tau, sigma, lse, delta, lsei, gce,
         visited, tile):
    B, H, T, D = q.shape
    G, J, E = k.shape[1], qi.shape[1], qi.shape[-1]
    n = T // tile
    f32 = jnp.float32
    scale = float(1.0 / np.sqrt(D))
    args = (visited, q, k, v, do, qi, ki, w, tau, sigma, lse, delta, lsei,
            gce)

    def specs(qt, kt):
        """In-specs from the grid's (q tile, kv tile) functions."""
        heads_at = lambda *a: (a[0], 0, qt(*a), 0)            # noqa: E731
        keys_at = lambda *a: (a[0], 0, kt(*a), 0)             # noqa: E731
        vec = lambda width: pl.BlockSpec(                     # noqa: E731
            (None, tile, width), lambda *a: (a[0], qt(*a), 0))
        return [pl.BlockSpec((None, H, tile, D), heads_at),
                pl.BlockSpec((None, G, tile, D), keys_at),
                pl.BlockSpec((None, G, tile, D), keys_at),
                pl.BlockSpec((None, H, tile, D), heads_at),
                pl.BlockSpec((None, J, tile, E), heads_at),
                pl.BlockSpec((None, tile, E), lambda *a: (a[0], kt(*a), 0)),
                vec(J), vec(1), vec(1), vec(H), vec(H), vec(1), vec(1)]

    # q side: grid (b, q tile i, kv tile j), later kv tiles read the
    # diagonal's again (no copy) and compute nothing
    qt = lambda b, i, j, vis: i                               # noqa: E731
    kt = lambda b, i, j, vis: jnp.minimum(j, i)               # noqa: E731
    dq, dqi, dw = pl.pallas_call(
        functools.partial(_dq_kernel, heads=J, scale=scale),
        name="dsa_attn_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n, n), in_specs=specs(qt, kt),
            out_specs=[
                pl.BlockSpec((None, H, tile, D),
                             lambda b, i, j, vis: (b, 0, i, 0)),
                pl.BlockSpec((None, J, tile, E),
                             lambda b, i, j, vis: (b, 0, i, 0)),
                pl.BlockSpec((None, tile, J), lambda b, i, j, vis: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((H, tile, D), f32),
                            pltpu.VMEM((J, tile, E), f32),
                            pltpu.VMEM((tile, J), f32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(qi.shape, f32),
                   jax.ShapeDtypeStruct(w.shape, f32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
    )(*args)
    # k/v side: grid (b, kv tile j, q tile i), earlier q tiles read the
    # diagonal's
    qt = lambda b, j, i, vis: jnp.maximum(i, j)               # noqa: E731
    kt = lambda b, j, i, vis: j                               # noqa: E731
    dk, dv, dki = pl.pallas_call(
        functools.partial(_dkv_kernel, heads=J, scale=scale),
        name="dsa_attn_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n, n), in_specs=specs(qt, kt),
            out_specs=[
                pl.BlockSpec((None, G, tile, D),
                             lambda b, j, i, vis: (b, 0, j, 0)),
                pl.BlockSpec((None, G, tile, D),
                             lambda b, j, i, vis: (b, 0, j, 0)),
                pl.BlockSpec((None, tile, E), lambda b, j, i, vis: (b, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((G, tile, D), f32),
                            pltpu.VMEM((G, tile, D), f32),
                            pltpu.VMEM((tile, E), f32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(ki.shape, f32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
    )(*args)
    return dq, dk, dv, dqi, dki, dw


# ---------------------------------------------------------------------------
# the differentiable op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(13,))
def _attend(q, k, v, qi, ki, w, o, lse, lsei, ce, tau, sigma, visited,
            tile):
    """``(o, ce)`` as the forward kernel computed them, differentiable
    in q, k, v and the indexer's q, k and w: the forward kernel runs on
    values without a gradient, so that a layer's checkpoint can keep
    what it returned, and this carries the backward kernels."""
    return o, ce


def _attend_fwd(q, k, v, qi, ki, w, o, lse, lsei, ce, tau, sigma, visited,
                tile):
    return (o, ce), (q, k, v, qi, ki, w, o, lse, lsei, tau, sigma, visited)


def _attend_bwd(tile, res, cts):
    q, k, v, qi, ki, w, o, lse, lsei, tau, sigma, visited = res
    do, dce = cts
    do = do.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    dq, dk, dv, dqi, dki, dw = _bwd(
        q, k, v, do, qi, ki, w, tau, sigma, lse, delta.swapaxes(1, 2), lsei,
        dce.astype(jnp.float32), visited, tile)
    return (dq, dk, dv, dqi.astype(qi.dtype), dki.astype(ki.dtype),
            dw.astype(w.dtype), None, None, None, None, None, None, None)


_attend.defvjp(_attend_fwd, _attend_bwd)


def dsa_attention(q, k, v, qi, ki, w, *, topk: int, tile: int = TILE):
    """Attention of every head over the keys its query's indexer scores
    chose.  ``q`` [B, T, H, D], ``k``/``v`` [B, T, G, D] (q head h reads
    k/v head ``h // (H / G)``), ``qi`` [B, T, J, E], ``ki`` [B, T, E],
    ``w`` [B, T, J] float32 (scaled).  Returns ``(o [B, T, H, D], ce [B,
    T] float32, stats)``: ``ce`` the indexer's cross-entropy a query,
    whose gradient reaches ``qi``, ``ki`` and ``w`` alone (the loss of
    ``models/transformer.py`` sums it); ``stats`` int32 counts for the
    tracer: ``chosen_pairs`` (the selection's), ``attended_pairs``
    (those the forward kernel's mask let through: equal, or the two
    computed the scores differently), ``tiles_visited`` and
    ``tiles_causal`` (``tile`` x ``tile`` tiles the attention computed,
    of those on or below the diagonal)."""
    B, T, H, D = q.shape
    if T % tile:
        raise ValueError(f"{T} positions are not whole tiles of {tile}")
    n = T // tile
    sg = lax.stop_gradient
    qh, kh, vh, qit = (x.swapaxes(1, 2) for x in (q, k, v, qi))
    tau, sigma, counts = select(sg(qit), sg(ki), sg(w), topk=topk, tile=tile)
    tau = checkpoint_name(tau, TAU_NAME)
    sigma = checkpoint_name(sigma, SIGMA_NAME)
    tiles = counts.reshape(B, n, tile, n).sum(2)
    visited = checkpoint_name((tiles > 0).astype(jnp.int32).reshape(-1),
                              VISITED_NAME)
    o, lse, lsei, ce, attended = _fwd(
        sg(qh), sg(kh), sg(vh), sg(qit), sg(ki), sg(w), tau, sigma, visited,
        tile)
    o = checkpoint_name(o, O_NAME)
    lse = checkpoint_name(lse, LSE_NAME)
    lsei = checkpoint_name(lsei, LSEI_NAME)
    o, ce = _attend(qh, kh, vh, qit, ki, w, o, lse, lsei, ce, tau, sigma,
                    visited, tile)
    # int32: T x topk pairs pass float32's 2^24
    stats = {"chosen_pairs": jnp.sum(counts.astype(jnp.int32)),
             "attended_pairs": jnp.sum(attended.astype(jnp.int32)),
             "tiles_visited": jnp.sum(visited),
             "tiles_causal": jnp.int32(B * n * (n + 1) // 2)}
    return o.swapaxes(1, 2), ce[..., 0], stats


def named_bytes(q, tile: int = TILE) -> int:
    """What :func:`dsa_attention` names for a checkpoint around it to
    keep (:data:`KEPT_NAMES`) at ``q`` [B, T, H, D], in bytes from
    shapes: ``o`` in q's dtype, ``lse`` [B, T, H] and ``lsei`` [B, T,
    1] float32, ``tau`` and ``sigma`` [B, T, 1] and the tiles visited
    [B (T / tile)^2] int32."""
    B, T, H, _ = q.shape
    n = T // tile
    return q.size * q.dtype.itemsize + 4 * B * (T * H + 3 * T + n * n)


def dsa_attention_xla(q, k, v, qi, ki, w, *, topk: int, tile: int = TILE):
    """:func:`dsa_attention`'s equations as plain XLA, for small shapes
    off the chip: the scores once for every pair (float32), the chosen
    pairs by ``lax.top_k`` over their keys (ties to the lower position,
    ``-0`` and ``+0`` one score), dense attention under the mask, the
    same outputs and counts (``tile`` only counts the tiles)."""
    B, T, H, D = q.shape
    n = -(-T // tile)
    f32 = jnp.float32
    r = jnp.einsum("btje,bse->bjts", qi, ki, preferred_element_type=f32)
    idx = sum(w[:, :, j, None] * jnp.maximum(r[:, j], 0.0)
              for j in range(qi.shape[2]))
    causal = jnp.tril(jnp.ones((T, T), bool))
    keys, at = lax.top_k(jnp.where(causal, order_key(idx), INT_MIN),
                         min(topk, T))
    rows = (jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None])
    ch = jnp.zeros((B, T, T), bool).at[rows + (at,)].set(
        (at <= jnp.arange(T)[:, None]))
    kk, vv = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, kk,
                   preferred_element_type=f32) / np.sqrt(D)
    a = jax.nn.softmax(jnp.where(ch[:, None], s, NEG), axis=-1)
    a = jnp.where(ch[:, None], a, 0.0)
    o = jnp.einsum("bhts,bshd->bthd", a.astype(v.dtype), vv,
                   preferred_element_type=f32).astype(q.dtype)
    p = lax.stop_gradient(jnp.mean(a, axis=1))
    logq = jax.nn.log_softmax(jnp.where(ch, idx, NEG), axis=-1)
    ce = -jnp.sum(jnp.where(ch, p * logq, 0.0), axis=-1)
    pad = n * tile - T
    tiles = jnp.pad(ch, ((0, 0), (0, pad), (0, pad))).reshape(
        B, n, tile, n, tile).any((2, 4))
    pairs = jnp.sum(ch, dtype=jnp.int32)
    return o, ce, {"chosen_pairs": pairs, "attended_pairs": pairs,
                   "tiles_visited": jnp.sum(tiles, dtype=jnp.int32),
                   "tiles_causal": jnp.int32(B * n * (n + 1) // 2)}
