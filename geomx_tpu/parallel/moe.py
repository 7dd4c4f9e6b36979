"""Expert parallelism: top-k routed MoE, with capacity (GShard one-hot
dispatch: :func:`moe_ffn_topk`) or without (sorted rows and grouped
products over the experts held: :func:`routed_ffn`, at the end).

Absent from the reference (SURVEY.md §2.3 — GeoMX has no MoE/EP
anywhere); a TPU-design addition.  Round-2 shipped dense routing (every
expert computes every token — exact but O(E) FLOPs); this module is the
real thing: GShard/Switch-style top-k routing where each token is
computed by only its k chosen experts, bounded by a per-group expert
capacity, so **per-token FLOPs are independent of the expert count**.

Design notes (why this shape and not a sort/scatter kernel):

- Dispatch and combine are expressed as *einsums over one-hot tensors*
  — the formulation GSPMD partitions natively.  With experts sharded
  ``P("tp")`` (ep aliases tp: each device owns E/tp experts) and
  activations replicated over tp, XLA partitions the dispatch einsum
  with zero communication and inserts exactly one psum at the combine —
  the same collective footprint as the Megatron MLP it replaces.  This
  is no longer just a claim: tests/test_moe_collectives.py compiles the
  sharded train step and asserts ZERO all-gather/all-to-all in the
  optimized HLO, matching the dense-FFN peer (the audit also caught and
  fixed a d_model-sharded embedding that was gathering the residual
  stream in front of every matmul — see models/transformer.param_specs).
- Shapes are static: capacity ``C = ceil(S*k*cf/E)`` is computed from
  static dims, tokens past capacity are dropped (standard GShard
  semantics), and the schedule contains no data-dependent control flow
  — everything tiles onto the MXU.
- Tokens route in groups (the leading batch dim): capacity is per
  group, which bounds the dispatch tensor at [G,S,E,C] = S²·k·cf
  elements per group instead of the global (G·S)² blowup.

Exactness anchor: with ``k = E`` and ``capacity = S`` the dispatch is
total (every token reaches every expert with its full softmax gate), so
the layer reproduces dense routing bit-for-bit — that equivalence is the
correctness test (tests/test_moe.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def expert_capacity(tokens_per_group: int, n_experts: int, k: int,
                    capacity_factor: float) -> int:
    """Per-group per-expert slot count: ceil(S·k·cf / E), min 1."""
    return max(1, math.ceil(tokens_per_group * k * capacity_factor
                            / n_experts))


def topk_dispatch_combine(
    router_logits: jax.Array,
    k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing tensors for grouped tokens.

    ``router_logits``: [G, S, E] float32 (G groups of S tokens).
    Returns ``(dispatch, combine, aux_loss)``:

    - ``dispatch`` [G, S, E, C] float32 in {0,1} — token s of group g
      occupies slot c of expert e;
    - ``combine``  [G, S, E, C] float32 — dispatch scaled by the token's
      (renormalized) gate for that expert;
    - ``aux_loss`` scalar — Switch-style load-balancing loss
      (E · Σ_e fraction_tokens_e · mean_router_prob_e), to be added to
      the training objective with a small coefficient.

    Priority is choice-major then token-major (all first choices claim
    slots before any second choice), matching GShard so earlier tokens
    never lose their first-choice slot to a later token's second choice.
    """
    G, S, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, k)          # [G, S, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [G,S,k,E]

    # position of each (token, choice) within its expert's queue,
    # counted choice-major: cumsum over the flattened [k*S] order
    oh_km = jnp.swapaxes(onehot, 1, 2)                 # [G, k, S, E]
    cum = jnp.cumsum(oh_km.reshape(G, k * S, E), axis=1)
    pos_km = cum.reshape(G, k, S, E) - oh_km           # exclusive cumsum
    pos = jnp.swapaxes(pos_km, 1, 2)                   # [G, S, k, E]
    pos_in_expert = jnp.sum(pos * onehot, axis=-1).astype(jnp.int32)

    keep = (pos_in_expert < capacity).astype(jnp.float32)
    loc = jax.nn.one_hot(pos_in_expert, capacity,
                         dtype=jnp.float32)            # [G, S, k, C]

    # contract the choice dim without materializing [G,S,k,E,C]
    dispatch = jnp.einsum("gske,gskc->gsec", onehot * keep[..., None], loc)
    combine = jnp.einsum(
        "gske,gskc->gsec",
        onehot * (gate_vals * keep)[..., None], loc)

    # Switch aux loss: encourages uniform expert load.  fraction of
    # tokens whose FIRST choice is e  ·  mean router prob of e
    first = jax.nn.one_hot(gate_idx[..., 0], E, dtype=jnp.float32)
    frac_tokens = jnp.mean(first, axis=(0, 1))         # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))           # [E]
    aux_loss = E * jnp.sum(frac_tokens * mean_prob)
    return dispatch, combine, aux_loss


def moe_ffn_topk(
    x: jax.Array,
    router_w: jax.Array,
    we1: jax.Array,
    we2: jax.Array,
    k: int,
    capacity_factor: float = 1.25,
    capacity: Optional[int] = None,
    compute_dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed expert FFN.

    ``x`` [G, S, D] (groups × tokens × model dim), ``router_w`` [D, E],
    ``we1`` [E, D, F], ``we2`` [E, F, D].  Returns ``(y, aux_loss)``
    with ``y`` [G, S, D] in ``compute_dtype``.

    Expert compute runs as [E, G, C, D] einsums — expert dim leading so
    a ``P("tp")`` sharding on we1/we2/xe keeps every matmul local to
    the expert's device; the combine einsum is where GSPMD inserts the
    single psum over tp.
    """
    G, S, D = x.shape
    E = router_w.shape[-1]
    if capacity is None:
        capacity = expert_capacity(S, E, k, capacity_factor)

    logits = jnp.einsum("gsd,de->gse", x.astype(jnp.float32), router_w)
    dispatch, combine, aux_loss = topk_dispatch_combine(logits, k, capacity)

    cd = compute_dtype
    xe = jnp.einsum("gsec,gsd->egcd", dispatch.astype(cd), x.astype(cd))
    up = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", xe, we1.astype(cd)))
    ye = jnp.einsum("egcf,efd->egcd", up, we2.astype(cd))
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(cd), ye)
    return y.astype(cd), aux_loss


# ---------------------------------------------------------------------------
# routing without capacity: one chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------
#
# What today's published sparse models do, and the capacity path above
# cannot: sigmoid scores with a selection bias, top-k of ALL the
# deployment's experts with the chosen weights renormalised, gated
# three-matrix experts, and NO dropped token however uneven the load.
# The layer is told which experts it holds (``first`` and the leading
# dimension of the stacks); it routes over all of them and computes the
# part of the result its own experts give, which is what expert
# parallelism asks of a chip.  The exchange that would bring other
# chips' tokens here and send the partial sums back is not simulated.
#
# Shapes are static: the (token, choice) pairs are sorted so that those
# whose expert is held come first, by expert, and go through a buffer of
# a static number of rows, twice what an even router sends
# (``chunk_rows``), a chunk of the sorted pairs at a time: as many
# chunks as the held pairs fill, counted in the program (one for the
# router of an even deployment, tokens x k rows' worth in the worst
# case: every choice of every token held here).  The three products run
# as grouped products over the held experts' row groups and visit no
# row past the last group, so their time follows the rows really routed
# here; everything XLA does around them (the gathers, the masks, the
# gate) runs over the whole buffer, hence a buffer sized by the load.

# (tm, tk, tn) of jax's megablox kernels, by a sweep on the v5e at 8
# groups of about 512 rows of 2048 x 1536 (PERF.md section 6, PR 35):
# the row tile, and the most of a stack's tile, tk x tn elements of it
# in VMEM twice over
GMM_TILING = (256, 2048, 768)


def gmm_tiling(rows: int, k: int, n: int):
    """The (tm, tk, tn) of one grouped product of ``rows`` x ``k`` by
    stacks of ``k`` x ``n``, chosen from the shape as
    ``_flash_block_sizes`` chooses attention's: the row tile of
    :data:`GMM_TILING`, cut to the rows; along the wider of the stacks'
    two dimensions the largest multiple of 128 that divides it, 2048 at
    most; along the other the largest such divisor that keeps the
    stack's tile within 2048 x 768 elements.  The backward products of
    a layer (the stacks transposed, the stacks' gradient) are handed
    the same tiling by jax's ``gmm``, so both of a layer's shapes, (k,
    n) and (n, k), get one answer: (256, 2048, 768) at 2048 x 1536,
    (256, 1152, 1024) at 2304 x 1024."""
    tm, top_k, top_n = GMM_TILING

    def tile(size: int, most: int) -> int:
        fits = [t for t in range(128, min(size, most) + 1, 128)
                if size % t == 0]
        return fits[-1] if fits else size

    tk = tile(max(k, n), top_k)
    tn = tile(min(k, n), max(128, top_k * top_n // tk))
    return math.gcd(tm, rows), tk, tn


def _grouped(lhs, rhs, group_sizes, impl: str):
    """``lhs[rows of group g] @ rhs[g]`` for every group: rows
    [sum(sizes[:g]), sum(sizes[:g+1])) of ``lhs`` [M, K] meet ``rhs[g]``
    [K, N].  ``ragged`` is ``lax.ragged_dot`` (any backend; XLA's own
    grouped product on a TPU); ``gmm`` jax's megablox Pallas kernels
    (TPU, or its interpreter), whose output rows past the last group are
    uninitialised: the caller masks them.  A trace names a kernel by the
    scope it was called in, and a transformation traced around the call
    wraps the innermost scope's name (``transpose(jvp(jit(gmm)))``): the
    scope here takes that, and the kernels stay ``gmm`` and ``tgmm``
    however the layer is differentiated."""
    if impl == "ragged":
        return lax.ragged_dot(lhs, rhs, group_sizes)
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import ops

        with jax.named_scope("grouped"):
            return ops.gmm(lhs, rhs, group_sizes, lhs.dtype,
                           gmm_tiling(lhs.shape[0], *rhs.shape[1:]))
    raise ValueError(f"unknown expert_impl {impl!r}")


def chunk_rows(pairs: int, held: int, experts: int) -> int:
    """The sorted buffer's rows: twice the ``pairs * held / experts``
    rows an even router sends the ``held`` of ``experts`` experts, a
    whole number of ``GMM_TILING`` row tiles, ``pairs`` (every choice of
    every token) at most: all of them where every expert is held."""
    tile = GMM_TILING[0]
    return min(pairs, tile * math.ceil(2 * pairs * held / experts / tile))


def _rows_of(inv, c: int, k: int, first, n_rows, buffer_rows: int):
    """Where choice ``c`` of every token sits in the buffer that holds
    the ``n_rows`` sorted pairs from pair ``first`` on, and whether it
    is there at all: a pair of another chunk, or one whose expert is not
    held (those sort behind every held pair), has its index clamped and
    its term zeroed."""
    at = inv[c::k] - first
    return (jnp.clip(at, 0, buffer_rows - 1),
            ((at >= 0) & (at < n_rows))[:, None])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _to_sorted(x, order, inv, first, n_rows, k: int):
    """Tokens ``x`` [N, D] to the rows of their (token, choice) pairs in
    sorted order [B, D]: row m is the token of pair ``order[m]``, the
    B = ``len(order)`` sorted pairs from pair ``first`` on.  The
    transpose sums, for each token, the rows its pairs went to (``inv``,
    the inverse of the whole order): gathers, where XLA would
    scatter-add."""
    return x[order // k]


def _to_sorted_fwd(x, order, inv, first, n_rows, k):
    return x[order // k], (inv, first, n_rows)


def _to_sorted_bwd(k, res, g):
    inv, first, n_rows = res
    total = 0
    for c in range(k):
        at, there = _rows_of(inv, c, k, first, n_rows, g.shape[0])
        total = total + jnp.where(there, g[at], 0).astype(jnp.float32)
    return total.astype(g.dtype), None, None, None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@jax.custom_vjp
def _from_sorted(out, weights, order, inv, first, n_rows):
    """Sorted rows ``out`` [B, D] back to tokens [N, D] float32: each
    token's rows in this buffer (choice c of token n is row ``inv[n * k
    + c] - first``) summed with its ``weights`` [N, k].  Row by choice,
    never an [N, k, D] array, whose short middle dimension costs a
    relayout."""
    k = weights.shape[1]
    total = 0
    for c in range(k):
        at, there = _rows_of(inv, c, k, first, n_rows, out.shape[0])
        total = total + (jnp.where(there, out[at], 0).astype(jnp.float32)
                         * weights[:, c, None])
    return total


def _from_sorted_fwd(out, weights, order, inv, first, n_rows):
    return (_from_sorted(out, weights, order, inv, first, n_rows),
            (out, weights, order, inv, first, n_rows))


def _from_sorted_bwd(res, dy):
    out, weights, order, inv, first, n_rows = res
    k = weights.shape[1]
    d_out = (dy[order // k] * weights.reshape(-1)[order][:, None]
             ).astype(out.dtype)
    d_weights = []
    for c in range(k):
        at, there = _rows_of(inv, c, k, first, n_rows, out.shape[0])
        d_weights.append(jnp.sum(
            jnp.where(there, out[at], 0).astype(jnp.float32) * dy, axis=-1))
    return d_out, jnp.stack(d_weights, axis=1), None, None, None, None


_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


def route_topk(x, router_w, bias, k: int, scale: float = 1.0):
    """The router: ``(idx [N, k] int32, weights [N, k] float32)`` for
    tokens ``x`` [N, D].  Scores are ``sigmoid(x @ router_w)`` in
    float32 whatever the compute dtype; the k experts are the top k of
    ``score + bias`` (the bias selects and never weighs, and carries no
    gradient); the weights are the chosen scores over their sum (+1e-6),
    times ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, idx = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)
    return idx, weights * scale


def routed_ffn(x, router_w, bias, experts, first: int, k: int,
               scale: float = 1.0, impl: str = "ragged",
               compute_dtype=jnp.bfloat16, shared=None,
               router_grad: bool = True):
    """:func:`_routed_ffn` under ``jax.checkpoint``: the backward pass
    routes and sorts again rather than keep what the router computed
    (the experts' own buffers are never kept: :func:`_experts`).
    ``shared``, the stacks ``w1`` / ``w3`` [D, F] and ``w2`` [F, D] of
    the experts every token passes, adds :func:`shared_ffn` of ``x`` to
    the routed part, once, whichever share of the experts is held.
    ``router_grad=False``: the routing weights are constants of the
    backward pass, so the router's gradient is zero and none reaches
    ``x`` through the scores.  A share's part of that gradient is a pull
    toward the experts it holds (the deployment sums it over the
    shares, and every expert pulls); stepped on alone it moves the load
    onto them."""
    fn = functools.partial(_routed_ffn, first=first, k=k, scale=scale,
                           impl=impl, compute_dtype=compute_dtype,
                           router_grad=router_grad)
    if shared is None:
        return jax.checkpoint(fn)(x, router_w, bias, experts)

    def both(x, router_w, bias, experts, shared):
        y, route = fn(x, router_w, bias, experts)
        return y + shared_ffn(x, shared, compute_dtype), route

    return jax.checkpoint(both)(x, router_w, bias, experts, shared)


def shared_ffn(x, shared, compute_dtype=jnp.bfloat16):
    """The shared experts: ``w2 (silu(w1 x) * w3 x)`` of every token,
    dense work (several shared experts are one of their summed
    width)."""
    cd = compute_dtype
    x = x.astype(cd)
    up = (jax.nn.silu(jnp.dot(x, shared["w1"].astype(cd)))
          * jnp.dot(x, shared["w3"].astype(cd)))
    return jnp.dot(up, shared["w2"].astype(cd))


def _chunk(c, x, weights, order, inv, rows, experts, *, buffer_rows: int,
           k: int, impl: str):
    """The held experts' part of the layer for chunk ``c`` of the sorted
    pairs, pairs ``c * buffer_rows`` on, in a buffer of ``buffer_rows``
    rows: tokens ``x`` [N, D] to the buffer, the three grouped products
    over what the chunk holds of the groups ``rows``, and back to the
    tokens [N, D] float32 with the router's ``weights``.  The products
    leave the rows past the last group uninitialised (``gmm``): masked
    going in and coming out."""
    first = c * buffer_rows
    ends = jnp.cumsum(rows)
    mine = (jnp.clip(ends - first, 0, buffer_rows)
            - jnp.clip(ends - rows - first, 0, buffer_rows))
    n_rows = jnp.sum(mine)
    order = lax.dynamic_slice(order, (first,), (buffer_rows,))
    valid = (jnp.arange(buffer_rows) < n_rows)[:, None]
    xs = jnp.where(valid, _to_sorted(x, order, inv, first, n_rows, k), 0)
    gate = _grouped(xs, experts["w1"], mine, impl)
    up = _grouped(xs, experts["w3"], mine, impl)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(x.dtype)
    out = jnp.where(valid, _grouped(hidden, experts["w2"], mine, impl), 0)
    return _from_sorted(out, weights, order, inv, first, n_rows)


def _over_chunks(chunks, whole: bool, body, init):
    """``init`` with ``body(c)`` added for every chunk ``c`` of
    ``chunks``, a count made in the program: a loop of that many turns,
    and one call where the buffer is the ``whole`` of the pairs."""
    add = functools.partial(jax.tree_util.tree_map,
                            lambda a, b: a + b.astype(a.dtype))
    if whole:
        return add(init, body(0))
    return lax.while_loop(
        lambda carry: carry[0] < chunks,
        lambda carry: (carry[0] + 1, add(carry[1], body(carry[0]))),
        (jnp.zeros((), jnp.int32), init))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _experts(buffer_rows: int, k: int, impl: str, chunks, x, weights, order,
             inv, rows, experts):
    """The held experts' part of the layer, [N, D] float32: the sum of
    :func:`_chunk` over the ``chunks`` chunks of ``buffer_rows`` sorted
    pairs that hold a held pair.  Differentiated by hand: a loop whose
    length the program counts has no transpose, and the buffers are not
    to be kept; the backward pass is a loop of its own in which every
    chunk computes its buffer again and transposes it, and what is kept
    is what came in."""
    stacks = {n: w.astype(x.dtype) for n, w in experts.items()}
    return _over_chunks(
        chunks, order.shape[0] == buffer_rows,
        lambda c: _chunk(c, x, weights, order, inv, rows, stacks,
                         buffer_rows=buffer_rows, k=k, impl=impl),
        jnp.zeros(x.shape, jnp.float32))


def _experts_fwd(buffer_rows, k, impl, chunks, x, weights, order, inv, rows,
                 experts):
    return (_experts(buffer_rows, k, impl, chunks, x, weights, order, inv,
                     rows, experts),
            (chunks, x, weights, order, inv, rows, experts))


def _experts_bwd(buffer_rows, k, impl, res, dy):
    chunks, x, weights, order, inv, rows, experts = res
    stacks = {n: w.astype(x.dtype) for n, w in experts.items()}

    def body(c):
        _, pull = jax.vjp(
            lambda x, weights, experts: _chunk(
                c, x, weights, order, inv, rows, experts,
                buffer_rows=buffer_rows, k=k, impl=impl),
            x, weights, stacks)
        return pull(dy)

    # summed in float32 whatever the compute dtype, as one chunk sums
    dx, dweights, dexperts = _over_chunks(
        chunks, order.shape[0] == buffer_rows, body, jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32),
            (x, weights, experts)))
    return (None, dx.astype(x.dtype), dweights, None, None, None,
            jax.tree_util.tree_map(lambda d, w: d.astype(w.dtype), dexperts,
                                   experts))


_experts.defvjp(_experts_fwd, _experts_bwd)


def _routed_ffn(x, router_w, bias, experts, first: int, k: int,
                scale: float, impl: str, compute_dtype,
                router_grad: bool = True):
    """One chip's share of a routed expert layer, no token dropped.

    ``x`` [..., D]; ``router_w`` [D, E_all] and ``bias`` [E_all] over
    ALL the deployment's experts; ``experts`` the stacks held here,
    ``w1`` / ``w3`` [E, D, F] and ``w2`` [E, F, D], which are experts
    ``first .. first + E - 1``.  Returns ``(y, route)``: ``y`` the sum
    over each token's chosen experts that are held here of ``weight *
    w2(silu(w1 x) * w3 x)``, zero for a token none of whose experts is
    held, in ``compute_dtype``; ``route`` int32 counts for the tracer:
    ``rows`` [E] the rows each held expert's products were given,
    ``held_pairs`` the (token, choice) pairs whose expert is held
    (counted from the router's choice, not from the groups: their
    difference is what was dropped, 0), ``empty_tokens`` the tokens none
    of whose experts is held, ``chunks`` the chunks the held pairs went
    through the sorted buffer in and ``buffer_rows`` the buffer's rows
    over all of them (:func:`chunk_rows` each).

    The buffer is sized by what the router sent: the held pairs go
    through it a chunk at a time, in as many chunks as they fill,
    counted in the program.  Tokens x k rows' worth of chunks take the
    worst case, so no load is refused; the router of an even deployment
    fills half of one."""
    cd = compute_dtype
    lead, D = x.shape[:-1], x.shape[-1]
    x = x.reshape(-1, D)
    N, E = x.shape[0], experts["w1"].shape[0]
    idx, weights = route_topk(x, router_w, bias, k, scale)
    if not router_grad:
        weights = lax.stop_gradient(weights)

    local = idx - first
    held = (local >= 0) & (local < E)
    # held pairs first, by expert; the others share the sentinel E
    key = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    rows = jnp.sum(key[:, None] == jnp.arange(E, dtype=key.dtype)[None],
                   axis=0, dtype=jnp.int32)

    buffer_rows = chunk_rows(N * k, E, router_w.shape[1])
    if buffer_rows == N * k:
        chunks = jnp.ones((), jnp.int32)
    else:
        chunks = -(-jnp.sum(rows) // buffer_rows)
        # whole chunks to slice, and more rows than one (what tells
        # ``_experts`` that there is a loop): the last chunk may reach
        # past the pairs
        order = jnp.pad(order, (0, buffer_rows - (N * k) % buffer_rows))
    y = _experts(buffer_rows, k, impl, chunks, x.astype(cd), weights, order,
                 inv, rows, experts)
    route = {"rows": rows,
             "held_pairs": jnp.sum(held, dtype=jnp.int32),
             "empty_tokens": jnp.sum(~jnp.any(held, axis=-1),
                                     dtype=jnp.int32),
             "chunks": chunks, "buffer_rows": chunks * buffer_rows}
    return y.astype(cd).reshape(*lead, D), route
