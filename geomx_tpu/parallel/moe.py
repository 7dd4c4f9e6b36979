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
# whose expert is held come first, by expert, in a buffer of tokens x k
# rows (the worst case: every choice of every token held here).  The
# three products run as grouped products over the held experts' row
# groups and visit no row past the last group, so their time follows
# the rows really routed here, not the buffer.

# (tm, tk, tn) of jax's megablox kernels, by a sweep on the v5e at 8
# groups of about 512 rows of 2048 x 1536 (PERF.md section 6, PR 35)
GMM_TILING = (256, 2048, 768)


def _grouped(lhs, rhs, group_sizes, impl: str):
    """``lhs[rows of group g] @ rhs[g]`` for every group: rows
    [sum(sizes[:g]), sum(sizes[:g+1])) of ``lhs`` [M, K] meet ``rhs[g]``
    [K, N].  ``ragged`` is ``lax.ragged_dot`` (any backend; XLA's own
    grouped product on a TPU); ``gmm`` jax's megablox Pallas kernels
    (TPU, or its interpreter), whose output rows past the last group are
    uninitialised: the caller masks them."""
    if impl == "ragged":
        return lax.ragged_dot(lhs, rhs, group_sizes)
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox import ops

        tm = math.gcd(GMM_TILING[0], lhs.shape[0])
        return ops.gmm(lhs, rhs, group_sizes, lhs.dtype,
                       (tm,) + GMM_TILING[1:])
    raise ValueError(f"unknown expert_impl {impl!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_sorted(x, order, inv, k: int):
    """Tokens ``x`` [N, D] to their (token, choice) pairs' rows in
    sorted order [N * k, D]: row m is the token of pair ``order[m]``.
    The transpose sums, for each token, the k rows its pairs went to
    (``inv``, the inverse of ``order``): gathers, where XLA would
    scatter-add."""
    return x[order // k]


def _to_sorted_fwd(x, order, inv, k):
    return x[order // k], inv


def _to_sorted_bwd(k, inv, g):
    return (sum(g[inv[c::k]].astype(jnp.float32) for c in range(k)
                ).astype(g.dtype), None, None)


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@jax.custom_vjp
def _from_sorted(out, weights, order, inv):
    """Sorted rows ``out`` [N * k, D] back to tokens [N, D] float32:
    each token's k rows (choice c of token n is row ``inv[n * k + c]``)
    summed with its ``weights`` [N, k].  Row by choice, never an
    [N, k, D] array, whose short middle dimension costs a relayout."""
    k = weights.shape[1]
    return sum(out[inv[c::k]].astype(jnp.float32) * weights[:, c, None]
               for c in range(k))


def _from_sorted_fwd(out, weights, order, inv):
    return _from_sorted(out, weights, order, inv), (out, weights, order, inv)


def _from_sorted_bwd(res, dy):
    out, weights, order, inv = res
    k = weights.shape[1]
    d_out = (dy[order // k] * weights.reshape(-1)[order][:, None]
             ).astype(out.dtype)
    d_weights = jnp.stack(
        [jnp.sum(out[inv[c::k]].astype(jnp.float32) * dy, axis=-1)
         for c in range(k)], axis=1)
    return d_out, d_weights, None, None


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
               compute_dtype=jnp.bfloat16):
    """:func:`_routed_ffn` under ``jax.checkpoint``: the backward pass
    sorts, gathers and multiplies again rather than keep the layer's
    buffers, which are sized for the worst case (tokens x k rows, eight
    times the rows an even router sends an eighth of the experts): kept,
    they are 0.6 GB a layer at 8,192 tokens, 2048 wide (PERF.md)."""
    fn = functools.partial(_routed_ffn, first=first, k=k, scale=scale,
                           impl=impl, compute_dtype=compute_dtype)
    return jax.checkpoint(fn)(x, router_w, bias, experts)


def _routed_ffn(x, router_w, bias, experts, first: int, k: int,
                scale: float, impl: str, compute_dtype):
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
    of whose experts is held."""
    cd = compute_dtype
    lead, D = x.shape[:-1], x.shape[-1]
    x = x.reshape(-1, D)
    N, E = x.shape[0], experts["w1"].shape[0]
    idx, weights = route_topk(x, router_w, bias, k, scale)

    local = idx - first
    held = (local >= 0) & (local < E)
    # held pairs first, by expert; the others share the sentinel E
    key = jnp.where(held, local, E).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    rows = jnp.sum(key[:, None] == jnp.arange(E, dtype=key.dtype)[None],
                   axis=0, dtype=jnp.int32)
    valid = (jnp.arange(N * k) < jnp.sum(rows))[:, None]

    xs = jnp.where(valid, _to_sorted(x.astype(cd), order, inv, k), 0)
    gate = _grouped(xs, experts["w1"].astype(cd), rows, impl)
    up = _grouped(xs, experts["w3"].astype(cd), rows, impl)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(cd)
    out = jnp.where(valid, _grouped(hidden, experts["w2"].astype(cd), rows,
                                    impl), 0)
    # back to the tokens; a pair not held lands on a zero row
    y = _from_sorted(out, weights, order, inv)
    route = {"rows": rows,
             "held_pairs": jnp.sum(held, dtype=jnp.int32),
             "empty_tokens": jnp.sum(~jnp.any(held, axis=-1),
                                     dtype=jnp.int32)}
    return y.astype(cd).reshape(*lead, D), route
