"""The flagship's counts: parameters, and the operations and bytes the
algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  ``bench.py``'s ``_transformer_train_flops_per_step`` has the
same idea but counts the masked half of causal attention and the
embedding gather; this copy counts neither (see ``PERF.md``).  Every
function takes ``cfg``, the configuration's keys as ``obs["model"]``
holds them; a ``trace_kernel`` reader file names a kernel function here
by ``fn`` and calls it as ``fn(cfg, batch) -> (FLOPs, bytes)``.
"""

from __future__ import annotations


def n_params(cfg: dict) -> int:
    """Parameters of the flagship LM (tied head, learned positions)."""
    d, f = cfg["d_model"], cfg["d_ff"]
    return (cfg["vocab"] * d + cfg["max_seq"] * d + d
            + cfg["n_layers"] * (4 * d * d + 2 * d * f + 2 * d))


def train_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs the forward and backward passes REQUIRE per token at
    sequence length ``max_seq``; recomputation is not counted.

    Dense part: 6 x the parameters that sit in a matmul (the four
    attention projections, the two MLP matrices, the tied head once;
    the embedding lookup and the positions are gathers and adds).
    Attention: QK^T and PV are 2 x head_dim x n_heads = 2 x d_model
    FLOPs per (query, key) pair each; under the causal mask a sequence
    has T(T+1)/2 pairs, (T+1)/2 a token, counted once; x3 for forward
    plus the two backward matmuls per forward matmul.
    """
    d, f, L, T = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["max_seq"]
    matmul_params = L * (4 * d * d + 2 * d * f) + cfg["vocab"] * d
    attn_fwd = L * 2 * (2 * d) * (T + 1) / 2
    return 6.0 * matmul_params + 3.0 * attn_fwd


def _attn_geometry(cfg: dict, batch: int):
    heads, T = cfg["n_heads"], cfg["max_seq"]
    dh = cfg["d_model"] // heads
    pairs = batch * heads * T * (T + 1) / 2      # causal (q, k) pairs
    tile = batch * heads * T * dh                # elements of q, k, v or o
    return pairs, tile, dh, batch * heads * T


def _flash(cfg, batch, matmuls, tiles, stats):
    """(FLOPs, bytes) of one flash kernel call on ``batch`` sequences:
    ``matmuls`` causal matmuls of 2 x head_dim FLOPs a pair, ``tiles``
    bf16 [B, H, T, Dh] arrays and ``stats`` f32 [B, H, T] row vectors
    read or written once."""
    pairs, tile, dh, rows = _attn_geometry(cfg, batch)
    return matmuls * 2.0 * dh * pairs, tiles * 2.0 * tile + stats * 4.0 * rows


def flash_fwd(cfg: dict, batch: int):
    """QK^T and PV; reads q, k, v, writes o and the row statistics l, m."""
    return _flash(cfg, batch, matmuls=2, tiles=4, stats=2)


def flash_bwd_dkv(cfg: dict, batch: int):
    """What a call that returns dK and dV must do from q, k, v, dO and the
    saved statistics: S = QK^T again, dV = P^T dO, dP = dO V^T,
    dK = dS^T Q; reads q, k, v, dO, l, m, di, writes dk, dv."""
    return _flash(cfg, batch, matmuls=4, tiles=6, stats=3)


def flash_bwd_dq(cfg: dict, batch: int):
    """S = QK^T again, dP = dO V^T, dQ = dS K; reads q, k, v, dO, l, m,
    di, writes dq."""
    return _flash(cfg, batch, matmuls=3, tiles=5, stats=3)
