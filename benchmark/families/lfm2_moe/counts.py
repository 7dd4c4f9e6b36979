"""LFM2-MoE's counts: parameters, and the operations and bytes the
algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Every function takes ``cfg``, the configuration's keys as
``obs["model"]`` holds them (the published config's names); a
``trace_kernel`` reader file names a kernel function here by ``fn`` and
calls it as ``fn(cfg, batch) -> (FLOPs, bytes)``.

What the chip holds is its share (``num_experts`` of the router's
``router_experts``, ``vocab`` ids), and what is counted is that share's
work: a token meets on average ``num_experts_per_tok x num_experts /
router_experts`` of the experts held.
"""

from __future__ import annotations


def _layers(cfg: dict) -> list:
    a = cfg["first_layer"]
    return cfg["layer_types"][a:a + cfg["num_hidden_layers"]]


def _head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _operator_matmul_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "conv":
        return 3 * d * d + d * d                       # w_in, w_out
    dh = _head_dim(cfg)
    return (2 * d * cfg["num_attention_heads"] * dh    # wq, wo
            + 2 * d * cfg["num_key_value_heads"] * dh)  # wk, wv


def _expected_held(cfg: dict) -> float:
    """Held experts a token meets, on average, under even routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_experts"])


def n_params(cfg: dict) -> int:
    """Every element the parameter tree holds and the kvstore carries:
    the trained parameters and the routed layers' expert bias
    (``router_experts`` constants a layer, which no gradient moves)."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    total = cfg["vocab"] * d + d                       # tied head, ln_f
    for i, kind in enumerate(_layers(cfg)):
        total += 2 * d + _operator_matmul_params(cfg, kind)
        if kind == "conv":
            total += d * cfg["conv_L_cache"]           # the taps
        else:
            total += 2 * _head_dim(cfg)                # q_norm, k_norm
        if i < cfg["num_dense_layers"]:
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += (d * cfg["router_experts"] + cfg["router_experts"]
                      + cfg["num_experts"] * 3 * d * fe)
    return total


def n_expert_params(cfg: dict) -> int:
    """Of them, the elements of the stacked expert leaves."""
    routed = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return (routed * cfg["num_experts"] * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def train_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs the forward and backward passes REQUIRE per token at
    sequence length ``max_seq``; recomputation is not counted.

    6 x the parameters a token meets in a matmul: each layer's operator
    (``w_in`` and ``w_out``, or the four attention projections), the
    dense FFN's three matrices or the router and the EXPECTED held
    experts' three (4 x 8 / 64 = half an expert a token in the
    benchmark's cut), the tied head once.  The depthwise taps, norms,
    rotary turns and gathers are not matmuls.  Attention: QK^T and PV
    are 2 x head size x q heads FLOPs per (query, key) pair each; under
    the causal mask a sequence has T(T+1)/2 pairs, (T+1)/2 a token,
    counted once; x 3 for forward plus the two backward matmuls per
    forward matmul.
    """
    d, fe, T = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["max_seq"]
    matmul_params = cfg["vocab"] * d
    attn_fwd = 0.0
    for i, kind in enumerate(_layers(cfg)):
        matmul_params += _operator_matmul_params(cfg, kind)
        if kind != "conv":
            attn_fwd += 2 * (2 * d) * (T + 1) / 2
        if i < cfg["num_dense_layers"]:
            matmul_params += 3 * d * cfg["intermediate_size"]
        else:
            matmul_params += (d * cfg["router_experts"]
                              + _expected_held(cfg) * 3 * d * fe)
    return 6.0 * matmul_params + 3.0 * attn_fwd


def _flash(cfg, batch, matmuls, tiles, stats):
    """(FLOPs, bytes) of one flash kernel call on ``batch`` sequences of
    ``num_attention_heads`` heads (k and v arrive repeated to as many):
    ``matmuls`` causal matmuls of 2 x head size FLOPs a pair, ``tiles``
    bf16 [B, H, T, Dh] arrays and ``stats`` f32 [B, H, T] row vectors
    read or written once."""
    heads, T, dh = cfg["num_attention_heads"], cfg["max_seq"], _head_dim(cfg)
    pairs = batch * heads * T * (T + 1) / 2
    tile, rows = batch * heads * T * dh, batch * heads * T
    return matmuls * 2.0 * dh * pairs, tiles * 2.0 * tile + stats * 4.0 * rows


def flash_fwd(cfg: dict, batch: int):
    """QK^T and PV; reads q, k, v, writes o and the row statistics l, m."""
    return _flash(cfg, batch, matmuls=2, tiles=4, stats=2)


def flash_bwd_dkv(cfg: dict, batch: int):
    """S = QK^T again, dV = P^T dO, dP = dO V^T, dK = dS^T Q; reads q,
    k, v, dO, l, m, di, writes dk, dv."""
    return _flash(cfg, batch, matmuls=4, tiles=6, stats=3)


def flash_bwd_dq(cfg: dict, batch: int):
    """S = QK^T again, dP = dO V^T, dQ = dS K; reads q, k, v, dO, l, m,
    di, writes dq."""
    return _flash(cfg, batch, matmuls=3, tiles=5, stats=3)


def expert_gmm(cfg: dict, batch: int):
    """One grouped product of a routed layer over the EXPECTED rows
    (``batch x max_seq`` tokens x the held experts a token meets under
    even routing: 4,096 in the benchmark's cut; a call sees the rows the
    router really sent, more or fewer): rows x hidden x expert width
    multiply-adds whichever of the three shapes it is (rows x hidden by
    the stacks, rows x width by the stacks transposed, or the two row
    matrices against each other for the stacks' gradient); reads and
    writes a rows x hidden, a rows x width and a stack-sized array once,
    in bfloat16."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * cfg["max_seq"] * _expected_held(cfg)
    return (2.0 * rows * d * fe,
            2.0 * (rows * d + rows * fe + cfg["num_experts"] * d * fe))
