"""Laguna's counts: parameters, and the operations and bytes the
algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Every function takes ``cfg``, the configuration's keys as
``obs["model"]`` holds them (the published config's names); a
``trace_kernel`` reader file names a kernel function here by ``fn`` and
calls it as ``fn(cfg, batch) -> (FLOPs, bytes)``, for ONE call of that
kernel on ``batch`` sequences (a layer's).

What the chip holds is its share (``num_experts`` of the router's
``router_experts``, the heads ``num_attention_heads_per_layer`` gives,
``vocab`` ids), and what is counted is that share's work: a token meets
on average ``num_experts_per_tok x num_experts / router_experts`` of the
experts held, and the shared expert always.  Attention is counted at the
pairs the equations need: a full layer's causal half, a windowed
layer's ``sum_t min(t + 1, sliding_window)``.  What the kernels compute
beyond that (whole tiles under a mask) is time, not work.
"""

from __future__ import annotations


def _layers(cfg: dict) -> list:
    """(kind, q heads, dense) for each layer run."""
    a, n = cfg["first_layer"], cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][a:a + n],
                    cfg["num_attention_heads_per_layer"][a:a + n],
                    [m == "dense" for m in cfg["mlp_layer_types"][a:a + n]]))


def _heads(cfg: dict, kind: str) -> int:
    return next(h for k, h, _ in _layers(cfg) if k == kind)


def causal_pairs(cfg: dict) -> int:
    """(query, key) pairs with the key at or before the query, a
    sequence."""
    T = cfg["max_seq"]
    return T * (T + 1) // 2


def window_pairs(cfg: dict) -> int:
    """The pairs a windowed layer's queries see, a sequence: ``sum_t
    min(t + 1, sliding_window)``."""
    T, w = cfg["max_seq"], min(cfg["sliding_window"], cfg["max_seq"])
    return w * (w + 1) // 2 + (T - w) * w


def _attention_params(cfg: dict, heads: int) -> int:
    """wq, wo, the gate and wk, wv: the matmuls' parameters."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    return 2 * d * heads * dh + d * heads + 2 * d * kv * dh


def _expert(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _expected_held(cfg: dict) -> float:
    """Held experts a token meets, on average, under even routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_experts"])


def n_params(cfg: dict) -> int:
    """Every element the parameter tree holds and the kvstore carries."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab"] * d + d            # embedding, head, ln_f
    for _kind, heads, dense in _layers(cfg):
        total += 2 * d + _attention_params(cfg, heads)
        if dense:
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += (d * cfg["router_experts"]
                      + (cfg["num_experts"] + 1) * _expert(cfg))
    return total


def n_expert_params(cfg: dict) -> int:
    """Of them, the elements of the stacked expert leaves."""
    routed = sum(not dense for _, _, dense in _layers(cfg))
    return routed * cfg["num_experts"] * _expert(cfg)


def attention_flops_per_token(cfg: dict, kind: str) -> float:
    """A layer's attention core, forward, a token: QK^T and PV, 2 x
    head_dim FLOPs a pair and q head each, over the pairs the layer's
    queries see."""
    pairs = window_pairs(cfg) if kind == "sliding_attention" else (
        causal_pairs(cfg))
    return 4.0 * _heads(cfg, kind) * cfg["head_dim"] * pairs / cfg["max_seq"]


def train_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs the forward and backward passes REQUIRE per token at
    sequence length ``max_seq``; recomputation is not counted.

    6 x the parameters a token meets in a matmul whose input and weights
    both take a gradient: each layer's attention projections and gate,
    the dense FFN's three matrices or the EXPECTED held experts' three
    (10 x 8 / 256 = 0.3125 of an expert a token in the benchmark's cut)
    and the shared expert's three, the untied head once (the embedding
    is a gather).  2 x the router's, whose weights are constants of the
    backward pass (``router_grad`` false: neither gradient is required).
    The attention cores (:func:`attention_flops_per_token`) x 3 for
    forward and backward.  Norms, rotations, gates' sigmoids and the
    softmaxes are not matmuls."""
    d = cfg["hidden_size"]
    total = 6.0 * cfg["vocab"] * d
    for kind, heads, dense in _layers(cfg):
        total += (6.0 * _attention_params(cfg, heads)
                  + 3.0 * attention_flops_per_token(cfg, kind))
        if dense:
            total += 6.0 * 3 * d * cfg["intermediate_size"]
        else:
            total += (2.0 * d * cfg["router_experts"]
                      + 6.0 * (_expected_held(cfg) + 1) * _expert(cfg))
    return total


def _attn_call(cfg: dict, batch: int, kind: str, matmuls: int,
               q_sized: int, kv_sized: int, row_stats: int):
    """(FLOPs, bytes) of one attention kernel call on ``batch``
    sequences of a ``kind`` layer's heads: ``matmuls`` products of 2 x
    head_dim FLOPs at each pair the layer's queries see and q head;
    ``q_sized`` bf16 arrays of the q heads' size (q, o, dO, dQ),
    ``kv_sized`` of the k/v heads' (k, v, dK, dV) and ``row_stats``
    float32 values a row and q head (log-sum-exp, the backward's
    delta), each read or written once."""
    T, dh = cfg["max_seq"], cfg["head_dim"]
    heads, kv = _heads(cfg, kind), cfg["num_key_value_heads"]
    pairs = window_pairs(cfg) if kind == "sliding_attention" else (
        causal_pairs(cfg))
    rows = batch * T
    return (2.0 * matmuls * dh * heads * pairs * batch,
            rows * (2.0 * dh * (q_sized * heads + kv_sized * kv)
                    + 4.0 * row_stats * heads))


def window_attn_fwd(cfg: dict, batch: int):
    """QK^T and PV at the pairs in the window; reads q, k, v, writes o
    and each row's log-sum-exp."""
    return _attn_call(cfg, batch, "sliding_attention", 2, 2, 2, 1)


def window_attn_bwd_dq(cfg: dict, batch: int):
    """S = QK^T again, dP = dO V^T, dQ = dS K; reads q, k, v, dO, the
    log-sum-exp and delta, writes dq."""
    return _attn_call(cfg, batch, "sliding_attention", 3, 3, 2, 2)


def window_attn_bwd_dkv(cfg: dict, batch: int):
    """S = QK^T again, dV = P^T dO, dP = dO V^T, dK = dS^T Q; reads q,
    k, v, dO, the log-sum-exp and delta, writes dk and dv."""
    return _attn_call(cfg, batch, "sliding_attention", 4, 2, 4, 2)


def flash_fwd(cfg: dict, batch: int):
    """A full layer's flash forward: QK^T and PV at the causal pairs;
    reads q, k, v, writes o and the row statistics l, m."""
    return _attn_call(cfg, batch, "full_attention", 2, 2, 2, 2)


def flash_bwd_dkv(cfg: dict, batch: int):
    """S = QK^T again, dV = P^T dO, dP = dO V^T, dK = dS^T Q; reads q,
    k, v, dO, l, m, di, writes dk, dv."""
    return _attn_call(cfg, batch, "full_attention", 4, 2, 4, 3)


def flash_bwd_dq(cfg: dict, batch: int):
    """S = QK^T again, dP = dO V^T, dQ = dS K; reads q, k, v, dO, l, m,
    di, writes dq."""
    return _attn_call(cfg, batch, "full_attention", 3, 3, 2, 3)


def expert_gmm(cfg: dict, batch: int):
    """One grouped product of a routed layer over the EXPECTED rows
    (``batch x max_seq`` tokens x the held experts a token meets under
    even routing: 2,560 in the benchmark's cut, 320 an expert; a call
    sees the rows the router really sent, more or fewer): rows x hidden
    x expert width multiply-adds whichever of the three shapes it is;
    reads and writes a rows x hidden, a rows x width and a stack-sized
    array once, in bfloat16."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * cfg["max_seq"] * _expected_held(cfg)
    return (2.0 * rows * d * fe,
            2.0 * (rows * d + rows * fe + cfg["num_experts"] * d * fe))
