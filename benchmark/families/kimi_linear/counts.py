"""Kimi-Linear's counts: parameters, and the operations and bytes the
algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change the
yardstick.  Every function takes ``cfg``, the configuration's keys as
``obs["model"]`` holds them (the published config's names); a
``trace_kernel`` reader file names a kernel function here by ``fn`` and
calls it as ``fn(cfg, batch) -> (FLOPs, bytes)``.

What the chip holds is its share (``num_experts`` of the router's
``router_experts``, ``vocab`` ids), and what is counted is that share's
work: a token meets on average ``num_experts_per_token x num_experts /
router_experts`` of the experts held, and the shared expert always.
"""

from __future__ import annotations


def _layers(cfg: dict) -> list:
    """``kda`` or ``mla`` for each layer run, then whether it is dense."""
    lin = cfg["linear_attn_config"]
    a = cfg["first_layer"]
    numbers = range(a, a + cfg["num_hidden_layers"])
    return [("kda" if i in lin["kda_layers"] else "mla",
             i <= cfg["first_k_dense_replace"]) for i in numbers]


def _qk_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def _mixer_matmul_params(cfg: dict, kind: str) -> int:
    d = cfg["hidden_size"]
    if kind == "kda":
        lin = cfg["linear_attn_config"]
        hk, k = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
        return (4 * d * hk                      # wq, wk, wv, wo
                + 2 * (d * k + k * hk)          # f_a f_b, g_a g_b
                + d * lin["num_heads"])         # w_beta
    h = cfg["num_attention_heads"]
    return (d * h * _qk_dim(cfg)                                 # wq
            + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            + cfg["kv_lora_rank"] * h * (cfg["qk_nope_head_dim"]
                                         + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)                          # wo


def _mixer_other_params(cfg: dict, kind: str) -> int:
    if kind == "kda":
        lin = cfg["linear_attn_config"]
        hk = lin["num_heads"] * lin["head_dim"]
        return (3 * hk * lin["short_conv_kernel_size"]     # the taps
                + hk + lin["num_heads"] + lin["head_dim"])  # dt_bias,
        #                                            A_log, o_norm
    return cfg["kv_lora_rank"]                              # kv_norm


def _expected_held(cfg: dict) -> float:
    """Held experts a token meets, on average, under even routing."""
    return (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["router_experts"])


def _expert(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def n_params(cfg: dict) -> int:
    """Every element the parameter tree holds and the kvstore carries:
    the trained parameters and the routed layers' expert bias
    (``router_experts`` constants a layer, which no gradient moves)."""
    d = cfg["hidden_size"]
    total = 2 * cfg["vocab"] * d + d            # embedding, head, ln_f
    for kind, dense in _layers(cfg):
        total += (2 * d + _mixer_matmul_params(cfg, kind)
                  + _mixer_other_params(cfg, kind))
        if dense:
            total += 3 * d * cfg["intermediate_size"]
        else:
            total += (d * cfg["router_experts"] + cfg["router_experts"]
                      + (cfg["num_experts"] + cfg["num_shared_experts"])
                      * _expert(cfg))
    return total


def n_expert_params(cfg: dict) -> int:
    """Of them, the elements of the stacked expert leaves."""
    routed = sum(not dense for _, dense in _layers(cfg))
    return routed * cfg["num_experts"] * _expert(cfg)


def kda_flops_per_token(cfg: dict) -> float:
    """FLOPs of the chunked delta rule a token, one layer, forward, with
    C = ``kda_chunk`` positions a chunk and H heads of K = V channels.
    A chunk and head: the decayed pair products A (k k^T, strictly
    lower) and B (q k^T, lower) C^2 K multiply-adds together; W = M (k
    exp G) and U = M v with M lower triangular C^2 (K + V) / 2; V_new =
    U - W S, the state's update and (q exp G) S, C K V each; B V_new
    C^2 V / 2.  The triangular inverse (C^3 / 3) and the elementwise
    decays are not matrix products and not counted.  Over C tokens:
    2 (C (3 K + 2 V) / 2 + 3 K V) FLOPs a token and head."""
    lin = cfg["linear_attn_config"]
    c, k = cfg["kda_chunk"], lin["head_dim"]
    return lin["num_heads"] * 2.0 * (c * 5 * k / 2 + 3 * k * k)


def train_flops_per_token(cfg: dict) -> float:
    """Matmul FLOPs the forward and backward passes REQUIRE per token at
    sequence length ``max_seq``; recomputation is not counted.

    6 x the parameters a token meets in a matmul: each layer's mixer,
    the dense FFN's three matrices or the router, the EXPECTED held
    experts' three (8 x 8 / 256 = a quarter of an expert a token in the
    benchmark's cut) and the shared expert's three, the untied head
    once (the embedding is a gather).  The depthwise taps, norms and
    gates are not matmuls.  Latent attention: QK^T at the q/k width and
    PV at the v width, 2 x width x heads FLOPs per (query, key) pair
    each; under the causal mask a sequence has T(T+1)/2 pairs, (T+1)/2
    a token, counted once.  The scan: :func:`kda_flops_per_token`.
    Both x 3 for forward plus the two backward matmuls per forward
    matmul."""
    d, T = cfg["hidden_size"], cfg["max_seq"]
    matmul_params = cfg["vocab"] * d
    mixing_fwd = 0.0
    for kind, dense in _layers(cfg):
        matmul_params += _mixer_matmul_params(cfg, kind)
        if kind == "kda":
            mixing_fwd += kda_flops_per_token(cfg)
        else:
            mixing_fwd += (2 * cfg["num_attention_heads"]
                           * (_qk_dim(cfg) + cfg["v_head_dim"]) * (T + 1) / 2)
        if dense:
            matmul_params += 3 * d * cfg["intermediate_size"]
        else:
            matmul_params += (d * cfg["router_experts"]
                              + (_expected_held(cfg)
                                 + cfg["num_shared_experts"]) * _expert(cfg))
    return 6.0 * matmul_params + 3.0 * mixing_fwd


def _flash(cfg, batch, qk_matmuls, v_matmuls, qk_tiles, v_tiles, stats):
    """(FLOPs, bytes) of one flash kernel call on ``batch`` sequences of
    the latent layer's heads: causal matmuls over the q/k width
    (``qk_matmuls``) and over the v width (``v_matmuls``), 2 x width
    FLOPs a pair; bf16 [B, H, T, width] arrays of either width and f32
    [B, H, T] row vectors read or written once.  What the ALGORITHM
    needs: the system pads every head to 256 channels to meet jax's
    kernels, and that is time, not work."""
    heads, T = cfg["num_attention_heads"], cfg["max_seq"]
    dq, dv = _qk_dim(cfg), cfg["v_head_dim"]
    pairs = batch * heads * T * (T + 1) / 2
    rows = batch * heads * T
    return (2.0 * (qk_matmuls * dq + v_matmuls * dv) * pairs,
            2.0 * rows * (qk_tiles * dq + v_tiles * dv) + stats * 4.0 * rows)


def flash_fwd(cfg: dict, batch: int):
    """QK^T and PV; reads q, k, v, writes o and the row statistics l, m."""
    return _flash(cfg, batch, 1, 1, qk_tiles=2, v_tiles=2, stats=2)


def flash_bwd_dkv(cfg: dict, batch: int):
    """S = QK^T again, dV = P^T dO, dP = dO V^T, dK = dS^T Q; reads q,
    k, v, dO, l, m, di, writes dk, dv."""
    return _flash(cfg, batch, 2, 2, qk_tiles=3, v_tiles=3, stats=3)


def flash_bwd_dq(cfg: dict, batch: int):
    """S = QK^T again, dP = dO V^T, dQ = dS K; reads q, k, v, dO, l, m,
    di, writes dq."""
    return _flash(cfg, batch, 2, 1, qk_tiles=3, v_tiles=2, stats=3)


def expert_gmm(cfg: dict, batch: int):
    """One grouped product of a routed layer over the EXPECTED rows
    (``batch x max_seq`` tokens x the held experts a token meets under
    even routing: 2,048 in the benchmark's cut, 256 an expert; a call
    sees the rows the router really sent, more or fewer): rows x hidden
    x expert width multiply-adds whichever of the three shapes it is;
    reads and writes a rows x hidden, a rows x width and a stack-sized
    array once, in bfloat16."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    rows = batch * cfg["max_seq"] * _expected_held(cfg)
    return (2.0 * rows * d * fe,
            2.0 * (rows * d + rows * fe + cfg["num_experts"] * d * fe))
