"""Flagship transformer: GPT-style LM with dp/sp/tp(/ep) mesh parallelism.

The reference's model zoo is single-device-per-worker CNNs
(SURVEY.md §2.3); this model is the TPU-native flagship exercising the
parallelism the reference lacks:

- **tp**: Megatron-style sharded projections — qkv/up-proj column-sharded,
  out/down-proj row-sharded; XLA/GSPMD inserts the psums.
- **sp**: sequence dimension sharded; attention runs inside shard_map
  as ring attention (`geomx_tpu.parallel.ring_attention`, K/V blocks
  rotating over ICI neighbors) or Ulysses all-to-all
  (`geomx_tpu.parallel.ulysses`, head↔seq re-sharding) — selected by
  ``TransformerConfig.sp_attn``.
- **dp**: batch sharded; gradient AllReduce inserted by XLA.
- **ep**: MoE layers (optional) shard the expert dimension over the tp
  axis.  ``moe_top_k=0`` is dense routing (every expert computes,
  combine weighted by the router — exact); ``moe_top_k>0`` is real EP:
  GShard-style top-k dispatch with capacity (``parallel/moe.py``),
  per-token FLOPs independent of the expert count.

Beyond the flagship's GPT block the config composes what today's
published architectures are made of, each off by default (all off is
the flagship, whose program does not change): grouped-query heads
(``n_kv_heads``), RMSNorm on q and k (``qk_norm``), rotary positions
(``rope_theta``), a gated FFN (``gated_ffn``), a per-layer operator
pattern (``layer_types``: ``full_attention`` or the gated short
convolution ``conv``, the gated delta-rule scan ``kda`` of
``ops/kda.py``, or latent attention ``mla``: keys and values through a
low-rank latent, wider q/k heads than v heads), no positions at all
(``no_positions``), an untied head (``tied_head=False``), and after
``n_dense_layers`` leading dense layers one chip's share of routed
experts with no capacity and no dropped token (``router_experts``;
``parallel/moe.py`` ``routed_ffn``), with shared experts that every
token passes added to them (``n_shared_experts``).

Pure-jax functional style: ``init_params`` builds a pytree,
``param_specs`` mirrors it with PartitionSpecs, ``make_apply`` returns the
forward.  bf16 activations, f32 params/accumulators.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from geomx_tpu.ops.kda import KEPT_NAMES, chunk_kda
from geomx_tpu.parallel.ring_attention import (
    dense_attention, fast_dense_attention, ring_attention)
from geomx_tpu.parallel.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    max_seq: int = 512
    moe_every: int = 0       # every Nth layer is MoE (0 = none)
    n_experts: int = 4
    moe_top_k: int = 0       # 0 = dense routing (every expert computes,
    #                          exact); k>0 = GShard-style top-k dispatch
    #                          with capacity (per-token FLOPs independent
    #                          of n_experts — parallel/moe.py)
    moe_capacity_factor: float = 1.25
    compute_dtype: Any = jnp.bfloat16
    sp_attn: str = "ring"    # "ring" (K/V rotation, any head count) or
    #                          "ulysses" (head<->seq all-to-all; needs
    #                          per-device heads divisible by sp)
    attn_impl: str = "fast"  # single-device attention: "fast" (bf16 MXU
    #                          matmuls, fp32 accum/softmax), "dense"
    #                          (all-fp32 reference), "flash" (jax's
    #                          pallas fused kernel, real TPU only; its
    #                          tiles come from the call's sequence
    #                          length and head width:
    #                          _flash_block_sizes)
    remat: bool = False      # jax.checkpoint each layer: recompute
    #                          activations in bwd, trading ~1/3 more
    #                          fwd FLOPs for O(L) less HBM — the TPU
    #                          recipe for big batches / long seq.
    #                          Kept and not recomputed: what a layer's
    #                          program names for it (ops/kda.py
    #                          KEPT_NAMES: a kda layer's scan states and
    #                          output, 134 MB a layer at [1, 8192, 32,
    #                          128] in bf16, so that the recompute runs
    #                          no scan); no other layer names anything
    # ---- off by default: all off is the flagship's GPT block ----------
    n_kv_heads: int = 0      # grouped-query attention: k and v heads,
    #                          each serving n_heads // n_kv_heads
    #                          consecutive q heads (0 = n_heads)
    qk_norm: bool = False    # RMSNorm with a learned scale over each
    #                          head's channels of q and of k
    rope_theta: float = 0.0  # > 0: rotary positions over the whole head
    #                          (rotate-half pairing) and no learned
    #                          ``pos`` table
    norm_eps: float = 1e-6
    gated_ffn: bool = False  # w2(silu(w1 x) * w3 x), not w2 gelu(w1 x)
    layer_types: Tuple[str, ...] = ()   # per layer "full_attention",
    #                          "conv" (gated short convolution), "kda"
    #                          (gated delta-rule scan) or "mla" (latent
    #                          attention); empty = attention everywhere
    conv_kernel: int = 3     # taps of the short convolution
    n_dense_layers: int = 0  # leading layers that keep the dense FFN
    router_experts: int = 0  # > 0: every later layer routes over this
    #                          many experts (the whole deployment's),
    #                          moe_top_k a token, and holds n_experts of
    #                          them, first_expert onwards, d_expert wide
    first_expert: int = 0
    d_expert: int = 0
    routed_scale: float = 1.0
    expert_impl: str = "ragged"   # the grouped products: "ragged"
    #                          (lax.ragged_dot, any backend) or "gmm"
    #                          (jax's megablox kernels, TPU only)
    n_shared_experts: int = 0     # experts every token passes, added to
    #                          a routed layer's part, d_expert wide each
    router_grad: bool = True      # False: a routed layer's routing
    #                          weights are constants of the backward
    #                          pass (``routed_ffn``): a share's part of
    #                          the router's gradient pulls the load onto
    #                          the experts it holds
    no_positions: bool = False    # neither a learned table nor a
    #                          rotation: the order is the scan's and the
    #                          causal mask's
    tied_head: bool = True   # logits = final norm @ embed^T; False: a
    #                          leaf ``head`` of its own
    # "kda" layers: q, k and v heads of kda_head_dim channels through a
    # depthwise causal convolution of kda_conv_kernel taps and silu, the
    # scan of ops/kda.py in chunks of kda_chunk positions
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_chunk: int = 64
    # "mla" layers: n_heads q/k heads of qk_nope_dim + qk_rope_dim
    # channels (the latter one key shared by every head, straight from
    # the input; never rotated here) against v heads of v_head_dim, k
    # and v from a normed latent of kv_lora_rank
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.n_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} "
                             f"layers, n_layers is {self.n_layers}")
        unknown = set(self.layer_types) - {"full_attention", "conv", "kda",
                                           "mla"}
        if unknown:
            raise ValueError(f"unknown layer type(s) {sorted(unknown)}")
        if "kda" in self.layer_types and not (
                self.kda_heads > 0 and self.kda_head_dim > 0):
            raise ValueError("a kda layer needs kda_heads and kda_head_dim")
        if "mla" in self.layer_types and not (
                self.kv_lora_rank > 0 and self.qk_nope_dim > 0
                and self.v_head_dim > 0):
            raise ValueError("an mla layer needs kv_lora_rank, qk_nope_dim "
                             "and v_head_dim")
        if self.no_positions and self.rope_theta:
            raise ValueError("no_positions and rope_theta exclude each other")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"{self.n_heads} q heads do not divide over "
                             f"{self.kv_heads} k/v heads")
        if self.router_experts and (self.moe_every or not (
                0 < self.moe_top_k <= self.router_experts
                and 0 <= self.first_expert
                and self.first_expert + self.n_experts
                <= self.router_experts and self.d_expert > 0)):
            raise ValueError(
                "a routed layer needs moe_every 0, moe_top_k in 1.."
                "router_experts, d_expert > 0 and the held experts "
                "first_expert..first_expert+n_experts-1 among them")

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def is_moe(self, layer: int) -> bool:
        return self.moe_every > 0 and (layer + 1) % self.moe_every == 0

    def is_routed(self, layer: int) -> bool:
        return self.router_experts > 0 and layer >= self.n_dense_layers

    def is_conv(self, layer: int) -> bool:
        return self.kind(layer) == "conv"

    def kind(self, layer: int) -> str:
        return (self.layer_types[layer] if self.layer_types
                else "full_attention")


def init_params(cfg: TransformerConfig, rng: jax.Array) -> Dict:
    def dense(key, shape, scale=None):
        scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
        return jax.random.normal(key, shape, jnp.float32) * scale

    keys = jax.random.split(rng, 3 + cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": dense(keys[0], (cfg.vocab, cfg.d_model), scale=0.02),
        "pos": dense(keys[1], (cfg.max_seq, cfg.d_model), scale=0.02),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": [],
    }
    if cfg.rope_theta or cfg.no_positions:
        del params["pos"]
    if not cfg.tied_head:
        params["head"] = dense(keys[2], (cfg.vocab, cfg.d_model),
                               scale=1.0 / np.sqrt(cfg.d_model))
    H, Hkv, Dh, D, F = (cfg.n_heads, cfg.kv_heads, cfg.head_dim,
                        cfg.d_model, cfg.d_ff)
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[3 + i], 8)
        layer = {
            "ln1": jnp.ones((D,), jnp.float32),
            "ln2": jnp.ones((D,), jnp.float32),
        }
        if cfg.is_conv(i):
            layer["w_in"] = dense(k[0], (D, 3 * D))
            layer["conv"] = dense(k[1], (D, cfg.conv_kernel),
                                  scale=1.0 / np.sqrt(cfg.conv_kernel))
            layer["w_out"] = dense(k[3], (D, D))
        elif cfg.kind(i) == "kda":
            layer.update(_init_kda(cfg, k[0], dense))
        elif cfg.kind(i) == "mla":
            layer.update(_init_mla(cfg, k[0], dense))
        else:
            layer["wq"] = dense(k[0], (D, H, Dh))
            layer["wk"] = dense(k[1], (D, Hkv, Dh))
            layer["wv"] = dense(k[2], (D, Hkv, Dh))
            layer["wo"] = dense(k[3], (H, Dh, D), scale=1.0 / np.sqrt(D))
            if cfg.qk_norm:
                layer["q_norm"] = jnp.ones((Dh,), jnp.float32)
                layer["k_norm"] = jnp.ones((Dh,), jnp.float32)
        if cfg.is_moe(i):
            E = cfg.n_experts
            layer["router"] = dense(k[6], (D, E), scale=0.02)
            layer["we1"] = dense(k[4], (E, D, F))
            layer["we2"] = dense(k[5], (E, F, D), scale=1.0 / np.sqrt(F))
        elif cfg.is_routed(i):
            E, Fe = cfg.n_experts, cfg.d_expert
            ke = jax.random.split(k[4], 3)
            layer["router"] = dense(k[6], (D, cfg.router_experts))
            # selects and never weighs; no gradient reaches it, and no
            # published rule moves it: a seeded constant.  Its spread is
            # small where it acts: at the top-k threshold a score moves
            # 0.15 a unit of logit, so 0.002 is a logit offset of 0.013
            # (0.02 was one of 0.13 and skewed the experts' loads by a
            # quarter, against what a selection bias is for)
            layer["expert_bias"] = dense(k[7], (cfg.router_experts,),
                                         scale=0.002)
            # one leaf a stack: a kvstore key each (KeyPlan group
            # "expert": a path through the key ``experts``)
            layer["experts"] = {
                "w1": dense(ke[0], (E, D, Fe), scale=1.0 / np.sqrt(D)),
                "w3": dense(ke[1], (E, D, Fe), scale=1.0 / np.sqrt(D)),
                "w2": dense(ke[2], (E, Fe, D), scale=1.0 / np.sqrt(Fe)),
            }
            if cfg.n_shared_experts:
                Fs = cfg.n_shared_experts * Fe
                ks = jax.random.split(k[5], 3)
                layer["shared"] = {
                    "w1": dense(ks[0], (D, Fs)), "w3": dense(ks[1], (D, Fs)),
                    "w2": dense(ks[2], (Fs, D))}
        else:
            layer["w1"] = dense(k[4], (D, F))
            layer["w2"] = dense(k[5], (F, D), scale=1.0 / np.sqrt(F))
            if cfg.gated_ffn:
                layer["w3"] = dense(k[7], (D, F))
        params["layers"].append(layer)
    return params


def _init_kda(cfg: TransformerConfig, key, dense) -> Dict:
    """A ``kda`` layer's leaves.  The decay's are the family's and not
    all near 1: ``A_log`` = log U(1, 16) a head, ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly in [0.001, 0.1] a channel."""
    D, H, K, taps = (cfg.d_model, cfg.kda_heads, cfg.kda_head_dim,
                     cfg.kda_conv_kernel)
    k = jax.random.split(key, 14)
    dt = jnp.exp(jax.random.uniform(
        k[12], (H, K), jnp.float32, np.log(1e-3), np.log(1e-1)))
    layer = {
        "A_log": jnp.log(jax.random.uniform(k[11], (H,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "f_a": dense(k[6], (D, K)), "f_b": dense(k[7], (K, H, K)),
        "w_beta": dense(k[8], (D, H)),
        "g_a": dense(k[9], (D, K)), "g_b": dense(k[10], (K, H, K)),
        "o_norm": jnp.ones((K,), jnp.float32),
        "wo": dense(k[13], (H, K, D), scale=1.0 / np.sqrt(H * K)),
    }
    for j, n in enumerate("qkv"):
        layer["w" + n] = dense(k[j], (D, H, K))
        layer["conv_" + n] = dense(k[3 + j], (H, K, taps),
                                   scale=1.0 / np.sqrt(taps))
    return layer


def _init_mla(cfg: TransformerConfig, key, dense) -> Dict:
    D, H, R = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    k = jax.random.split(key, 4)
    return {
        "wq": dense(k[0], (D, H, cfg.qk_nope_dim + cfg.qk_rope_dim)),
        "w_kv_a": dense(k[1], (D, R + cfg.qk_rope_dim)),
        "kv_norm": jnp.ones((R,), jnp.float32),
        "w_kv_b": dense(k[2], (R, H, cfg.qk_nope_dim + cfg.v_head_dim)),
        "wo": dense(k[3], (H, cfg.v_head_dim, D),
                    scale=1.0 / np.sqrt(H * cfg.v_head_dim)),
    }


def param_specs(cfg: TransformerConfig) -> Dict:
    """PartitionSpec pytree mirroring init_params.

    tp shards: head dim of qkv, first dim of wo, cols of w1/up, rows of
    w2/down.  MoE experts shard over the same axis (ep aliases tp on
    small meshes — each device owns E/tp experts)."""
    specs: Dict[str, Any] = {
        # vocab-parallel (Megatron-style), NOT d_model-sharded: a
        # d-sharded embedding makes the residual stream enter every
        # layer sharded on d, and GSPMD then all-gathers the activations
        # in front of EVERY qkv/ffn matmul (measured: 10 activation
        # all-gathers per 2-layer step vs 0 with vocab-parallel — see
        # tests/test_moe_collectives.py, the r4 collective audit)
        "embed": P("tp", None),
        "pos": P(None, None),
        "ln_f": P(None),
        "layers": [],
    }
    if cfg.rope_theta or cfg.no_positions:
        del specs["pos"]
    if not cfg.tied_head:
        specs["head"] = P("tp", None)
    whole = lambda leaf: P(*(None,) * leaf.ndim)      # noqa: E731
    for i in range(cfg.n_layers):
        layer = {"ln1": P(None), "ln2": P(None)}
        if cfg.is_conv(i):
            # the depthwise taps follow their channels: not split yet
            layer.update(w_in=P(None, None), conv=P(None, None),
                         w_out=P(None, None))
        elif cfg.kind(i) in ("kda", "mla"):
            # not split yet: whole on every device
            init = _init_kda if cfg.kind(i) == "kda" else _init_mla
            layer.update(jax.tree_util.tree_map(whole, jax.eval_shape(
                lambda: init(cfg, jax.random.PRNGKey(0),
                             lambda key, shape, scale=None:
                             jnp.zeros(shape, jnp.float32)))))
        else:
            layer.update(wq=P(None, "tp", None), wk=P(None, "tp", None),
                         wv=P(None, "tp", None), wo=P("tp", None, None))
            if cfg.qk_norm:
                layer.update(q_norm=P(None), k_norm=P(None))
        if cfg.is_moe(i):
            layer["router"] = P(None, None)
            layer["we1"] = P("tp", None, None)   # expert-parallel (ep≡tp)
            layer["we2"] = P("tp", None, None)
        elif cfg.is_routed(i):
            # a held share is one chip's: whole on every device
            layer["router"] = P(None, None)
            layer["expert_bias"] = P(None)
            layer["experts"] = {n: P(None, None, None)
                                for n in ("w1", "w3", "w2")}
            if cfg.n_shared_experts:
                layer["shared"] = {"w1": P(None, "tp"), "w3": P(None, "tp"),
                                   "w2": P("tp", None)}
        else:
            layer["w1"] = P(None, "tp")
            layer["w2"] = P("tp", None)
            if cfg.gated_ffn:
                layer["w3"] = P(None, "tp")
        specs["layers"].append(layer)
    return specs


def _rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _rope(x, theta: float):
    """Rotary positions over the whole head of ``x`` [B, T, H, Dh],
    rotate-half pairing: channel i turns with channel i + Dh/2 by the
    angle ``t * theta ** (-2i / Dh)``; float32 inside."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _short_conv(cfg: "TransformerConfig", layer, h):
    """The gated short convolution: ``[b, c, z] = split(w_in h)``, a
    depthwise causal convolution of ``b * z`` over time (``conv_kernel``
    taps, zeros left of the sequence, no bias), gated by ``c``, then
    ``w_out``.  The taps are summed in float32."""
    cd, K, T = cfg.compute_dtype, cfg.conv_kernel, h.shape[1]
    b, c, z = jnp.split(
        jnp.einsum("btd,de->bte", h, layer["w_in"].astype(cd)), 3, axis=-1)
    u = jnp.pad((b * z).astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    taps = layer["conv"].astype(jnp.float32)
    y = sum(u[:, j:j + T] * taps[:, j] for j in range(K))
    return jnp.einsum("btd,de->bte", c * y.astype(cd),
                      layer["w_out"].astype(cd))


def make_apply(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
               return_aux: bool = False, return_route: bool = False):
    """Build the forward fn.  With a mesh containing an ``sp`` axis of
    size > 1, attention runs sequence-parallel in shard_map — ring
    attention or Ulysses all-to-all per ``cfg.sp_attn`` — otherwise the
    dense single-device path.

    ``return_aux=True`` makes the fn return ``(logits, aux)`` where aux
    is the summed MoE load-balancing loss (zero without top-k MoE); the
    default keeps the historical logits-only signature.  TRAINING a
    top-k MoE through the logits-only form discards the load-balancing
    pressure (router collapse, silent capacity drops) — fine for
    inference/forward comparisons, so it warns instead of raising.

    ``return_route=True`` appends what the routed layers saw (None
    without any): int32 ``rows`` [routed layers, experts held],
    ``held_pairs``, ``empty_tokens``, ``chunks`` and ``buffer_rows``
    [routed layers], as ``parallel/moe.py`` ``routed_ffn`` counts them;
    and, for a model with ``kda`` layers, what their scans saw, a row a
    layer (``ops/kda.py`` ``chunk_kda``'s ``stats``)."""
    if cfg.moe_every > 0 and cfg.moe_top_k > 0 and not return_aux:
        import warnings

        warnings.warn(
            "make_apply(return_aux=False) with top-k MoE discards the "
            "load-balancing aux loss; use return_aux=True + "
            "lm_loss_with_aux for training", stacklevel=2)
    if cfg.sp_attn not in ("ring", "ulysses"):
        raise ValueError(
            f"sp_attn must be 'ring' or 'ulysses', got {cfg.sp_attn!r}")
    use_ring = mesh is not None and "sp" in mesh.axis_names and mesh.shape["sp"] > 1

    def attn_op(q, k, v):
        if not use_ring:
            return _single_device_attention(cfg, q, k, v)
        # attn_impl="dense" keeps the all-fp32 reference blocks;
        # "flash" fuses each ring block in a pallas kernel (no HBM
        # probs); anything else runs bf16-on-MXU einsum blocks with
        # fp32 accum.  Ulysses does whole-sequence attention after its
        # all-to-all, so it takes the boolean fast path only.
        fast = ("flash" if cfg.attn_impl == "flash"
                else cfg.attn_impl != "dense")
        if cfg.sp_attn == "ulysses":
            sp_fn = lambda a, b, c: ulysses_attention(  # noqa: E731
                a, b, c, axis_name="sp", causal=True,
                fast=cfg.attn_impl != "dense")
        else:
            sp_fn = lambda a, b, c: ring_attention(  # noqa: E731
                a, b, c, axis_name="sp", axis_size=mesh.shape["sp"],
                causal=True, fast=fast)
        spec = P("dp", "sp", "tp", None)
        f = shard_map(
            sp_fn,
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        return f(q, k, v)

    def apply(params, tokens):
        """tokens [B, T] int32 → logits [B, T, vocab] float32."""
        cd = cfg.compute_dtype
        B, T = tokens.shape
        x = params["embed"][tokens].astype(cd)
        if not (cfg.rope_theta or cfg.no_positions):
            x = x + params["pos"][:T][None].astype(cd)
        shard = None
        if use_ring:
            shard = NamedSharding(mesh, P("dp", "sp", "tp", None))

        def layer_fn(layer, x, i):
            return _layer_forward(cfg, i, layer, x, attn_op, shard)

        if cfg.remat:
            # a layer that names nothing keeps nothing
            layer_fn = jax.checkpoint(
                layer_fn, static_argnums=(2,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *KEPT_NAMES))
        aux_total = jnp.zeros((), jnp.float32)
        routes, scans = [], []
        for i, layer in enumerate(params["layers"]):
            x, aux, route, scan = layer_fn(layer, x, i)
            aux_total = aux_total + aux
            if route is not None:
                routes.append(route)
            if scan is not None:
                scans.append(scan)
        x = _rms_norm(x, params["ln_f"], cfg.norm_eps)
        head = params["embed" if cfg.tied_head else "head"]
        logits = jnp.einsum("btd,vd->btv", x, head.astype(cd))
        logits = logits.astype(jnp.float32)
        out = (logits, aux_total) if return_aux else (logits,)
        if return_route:
            stack = lambda rows: jax.tree_util.tree_map(    # noqa: E731
                lambda *a: jnp.stack(a), *rows) if rows else None
            out += (stack(routes),)
            if scans:
                out += (stack(scans),)
        return out if len(out) > 1 else logits

    return apply


# Tiles of jax's TPU flash kernels (forward, dK/dV, dQ), largest first,
# and the largest each of the kernels' sizes takes.  jax's own default
# is 128 for every size, which at [4, 16, 2048, 128] is 16,384 grid
# steps a call at 0.35-0.5 us each: the kernels ran at 8% of their
# roofline on the v5e, bound by the price of a grid step.  The tops are
# the fastest sizes of a sweep at that shape on the v5e that fit the
# default scoped VMEM and add under 2 s of Mosaic compile (PERF.md
# section 6, PR 34); a minor size never tops its major.
_FLASH_TILES = (1024, 512, 256, 128)
_FLASH_TILE_TOPS = dict(
    block_q=512, block_k_major=512, block_k=512,
    block_q_major_dkv=512, block_q_dkv=512,
    block_k_major_dkv=1024, block_k_dkv=512,
    block_q_dq=1024, block_k_major_dq=512, block_k_dq=512)


def _flash_block_sizes(seq_len: int, head_dim: int):
    """The ``BlockSizes`` for one call of jax's TPU flash attention,
    chosen from the shape it is called with: each size is the largest
    tile, up to its top, that divides ``seq_len``.  ``None``, jax's
    default, where no tile does (a sequence shorter than 128 or not a
    multiple of it)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    least = _FLASH_TILES[-1]
    if seq_len % least:
        return None
    # a tile's rows are head_dim wide in VMEM: the tops fit it up to a
    # head width of 512 and shrink with a wider one
    narrow = -(-head_dim // 512)
    return BlockSizes(block_b=1, **{
        size: next(t for t in _FLASH_TILES
                   if t <= max(top // narrow, least) and seq_len % t == 0)
        for size, top in _FLASH_TILE_TOPS.items()})


def _single_device_attention(cfg: TransformerConfig, q, k, v):
    """Dispatch the single-device attention per ``cfg.attn_impl``."""
    if cfg.attn_impl == "dense":
        return dense_attention(q, k, v, causal=True)
    if cfg.attn_impl == "fast":
        return fast_dense_attention(q, k, v, causal=True)
    if cfg.attn_impl == "flash":
        # jax's pallas TPU flash kernel wants [B, H, T, Dh]; ours is
        # [B, T, H, Dh].  Lowers only for a TPU (tests run it under
        # the TPU interpreter, chip_smoke.py compiled).
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention)

        sm = float(1.0 / np.sqrt(q.shape[-1]))
        dv = v.shape[-1]
        if q.shape[-1] != dv:
            # the kernels take one head width, a multiple of 128 past
            # 128: q/k heads wider than v's (latent attention's 192
            # against 128) all go in padded with zeros to the next such
            # width, which adds nothing to q k^T and zero channels to
            # the output, cut off again
            wide = -(-max(q.shape[-1], dv) // 128) * 128
            q, k, v = (jnp.pad(a, ((0, 0),) * 3 + ((0, wide - a.shape[-1]),))
                       for a in (q, k, v))
        o = flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=True, sm_scale=sm,
            block_sizes=_flash_block_sizes(q.shape[1], q.shape[-1]))
        return o.swapaxes(1, 2)[..., :dv]
    raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")


def _attention(cfg: TransformerConfig, layer, h, attn_op, shard=None):
    """Causal self-attention of one block on the normed stream ``h``:
    the flagship's multi-head form, and with ``n_kv_heads`` /
    ``qk_norm`` / ``rope_theta`` set grouped-query heads (each k/v head
    repeated for the consecutive q heads it serves, so that every
    attention implementation sees equal head counts), RMSNorm over each
    head's channels of q and k, and rotary positions."""
    cd = cfg.compute_dtype
    q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(cd))
    k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(cd))
    v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(cd))
    if cfg.qk_norm:
        q = _rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = _rms_norm(k, layer["k_norm"], cfg.norm_eps)
    if cfg.rope_theta:
        q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    if cfg.kv_heads != cfg.n_heads:
        group = cfg.n_heads // cfg.kv_heads
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if shard is not None:
        q = lax.with_sharding_constraint(q, shard)
        k = lax.with_sharding_constraint(k, shard)
        v = lax.with_sharding_constraint(v, shard)
    a = attn_op(q, k, v)
    return jnp.einsum("bthk,hkd->btd", a, layer["wo"].astype(cd))


def _latent_attention(cfg: TransformerConfig, layer, h, attn_op):
    """Causal attention whose keys and values come through a low-rank
    latent: ``[c, k_r] = split(w_kv_a h)``, ``[k_n, v] = split(w_kv_b
    rms(c))`` a head, ``k = [k_n, k_r]`` with ``k_r`` the same for every
    head.  q and k heads are ``qk_nope_dim + qk_rope_dim`` wide, v heads
    ``v_head_dim``; the softmax scale is the q/k width's.  The ``rope``
    channels are not rotated (no positions)."""
    cd = cfg.compute_dtype
    R, Dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(cd))
    kv_a = jnp.einsum("btd,dr->btr", h, layer["w_kv_a"].astype(cd))
    c = _rms_norm(kv_a[..., :R], layer["kv_norm"], cfg.norm_eps)
    kv = jnp.einsum("btr,rhk->bthk", c, layer["w_kv_b"].astype(cd))
    k_r = jnp.broadcast_to(kv_a[:, :, None, R:],
                           kv.shape[:3] + (cfg.qk_rope_dim,))
    k = jnp.concatenate([kv[..., :Dn], k_r], axis=-1)
    a = attn_op(q, k, kv[..., Dn:])
    return jnp.einsum("bthk,hkd->btd", a, layer["wo"].astype(cd))


def _conv_silu(x, taps):
    """``silu`` of the depthwise causal convolution of ``x`` [B, T, H,
    K] over time with ``taps`` [H, K, taps] (zeros left of the sequence,
    no bias), float32."""
    T, n = x.shape[1], taps.shape[-1]
    u = jnp.pad(x.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    return jax.nn.silu(sum(u[:, j:j + T] * taps[..., j] for j in range(n)))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(cfg: TransformerConfig, layer, h):
    """The gated delta-rule mixer: q, k, v through a short convolution
    and silu, q and k to unit length a head (q times head size ^ -1/2),
    a per-channel log decay ``-exp(A_log) softplus(f_b f_a h +
    dt_bias)`` and a step ``sigmoid(w_beta h)``, both float32, the scan
    (``ops/kda.py``), then RMSNorm over each head's channels, a sigmoid
    output gate through a low-rank pair, and ``wo``.  Returns ``(y,
    scan)``, the scan's counts for the tracer: ``chunk_kda``'s, and
    ``kept_bytes``, the states and output that the layer's checkpoint
    keeps for the backward pass under ``cfg.remat`` (0 without)."""
    cd = cfg.compute_dtype
    q, k, v = (_conv_silu(
        jnp.einsum("btd,dhk->bthk", h, layer["w" + n].astype(cd)),
        layer["conv_" + n]) for n in "qkv")
    q = _unit(q) * float(cfg.kda_head_dim ** -0.5)
    low = lambda a, b: jnp.einsum(                      # noqa: E731
        "btr,rhk->bthk", jnp.einsum("btd,dr->btr", h, layer[a].astype(cd)),
        layer[b].astype(cd)).astype(jnp.float32)
    g = -jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(
        low("f_a", "f_b") + layer["dt_bias"])
    beta = jax.nn.sigmoid(jnp.einsum(
        "btd,dh->bth", h, layer["w_beta"].astype(cd)).astype(jnp.float32))
    o, stats = chunk_kda(q.astype(cd), _unit(k).astype(cd), v.astype(cd), g,
                         beta, chunk=cfg.kda_chunk)
    o = (_rms_norm(o, layer["o_norm"], cfg.norm_eps).astype(jnp.float32)
         * jax.nn.sigmoid(low("g_a", "g_b"))).astype(cd)
    scan = {"log_decay_min": stats["log_decay_min"],
            "chunks": jnp.int32(stats["chunks"]),
            "chunk": jnp.int32(cfg.kda_chunk),
            "state_bytes": jnp.float32(stats["state_bytes"]),
            # what make_apply's checkpoint of the layer keeps of it
            "kept_bytes": jnp.float32(
                stats["named_bytes"] if cfg.remat else 0)}
    return jnp.einsum("bthk,hkd->btd", o, layer["wo"].astype(cd)), scan


def _layer_forward(cfg: TransformerConfig, i: int, layer, x, attn_op,
                   shard=None):
    """One block: the layer's operator (attention, latent attention, the
    gated short convolution or the delta-rule scan) and its FFN (dense,
    MoE or routed share), each a residual.  Returns ``(x, aux, route,
    scan)``: the capacity MoE's load-balancing loss (0 elsewhere), a
    routed layer's counts and a ``kda`` layer's (None elsewhere)."""
    cd = cfg.compute_dtype
    h = _rms_norm(x, layer["ln1"], cfg.norm_eps)
    scan = None
    if cfg.is_conv(i):
        x = x + _short_conv(cfg, layer, h)
    elif cfg.kind(i) == "kda":
        y, scan = _kda(cfg, layer, h)
        x = x + y
    elif cfg.kind(i) == "mla":
        x = x + _latent_attention(cfg, layer, h, attn_op)
    else:
        x = x + _attention(cfg, layer, h, attn_op, shard)
    h = _rms_norm(x, layer["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    route = None
    if cfg.is_routed(i):
        from geomx_tpu.parallel.moe import routed_ffn
        y, route = routed_ffn(
            h, layer["router"], layer["expert_bias"], layer["experts"],
            first=cfg.first_expert, k=cfg.moe_top_k,
            scale=cfg.routed_scale, impl=cfg.expert_impl, compute_dtype=cd,
            shared=layer.get("shared"), router_grad=cfg.router_grad)
        x = x + y
    elif cfg.is_moe(i):
        if cfg.moe_top_k > 0:
            # real EP: top-k routing with capacity; each token computed
            # by only its k experts (parallel/moe.py, batch = groups)
            from geomx_tpu.parallel.moe import moe_ffn_topk
            y, aux = moe_ffn_topk(
                h, layer["router"], layer["we1"], layer["we2"],
                k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                compute_dtype=cd)
            x = x + y
        else:
            # dense-routing MoE: every expert computes, outputs are
            # combined by router weights (exact; experts sharded tp/ep)
            gates = jax.nn.softmax(
                jnp.einsum("btd,de->bte", h.astype(jnp.float32),
                           layer["router"]), axis=-1).astype(cd)
            up = jnp.einsum("btd,edf->btef", h, layer["we1"].astype(cd))
            up = jax.nn.gelu(up)
            down = jnp.einsum("btef,efd->bted", up, layer["we2"].astype(cd))
            x = x + jnp.einsum("bted,bte->btd", down, gates)
    else:
        up = jnp.einsum("btd,df->btf", h, layer["w1"].astype(cd))
        if cfg.gated_ffn:
            up = jax.nn.silu(up) * jnp.einsum("btd,df->btf", h,
                                              layer["w3"].astype(cd))
        else:
            up = jax.nn.gelu(up)
        x = x + jnp.einsum("btf,fd->btd", up, layer["w2"].astype(cd))
    return x, aux, route, scan


def make_staged(cfg: TransformerConfig, rng: jax.Array):
    """The flagship split for the P3-overlap worker loop
    (``geomx_tpu.overlap``): stage 0 = embedding(+pos), one stage per
    transformer layer (dense attention — the single-chip path), final
    stage = ln_f + UNTIED LM head.  The head must be untied because
    tied embeddings would place one tensor in two stages, breaking
    per-stage push/pull ownership.

    Returns ``(stage_fns, stage_params)`` ready for
    ``overlap.StagedModel`` / ``run_worker_overlapped``.
    """
    if cfg.moe_every > 0 and cfg.moe_top_k > 0:
        # the staged loop has no channel for the MoE aux loss; dropping
        # it silently would train top-k routers without load balancing
        raise ValueError("make_staged supports dense-routing MoE only "
                         "(moe_top_k must be 0): the staged loss has no "
                         "aux-loss channel")
    if cfg.rope_theta or cfg.no_positions:
        raise ValueError("make_staged's embedding stage adds the learned "
                         "positions: rope_theta must be 0 and no_positions "
                         "false")
    if cfg.layer_types or cfg.router_experts:
        raise ValueError("make_staged's stages are attention with a dense "
                         "FFN: layer_types must be empty and "
                         "router_experts 0")
    params = init_params(cfg, rng)
    head = jax.random.normal(
        jax.random.fold_in(rng, 7), (cfg.d_model, cfg.vocab),
        jnp.float32) / np.sqrt(cfg.d_model)

    def embed_fn(p, tokens):
        cd = cfg.compute_dtype
        x = p["embed"][tokens].astype(cd)
        return x + p["pos"][:tokens.shape[1]][None].astype(cd)

    def layer_fn(p, x, i=0):
        return _layer_forward(
            cfg, i, p, x,
            lambda q, k, v: _single_device_attention(cfg, q, k, v))[0]

    def head_fn(p, x):
        x = _rms_norm(x, p["ln_f"])
        return jnp.einsum(
            "btd,dv->btv", x, p["head"].astype(cfg.compute_dtype)
        ).astype(jnp.float32)

    stage_fns = [embed_fn]
    stage_params = [{"embed": params["embed"], "pos": params["pos"]}]
    for i, layer in enumerate(params["layers"]):
        stage_fns.append(lambda p, x, i=i: layer_fn(p, x, i))
        stage_params.append(layer)
    stage_fns.append(head_fn)
    stage_params.append({"ln_f": params["ln_f"], "head": head})
    return stage_fns, stage_params


def token_cross_entropy(logits, tokens):
    """Next-token cross-entropy (shift by one) — THE LM objective; every
    consumer (lm_loss, the examples, the dryrun) must route
    through here so they all measure the same thing."""
    logp = jax.nn.log_softmax(logits[:, :-1])
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(ll)


def lm_loss(apply_fn, params, tokens):
    """Next-token cross-entropy (shift by one)."""
    return token_cross_entropy(apply_fn(params, tokens), tokens)


AUX_COEF = 0.01  # MoE load-balancing aux weight — the ONE definition
#                  (make_lm_grad_fn and examples/lm.py reuse it)


def lm_loss_with_aux(apply_fn, params, tokens, aux_coef: float = AUX_COEF):
    """LM loss + MoE load-balancing aux.  ``apply_fn`` must come from
    ``make_apply(..., return_aux=True)``."""
    logits, aux = apply_fn(params, tokens)
    return token_cross_entropy(logits, tokens) + aux_coef * aux


def make_lm_grad_fn(cfg: "TransformerConfig"):
    """Jitted ``grad_fn(params, x, y) -> (loss, acc, grads)`` with the
    worker-loop signature (``training.run_worker``); y is ignored (the
    LM objective shifts x).  The launcher's LM workload and the
    benchmark's families train this step.  Top-k MoE
    configs train with the load-balancing aux folded in (the same
    objective examples/lm.py uses).  A config with routed layers
    (``router_experts``) returns a fourth value, ``{"moe_route": ...}``:
    the layers' int32 counts, which the worker loop reads to the host in
    a sampled round only (span ``moe.route``); one with ``kda`` layers
    adds ``"kda_scan"`` to it, their scans' counts (span ``kda.scan``)."""
    use_aux = cfg.moe_every > 0 and cfg.moe_top_k > 0
    routed = any(cfg.is_routed(i) for i in range(cfg.n_layers))
    scanned = "kda" in cfg.layer_types
    apply_fn = make_apply(cfg, return_aux=use_aux,
                          return_route=routed or scanned)

    @jax.jit
    def grad_fn(p, x, _y):
        def loss_fn(p):
            out = apply_fn(p, x)
            out = out if isinstance(out, tuple) else (out,)
            logits = out[0]
            aux = out[1] if use_aux else 0.0
            loss = token_cross_entropy(logits, x) + AUX_COEF * aux
            acc = jnp.mean(jnp.argmax(logits[:, :-1], axis=-1) == x[:, 1:])
            seen = {}
            if routed:
                seen["moe_route"] = out[1 + use_aux]
            if scanned:
                seen["kda_scan"] = out[-1]
            return loss, (acc, seen)

        (loss, (acc, seen)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(p)
        if seen:
            return loss, acc, g, seen
        return loss, acc, g

    return grad_fn
