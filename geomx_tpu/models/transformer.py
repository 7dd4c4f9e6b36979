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

Pure-jax functional style: ``init_params`` builds a pytree,
``param_specs`` mirrors it with PartitionSpecs, ``make_apply`` returns the
forward.  bf16 activations, f32 params/accumulators.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
    #                          recipe for big batches / long seq

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    def is_moe(self, layer: int) -> bool:
        return self.moe_every > 0 and (layer + 1) % self.moe_every == 0


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
    H, Dh, D, F = cfg.n_heads, cfg.head_dim, cfg.d_model, cfg.d_ff
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[3 + i], 8)
        layer = {
            "ln1": jnp.ones((D,), jnp.float32),
            "ln2": jnp.ones((D,), jnp.float32),
            "wq": dense(k[0], (D, H, Dh)),
            "wk": dense(k[1], (D, H, Dh)),
            "wv": dense(k[2], (D, H, Dh)),
            "wo": dense(k[3], (H, Dh, D), scale=1.0 / np.sqrt(D)),
        }
        if cfg.is_moe(i):
            E = cfg.n_experts
            layer["router"] = dense(k[6], (D, E), scale=0.02)
            layer["we1"] = dense(k[4], (E, D, F))
            layer["we2"] = dense(k[5], (E, F, D), scale=1.0 / np.sqrt(F))
        else:
            layer["w1"] = dense(k[4], (D, F))
            layer["w2"] = dense(k[5], (F, D), scale=1.0 / np.sqrt(F))
        params["layers"].append(layer)
    return params


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
    for i in range(cfg.n_layers):
        layer = {
            "ln1": P(None),
            "ln2": P(None),
            "wq": P(None, "tp", None),
            "wk": P(None, "tp", None),
            "wv": P(None, "tp", None),
            "wo": P("tp", None, None),
        }
        if cfg.is_moe(i):
            layer["router"] = P(None, None)
            layer["we1"] = P("tp", None, None)   # expert-parallel (ep≡tp)
            layer["we2"] = P("tp", None, None)
        else:
            layer["w1"] = P(None, "tp")
            layer["w2"] = P("tp", None)
        specs["layers"].append(layer)
    return specs


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def make_apply(cfg: TransformerConfig, mesh: Optional[Mesh] = None,
               return_aux: bool = False):
    """Build the forward fn.  With a mesh containing an ``sp`` axis of
    size > 1, attention runs sequence-parallel in shard_map — ring
    attention or Ulysses all-to-all per ``cfg.sp_attn`` — otherwise the
    dense single-device path.

    ``return_aux=True`` makes the fn return ``(logits, aux)`` where aux
    is the summed MoE load-balancing loss (zero without top-k MoE); the
    default keeps the historical logits-only signature.  TRAINING a
    top-k MoE through the logits-only form discards the load-balancing
    pressure (router collapse, silent capacity drops) — fine for
    inference/forward comparisons, so it warns instead of raising."""
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
        x = x + params["pos"][:T][None].astype(cd)
        shard = None
        if use_ring:
            shard = NamedSharding(mesh, P("dp", "sp", "tp", None))

        def layer_fn(layer, x, i):
            return _layer_forward(cfg, i, layer, x, attn_op, shard)

        if cfg.remat:
            layer_fn = jax.checkpoint(layer_fn, static_argnums=(2,))
        aux_total = jnp.zeros((), jnp.float32)
        for i, layer in enumerate(params["layers"]):
            x, aux = layer_fn(layer, x, i)
            aux_total = aux_total + aux
        x = _rms_norm(x, params["ln_f"])
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(cd))
        logits = logits.astype(jnp.float32)
        return (logits, aux_total) if return_aux else logits

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
        o = flash_attention(
            q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
            causal=True, sm_scale=sm,
            block_sizes=_flash_block_sizes(q.shape[1], q.shape[-1]))
        return o.swapaxes(1, 2)
    raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")


def _layer_forward(cfg: TransformerConfig, i: int, layer, x, attn_op,
                   shard=None):
    """One transformer block (attention + MLP/MoE residual)."""
    cd = cfg.compute_dtype
    h = _rms_norm(x, layer["ln1"])
    q = jnp.einsum("btd,dhk->bthk", h, layer["wq"].astype(cd))
    k = jnp.einsum("btd,dhk->bthk", h, layer["wk"].astype(cd))
    v = jnp.einsum("btd,dhk->bthk", h, layer["wv"].astype(cd))
    if shard is not None:
        q = lax.with_sharding_constraint(q, shard)
        k = lax.with_sharding_constraint(k, shard)
        v = lax.with_sharding_constraint(v, shard)
    a = attn_op(q, k, v)
    x = x + jnp.einsum("bthk,hkd->btd", a, layer["wo"].astype(cd))
    h = _rms_norm(x, layer["ln2"])
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe(i):
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
        up = jax.nn.gelu(jnp.einsum("btd,df->btf", h,
                                    layer["w1"].astype(cd)))
        x = x + jnp.einsum("btf,fd->btd", up, layer["w2"].astype(cd))
    return x, aux


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
    benchmark's flagship family train this step.  Top-k MoE
    configs train with the load-balancing aux folded in (the same
    objective examples/lm.py uses)."""
    use_aux = cfg.moe_every > 0 and cfg.moe_top_k > 0
    apply_fn = make_apply(cfg, return_aux=use_aux)

    @jax.jit
    def grad_fn(p, x, _y):
        def loss_fn(p):
            out = apply_fn(p, x)
            logits, aux = out if use_aux else (out, 0.0)
            loss = token_cross_entropy(logits, x) + AUX_COEF * aux
            acc = jnp.mean(jnp.argmax(logits[:, :-1], axis=-1) == x[:, 1:])
            return loss, acc

        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, acc, g

    return grad_fn
