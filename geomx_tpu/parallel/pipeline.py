"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

Absent from the reference (SURVEY.md §2.3 — no PP anywhere); a TPU-design
addition.  A stack of identical blocks is sharded layer-wise over the
``pp`` mesh axis (each device owns ``L / pp`` consecutive blocks).  The
batch splits into M microbatches; activations flow rank→rank+1 via
``lax.ppermute`` each tick, so at steady state all stages compute
concurrently.  The whole schedule is a ``lax.scan`` (M + pp − 1 ticks)
inside ``shard_map`` — fully differentiable, so one jit compiles the
complete pipelined train step.

Bubble fraction is the usual (pp−1)/(M+pp−1); pick M ≥ 4·pp in practice.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    mesh: Mesh,
    block_fn: Callable,
    stacked_params,
    x_mb: jax.Array,
    axis: str = "pp",
    dp_axis: Optional[str] = None,
):
    """Run microbatches through the pipelined block stack.

    - ``block_fn(params_one_block, x) -> x`` applies ONE block.
    - ``stacked_params``: pytree whose leaves have a leading layer dim L,
      sharded ``P(axis)`` (L must divide by the pp axis size).
    - ``x_mb``: [M, mb, ...] microbatches, replicated across ``axis``.
    - ``dp_axis``: optional mesh axis sharding the microbatch dim (index
      1) — pp×dp composition: each dp shard runs its own pipeline over
      its slice of every microbatch; the pp collectives (ppermute
      relays, final psum) stay within a dp coordinate, and the gradient
      AllReduce over dp is inserted by shard_map's transpose as usual.

    Returns [M, mb, ...] outputs, replicated over ``axis`` (sharded over
    ``dp_axis`` if given).
    """
    pp = mesh.shape[axis]

    def stage(params_local, x):
        def apply_local(h):
            h, _ = lax.scan(lambda c, p: (block_fn(p, c), None),
                            h, params_local)
            return h

        my = lax.axis_index(axis)
        M = x.shape[0]
        steps = M + pp - 1
        zero_mb = jnp.zeros_like(x[0])
        fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]

        def tick(carry, t):
            prev_act, out_buf = carry
            # rank 0 feeds microbatch t (garbage past M never lands in a
            # valid output slot); other ranks consume the relayed act
            x_t = lax.dynamic_index_in_dim(x, jnp.minimum(t, M - 1), axis=0,
                                           keepdims=False)
            inp = jnp.where(my == 0, x_t, prev_act)
            h = apply_local(inp)
            # last rank writes finished microbatch t-(pp-1)
            out_idx = t - (pp - 1)
            write = jnp.logical_and(my == pp - 1, out_idx >= 0)
            safe_idx = jnp.clip(out_idx, 0, M - 1)
            cur = lax.dynamic_index_in_dim(out_buf, safe_idx, 0,
                                           keepdims=False)
            new = jnp.where(write, h, cur)
            out_buf = lax.dynamic_update_index_in_dim(out_buf, new,
                                                      safe_idx, 0)
            # relay my activation to the next stage
            nxt = lax.ppermute(h, axis, fwd_perm)
            return (nxt, out_buf), None

        out0 = jnp.zeros_like(x)
        (_, out), _ = lax.scan(tick, (zero_mb, out0), jnp.arange(steps))
        # only the last rank holds real outputs; psum broadcasts them
        # (all other ranks contribute zeros)
        mask = jnp.where(my == pp - 1, 1.0, 0.0).astype(out.dtype)
        return lax.psum(out * mask, axis)

    x_spec = P(*([None, dp_axis] + [None] * (x_mb.ndim - 2))
               if dp_axis else [None] * x_mb.ndim)
    return shard_map(
        stage, mesh=mesh,
        in_specs=(P(axis), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stacked_params, x_mb)


def mlp_block(params, x):
    """Reference block for tests/dry runs: pre-norm MLP residual block."""
    w1, w2 = params["w1"], params["w2"]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    h = x * lax.rsqrt(var + 1e-6)
    return x + jax.nn.gelu(h @ w1) @ w2


def init_mlp_stack(rng, n_layers: int, d: int, f: int):
    k1, k2 = jax.random.split(rng)
    scale1 = 1.0 / jnp.sqrt(d)
    scale2 = 1.0 / jnp.sqrt(f)
    return {
        "w1": jax.random.normal(k1, (n_layers, d, f), jnp.float32) * scale1,
        "w2": jax.random.normal(k2, (n_layers, f, d), jnp.float32) * scale2,
    }


def sequential_apply(stacked_params, x_mb, block_fn=mlp_block):
    """Single-device reference: same math, no pipeline."""
    def apply_one(x):
        h, _ = lax.scan(lambda c, p: (block_fn(p, c), None), x, stacked_params)
        return h

    return jax.vmap(apply_one)(x_mb)


# --------------------------------------------------------------------------
# flagship transformer over pp(+dp) — VERDICT r2 item 5
# --------------------------------------------------------------------------

def stack_layers(layers):
    """Stack a list of identical-structure layer pytrees along a new
    leading dim (the pp shard dim).  Homogeneous (non-MoE) layers only."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def init_pp_transformer(cfg, rng):
    """Flagship params in pipeline layout: ``layers`` stacked [L, ...]
    (shard ``P("pp")``), embedding/head UNTIED (same reasoning as
    ``make_staged``: one tensor must not live in two stages when each
    stage's grads are pushed to the kvstore independently)."""
    from geomx_tpu.models.transformer import init_params

    assert not (cfg.moe_every or cfg.layer_types or cfg.router_experts
                or cfg.rope_theta), \
        "pp flagship pipelines homogeneous layers with learned positions"
    params = init_params(cfg, rng)
    import numpy as np
    head = jax.random.normal(
        jax.random.fold_in(rng, 7), (cfg.d_model, cfg.vocab),
        jnp.float32) / np.sqrt(cfg.d_model)
    return {
        "embed": params["embed"],
        "pos": params["pos"],
        "layers": stack_layers(params["layers"]),
        "ln_f": params["ln_f"],
        "head": head,
    }


def pp_param_specs(pp_params, axis: str = "pp"):
    """PartitionSpecs mirroring an ``init_pp_transformer`` tree: layer
    stack sharded over pp (leading dim), everything else replicated."""
    return {
        "embed": P(None, None),
        "pos": P(None, None),
        "layers": jax.tree_util.tree_map(
            lambda leaf: P(*([axis] + [None] * (leaf.ndim - 1))),
            pp_params["layers"]),
        "ln_f": P(None),
        "head": P(None, None),
    }


def make_pp_apply(cfg, mesh: Mesh, n_microbatches: int,
                  axis: str = "pp", dp_axis: Optional[str] = None):
    """Pipelined flagship forward: embed (replicated over pp) → GPipe
    schedule over the stacked transformer layers → ln_f + untied head.
    One jit compiles the whole thing; grads flow through the schedule
    (the scan is differentiable), so ``value_and_grad`` of the returned
    apply is the full pipelined train step."""
    from geomx_tpu.models.transformer import (
        _layer_forward, _rms_norm, _single_device_attention)

    # same guard as init_pp_transformer: block() routes every layer
    # through _layer_forward(idx=0), which silently applies dense FFN
    # (and drops the aux loss) for a MoE config
    assert not (cfg.moe_every or cfg.layer_types or cfg.router_experts
                or cfg.rope_theta), \
        "pp flagship pipelines homogeneous layers with learned positions"

    def block(layer, x):
        return _layer_forward(
            cfg, 0, layer, x,
            lambda q, k, v: _single_device_attention(cfg, q, k, v))[0]

    def apply(pp_params, tokens):
        B, T = tokens.shape
        M = n_microbatches
        assert B % M == 0, (B, M)
        cd = cfg.compute_dtype
        x = pp_params["embed"][tokens].astype(cd)
        x = x + pp_params["pos"][:T][None].astype(cd)
        x_mb = x.reshape(M, B // M, T, cfg.d_model)
        out = pipeline_apply(mesh, block, pp_params["layers"], x_mb,
                             axis=axis, dp_axis=dp_axis)
        x = out.reshape(B, T, cfg.d_model)
        x = _rms_norm(x, pp_params["ln_f"])
        logits = jnp.einsum("btd,dv->btv", x, pp_params["head"].astype(cd))
        return logits.astype(jnp.float32)

    return apply
