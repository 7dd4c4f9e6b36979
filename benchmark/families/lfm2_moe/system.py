"""LFM2-MoE's entry into the system: the one file of the family that
imports ``geomx_tpu``.  The configuration's keys are the published
config's own names; this is where they become the program's."""

from __future__ import annotations


def layer_types(model: dict) -> tuple:
    """The operators of the layers run: ``num_hidden_layers`` entries of
    the published ``layer_types`` from published layer ``first_layer``
    on (the leading dense layers count once: the cut starts at the last
    of them)."""
    a = model["first_layer"]
    return tuple(model["layer_types"][a:a + model["num_hidden_layers"]])


def config(model: dict, compute_dtype: str):
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab=model["vocab"], max_seq=model["max_seq"],
        d_model=model["hidden_size"], d_ff=model["intermediate_size"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        n_layers=model["num_hidden_layers"], layer_types=layer_types(model),
        conv_kernel=model["conv_L_cache"], norm_eps=model["norm_eps"],
        rope_theta=float(model["rope_parameters"]["rope_theta"]),
        qk_norm=True, gated_ffn=True,
        n_dense_layers=model["num_dense_layers"],
        router_experts=model["router_experts"],
        moe_top_k=model["num_experts_per_tok"],
        n_experts=model["num_experts"], first_expert=model["first_expert"],
        d_expert=model["moe_intermediate_size"],
        routed_scale=float(model["routed_scaling_factor"]),
        expert_impl=model["expert_impl"], attn_impl=model["attn_impl"],
        remat=model["remat"], compute_dtype=jnp.dtype(compute_dtype))


def build(model: dict, compute_dtype: str):
    """From the configuration's keys: (``init(key) -> params``, seeded,
    to be jitted by the caller; the jitted ``grad_fn(params, x, y) ->
    (loss, acc, grads, {"moe_route": counts})`` that ``Trainer`` takes)."""
    from geomx_tpu.models.transformer import init_params, make_lm_grad_fn

    mcfg = config(model, compute_dtype)
    return (lambda key: init_params(mcfg, key)), make_lm_grad_fn(mcfg)
