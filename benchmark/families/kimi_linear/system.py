"""Kimi-Linear's entry into the system: the one file of the family that
imports ``geomx_tpu``.  The configuration's keys are the published
config's own names; this is where they become the program's."""

from __future__ import annotations


def layer_types(model: dict) -> tuple:
    """The mixers of the layers run: published layers ``first_layer ..
    first_layer + num_hidden_layers - 1`` (numbered from 1, as
    ``linear_attn_config`` numbers them), ``kda`` or ``mla``."""
    lin = model["linear_attn_config"]
    kinds = {**dict.fromkeys(lin["kda_layers"], "kda"),
             **dict.fromkeys(lin["full_attn_layers"], "mla")}
    a = model["first_layer"]
    return tuple(kinds[i] for i in range(a, a + model["num_hidden_layers"]))


def config(model: dict, compute_dtype: str):
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import TransformerConfig

    lin = model["linear_attn_config"]
    if not model["mla_use_nope"] or model["tie_word_embeddings"]:
        raise ValueError("the family has no positions and an untied head")
    return TransformerConfig(
        vocab=model["vocab"], max_seq=model["max_seq"],
        d_model=model["hidden_size"], d_ff=model["intermediate_size"],
        n_heads=model["num_attention_heads"],
        n_layers=model["num_hidden_layers"], layer_types=layer_types(model),
        norm_eps=model["rms_norm_eps"], gated_ffn=True,
        no_positions=True, tied_head=False,
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv_kernel=lin["short_conv_kernel_size"],
        kda_chunk=model["kda_chunk"],
        kv_lora_rank=model["kv_lora_rank"],
        qk_nope_dim=model["qk_nope_head_dim"],
        qk_rope_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        # the leading dense layers that the cut still holds
        n_dense_layers=max(0, model["first_k_dense_replace"]
                           - (model["first_layer"] - 1)),
        router_experts=model["router_experts"],
        moe_top_k=model["num_experts_per_token"],
        n_experts=model["num_experts"], first_expert=model["first_expert"],
        d_expert=model["moe_intermediate_size"],
        n_shared_experts=model["num_shared_experts"],
        # one share of the experts: its part of the router's gradient is
        # a pull toward its own (the file's ``assumed``)
        router_grad=False,
        routed_scale=float(model["routed_scaling_factor"]),
        expert_impl=model["expert_impl"], attn_impl=model["attn_impl"],
        remat=model["remat"], compute_dtype=jnp.dtype(compute_dtype))


def build(model: dict, compute_dtype: str):
    """From the configuration's keys: (``init(key) -> params``, seeded,
    to be jitted by the caller; the jitted ``grad_fn(params, x, y) ->
    (loss, acc, grads, {"moe_route": counts, "kda_scan": counts})`` that
    ``Trainer`` takes)."""
    from geomx_tpu.models.transformer import make_lm_grad_fn

    mcfg = config(model, compute_dtype)
    return (lambda key: init(mcfg, key)), make_lm_grad_fn(mcfg)


def init(mcfg, key):
    """The seeded weights: the program's ``init_params`` (every matrix
    N(0, 1 / fan_in), the KDA layers' decays), with what the config does
    not say and the file's ``assumed`` does: a unit-variance embedding
    beside an untied head, and GPT-2's scaled init, ``(2 L) ** -0.5``,
    on the matrices that write to the residual stream."""
    import jax

    from geomx_tpu.models.transformer import init_params

    params = init_params(mcfg, key)
    params["embed"] = jax.random.normal(
        jax.random.fold_in(key, 1), params["embed"].shape,
        params["embed"].dtype)
    gain = (2 * mcfg.n_layers) ** -0.5
    for layer in params["layers"]:
        for group in (layer, layer.get("experts"), layer.get("shared")):
            for name in ("wo", "w2"):
                if group is not None and name in group:
                    group[name] = group[name] * gain
    return params
