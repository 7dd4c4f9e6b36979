"""Laguna's entry into the system: the one file of the family that
imports ``geomx_tpu``.  The configuration's keys are the published
config's own names; this is where they become the program's."""

from __future__ import annotations


def run(model: dict, key: str) -> list:
    """The entries of the per-layer list ``key`` for the layers run:
    published layers ``first_layer .. first_layer + num_hidden_layers -
    1`` (numbered from 0, as ``mlp_only_layers`` numbers them)."""
    a = model["first_layer"]
    return list(model[key][a:a + model["num_hidden_layers"]])


def heads(model: dict) -> dict:
    """The q heads a layer of each kind holds, from
    ``num_attention_heads_per_layer``: one count a kind."""
    by_kind: dict = {}
    for kind, n in zip(run(model, "layer_types"),
                       run(model, "num_attention_heads_per_layer")):
        by_kind.setdefault(kind, set()).add(n)
    if any(len(n) != 1 for n in by_kind.values()):
        raise ValueError(f"one head count a layer kind, not {by_kind}")
    return {kind: n.pop() for kind, n in by_kind.items()}


def config(model: dict, compute_dtype: str):
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import TransformerConfig, Yarn

    full = model["rope_parameters"]["full_attention"]
    slide = model["rope_parameters"]["sliding_attention"]
    mlp = run(model, "mlp_layer_types")
    dense = mlp.index("sparse") if "sparse" in mlp else len(mlp)
    held = heads(model)
    if (model["tie_word_embeddings"] or model["gating"] != "per-head"
            or set(run(model, "gating_types")) != {"per_head"}
            or "dense" in mlp[dense:] or not model["norm_topk_prob"]
            or model["moe_router_logit_softcapping"]
            or model["shared_expert_intermediate_size"]
            != model["moe_intermediate_size"]
            or full["rope_type"] != "yarn" or slide["rope_type"] != "default"
            or slide["partial_rotary_factor"] != 1
            or held.get("full_attention", model["num_attention_heads"])
            != model["num_attention_heads"]
            or model["first_head"] % model["num_key_value_heads"]):
        raise ValueError(
            "the family is per-head gated attention, full layers under a "
            "partial YaRN rotary and windowed ones under a whole default "
            "one, leading dense layers then routed experts with "
            "renormalised top-k weights, no softcap and one shared expert "
            "as wide as a routed one, an untied head, and a head share "
            "that starts at a k/v head")
    return TransformerConfig(
        vocab=model["vocab"], max_seq=model["max_seq"],
        d_model=model["hidden_size"], d_ff=model["intermediate_size"],
        n_heads=model["num_attention_heads"],
        window_heads=held.get("sliding_attention", 0),
        n_kv_heads=model["num_key_value_heads"], d_head=model["head_dim"],
        n_layers=model["num_hidden_layers"],
        layer_types=tuple(run(model, "layer_types")),
        norm_eps=model["rms_norm_eps"], gated_ffn=True, tied_head=False,
        head_gate=True, window=model["sliding_window"],
        window_rope_theta=float(slide["rope_theta"]),
        rope_theta=float(full["rope_theta"]),
        rope_dim=int(model["head_dim"] * full["partial_rotary_factor"]),
        yarn=Yarn(factor=float(full["factor"]),
                  original_max_positions=full[
                      "original_max_position_embeddings"],
                  beta_fast=float(full["beta_fast"]),
                  beta_slow=float(full["beta_slow"]),
                  attention_factor=float(full["attention_factor"])),
        n_dense_layers=dense, router_experts=model["router_experts"],
        moe_top_k=model["num_experts_per_tok"],
        n_experts=model["num_experts"], first_expert=model["first_expert"],
        d_expert=model["moe_intermediate_size"], n_shared_experts=1,
        router_score="softmax",
        # one share of the experts: its part of the router's gradient is
        # a pull toward its own (the file's ``assumed``)
        router_grad=False,
        routed_scale=float(model["moe_routed_scaling_factor"]),
        expert_impl=model["expert_impl"], attn_impl=model["attn_impl"],
        remat=model["remat"], compute_dtype=jnp.dtype(compute_dtype))


def build(model: dict, compute_dtype: str):
    """From the configuration's keys: (``init(key) -> params``, seeded,
    to be jitted by the caller; the jitted ``grad_fn(params, x, y) ->
    (loss, acc, grads, {"moe_route": counts, "attn_window": counts})``
    that ``Trainer`` takes)."""
    from geomx_tpu.models.transformer import make_lm_grad_fn

    mcfg = config(model, compute_dtype)
    return (lambda key: init(mcfg, key)), make_lm_grad_fn(mcfg)


def init(mcfg, key):
    """The seeded weights: the program's ``init_params`` (every matrix
    N(0, 1 / fan_in), norms 1), with what the config does not say and
    the file's ``assumed`` does: a unit-variance embedding beside an
    untied head, and GPT-2's scaled init, ``(2 L) ** -0.5``, on the
    matrices that write to the residual stream."""
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
