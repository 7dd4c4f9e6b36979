"""The flagship's entry into the system: the one file of the family that
imports ``geomx_tpu``."""

from __future__ import annotations

SIZE_KEYS = ("vocab", "d_model", "n_heads", "n_layers", "d_ff", "max_seq")


def build(model: dict, compute_dtype: str):
    """From the configuration's keys: (``init(key) -> params``, seeded,
    to be jitted by the caller; the jitted ``grad_fn(params, x, y) ->
    (loss, acc, grads)`` that ``Trainer`` takes)."""
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import (TransformerConfig, init_params,
                                              make_lm_grad_fn)

    mcfg = TransformerConfig(**{k: model[k] for k in SIZE_KEYS},
                             attn_impl=model["attn_impl"],
                             compute_dtype=jnp.dtype(compute_dtype))
    return (lambda key: init_params(mcfg, key)), make_lm_grad_fn(mcfg)
