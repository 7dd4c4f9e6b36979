"""Flash-attention correctness without the chip.

``attn_impl="flash"`` (models/transformer.py::_single_device_attention)
lowers only for a TPU; these tests run the very same code under pallas
**TPU interpret mode** on CPU, so a broken kernel or a wrong layout swap
is caught before a chip run.  ``chip_smoke.py`` compiles the kernel
uninterpreted at the flagship geometry.  Tolerances: the interpret-mode
kernel computes in fp32, so fwd is compared tightly; bwd goes through
the kernel's custom VJP (the path the train step uses).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from geomx_tpu.models.transformer import (
    TransformerConfig, _single_device_attention,
)
from geomx_tpu.parallel.ring_attention import dense_attention

# [B, T, H, Dh] — the transformer's layout; Dh=128 matches MFU_CFG's
# head_dim and the kernel's native lane width
B, T, H, D = 1, 256, 2, 128


def _qkv(dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D), dtype) for k in ks)


def test_flash_forward_matches_dense_interpret():
    cfg = TransformerConfig(attn_impl="flash")
    q, k, v = _qkv()
    with force_tpu_interpret_mode():
        o = np.asarray(_single_device_attention(cfg, q, k, v))
    r = np.asarray(dense_attention(q, k, v, causal=True))
    np.testing.assert_allclose(o, r, rtol=1e-4, atol=1e-4)


def test_flash_backward_matches_dense_interpret():
    """The custom-VJP backward — the path every train step exercises."""
    cfg = TransformerConfig(attn_impl="flash")
    q, k, v = _qkv(seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(_single_device_attention(cfg, q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    with force_tpu_interpret_mode():
        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gf = jax.tree_util.tree_map(np.asarray, gf)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            a, np.asarray(b), rtol=1e-3, atol=1e-3,
            err_msg=f"grad wrt {name}")


def test_flash_bf16_within_tolerance_interpret():
    """bf16 inputs — the dtype the benchmark's flagship computes in."""
    cfg = TransformerConfig(attn_impl="flash")
    q, k, v = _qkv(jnp.bfloat16, seed=2)
    with force_tpu_interpret_mode():
        o = np.asarray(
            _single_device_attention(cfg, q, k, v).astype(jnp.float32))
    r = np.asarray(dense_attention(q, k, v, causal=True)
                   .astype(jnp.float32))
    assert np.max(np.abs(o - r)) < 5e-2
