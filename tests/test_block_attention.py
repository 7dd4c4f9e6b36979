"""Interpret-mode coverage for the pallas flash block kernel.

``ops/block_attention.flash_block_attention`` is the ring-attention
``fast="flash"`` production path (reachable via ``make_apply`` with
``attn_impl="flash"`` on an sp mesh) — distinct from the single-device
path in tests/test_flash.py, which uses jax's library flash kernel.
These tests run OUR kernel under pallas TPU interpret mode on CPU:

- the three ring-hop geometries the offsets encode — diagonal (causal
  triangle), below-diagonal (fully visible), above-diagonal (fully
  masked) — forward partials (m, l, o) against the einsum reference;
- gradients through the custom VJP (the train-step path);
- ring_attention(fast="flash") against dense_attention under shard_map
  on the virtual sp mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.experimental.pallas.tpu import force_tpu_interpret_mode
from jax.sharding import PartitionSpec as P

from geomx_tpu.ops.block_attention import (
    _block_attn_ref, flash_block_attention)
from geomx_tpu.parallel import make_mesh, ring_attention
from geomx_tpu.parallel.ring_attention import dense_attention

# [B, T, H, D]; D = 128 matches the kernel's native lane width and the
# flagship head_dim.  Tq=64 exercises multiple bq-block grid steps.
B, TQ, TK, H, D = 1, 64, 64, 2, 128

# (q_off, k_off): diagonal hop (causal triangle), below-diagonal (q
# strictly after k: fully visible), above-diagonal (q strictly before
# k: fully masked — m pinned at -1e30, junk l/o wiped by the ring merge)
OFFSETS = [(0, 0), (TK, 0), (0, TK)]


def _qkv(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, TQ, H, D), dtype)
    k = jax.random.normal(ks[1], (B, TK, H, D), dtype)
    v = jax.random.normal(ks[2], (B, TK, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("q_off,k_off", OFFSETS)
def test_flash_block_forward_matches_ref(q_off, k_off):
    q, k, v = _qkv()
    offs = jnp.array([q_off, k_off], jnp.int32)
    with force_tpu_interpret_mode():
        m, l, o = jax.tree_util.tree_map(
            np.asarray, flash_block_attention(q, k, v, offs, True))
    rm, rl, ro = jax.tree_util.tree_map(
        np.asarray, _block_attn_ref(q, k, v, offs, True))
    np.testing.assert_allclose(m, rm, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l, rl, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, ro, rtol=1e-4, atol=1e-4)
    if q_off < k_off:  # fully masked hop: every row's max is the mask
        assert np.all(m <= -1e29)


def test_flash_block_noncausal_forward():
    q, k, v = _qkv(seed=3)
    offs = jnp.array([0, 0], jnp.int32)
    with force_tpu_interpret_mode():
        m, l, o = jax.tree_util.tree_map(
            np.asarray, flash_block_attention(q, k, v, offs, False))
    rm, rl, ro = jax.tree_util.tree_map(
        np.asarray, _block_attn_ref(q, k, v, offs, False))
    np.testing.assert_allclose(m, rm, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(l, rl, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(o, ro, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("q_off,k_off", OFFSETS[:2])
def test_flash_block_grads_match_ref(q_off, k_off):
    """Custom-VJP gradients vs differentiating the einsum reference.
    (The fully-masked hop is excluded: its m is the constant -1e30 and
    its l/o are wiped by the ring merge, so its grads never matter.)"""
    q, k, v = _qkv(seed=1)
    offs = jnp.array([q_off, k_off], jnp.int32)

    def loss_flash(q, k, v):
        m, l, o = flash_block_attention(q, k, v, offs, True)
        return jnp.sum(o ** 2) + jnp.sum(l ** 2) + jnp.sum(m ** 2)

    def loss_ref(q, k, v):
        m, l, o = _block_attn_ref(q, k, v, offs, True)
        return jnp.sum(o ** 2) + jnp.sum(l ** 2) + jnp.sum(m ** 2)

    with force_tpu_interpret_mode():
        gf = jax.tree_util.tree_map(
            np.asarray, jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v))
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-3, atol=1e-3,
                                   err_msg=f"grad wrt {name}")


def test_ring_attention_flash_matches_dense():
    """The production wiring: fast="flash" inside shard_map over the sp
    mesh must track the fp32 dense reference."""
    mesh = make_mesh({"sp": 4})
    T = 4 * TQ  # global seq; TQ per device
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    ref = dense_attention(q, k, v, causal=True)
    spec = P(None, "sp", None, None)
    f = shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", axis_size=4,
                                       causal=True, fast="flash"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    with force_tpu_interpret_mode():
        out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


def test_ring_attention_flash_grads_match_dense():
    """End-to-end train-step path: grads of a scalar loss through the
    sharded flash ring vs the dense reference.

    The grad is jitted, as every train step is.  Un-jitted, ``jax.grad``
    dispatches the backward's eager ops from the main thread while the
    forward's interpreter callbacks are still in flight on the virtual
    devices' threads, and the TPU interpreter (which itself dispatches
    jax ops from inside those callbacks) deadlocks on jax 0.9.0.  The
    same grads are checked uninterpreted on four real chips by
    ``chip_smoke.py``."""
    mesh = make_mesh({"sp": 4})
    T = 4 * TQ
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
               for _ in range(3))
    spec = P(None, "sp", None, None)
    ring = shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", axis_size=4,
                                       causal=True, fast="flash"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    with force_tpu_interpret_mode():
        gf = jax.tree_util.tree_map(np.asarray, jax.jit(jax.grad(
            lambda a, b, c: jnp.sum(ring(a, b, c) ** 2),
            argnums=(0, 1, 2)))(q, k, v))
    gr = jax.grad(
        lambda a, b, c: jnp.sum(dense_attention(a, b, c, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, np.asarray(b), rtol=2e-3, atol=2e-3,
                                   err_msg=f"grad wrt {name}")
