"""Flash-attention correctness without the chip.

``attn_impl="flash"`` (models/transformer.py::_single_device_attention)
lowers only for a TPU; these tests run the very same code under pallas
**TPU interpret mode** on CPU, so a broken kernel or a wrong layout swap
is caught before a chip run.  ``chip_smoke.py`` compiles the kernel
uninterpreted at the flagship geometry.  Tolerances: the interpret-mode
kernel computes in fp32, so fwd is compared tightly; bwd goes through
the kernel's custom VJP (the path the train step uses).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from geomx_tpu.models.transformer import (
    TransformerConfig, _flash_block_sizes, _single_device_attention,
)
from geomx_tpu.parallel.ring_attention import dense_attention

# [B, T, H, Dh] — the transformer's layout; Dh=128 matches MFU_CFG's
# head_dim and the kernel's native lane width
B, T, H, D = 1, 256, 2, 128


def _qkv(dtype=jnp.float32, seed=0, shape=(B, T, H, D)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


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


# (field, the field it must divide): the rules of jax's BlockSizes
_MINOR_OF = (("block_k", "block_k_major"),
             ("block_q_dkv", "block_q_major_dkv"),
             ("block_k_dkv", "block_k_major_dkv"),
             ("block_k_dq", "block_k_major_dq"))


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("seq_len", [64, 128, 256, 384, 1024, 2048, 4096])
def test_flash_block_sizes_tile_the_shape_they_are_given(seq_len, head_dim):
    """The chooser reads shapes only: every size divides the sequence,
    every minor size its major, all eleven fields are set (the backward
    needs them), and a sequence no tile divides gets jax's default."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    bs = _flash_block_sizes(seq_len, head_dim)
    if seq_len % 128:
        assert bs is None
        return
    fields = dataclasses.asdict(bs)
    assert len(fields) == 11 and bs.has_backward_blocks
    assert BlockSizes(**fields) == bs   # the class's own rules hold
    assert fields.pop("block_b") == 1
    for name, size in fields.items():
        assert 128 <= size <= seq_len and seq_len % size == 0, (name, size)
    for minor, major in _MINOR_OF:
        assert fields[major] % fields[minor] == 0, (minor, major)
    if seq_len >= 1024:
        # what the change is for: a sixteenth of the grid steps or fewer
        # at the flagship's length
        assert fields["block_q"] * fields["block_k_major"] >= 16 * 128 * 128


def test_flash_block_sizes_shrink_for_a_head_wider_than_512():
    """A tile's rows are as wide as the head in VMEM: the v5e's compiler
    refuses the tops at a head width of 768 and takes them halved
    (``tests/test_tpu_compile.py`` compiles that case)."""
    narrow = dataclasses.asdict(_flash_block_sizes(2048, 128))
    assert dataclasses.asdict(_flash_block_sizes(2048, 512)) == narrow
    wide = dataclasses.asdict(_flash_block_sizes(2048, 1024))
    assert wide.pop("block_b") == narrow.pop("block_b") == 1
    assert wide == {k: v // 2 for k, v in narrow.items()}
    floor = dataclasses.asdict(_flash_block_sizes(2048, 8192))
    assert floor.pop("block_b") == 1 and set(floor.values()) == {128}


def test_flash_across_blocks_larger_than_128_matches_dense_interpret():
    """At the flagship's length every kernel gets tiles above jax's 128
    and still several q and k blocks of them, so the kernels' causal
    skip of whole blocks and the mask inside the diagonal ones are
    checked, forward and through the custom VJP, before a chip run."""
    cfg = TransformerConfig(attn_impl="flash")
    t = 2048
    bs = _flash_block_sizes(t, D)
    for size in (bs.block_q, bs.block_k_major, bs.block_q_major_dkv,
                 bs.block_k_major_dkv, bs.block_q_dq, bs.block_k_major_dq):
        assert 128 < size < t
    q, k, v = _qkv(seed=3, shape=(1, t, 1, D))

    def both(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v) ** 2)
        return jax.jit(lambda q, k, v: (
            attn(q, k, v), jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    with force_tpu_interpret_mode():
        o, g = both(lambda q, k, v: _single_device_attention(cfg, q, k, v))(
            q, k, v)
        o, g = np.asarray(o), [np.asarray(x) for x in g]
    ro, rg = both(lambda q, k, v: dense_attention(q, k, v, causal=True))(
        q, k, v)
    np.testing.assert_allclose(o, np.asarray(ro), rtol=1e-4, atol=1e-4)
    for a, b, name in zip(g, rg, "qkv"):
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-3, atol=1e-3,
                                   err_msg=f"grad wrt {name}")
