"""Quantized intra-slice gradient all-reduce (EQuARX-style; PAPERS.md).

TPU-native addition beyond the reference: int8 block-quantized
reduce-scatter + all-gather in place of the fp32 gradient all-reduce
over ICI.  Tests run on the virtual 8-device CPU mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.parallel import make_mesh
from geomx_tpu.parallel.quantized_allreduce import (
    BLOCK, make_party_step_quantized, quantized_psum_mean)
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _mesh():
    n = len(jax.devices())
    return make_mesh({"dp": n, "sp": 1, "tp": 1}), n


def test_quantized_mean_matches_exact_within_block_bound():
    mesh, n = _mesh()
    rng = np.random.default_rng(0)
    # deliberately non-block-aligned length to exercise padding
    per_dev = rng.standard_normal((n, 1000)).astype(np.float32)

    f = shard_map(
        lambda x: quantized_psum_mean(x[0], "dp", n)[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    out = np.asarray(jax.jit(f)(jnp.asarray(per_dev)))
    exact = per_dev.mean(axis=0)
    # every replica got the same reduced vector
    for d in range(n):
        np.testing.assert_array_equal(out[d], out[0])
    # error bound: each element quantized at most twice, each at
    # <= absmax/127 of its block (loose global bound via the overall max)
    bound = 2.0 * np.abs(per_dev).max() / 127.0
    assert np.max(np.abs(out[0] - exact)) <= bound
    # and it is genuinely close in aggregate (not just bounded)
    rel = np.linalg.norm(out[0] - exact) / np.linalg.norm(exact)
    assert rel < 0.02, rel


def test_quantized_step_trains_like_exact_dp():
    """End-to-end: the quantized party step's loss trajectory tracks
    the exact-DP step on the identical model/data — int8 gradient wire
    noise must not change convergence at demo scale."""
    import optax

    from geomx_tpu.parallel.dp import make_party_step

    mesh, n = _mesh()
    rng = np.random.default_rng(1)
    W = rng.standard_normal((16, 4)).astype(np.float32) * 0.1
    x_all = rng.standard_normal((8 * n, 16)).astype(np.float32)
    y_all = (x_all @ W).argmax(-1).astype(np.int32)

    def grad_fn(params, x, y):
        def loss_fn(p):
            logits = x @ p["w"] + p["b"]
            ls = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            acc = (logits.argmax(-1) == y).mean()
            return ls, acc

        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return loss, acc, g

    def train(step_fn, steps=25, lr=0.5):
        p = {"w": jnp.zeros((16, 4)), "b": jnp.zeros((4,))}
        losses = []
        for _ in range(steps):
            loss, _acc, g = step_fn(p, x_all, y_all)
            p = jax.tree_util.tree_map(
                lambda a, b: a - lr * b, p, g)
            losses.append(float(loss))
        return losses

    l_exact = train(make_party_step(grad_fn, mesh))
    l_quant = train(make_party_step_quantized(grad_fn, mesh))
    assert l_exact[-1] < 0.7 * l_exact[0]          # it learns
    assert l_quant[-1] < 0.7 * l_quant[0]          # quantized learns too
    # trajectories stay close (same data, same init, bounded wire noise)
    assert abs(l_quant[-1] - l_exact[-1]) < 0.15, (l_exact[-1],
                                                   l_quant[-1])


def test_quantized_step_wire_is_int8():
    """The compiled HLO must exchange int8 (u8/s8) payloads on the
    data leg — an fp32 all-to-all would silently deliver none of the
    bytes saving.  Also sanity-runs the full quantized step once."""
    import re

    from jax.sharding import NamedSharding

    mesh, n = _mesh()

    def grad_fn(params, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p["w"] - y[:, None]) ** 2)

        g = jax.grad(loss_fn)(params)
        return loss_fn(params), jnp.float32(0), g

    step = make_party_step_quantized(grad_fn, mesh)
    loss, _a, _g = step({"w": jnp.zeros((64, 1))},
                        jnp.zeros((2 * n, 64)), jnp.zeros((2 * n,)))
    assert np.isfinite(float(loss))

    # audit the reduce itself: lower the shard-mapped collective
    f = shard_map(
        lambda v: quantized_psum_mean(v[0], "dp", n)[None],
        mesh=mesh, in_specs=P("dp"), out_specs=P("dp"), check_vma=False)
    arr = jax.device_put(jnp.zeros((n, 1024), jnp.float32),
                         NamedSharding(mesh, P("dp")))
    txt = jax.jit(f).lower(arr).compile().as_text()
    a2a = [ln for ln in txt.splitlines()
           if re.search(r" all-to-all(?:-start)?\(", ln)]
    assert a2a, "no all-to-all in compiled quantized reduce"
    assert any(re.search(r"(s8|u8)\[", ln) for ln in a2a), a2a[:3]
