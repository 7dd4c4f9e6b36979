"""The reference's own checks: its recurrence against a hand-worked
3-position example; the scan and attention in blocks are the scan and
attention; the router's weights are what the equations say; and its
gradient against the system's at a tiny size in float32, both sides
reached through the family's files, as the harness reaches them."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import data, family

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "kimi_linear")
reference = FAMILY.reference
CONFIG = json.loads((ROOT / "benchmark/configs/"
                     "kimi-linear-48b-a3b-ep32-l5-1chip.json").read_text())
TINY = {**{k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])},
        **FAMILY.needs["rehearsal"]}


@pytest.fixture(scope="module")
def setup():
    init, grad_fn = FAMILY.system.build(dict(TINY, attn_impl="dense"),
                                        "float32")
    params = init(jax.random.PRNGKey(3))
    tokens = data.affine_chain(np.random.default_rng(0), 4, 48, 64, 0.85)
    return grad_fn, params, tokens


def test_the_recurrence_on_a_hand_worked_example():
    """One head, two key channels that decay by 1/2 and 1/4 a position,
    one value channel, beta 1.  t1: k = (1, 0), v = 2: S = [[2], [0]],
    o = 2.  t2: S' = [[1], [0]]; k = (0, 1) reads 0 of it, v = 4: S =
    [[1], [4]], o = q . S = 5.  t3: S' = [[1/2], [1]]; k = (1, 0) reads
    1/2, v = 0: u = -1/2 ERASES what that key held: S = [[0], [1]], o =
    1.  With beta 1/2 at t3 half of it stays: o = 1/4 + 1."""
    g = jnp.log(jnp.array([0.5, 0.25]))
    k = jnp.array([[1.0, 0.0], [0.0, 1.0], [1.0, 0.0]])
    v = jnp.array([[2.0], [4.0], [0.0]])
    q = jnp.ones((3, 2))
    shape = lambda x: x[None, :, None]              # noqa: E731
    run = lambda beta: np.asarray(reference.delta_scan(       # noqa: E731
        shape(q), shape(k), shape(v), jnp.broadcast_to(g, (1, 3, 1, 2)),
        jnp.asarray(beta)[None, :, None]))[0, :, 0, 0]
    np.testing.assert_allclose(run([1.0, 1.0, 1.0]), [2.0, 5.0, 1.0],
                               rtol=1e-6)
    np.testing.assert_allclose(run([1.0, 1.0, 0.5]), [2.0, 5.0, 1.25],
                               rtol=1e-6)
    # a single step, by the equations
    S, o = reference.delta_step(
        jnp.array([[[[1.0], [4.0]]]]), q[None, None, 0], k[None, None, 2],
        v[None, None, 2], g[None, None], jnp.ones((1, 1)))
    np.testing.assert_allclose(S[0, 0], [[0.0], [1.0]], atol=1e-7)
    assert float(o[0, 0, 0]) == pytest.approx(1.0)


def test_the_blocked_scan_and_attention_equal_the_unblocked(setup):
    _, params, _ = setup
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q, k, v = (jax.random.normal(key, (2, 37, 3, 4)) for key in ks[:3])
    g = -jax.random.uniform(ks[3], (2, 37, 3, 4))
    beta = jax.nn.sigmoid(q[..., 0])
    whole = reference.delta_scan(q, k, v, g, beta, block=37)
    grad = lambda block: jax.grad(lambda v: jnp.sum(           # noqa: E731
        reference.delta_scan(q, k, v, g, beta, block=block) ** 2))(v)
    for block in (5, 16):
        np.testing.assert_allclose(
            reference.delta_scan(q, k, v, g, beta, block=block), whole,
            rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(grad(block), grad(37), rtol=1e-4,
                                   atol=1e-5)
    layer = params["layers"][3]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 32))
    full = reference.latent_attention(layer, h, block=48)
    np.testing.assert_allclose(reference.latent_attention(layer, h, block=16),
                               full, rtol=1e-5, atol=1e-6)
    # causal, and no positions: the first token's output is its own v
    later = h.at[:, 20:].add(1.0)
    np.testing.assert_allclose(
        reference.latent_attention(layer, later, block=16)[:, :20],
        full[:, :20], rtol=1e-5, atol=1e-6)


def test_router_weights_are_the_top_eight_renormalised_and_scaled(setup):
    _, params, _ = setup
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(2), (10, 32))
    w = np.asarray(reference.router_weights(layer, x))
    s = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    sel = s + np.asarray(layer["expert_bias"])
    for t in range(10):
        top = np.argsort(-sel[t])[:8]
        assert set(np.flatnonzero(w[t])) == set(top)
        np.testing.assert_allclose(
            w[t, top], 2.446 * s[t, top] / (s[t, top].sum() + 1e-6),
            rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.446, rtol=1e-5)


def test_loss_and_gradients_match_the_system(setup):
    grad_fn, params, tokens = setup
    loss, _acc, grads, _seen = grad_fn(params, tokens, tokens)
    ref_loss, ref_grads = reference.grads(params, tokens)
    assert float(ref_loss) == pytest.approx(float(loss), abs=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=1e-6 + 3e-4 * float(jnp.max(jnp.abs(r))),
            err_msg=jax.tree_util.keystr(path))
    # the loss in blocks of tokens is the loss
    whole = reference.loss_fn(params, tokens, block=10 ** 6)
    assert float(reference.loss_fn(params, tokens, block=50)) == (
        pytest.approx(float(whole), abs=2e-6))


def test_adam_steps_lower_the_loss(setup):
    _, params, tokens = setup
    losses = reference.train(params, [tokens[:2], tokens[2:], tokens[:2]],
                             lr=0.01)
    assert len(losses) == 3 and losses[2] < losses[0]
    # in place: the caller's host arrays are not the buffers given back
    again = reference.train(params, [tokens[:2]], lr=0.01)
    assert again[0] == pytest.approx(losses[0], abs=1e-6)
