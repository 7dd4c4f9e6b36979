"""The reference's own checks: attention in blocks is attention, a
window of the whole sequence is the causal mask, the router's weights
are what the equations say, and its gradient against the system's at a
tiny size in float32, both sides reached through the family's files, as
the harness reaches them."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import data, family

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "laguna")
reference = FAMILY.reference
CONFIG = json.loads((ROOT / "benchmark/configs/"
                     "laguna-s-2.1-ep32-tp4-l5-1chip.json").read_text())
TINY = {**{k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])},
        **FAMILY.needs["rehearsal"]}


@pytest.fixture(scope="module")
def setup():
    init, grad_fn = FAMILY.system.build(dict(TINY, attn_impl="dense",
                                             sliding_window=8), "float32")
    params = init(jax.random.PRNGKey(3))
    tokens = data.affine_chain(np.random.default_rng(0), 4, 32, 64, 0.85)
    return grad_fn, params, tokens


def test_attention_in_blocks_and_its_window(setup):
    _, params, _ = setup
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 32))
    for i, full in ((0, True), (1, False)):
        layer = params["layers"][i]
        whole = reference.attention(layer, h, full, window=8, block=32)
        np.testing.assert_allclose(
            reference.attention(layer, h, full, window=8, block=5), whole,
            rtol=1e-5, atol=1e-6)
        # causal: what comes later changes nothing before it
        later = h.at[:, 20:].add(1.0)
        np.testing.assert_allclose(
            reference.attention(layer, later, full, window=8)[:, :20],
            whole[:, :20], rtol=1e-5, atol=1e-6)
    layer = params["layers"][1]
    # a sliding layer: a key 8 back is out of the window ...
    early = h.at[:, :4].add(1.0)
    out = reference.attention(layer, early, False, window=8)
    base = reference.attention(layer, h, False, window=8)
    np.testing.assert_allclose(out[:, 12:], base[:, 12:], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(out[:, 4:11] - base[:, 4:11]))) > 1e-3
    # ... and a window past the sequence is the causal mask
    np.testing.assert_allclose(
        reference.attention(layer, h, False, window=32),
        reference.attention(layer, h, False, window=10 ** 6), rtol=1e-6)


def test_router_weights_are_the_top_ten_renormalised_and_scaled(setup):
    _, params, _ = setup
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(2), (10, 32))
    w = np.asarray(reference.router_weights(layer, x))
    r = np.asarray(jax.nn.softmax(x @ layer["router"], axis=-1))
    for t in range(10):
        top = np.argsort(-r[t])[:10]
        assert set(np.flatnonzero(w[t])) == set(top)
        np.testing.assert_allclose(w[t, top], 2.5 * r[t, top]
                                   / r[t, top].sum(), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)


def test_loss_and_gradients_match_the_system(setup):
    grad_fn, params, tokens = setup
    loss, _acc, grads, _seen = grad_fn(params, tokens, tokens)
    ref_loss, ref_grads = reference.grads(params, tokens, window=8)
    assert float(ref_loss) == pytest.approx(float(loss), abs=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=1e-6 + 3e-4 * float(jnp.max(jnp.abs(r))),
            err_msg=jax.tree_util.keystr(path))
    # the loss in blocks of tokens is the loss
    whole = reference.loss_fn(params, tokens, 8, block=10 ** 6)
    assert float(reference.loss_fn(params, tokens, 8, block=50)) == (
        pytest.approx(float(whole), abs=2e-6))


def test_adam_steps_lower_the_loss(setup):
    _, params, tokens = setup
    losses = reference.train(params, [tokens[:2], tokens[2:], tokens[:2]],
                             lr=0.01)
    assert len(losses) == 3 and losses[2] < losses[0]
    # in place: the caller's host arrays are not the buffers given back
    again = reference.train(params, [tokens[:2]], lr=0.01)
    assert again[0] == pytest.approx(losses[0], abs=1e-6)
