"""The reference's own checks: attention in blocks of queries is
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
FAMILY = family.load(ROOT, ["benchmark"], "lfm2_moe")
reference = FAMILY.reference
CONFIG = json.loads(
    (ROOT / "benchmark/configs/lfm2-24b-a2b-ep8-l5-1chip.json").read_text())
TINY = {**{k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])},
        **FAMILY.needs["rehearsal"]}


@pytest.fixture(scope="module")
def setup():
    init, grad_fn = FAMILY.system.build(dict(TINY, attn_impl="dense"),
                                        "float32")
    params = init(jax.random.PRNGKey(3))
    tokens = data.affine_chain(np.random.default_rng(0), 4, 32, 64, 0.85)
    return grad_fn, params, tokens


def test_blocked_attention_equals_unblocked(setup):
    _, params, _ = setup
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    whole = reference.attention(layer, h, block=32)
    for block in (8, 16):
        np.testing.assert_allclose(reference.attention(layer, h, block=block),
                                   whole, rtol=1e-5, atol=1e-6)
    # and so are its gradients, each block recomputed in the backward pass
    g = lambda block: jax.grad(lambda h: jnp.sum(          # noqa: E731
        reference.attention(layer, h, block=block) ** 2))(h)
    np.testing.assert_allclose(g(8), g(32), rtol=1e-4, atol=1e-5)
    # causal: a later token moves no earlier output
    later = h.at[:, 20:].add(1.0)
    np.testing.assert_allclose(
        reference.attention(layer, later, block=8)[:, :20], whole[:, :20],
        rtol=1e-5, atol=1e-6)


def test_router_weights_are_the_top_four_renormalised(setup):
    _, params, _ = setup
    layer = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(2), (10, 32))
    w = np.asarray(reference.router_weights(layer, x))
    s = np.asarray(jax.nn.sigmoid(x @ layer["router"]))
    sel = s + np.asarray(layer["expert_bias"])
    for t in range(10):
        top = np.argsort(-sel[t])[:4]
        assert set(np.flatnonzero(w[t])) == set(top)
        np.testing.assert_allclose(w[t, top], s[t, top] / (s[t, top].sum()
                                                           + 1e-6), rtol=1e-6)
    # the bias selects: leaving it out chooses other experts somewhere
    bare = np.asarray(reference.router_weights(
        {**layer, "expert_bias": jnp.zeros(16).at[3].set(1.0)}, x))
    assert np.all(bare[:, 3] > 0) and not np.all(w[:, 3] > 0)


def test_loss_and_gradients_match_the_system(setup):
    grad_fn, params, tokens = setup
    loss, _acc, grads, _route = grad_fn(params, tokens, tokens)
    ref_loss, ref_grads = reference.grads(params, tokens)
    assert float(ref_loss) == pytest.approx(float(loss), abs=2e-6)
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, rtol=2e-4, atol=1e-6 + 1e-4 * float(jnp.max(jnp.abs(r))),
            err_msg=jax.tree_util.keystr(path))


def test_adam_steps_lower_the_loss(setup):
    _, params, tokens = setup
    losses = reference.train(params, [tokens[:2], tokens[2:], tokens[:2]],
                             lr=0.01)
    assert len(losses) == 3 and losses[2] < losses[0]
