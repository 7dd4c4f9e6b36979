"""The flagship's plain reference against the system's own gradient at a
tiny size in float32, and its Adam against a hand-written step; both
sides reached through the family's files, as the harness reaches them."""

from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark.lib import data, family

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "flagship")
reference = FAMILY.reference
TINY = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32)


@pytest.fixture(scope="module")
def setup():
    # float32 and the all-float32 attention: the system's arithmetic with
    # its one departure (bf16 compute) taken out
    init, grad_fn = FAMILY.system.build(dict(TINY, attn_impl="dense"),
                                        "float32")
    params = init(jax.random.PRNGKey(3))
    tokens = data.affine_chain(np.random.default_rng(0), 6, 32, 64, 0.85)
    return grad_fn, params, tokens


def test_loss_and_gradients_match_make_lm_grad_fn(setup):
    grad_fn, params, tokens = setup
    loss, _acc, grads = grad_fn(params, tokens, tokens)
    ref_loss, ref_grads = reference.grads(params, tokens)
    # float32 on both sides; only the order of the sums differs
    assert float(ref_loss) == pytest.approx(float(loss), abs=2e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat) == 3 + 8 * TINY["n_layers"]
    for (path, g), r in zip(flat, ref_flat):
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(g), rtol=2e-4, atol=2e-7,
            err_msg=jax.tree_util.keystr(path))


def test_train_is_mean_gradient_adam(setup):
    grad_fn, params, tokens = setup
    lr = 3e-3
    batches = [tokens[:4], tokens[2:6]]
    got = reference.train(params, batches, lr)
    # by hand: two workers' halves averaged, then Adam's first step
    p = jax.tree_util.tree_map(np.asarray, params)
    halves = [grad_fn(p, b, b) for b in (batches[0][:2], batches[0][2:])]
    assert got[0] == pytest.approx(
        np.mean([float(h[0]) for h in halves]), abs=2e-6)
    g = jax.tree_util.tree_map(lambda a, b: (np.asarray(a) + np.asarray(b))
                               / 2, halves[0][2], halves[1][2])

    def adam1(w, g):
        m, v = 0.1 * g, 0.001 * g * g
        return w - lr * (m / 0.1) / (np.sqrt(v / 0.001) + 1e-8)

    p1 = jax.tree_util.tree_map(adam1, p, g)
    want = float(grad_fn(p1, batches[1], batches[1])[0])
    assert got[1] == pytest.approx(want, abs=2e-5)
    assert got[1] != pytest.approx(got[0], abs=1e-4)   # it did move


def test_the_rehearsal_sizes_are_the_tiny_ones():
    assert FAMILY.needs["rehearsal"] == dict(TINY, attn_impl="fast")
    assert set(FAMILY.needs["keys"]) == {"d_model", "n_heads", "n_layers",
                                         "d_ff", "attn_impl"}
    # depth is the one key of the family that a configuration may cut
    assert set(FAMILY.needs["keys"]) - set(FAMILY.needs["widths"]) == {
        "n_layers", "attn_impl"}
