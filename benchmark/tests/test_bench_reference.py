"""The plain reference against the system's own gradient at a tiny size
in float32, and its Adam against a hand-written step."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import data, reference

TINY = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32)


@pytest.fixture(scope="module")
def setup():
    from geomx_tpu.models.transformer import (TransformerConfig, init_params,
                                              make_lm_grad_fn)

    # float32 and the all-float32 attention: the system's arithmetic with
    # its one departure (bf16 compute) taken out
    mcfg = TransformerConfig(**TINY, attn_impl="dense",
                             compute_dtype=jnp.float32)
    params = init_params(mcfg, jax.random.PRNGKey(3))
    tokens = data.affine_chain(np.random.default_rng(0), 6, 32, 64, 0.85)
    return make_lm_grad_fn(mcfg), params, tokens


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


def test_the_generator_is_seeded_and_learnable():
    spec = {"order": 0.85, "pool_steps": 3}
    a = data.batch_pool(spec, 7, workers=2, batch=4, seq=32, vocab=64)
    b = data.batch_pool(spec, 7, workers=2, batch=4, seq=32, vocab=64)
    c = data.batch_pool(spec, 8, workers=2, batch=4, seq=32, vocab=64)
    assert a.shape == (3, 2, 4, 32) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0 <= a.min() and a.max() < 64
    follows = a[..., 1:] == (5 * a[..., :-1] + 17) % 64
    assert 0.8 < follows.mean() < 0.92      # order 0.85 + chance hits


MATCH = {"mode": "match_reference", "loss_tol": 0.002,
         "require_falling": True}
# the committed MPQ rule, so that what it lets through is pinned here
BAND = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                   / "mpq.json").read_text())["correct"]
REF = [9.4166, 9.4124, 9.2845]


@pytest.mark.parametrize("rule, losses, wrong, bad", [
    (MATCH, [9.4166, 9.4123, 9.2846, 9.05], 0, set()),
    (MATCH, [9.4166, 9.4123, 9.2900, 9.05], 1, {2}),      # step 2 is off
    (MATCH, [9.4166, 9.4123, 9.2846, 9.45], 1, set()),    # did not fall
    (MATCH, [float("nan"), 9.4123, 9.2846, 9.05], 2, {0}),
    (BAND, [9.4166, 9.3571, 9.3500, 9.25], 0, set()),     # seen on the chip
    (BAND, [9.4300, 9.3571, 9.3500, 9.25], 1, {0}),       # forward pass off
    (BAND, [9.4166, 9.3571, 9.2500, 9.25], 1, {2}),       # below the band
    (BAND, [9.4166, 9.3571, 9.4500, 9.25], 1, {2}),       # worse than none
    # updates dropped, staled or zeroed: the loss stays where it began
    # (the band's upper end is 9.4166 - 0.2 * 0.1321 + 0.002 = 9.3922),
    # or wanders there for the whole run
    (BAND, [9.4166, 9.4160, 9.4150, 9.25], 1, {2}),
    (BAND, [9.4166, 9.4100, 9.3930, 9.25], 1, {2}),
    (BAND, [9.4166, 9.3571, 9.3500, 9.42], 1, set()),     # did not fall
])
def test_compare_losses(rule, losses, wrong, bad):
    from benchmark.lib.harness import compare_losses

    failures, steps = compare_losses(losses, REF, rule, warmup=3)
    assert len(failures) == wrong and steps == bad, failures
