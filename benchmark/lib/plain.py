"""What every family's plain reference shares: the mean gradient over
every worker's batch and the optimizer's reference.

A family's ``reference.py`` gives the model's ``loss_fn(params, tokens)``
straight in ``jax.numpy``; from it :func:`summer` makes the summed loss
and gradient (float32, matmuls at ``jax.default_matmul_precision(
"highest")``, one sequence at a time, so that it fits beside nothing
else on a chip), :func:`mean_grads` their means, and :func:`adam_train`
K steps of standard Adam (Kingma & Ba 2015, bias-corrected, eps outside
the square root) on the mean gradient.  No kvstore, no codec, no
threads, nothing of ``geomx_tpu``.  The optimizer is the traffic mix's
(``trainer.optimizer``), not a model's, which is why it sits here.
Departure of the system from it: none found (``DeviceAdam`` is standard
Adam).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _with_highest(f):
    def g(*a):
        with jax.default_matmul_precision("highest"):
            return f(*a)
    return g


def summer(loss_fn):
    """``sums(params, tokens, device=None)`` -> (summed loss, summed
    gradient) of ``loss_fn`` over ``tokens`` [N, T], one sequence at a
    time."""

    @jax.jit
    @_with_highest
    def _accumulate(params, gsum, lsum, tokens):
        loss, g = jax.value_and_grad(loss_fn)(params, tokens)
        return jax.tree_util.tree_map(jnp.add, gsum, g), lsum + loss

    def sums(params, tokens, device=None):
        gsum = jax.tree_util.tree_map(jnp.zeros_like, params)
        lsum = jax.device_put(np.float32(0), device)
        for row in tokens:
            gsum, lsum = _accumulate(params, gsum, lsum,
                                     jax.device_put(row[None], device))
        return lsum, gsum

    return sums


def mean_grads(sums, params, tokens):
    """(mean loss, mean gradient) over ``tokens`` [N, T]; equal to the
    all-worker mean when every worker's batch has the same size."""
    lsum, gsum = sums(params, tokens)
    n = np.float32(len(tokens))
    return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)


@jax.jit
def _adam(params, m, v, gsum, n, t, lr):
    b1, b2, eps = 0.9, 0.999, 1e-8

    def leaf(w, m, v, g):
        g = g / n
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return w - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

    out = jax.tree_util.tree_map(leaf, params, m, v, gsum)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def adam_train(sums, params, batches, lr: float, device=None):
    """Run ``len(batches)`` steps of mean-gradient Adam from ``params``
    (host arrays); ``batches[k]`` is int32 [N, T], every worker's
    sequences of step k.  Returns the float loss BEFORE each update,
    which is what a worker's step reports."""
    params = jax.tree_util.tree_map(
        lambda a: jax.device_put(np.asarray(a, np.float32), device), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t, tokens in enumerate(batches, start=1):
        lsum, gsum = sums(params, tokens, device)
        n = np.float32(len(tokens))
        losses.append(float(lsum) / float(n))
        params, m, v = _adam(params, m, v, gsum, n, np.float32(t),
                             np.float32(lr))
    return losses
