"""The plain reference: the flagship GPT block straight in ``jax.numpy``.

Independent of ``geomx_tpu``: float32 throughout, matmuls at
``jax.default_matmul_precision("highest")``, full causal softmax
attention with no kernel, the mean gradient over every worker's batch
(one sequence at a time, so that it fits beside nothing else on a chip),
and standard Adam (Kingma & Ba 2015, bias-corrected, eps outside the
square root).  No kvstore, no codec, no threads.

It reads only the parameter tree the system was given as input
(``embed``, ``pos``, ``ln_f``, ``layers[i]`` with ``ln1 ln2 wq wk wv wo
w1 w2``).  The model it follows: learned positions, pre-norm RMSNorm
(eps 1e-6, learned scale), multi-head causal attention scaled by
1/sqrt(head_dim), tanh-approximated GELU MLP, final RMSNorm, head tied
to the embedding, next-token cross-entropy averaged over B x (T-1).
Departures of the system from this reference: it computes activations
and matmuls in bfloat16 (float32 parameters, norms and softmax);
``DeviceAdam`` is standard Adam, no departure found.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(params, tokens):
    """tokens int32 [B, T] -> logits float32 [B, T, vocab]."""
    B, T = tokens.shape
    x = params["embed"][tokens] + params["pos"][:T][None]
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    for layer in params["layers"]:
        h = _rms(x, layer["ln1"])
        q = jnp.einsum("btd,dhk->bhtk", h, layer["wq"])
        k = jnp.einsum("btd,dhk->bhtk", h, layer["wk"])
        v = jnp.einsum("btd,dhk->bhtk", h, layer["wv"])
        s = jnp.einsum("bhqk,bhsk->bhqs", q, k) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqs,bhsk->bhqk", p, v)
        x = x + jnp.einsum("bhtk,hkd->btd", a, layer["wo"])
        h = _rms(x, layer["ln2"])
        x = x + _gelu(h @ layer["w1"]) @ layer["w2"]
    return _rms(x, params["ln_f"]) @ params["embed"].T


def loss_fn(params, tokens):
    logp = jax.nn.log_softmax(forward(params, tokens)[:, :-1])
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def _with_highest(f):
    def g(*a):
        with jax.default_matmul_precision("highest"):
            return f(*a)
    return g


@jax.jit
@_with_highest
def _accumulate(params, gsum, lsum, tokens):
    loss, g = jax.value_and_grad(loss_fn)(params, tokens)
    return jax.tree_util.tree_map(jnp.add, gsum, g), lsum + loss


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


def _sums(params, tokens, device=None):
    """(summed loss, summed gradient) over ``tokens`` [N, T], one
    sequence at a time."""
    gsum = jax.tree_util.tree_map(jnp.zeros_like, params)
    lsum = jax.device_put(np.float32(0), device)
    for row in tokens:
        gsum, lsum = _accumulate(params, gsum, lsum,
                                 jax.device_put(row[None], device))
    return lsum, gsum


def grads(params, tokens):
    """(mean loss, mean gradient) over ``tokens`` [N, T]; equal to the
    all-worker mean when every worker's batch has the same size."""
    lsum, gsum = _sums(params, tokens)
    n = np.float32(len(tokens))
    return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)


def train(params, batches, lr: float, device=None):
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
        lsum, gsum = _sums(params, tokens, device)
        n = np.float32(len(tokens))
        losses.append(float(lsum) / float(n))
        params, m, v = _adam(params, m, v, gsum, n, np.float32(t),
                             np.float32(lr))
    return losses
