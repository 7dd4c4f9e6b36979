"""The plain reference: the flagship GPT block straight in ``jax.numpy``.

Independent of ``geomx_tpu``: float32 throughout, full causal softmax
attention with no kernel.  The mean gradient over every worker's batch
(matmuls at ``highest``, one sequence at a time) and the optimizer's
reference are ``benchmark/lib/plain.py``'s, shared by every family.

It reads only the parameter tree the system was given as input
(``embed``, ``pos``, ``ln_f``, ``layers[i]`` with ``ln1 ln2 wq wk wv wo
w1 w2``).  The model it follows: learned positions, pre-norm RMSNorm
(eps 1e-6, learned scale), multi-head causal attention scaled by
1/sqrt(head_dim), tanh-approximated GELU MLP, final RMSNorm, head tied
to the embedding, next-token cross-entropy averaged over B x (T-1).
Departures of the system from this reference: it computes activations
and matmuls in bfloat16 (float32 parameters, norms and softmax).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import plain


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


_sums = plain.summer(loss_fn)


def grads(params, tokens):
    """(mean loss, mean gradient) over ``tokens`` [N, T]."""
    return plain.mean_grads(_sums, params, tokens)


def train(params, batches, lr: float, device=None):
    """The float loss before each of ``len(batches)`` steps of
    mean-gradient Adam from ``params``; ``batches[k]`` is int32 [N, T]."""
    return plain.adam_train(_sums, params, batches, lr, device)
