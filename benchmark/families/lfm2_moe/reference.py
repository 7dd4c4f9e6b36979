"""The plain reference: LFM2-MoE's block straight in ``jax.numpy``.

Independent of ``geomx_tpu``: float32 throughout, matmuls at ``highest``
(``benchmark/lib/plain.py``, which also holds the mean gradient over
every worker's batch and the optimizer's reference).  It reads only the
parameter tree the system was given as input and takes every size from
the leaves' shapes; what no leaf can say is a constant of the family
below.  Source of the equations: ``model_type`` ``lfm2_moe``
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

All linear maps are without bias.  ``RMS(x; g) = x * rsqrt(mean(x^2) +
1e-5) * g``.

* Block ``l``: ``h = x + Op_l(RMS(x; ln1))``, ``y = h + FFN_l(RMS(h;
  ln2))``; after the last block ``RMS(.; ln_f)`` (``embedding_norm``),
  logits ``= . @ embed^T``, next-token cross-entropy over B x (T - 1).
* ``Op`` = ``conv`` (a layer with ``w_in``): ``[b, c, z] = split(w_in
  x, 3)``, ``u = b * z``, ``y_t = sum_j conv[:, j] * u_{t-(K-1)+j}``
  (depthwise, causal, K taps, zeros left of the sequence, no bias),
  ``Op(x) = w_out (c * y)``.
* ``Op`` = ``full_attention`` (a layer with ``wq``): q as H heads, k
  and v as Hkv heads of Dh channels; q and k each through an RMSNorm
  over the head's channels with a learned scale; rotary positions over
  the whole head (theta 1e6, rotate-half pairing); causal ``softmax(q
  k^T / sqrt(Dh)) v`` with each k/v head serving H / Hkv consecutive q
  heads; ``wo`` over the concatenated heads.
* ``FFN`` of a layer with ``w1``: ``w2 (silu(w1 x) * w3 x)``.
* ``FFN`` of a layer with ``router``: ``s = sigmoid(router x)`` over ALL
  the deployment's experts; ``I`` the 4 experts of largest ``s +
  expert_bias``; ``w_i = s_i / (sum_{j in I} s_j + 1e-6)``, times the
  routed scaling factor 1; the layer returns ``sum_{i in I, i held}
  w_i E_i(x)`` with ``E_i`` the gated three-matrix expert ``i``: THE
  CHIP'S SHARE.  The experts held are ``first .. first + E - 1`` (E the
  stacks' leading dimension), a token none of whose four is held gets
  zero, and that partial sum goes on to the next layer.  No shared
  expert, no auxiliary loss.

Departures, each deliberate:

* of this reference from the publication: the head is tied to the
  embedding (the family's convention; not a key of the published
  config); the expert bias is a seeded constant (no gradient reaches
  it; the config gives no rule that would move it); the absent experts'
  part of every layer is left out, as it is in the system.
* of the system from this reference: activations and matmuls in
  bfloat16 (float32 parameters, norms, softmax, router scores, rotary
  angles and convolution taps); attention by jax's flash kernels with k
  and v repeated to H heads; the held experts' products as grouped
  products over sorted rows where this file loops over the experts with
  every token masked by its weight; the top 4 by ``lax.top_k`` where
  this file thresholds at the fourth largest.

Attention runs a block of queries at a time, each block recomputed in
the backward pass, and every layer is recomputed there too
(``jax.checkpoint``: the same arithmetic twice, not other arithmetic):
at 8,192 tokens the float32 scores of 32 heads would be 8.6 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import plain

NORM_EPS = 1e-5
ROPE_THETA = 1e6
EXPERTS_PER_TOKEN = 4
ROUTED_SCALE = 1.0
FIRST_EXPERT = 0          # the experts this chip holds start here
QUERY_BLOCK = 512


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def short_conv(layer, h):
    """h [B, T, D] -> [B, T, D]."""
    T, K = h.shape[1], layer["conv"].shape[1]
    b, c, z = jnp.split(h @ layer["w_in"], 3, axis=-1)
    u = jnp.pad(b * z, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(u[:, j:j + T] * layer["conv"][:, j] for j in range(K))
    return (c * y) @ layer["w_out"]


def _rope(x):
    """x [B, H, T, Dh]: channel i turns with channel i + Dh/2."""
    T, half = x.shape[2], x.shape[3] // 2
    freq = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, q0: int):
    """Queries ``q0 ..`` of a block against keys ``0 .. q0 + block``."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    qi = q0 + jnp.arange(q.shape[2])[:, None]
    s = jnp.where(qi >= jnp.arange(k.shape[2])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def attention(layer, h, block: int = QUERY_BLOCK):
    """h [B, T, D] -> [B, T, D]; ``block`` queries at a time (any block
    gives the same result: ``tests/``)."""
    T = h.shape[1]
    q = jnp.einsum("btd,dhk->bhtk", h, layer["wq"])
    k = jnp.einsum("btd,dhk->bhtk", h, layer["wk"])
    v = jnp.einsum("btd,dhk->bhtk", h, layer["wv"])
    q, k = _rope(_rms(q, layer["q_norm"])), _rope(_rms(k, layer["k_norm"]))
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = [jax.checkpoint(_attend, static_argnums=3)(
               q[:, :, a:a + block], k[:, :, :a + block],
               v[:, :, :a + block], a)
           for a in range(0, T, block)]
    return jnp.einsum("bhtk,hkd->btd", jnp.concatenate(out, axis=2),
                      layer["wo"])


def gated_ffn(w1, w3, w2, x):
    return (_silu(x @ w1) * (x @ w3)) @ w2


def router_weights(layer, x):
    """x [N, D] -> [N, E_all]: each token's weight on every expert of
    the deployment, zero off its chosen four."""
    s = jax.nn.sigmoid(x @ layer["router"])
    sel = s + layer["expert_bias"]
    fourth = jnp.sort(sel, axis=-1)[:, -EXPERTS_PER_TOKEN]
    chosen = jnp.where(sel >= fourth[:, None], s, 0.0)
    return ROUTED_SCALE * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)


def expert_share(layer, h, first: int = FIRST_EXPERT):
    """The part of the routed layer that the experts held give: experts
    ``first .. first + E - 1`` of the router's, one at a time over every
    token, each token masked by its weight."""
    x = h.reshape(-1, h.shape[-1])
    w = router_weights(layer, x)
    e = layer["experts"]
    y = jnp.zeros_like(x)
    for i in range(e["w1"].shape[0]):
        y = y + w[:, first + i, None] * gated_ffn(
            e["w1"][i], e["w3"][i], e["w2"][i], x)
    return y.reshape(h.shape)


def block(layer, x, first: int = FIRST_EXPERT):
    h = _rms(x, layer["ln1"])
    x = x + (short_conv(layer, h) if "w_in" in layer else attention(layer, h))
    h = _rms(x, layer["ln2"])
    if "router" in layer:
        return x + expert_share(layer, h, first)
    return x + gated_ffn(layer["w1"], layer["w3"], layer["w2"], h)


def forward(params, tokens, first: int = FIRST_EXPERT):
    """tokens int32 [B, T] -> logits float32 [B, T, vocab]."""
    x = params["embed"][tokens]
    for layer in params["layers"]:
        x = jax.checkpoint(block, static_argnums=2)(layer, x, first)
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
