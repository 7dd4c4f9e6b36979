"""The plain reference: Laguna's blocks straight in ``jax.numpy``.

Independent of ``geomx_tpu``: float32 throughout, matmuls at ``highest``
(``jax.default_matmul_precision``).  It reads only the parameter tree the
system was given as input and takes every size from the leaves' shapes;
what no leaf can say is a constant of the family below.  Source:
``model_type`` ``laguna`` (https://huggingface.co/poolside/Laguna-S-2.1/
blob/main/config.json: ``layer_types``, ``rope_parameters``,
``sliding_window``, ``gating``), YaRN as HF transformers'
``_compute_yarn_parameters`` defines it, and the per-head output gate of
"Gated Attention for Large Language Models" (Qiu et al. 2025, arXiv
2505.06708, its headwise variant).

All linear maps are without bias.  ``RMS(x; w) = x * rsqrt(mean(x^2) +
1e-6) * w``.

* Block ``l``: ``h = x + Attn_l(RMS(x; ln1))``, ``y = h + FFN_l(RMS(h;
  ln2))``; after the last block ``RMS(.; ln_f)``, logits ``= . @
  head^T`` (untied), next-token cross-entropy over B x (T - 1).
* ``Attn``, on ``x`` [T, D] (H q heads and G kv heads of d, H / G
  consecutive q heads a kv head): ``q = x wq``, ``k = x wk``, ``v = x
  wv`` a head, rotated; ``o_th = sum_s softmax_s(q_th . k_{s,g(h)} /
  sqrt(d)) v_{s,g(h)}`` over the keys ``s`` the layer lets query ``t``
  see; ``o_th <- sigmoid(x wg)_th o_th``; then ``wo``.  Published layer
  ``i`` is full attention where ``i % 4 == 0`` (``layer_types``), and
  sliding otherwise:

  - full: keys ``s <= t``; the first ``d / 2`` channels of q and k
    turned in rotate-half pairs (channel ``i`` with ``i + d / 4``) by
    ``t w_i`` at YaRN's frequencies (:func:`yarn_inv_freq`), cos and
    sin times ``ATTENTION_FACTOR``, the other ``d / 2`` passed through;
  - sliding: keys ``t - WINDOW < s <= t``; the whole head turned
    (channel ``i`` with ``i + d / 2``) by ``t 1e4^(-2i / d)``.
* ``FFN`` of a layer with ``w1``: ``w2 (silu(w1 x) * w3 x)``.
* ``FFN`` of a layer with ``router``: ``r = softmax(x router)`` over ALL
  the deployment's experts, ``E`` its top 10, ``g_e = 2.5 r_e / sum_E
  r``; the layer returns ``sum_{e in E, e held} g_e E_e(x) +
  E_shared(x)``: THE CHIP'S SHARE of the routed part and the shared
  expert whole.  The experts held are ``first .. first + n - 1`` (n the
  stacks' leading dimension).  The heads held are the leaves' own: a
  share of the heads is its part of ``wo``'s sum.

Departures, each deliberate:

* of this reference from the publication: the absent experts' part of
  every layer is left out, as it is in the system, and with it their
  part of the router's gradient: the routing weights ``g_e`` are
  constants of the backward pass (the router is not trained from one
  share); so are the absent heads' parts of every attention layer.
* of the system from this reference: activations and matmuls in
  bfloat16 (float32 parameters, norms, softmax, rotary tables, gates,
  router scores); attention by jax's flash kernels (full layers, k and
  v repeated to the q heads) and by ``geomx_tpu/ops/window_attention.py``
  (sliding layers: only the 512 x 512 tiles the window meets) where
  this file masks every key; the held experts' products as grouped
  products over sorted rows where this file loops over the experts with
  every token masked by its weight; the top 10 by ``lax.top_k`` where
  this file thresholds at the tenth largest.

Blocking, which is the same arithmetic and not another algorithm:
attention runs a block of queries at a time against every key
(``lax.scan`` of one checkpointed body); the dense FFNs run a block of
rows at a time, the held experts one after the other (``lax.scan`` over
the stacks); every layer is recomputed in the backward pass; the loss is
summed over blocks of tokens.  Adam's m and v wait on the host between
steps, as in ``kimi_linear/reference.py``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
EXPERTS_PER_TOKEN = 10
ROUTED_SCALE = 2.5
FIRST_LAYER = 0           # published layer of the first layer run
FIRST_EXPERT = 0          # the experts this chip holds start here
PERIOD = 4                # layer_types: full attention every 4th
WINDOW = 512              # sliding_window
WINDOW_THETA = 1e4
FULL_THETA = 5e5
ROTARY_SHARE = 0.5        # partial_rotary_factor of the full layers
YARN_FACTOR = 128.0
YARN_ORIGINAL = 8192
BETA_FAST, BETA_SLOW = 32.0, 1.0
ATTENTION_FACTOR = 1.4852030263919618
QUERY_BLOCK = 256
ROW_BLOCK = 2048
LOSS_BLOCK = 2048


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) * scale


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def yarn_inv_freq(dim: int) -> np.ndarray:
    """YaRN's ``dim / 2`` frequencies (float64): the ramp runs over the
    pair index from the correction dim of ``BETA_FAST`` rotations
    (floored) to that of ``BETA_SLOW`` (ceiled), ``corr(r) = dim
    ln(L / (2 pi r)) / (2 ln theta)``; below it the pair keeps its own
    frequency, above it takes it divided by ``YARN_FACTOR``."""
    corr = [dim * math.log(YARN_ORIGINAL / (2 * math.pi * r))
            / (2 * math.log(FULL_THETA)) for r in (BETA_FAST, BETA_SLOW)]
    low, high = max(math.floor(corr[0]), 0), min(math.ceil(corr[1]), dim - 1)
    own = FULL_THETA ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return own * (1 - ramp) + own / YARN_FACTOR * ramp


def rotate(x, inv_freq, scale: float = 1.0):
    """x [B, T, heads, d]: its first ``2 len(inv_freq)`` channels turned
    by position in rotate-half pairs, cos and sin times ``scale``; the
    rest as they are."""
    half = len(inv_freq)
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32))
    cos, sin = (scale * jnp.cos(ang)[:, None], scale * jnp.sin(ang)[:, None])
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], -1)


def is_full(i: int) -> bool:
    """Whether layer ``i`` of those run is a full attention layer."""
    return (FIRST_LAYER + i) % PERIOD == 0


def _attend(q, k, v, q0, window):
    """Queries ``q0 ..`` of a block [B, H, Tq, d] against every key,
    masked to those each may see."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    t = q0 + jnp.arange(q.shape[2])[:, None]
    key = jnp.arange(k.shape[2])[None, :]
    s = jnp.where((key <= t) & (key > t - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def attention(layer, h, full: bool, window: int = WINDOW,
              block: int = QUERY_BLOCK):
    """h [B, T, D] -> [B, T, D]: full or sliding attention of the heads
    the leaves hold; ``block`` queries at a time, each block under a
    checkpoint (any block gives the same result: ``tests/``)."""
    B, T, _ = h.shape
    H, G, d = (layer["wq"].shape[1], layer["wk"].shape[1],
               layer["wq"].shape[2])
    q, k, v = (jnp.einsum("btd,dhk->bthk", h, layer[n])
               for n in ("wq", "wk", "wv"))
    if full:
        freq = yarn_inv_freq(int(d * ROTARY_SHARE))
        q, k = (rotate(x, freq, ATTENTION_FACTOR) for x in (q, k))
        window = T
    else:
        freq = WINDOW_THETA ** (-np.arange(0, d, 2) / d)
        q, k = rotate(q, freq), rotate(k, freq)
    k, v = (jnp.repeat(x, H // G, axis=2).transpose(0, 2, 1, 3)
            for x in (k, v))
    q = q.transpose(0, 2, 1, 3)
    block = min(block, T)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, -T % block), (0, 0)))
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, block, d)), 2, 0)

    def one(q0, q_block):
        return q0 + block, _attend(q_block, k, v, q0, window)

    _, o = jax.lax.scan(jax.checkpoint(one), jnp.int32(0), blocks)
    o = jnp.moveaxis(o, 0, 2).reshape(q.shape)[:, :, :T].transpose(0, 2, 1, 3)
    o = o * _sigmoid(h @ layer["wg"])[..., None]
    return jnp.einsum("bthk,hkd->btd", o, layer["wo"])


def gated_ffn(w1, w3, w2, x):
    return (_silu(x @ w1) * (x @ w3)) @ w2


def in_row_blocks(fn, x, block: int = ROW_BLOCK):
    """``fn`` over the rows of ``x`` [N, D], which it treats alike,
    ``block`` rows at a time under a checkpoint (whole where ``block``
    does not divide N)."""
    n = x.shape[0]
    if n <= block or n % block:
        return fn(x)
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(n // block, block, -1))
    return out.reshape(n, -1)


def router_weights(layer, x):
    """x [N, D] -> [N, E_all]: each token's weight on every expert of
    the deployment, zero off its chosen ten."""
    r = jax.nn.softmax(x @ layer["router"], axis=-1)
    tenth = jnp.sort(r, axis=-1)[:, -EXPERTS_PER_TOKEN]
    top = jnp.where(r >= tenth[:, None], r, 0.0)
    return ROUTED_SCALE * top / jnp.sum(top, axis=-1, keepdims=True)


def expert_share(layer, h, first: int = FIRST_EXPERT):
    """The part of the routed layer that the experts held give: experts
    ``first .. first + n - 1`` of the router's, one at a time over every
    token, each token masked by its weight.  Without the shared
    expert."""
    x = h.reshape(-1, h.shape[-1])
    e = layer["experts"]
    held = jax.lax.stop_gradient(
        router_weights(layer, x))[:, first:first + e["w1"].shape[0]]

    def one(y, expert):
        w1, w3, w2, weight = expert
        return y + weight[:, None] * gated_ffn(w1, w3, w2, x), None

    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x),
                        (e["w1"], e["w3"], e["w2"], held.T))
    return y.reshape(h.shape)


def dense_ffn(group, h):
    return in_row_blocks(
        lambda x: gated_ffn(group["w1"], group["w3"], group["w2"], x),
        h.reshape(-1, h.shape[-1])).reshape(h.shape)


def block(layer, x, i: int, first: int = FIRST_EXPERT,
          window: int = WINDOW):
    x = x + attention(layer, _rms(x, layer["ln1"]), is_full(i), window)
    h = _rms(x, layer["ln2"])
    if "router" in layer:
        return (x + expert_share(layer, h, first)
                + dense_ffn(layer["shared"], h))
    return x + dense_ffn(layer, h)


def hidden(params, tokens, window: int = WINDOW):
    """tokens int32 [B, T] -> the final normed stream [B, T, D]."""
    x = params["embed"][tokens]
    for i, layer in enumerate(params["layers"]):
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(
            layer, x, i, FIRST_EXPERT, window)
    return _rms(x, params["ln_f"])


def _nll_sum(head, x, targets):
    logp = jax.nn.log_softmax(x @ head.T)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))


def loss_fn(params, tokens, window: int = WINDOW, block: int = LOSS_BLOCK):
    x = hidden(params, tokens, window)[:, :-1]
    x = x.reshape(-1, x.shape[-1])
    y = tokens[:, 1:].reshape(-1)
    total = sum(jax.checkpoint(_nll_sum)(params["head"], x[a:a + block],
                                         y[a:a + block])
                for a in range(0, x.shape[0], block))
    return total / x.shape[0]


def _with_highest(f):
    @functools.wraps(f)
    def g(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return f(*a, **kw)
    return g


@functools.partial(jax.jit, static_argnums=(2,))
@_with_highest
def _value_and_grad(params, tokens, window):
    return jax.value_and_grad(loss_fn)(params, tokens, window)


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1))
@_with_highest
def _add(lsum, gsum, params, tokens, window):
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, window)
    return lsum + loss, jax.tree_util.tree_map(jnp.add, gsum, g)


def _sums(params, tokens, device=None, window: int = WINDOW):
    """(summed loss, summed gradient) over ``tokens`` [N, T], a sequence
    at a time."""
    rows = [jax.device_put(row[None], device) for row in np.asarray(tokens)]
    lsum, gsum = _value_and_grad(params, rows[0], window)
    for row in rows[1:]:
        lsum, gsum = _add(lsum, gsum, params, row, window)
    return lsum, gsum


def grads(params, tokens, window: int = WINDOW):
    """(mean loss, mean gradient) over ``tokens`` [N, T]."""
    lsum, gsum = _sums(params, tokens, window=window)
    n = np.float32(len(tokens))
    return lsum / n, jax.tree_util.tree_map(lambda g: g / n, gsum)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(w, m, v, gsum, n, t, lr):
    """Standard Adam (Kingma & Ba 2015, bias-corrected, eps outside the
    square root) on the mean gradient ``gsum / n`` of one leaf, as
    ``lib/plain.py``'s, in place."""
    b1, b2, eps = 0.9, 0.999, 1e-8
    g = gsum / n
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return w - lr * mhat / (jnp.sqrt(vhat) + eps), m, v


def train(params, batches, lr: float, device=None, return_params=False,
          window: int = WINDOW):
    """The float loss before each of ``len(batches)`` steps of
    mean-gradient Adam from ``params`` (host arrays); ``batches[k]`` is
    int32 [N, T].  ``return_params`` adds the weights after the last
    step.  Adam's m and v live on the HOST between steps and visit the
    device a leaf at a time: placement, not arithmetic."""
    flat, tree = jax.tree_util.tree_flatten(params)
    flat = [jax.device_put(np.array(a, np.float32), device) for a in flat]
    m = [np.zeros(a.shape, np.float32) for a in flat]
    v = [np.zeros(a.shape, np.float32) for a in flat]
    losses = []
    for t, tokens in enumerate(batches, start=1):
        lsum, gsum = _sums(jax.tree_util.tree_unflatten(tree, flat), tokens,
                           device, window)
        n = np.float32(len(tokens))
        losses.append(float(lsum) / float(n))
        gsum = jax.tree_util.tree_leaves(gsum)
        for i in range(len(flat)):
            flat[i], mi, vi = _adam_leaf(
                flat[i], jax.device_put(m[i], device),
                jax.device_put(v[i], device), gsum[i], n, np.float32(t),
                np.float32(lr))
            gsum[i] = None
            m[i], v[i] = np.asarray(mi), np.asarray(vi)
    params = jax.tree_util.tree_unflatten(tree, flat)
    return (losses, params) if return_params else losses
