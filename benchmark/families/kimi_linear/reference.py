"""The plain reference: Kimi-Linear's blocks straight in ``jax.numpy``.

Independent of ``geomx_tpu``: float32 throughout, matmuls at ``highest``
(``jax.default_matmul_precision``), the delta-rule scan A TOKEN AT A
TIME.  It reads only the parameter tree the system was given as input
and takes every size from the leaves' shapes; what no leaf can say is a
constant of the family below.  Source of the equations: ``model_type``
``kimi_linear`` (https://huggingface.co/moonshotai/
Kimi-Linear-48B-A3B-Instruct/blob/main/config.json) and the KDA
recurrence of "Kimi Linear: An Expressive, Efficient Attention
Architecture" (arXiv 2510.26692).

All linear maps are without bias.  ``RMS(x; w) = x * rsqrt(mean(x^2) +
1e-5) * w``.  ``unit(x) = x * rsqrt(sum(x^2) + 1e-6)``.

* Block ``l``: ``h = x + Mix_l(RMS(x; ln1))``, ``y = h + FFN_l(RMS(h;
  ln2))``; after the last block ``RMS(.; ln_f)``, logits ``= . @
  head^T`` (untied), next-token cross-entropy over B x (T - 1).  No
  positions anywhere.
* ``Mix`` = KDA (a layer with ``A_log``), H heads of K channels:
  ``q = silu(conv(wq x))``, ``k = silu(conv(wk x))``, ``v =
  silu(conv(wv x))`` with ``conv`` depthwise, causal, 4 taps a channel,
  zeros left of the sequence; ``q <- unit(q) * K^-1/2``, ``k <-
  unit(k)`` a head; log decay ``g = -exp(A_log[head]) * softplus(f_b
  (f_a x) + dt_bias)`` a channel; ``beta = sigmoid(w_beta x)`` a head.
  State ``S`` [K, V] a head, zero before position 0::

      S' = diag(exp(g_t)) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  Out: ``wo (RMS(o; o_norm) a head * sigmoid(g_b (g_a x)))``.
* ``Mix`` = MLA (a layer with ``w_kv_a``): ``q = wq x`` as H heads of
  Dn + Dr; ``[c, k_r] = split(w_kv_a x)`` (R and Dr); ``[k_n, v] =
  split(w_kv_b RMS(c; kv_norm))`` a head (Dn and Dv); ``k = [k_n,
  k_r]``, ``k_r`` the same for every head and NOT rotated
  (``mla_use_nope``); causal ``softmax(q k^T / sqrt(Dn + Dr)) v``;
  ``wo`` over the concatenated heads.
* ``FFN`` of a layer with ``w1``: ``w2 (silu(w1 x) * w3 x)``.
* ``FFN`` of a layer with ``router``: ``s = sigmoid(router x)`` over ALL
  the deployment's experts; ``I`` the 8 experts of largest ``s +
  expert_bias`` (one group: a plain top 8); ``w_i = 2.446 s_i /
  (sum_{j in I} s_j + 1e-6)``; the layer returns ``sum_{i in I, i held}
  w_i E_i(x) + E_shared(x)``: THE CHIP'S SHARE of the routed part and
  the shared expert whole.  The experts held are ``first .. first + E -
  1`` (E the stacks' leading dimension).

Departures, each deliberate:

* of this reference from the publication: the expert bias is a seeded
  constant (no gradient reaches it; the config gives no rule that would
  move it); the absent experts' part of every layer is left out, as it
  is in the system, and with it their part of the router's gradient:
  what is left of that gradient pulls toward the experts held alone, so
  the routing weights ``w_i`` are constants of the backward pass (the
  router is not trained from one share, and no gradient reaches ``x``
  through the scores); 1e-6 beside the sum of the chosen scores (the
  repo's router) where the published code has 1e-20.
* of the system from this reference: activations and matmuls in
  bfloat16 (float32 parameters, norms, softmax, router scores, decays,
  the triangular system and the carried state); the scan in chunks of
  64 positions (``geomx_tpu/ops/kda.py``) where this file steps a token
  at a time; latent attention by jax's flash kernels with q, k and v
  padded to 256 channels; the held experts' products as grouped
  products over sorted rows where this file loops over the experts with
  every token masked by its weight; the top 8 by ``lax.top_k`` where
  this file thresholds at the eighth largest.

Blocking, which is the same arithmetic and not another algorithm: the
scan runs ``SCAN_BLOCK`` positions under ``jax.checkpoint`` at a time,
so that the backward pass keeps a state a block and not 8,192 states of
2 MB; attention runs a block of queries at a time (``lax.scan``: one
compiled body); the dense FFNs run a block of rows at a time, the held
experts one after the other (``lax.scan`` over the stacks); every
layer is recomputed in the backward pass, a KDA layer's projections and
its output each under a checkpoint of their own; the loss is summed
over blocks of tokens.  Adam's m and v wait on the host between steps
and its update gives its buffers back (``donate_argnums``): at 602M
parameters weights, gradient, m and v are 9.6 GB, the gradient
program's temporaries and code 5.6 GB as compiled for the v5e, and the
harness leaves one 2.4 GB copy of the system's alive: together over a
chip's 16.9, as is ``lib/plain.py``'s update, which holds old and new
at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-5
EXPERTS_PER_TOKEN = 8
ROUTED_SCALE = 2.446
FIRST_EXPERT = 0          # the experts this chip holds start here
QUERY_BLOCK = 256
SCAN_BLOCK = 128
ROW_BLOCK = 2048
LOSS_BLOCK = 2048


def _rms(x, scale):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) * scale


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def conv_silu(x, taps):
    """x [B, T, H, K], taps [H, K, n] -> silu(y), y_t = sum_j taps[.., j]
    x_{t-(n-1)+j}."""
    T, n = x.shape[1], taps.shape[-1]
    u = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0), (0, 0)))
    return _silu(sum(u[:, j:j + T] * taps[..., j] for j in range(n)))


def delta_step(S, q, k, v, g, beta):
    """One position of the recurrence for every head: S [B, H, K, V],
    q, k, g [B, H, K], v [B, H, V], beta [B, H] -> (S_t, o_t)."""
    S = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
    S = S + k[..., None] * u[..., None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, q)


def _scan_block(S, xs):
    return jax.lax.scan(lambda S, x: delta_step(S, *x), S, xs)


def delta_scan(q, k, v, g, beta, block: int = SCAN_BLOCK):
    """[B, T, H, .] in, o [B, T, H, V] out: a token at a time, ``block``
    positions under one checkpoint (any block gives the same result:
    ``tests/``)."""
    B, T, H, K = k.shape
    block = min(block, T)
    pad = -T % block
    # a padded position decays nothing and writes nothing
    xs = [jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
          for x in (q, k, v, g, beta)]
    # time first, in blocks: [T / block, block, B, H, .]
    xs = tuple(jnp.moveaxis(x, 1, 0).reshape(
        (-1, block) + x.shape[:1] + x.shape[2:]) for x in xs)
    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(_scan_block), S0, xs)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :T]


def _low_rank(layer, h, a: str, b: str):
    return jnp.einsum("btr,rhk->bthk", h @ layer[a], layer[b])


def kda_inputs(layer, h):
    """h [B, T, D] -> the scan's q, k, v, g [B, T, H, K], beta [B, T, H]."""
    K = layer["wq"].shape[-1]
    q, k, v = (conv_silu(jnp.einsum("btd,dhk->bthk", h, layer["w" + n]),
                         layer["conv_" + n]) for n in "qkv")
    g = -jnp.exp(layer["A_log"])[:, None] * _softplus(
        _low_rank(layer, h, "f_a", "f_b") + layer["dt_bias"])
    return (_unit(q) * K ** -0.5, _unit(k), v, g,
            _sigmoid(h @ layer["w_beta"]))


def kda_output(layer, h, o):
    o = _rms(o, layer["o_norm"]) * _sigmoid(_low_rank(layer, h, "g_a", "g_b"))
    return jnp.einsum("bthk,hkd->btd", o, layer["wo"])


def kda(layer, h):
    """h [B, T, D] -> [B, T, D]; what goes into the scan and what comes
    out of it each under a checkpoint of its own."""
    o = delta_scan(*jax.checkpoint(kda_inputs)(layer, h))
    return jax.checkpoint(kda_output)(layer, h, o)


def _attend(q, k, v, q0):
    """Queries ``q0 ..`` of a block against every key, masked causally."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    qi = q0 + jnp.arange(q.shape[2])[:, None]
    s = jnp.where(qi >= jnp.arange(k.shape[2])[None, :], s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def latent_attention(layer, h, block: int = QUERY_BLOCK):
    """h [B, T, D] -> [B, T, D]; ``block`` queries at a time, each block
    under a checkpoint (any block gives the same result: ``tests/``)."""
    T, R = h.shape[1], layer["kv_norm"].shape[0]
    Dv = layer["wo"].shape[1]
    q = jnp.einsum("btd,dhk->bhtk", h, layer["wq"])
    kv_a = h @ layer["w_kv_a"]
    kv = jnp.einsum("btr,rhk->bhtk", _rms(kv_a[..., :R], layer["kv_norm"]),
                    layer["w_kv_b"])
    k_n, v = kv[..., :-Dv], kv[..., -Dv:]
    k_r = jnp.broadcast_to(kv_a[:, None, :, R:],
                           k_n.shape[:3] + (kv_a.shape[-1] - R,))
    k = jnp.concatenate([k_n, k_r], axis=-1)
    block = min(block, T)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, -T % block), (0, 0)))
    blocks = jnp.moveaxis(q.reshape(q.shape[:2] + (-1, block, q.shape[-1])),
                          2, 0)

    def one(q0, q_block):
        return q0 + block, _attend(q_block, k, v, q0)

    _, out = jax.lax.scan(jax.checkpoint(one), jnp.int32(0), blocks)
    out = jnp.moveaxis(out, 0, 2).reshape(q.shape[:3] + (Dv,))[:, :, :T]
    return jnp.einsum("bhtk,hkd->btd", out, layer["wo"])


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
    the deployment, zero off its chosen eight."""
    s = _sigmoid(x @ layer["router"])
    sel = s + layer["expert_bias"]
    eighth = jnp.sort(sel, axis=-1)[:, -EXPERTS_PER_TOKEN]
    chosen = jnp.where(sel >= eighth[:, None], s, 0.0)
    return ROUTED_SCALE * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-6)


def expert_share(layer, h, first: int = FIRST_EXPERT):
    """The part of the routed layer that the experts held give: experts
    ``first .. first + E - 1`` of the router's, one at a time over every
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


def shared_expert(layer, h):
    s = layer["shared"]
    return in_row_blocks(
        lambda x: gated_ffn(s["w1"], s["w3"], s["w2"], x),
        h.reshape(-1, h.shape[-1])).reshape(h.shape)


def block(layer, x, first: int = FIRST_EXPERT):
    h = _rms(x, layer["ln1"])
    x = x + (kda(layer, h) if "A_log" in layer else
             latent_attention(layer, h))
    h = _rms(x, layer["ln2"])
    if "router" in layer:
        return x + expert_share(layer, h, first) + shared_expert(layer, h)
    return x + in_row_blocks(
        lambda r: gated_ffn(layer["w1"], layer["w3"], layer["w2"], r),
        h.reshape(-1, h.shape[-1])).reshape(h.shape)


def hidden(params, tokens, first: int = FIRST_EXPERT):
    """tokens int32 [B, T] -> the final normed stream [B, T, D]."""
    x = params["embed"][tokens]
    for layer in params["layers"]:
        x = jax.checkpoint(block, static_argnums=2)(layer, x, first)
    return _rms(x, params["ln_f"])


def forward(params, tokens, first: int = FIRST_EXPERT):
    """tokens int32 [B, T] -> logits float32 [B, T, vocab]."""
    return hidden(params, tokens, first) @ params["head"].T


def _nll_sum(head, x, targets):
    logp = jax.nn.log_softmax(x @ head.T)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))


def loss_fn(params, tokens, block: int = LOSS_BLOCK):
    x = hidden(params, tokens)[:, :-1]
    x = x.reshape(-1, x.shape[-1])
    y = tokens[:, 1:].reshape(-1)
    total = sum(jax.checkpoint(_nll_sum)(params["head"], x[a:a + block],
                                         y[a:a + block])
                for a in range(0, x.shape[0], block))
    return total / x.shape[0]


def _with_highest(f):
    @functools.wraps(f)
    def g(*a):
        with jax.default_matmul_precision("highest"):
            return f(*a)
    return g


_value_and_grad = jax.jit(_with_highest(jax.value_and_grad(loss_fn)))


@functools.partial(jax.jit, donate_argnums=(0, 1))
@_with_highest
def _add(lsum, gsum, params, tokens):
    loss, g = jax.value_and_grad(loss_fn)(params, tokens)
    return lsum + loss, jax.tree_util.tree_map(jnp.add, gsum, g)


def _sums(params, tokens, device=None):
    """(summed loss, summed gradient) over ``tokens`` [N, T], a sequence
    at a time."""
    rows = [jax.device_put(row[None], device) for row in np.asarray(tokens)]
    lsum, gsum = _value_and_grad(params, rows[0])
    for row in rows[1:]:
        lsum, gsum = _add(lsum, gsum, params, row)
    return lsum, gsum


def grads(params, tokens):
    """(mean loss, mean gradient) over ``tokens`` [N, T]."""
    lsum, gsum = _sums(params, tokens)
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


def train(params, batches, lr: float, device=None, return_params=False):
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
                           device)
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
