"""The chunked gated delta rule (``geomx_tpu/ops/kda.py``, ISSUE 37)
against the recurrence a token at a time, forward and gradients, at
decays strong enough that the naive factored form overflows, at a
length no chunk divides; the triangular inverse; bfloat16."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from geomx_tpu.ops import kda

T = 150          # not a multiple of 16 nor of 64


def _inputs(strength, T=T, H=2, K=8, V=8, B=2, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    g = -strength * jax.random.uniform(ks[3], (B, T, H, K))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def recurrent_kda(q, k, v, g, beta):
    """The recurrence itself, a token at a time in float32 (``lax.scan``
    over T): what ``chunk_kda`` must equal."""
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)   # noqa: E731
    B, _, H, K = k.shape
    hi = lax.Precision.HIGHEST

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=hi))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi)

    S0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    _, o = lax.scan(step, S0, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1)


def _rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


def _both(chunk, x, w):
    """(o, gradients of sum(o * w)) of the chunked form and of the
    recurrence."""
    chunked = lambda *a: kda.chunk_kda(*a, chunk=chunk)[0]    # noqa: E731
    out = []
    for f in (chunked, recurrent_kda):
        out.append((jax.jit(f)(*x), jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2, 3, 4)))(*x)))
    return out


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("strength", [0.05, 6.0],
                         ids=["weak-decay", "overflowing-decay"])
def test_chunked_equals_the_recurrence_forward_and_backward(chunk, strength):
    """Float32 at ``highest`` on both sides: what is left is the order
    of the sums.  Seen 2.5e-6 at worst (the decay's gradient, chunk 64);
    1e-4 is forty times that and a hundredth of what bfloat16 leaves."""
    x = _inputs(strength)
    w = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    (o, grads), (ro, rgrads) = _both(chunk, x, w)
    assert bool(jnp.all(jnp.isfinite(o)))
    assert _rel(o, ro) < 1e-5
    for name, a, b in zip(("q", "k", "v", "g", "beta"), grads, rgrads):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, b) < 1e-4, (name, _rel(a, b))


def test_the_naive_factored_form_overflows_at_these_decays():
    """The trap the halving avoids: ``(k * exp(G)) (k * exp(-G))^T``
    needs ``exp(-G)``, and a chunk of 64 positions at a mean log decay
    of -3 a position cumulates -190 (float32 ends at 88)."""
    q, k, v, g, beta = _inputs(6.0, T=64)
    G = jnp.cumsum(g, axis=1)                    # one chunk of 64
    assert float(jnp.min(G)) < -88
    naive = jnp.einsum("bihk,bjhk->bhij", k * jnp.exp(G), k * jnp.exp(-G))
    assert not bool(jnp.all(jnp.isfinite(naive)))
    _, stats = kda.chunk_kda(q, k, v, g, beta, chunk=64)
    assert float(stats["log_decay_min"]) == pytest.approx(float(jnp.min(G)),
                                                          rel=1e-6)
    assert stats["chunks"] == 1
    # at chunk 16 the most negative cumulated decay is a chunk's, not
    # the sequence's
    _, short = kda.chunk_kda(q, k, v, g, beta, chunk=16)
    assert float(short["log_decay_min"]) > float(stats["log_decay_min"])
    # 4 chunks are one block: one state a head and sequence is kept
    assert short["chunks"] == 4 and short["state_bytes"] == 2 * 2 * 8 * 8 * 4


def test_bfloat16_stays_near_and_fails_the_float32_tolerance():
    """The cell's compute dtype: products in bfloat16 with float32 sums,
    decays and state.  Seen 5e-3 of the output's norm; the float32
    tolerance (1e-5) must fail: a bfloat16 scan cannot pass for the
    float32 one."""
    x = _inputs(1.0, dtype=jnp.bfloat16)
    o, _ = jax.jit(lambda *a: kda.chunk_kda(*a, chunk=64))(*x)
    assert o.dtype == jnp.bfloat16
    err = _rel(o, recurrent_kda(*x))
    assert 1e-5 < err < 3e-2, err


@pytest.mark.parametrize("size", [8, 16, 64])
def test_unit_lower_inverse(size):
    """Against ``jnp.linalg.inv``, with its hand-written gradient, and
    where every key of a chunk is alike (all ones below the diagonal):
    there a sum of powers of the matrix would cancel 1e17 against 1."""
    # entries the size of beta x (k_i . k_j): a random matrix of unit
    # entries has an inverse of 1e8 at 64 rows, which no method keeps
    low = 0.2 * jnp.tril(jax.random.normal(jax.random.PRNGKey(size),
                                           (3, size, size)), -1)
    eye = jnp.eye(size)
    np.testing.assert_allclose(kda.unit_lower_inverse(low),
                               jnp.linalg.inv(eye + low), rtol=2e-4,
                               atol=2e-4 * float(jnp.max(jnp.abs(
                                   jnp.linalg.inv(eye + low)))))
    w = jax.random.normal(jax.random.PRNGKey(1), low.shape)
    mine = jax.grad(lambda m: jnp.sum(kda.unit_lower_inverse(m) * w))(low)
    ref = jax.grad(lambda m: jnp.sum(jnp.linalg.inv(eye + m) * w))(low)
    np.testing.assert_allclose(jnp.tril(mine, -1), jnp.tril(ref, -1),
                               rtol=2e-3, atol=2e-3 * float(
                                   jnp.max(jnp.abs(ref))))
    alike = jnp.tril(jnp.ones((size, size)), -1)
    # (I + L)^-1 of the all-ones L: 1 on the diagonal, -1, then +-2^n
    np.testing.assert_allclose(
        kda.unit_lower_inverse(alike) @ (eye + alike), eye, atol=1e-2)


def test_a_checkpoint_that_saves_the_names_keeps_states_and_output(capsys):
    """What the scan names for a checkpoint around it (ISSUE 38): with
    ``save_only_these_names(*KEPT_NAMES)`` the residuals are the
    arguments, the float32 state each block starts from and the blocks'
    output, ``named_bytes`` in all, and nothing of a block's inside; a
    plain checkpoint keeps the arguments alone.  The gradient is the
    same to the bit either way."""
    from jax.ad_checkpoint import print_saved_residuals

    x = _inputs(1.0)                 # 150 positions: 3 blocks of 4 x 16
    seen = {}

    def loss(*a):
        o, stats = kda.chunk_kda(*a, chunk=16)
        seen.update(stats)
        return jnp.sum(o ** 2)

    def kept(f):
        print_saved_residuals(f, *x)
        lines = capsys.readouterr().out.splitlines()
        return sorted(re.match(r"\w+\[([\d,]+)\]", line).group(1)
                      for line in lines if "from the argument" not in line)

    saving = jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            *kda.KEPT_NAMES))
    assert kept(jax.checkpoint(loss)) == []
    assert kept(saving) == ["3,2,2,4,16,8", "3,2,2,8,8"]     # float32
    assert seen["state_bytes"] == 3 * 2 * 2 * 8 * 8 * 4
    assert seen["named_bytes"] == seen["state_bytes"] + (
        3 * 2 * 2 * 4 * 16 * 8 * 4)
    argnums = (0, 1, 2, 3, 4)
    for a, b in zip(jax.jit(jax.grad(saving, argnums))(*x),
                    jax.jit(jax.grad(jax.checkpoint(loss), argnums))(*x)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
