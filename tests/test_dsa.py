"""The learned sparse attention kernels (``geomx_tpu/ops/dsa.py``) under
the TPU interpreter on the CPU, at the rehearsal's sizes (4 heads of 8
over 2 kv heads, an indexer of 2 x 8, ``topk`` 8, 32 positions, tiles
of 8), against the equations in plain ``jax.numpy``: the selection with
its tie rule, attention over the chosen set and its gradients, and the
indexer's KL gradient.  Every interpreted call is waited for before the
next operation is dispatched: the TPU interpreter's callbacks deadlock
against dispatches made while they are in flight."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from geomx_tpu.ops import dsa

B, T, H, G, D, J, E, TOPK, TILE = 2, 32, 4, 2, 8, 2, 8, 8, 8


def scores(qi, ki, w):
    """I [B, T, T] as the kernels compute it: head by head, the same
    products summed in the same order."""
    out = 0.0
    for j in range(qi.shape[2]):
        r = jax.vmap(lambda a, b: lax.dot_general(
            a, b, dsa.TRANS_B, preferred_element_type=jnp.float32))(
                qi[:, :, j], ki)
        out = out + w[:, :, j, None] * jnp.maximum(r, 0.0)
    return out


def chosen_by_top_k(idx, topk):
    """The chosen pairs [B, T, T] by ``lax.top_k`` over the causal keys."""
    t = idx.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    vals, at = lax.top_k(jnp.where(causal, idx, -jnp.inf), topk)
    hit = jax.nn.one_hot(at, t, dtype=jnp.float32) * (vals > -jnp.inf)[..., None]
    return jnp.sum(hit, axis=-2) > 0


def plain(q, k, v, qi, ki, w, topk=TOPK):
    """(o, ce, chosen): the equations, float32."""
    idx = scores(qi, ki, w)
    ch = chosen_by_top_k(idx, topk)
    kk, vv = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, kk,
                   precision="highest") / np.sqrt(q.shape[-1])
    a = jax.nn.softmax(jnp.where(ch[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", a, vv, precision="highest")
    p = lax.stop_gradient(jnp.mean(a, axis=1))
    logq = jax.nn.log_softmax(jnp.where(ch, idx, -jnp.inf), axis=-1)
    ce = -jnp.sum(jnp.where(ch, p * logq, 0.0), axis=-1)
    return o, ce, ch


def inputs(seed, grid=None):
    """Seeded inputs; with ``grid`` every value a multiple of it, small,
    so that every score is exact in float32 and many tie."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(B, T, H, D), (B, T, G, D), (B, T, G, D), (B, T, J, E),
              (B, T, E), (B, T, J)]
    out = [jax.random.normal(key, s) for key, s in zip(ks, shapes)]
    if grid is not None:
        out[3:] = [jnp.round(x * 2) * grid for x in out[3:]]
    return out


def _waited(f):
    """``f`` jitted, its kernels under the TPU interpreter, its result
    waited for."""
    f = jax.jit(f)

    def call(*a):
        with force_tpu_interpret_mode():
            return jax.block_until_ready(f(*a))
    return call


run = _waited(lambda *a: dsa.dsa_attention(*a, topk=TOPK, tile=TILE))
selection = _waited(lambda qi, ki, w: dsa.select(
    qi.swapaxes(1, 2), ki, w, topk=TOPK, tile=TILE))


def kernel_chosen(qi, ki, w):
    """The chosen pairs [B, T, T] from the kernel's tau and sigma, on the
    scores as the kernels compute them."""
    tau, sigma, counts = selection(qi, ki, w)
    key = dsa.order_key(scores(qi, ki, w))
    s = jnp.arange(T)
    ch = ((key > tau) | ((key == tau) & (s <= sigma))) & (s <= s[:, None])
    return ch, counts


@pytest.mark.parametrize("grid", [0.5, 0.25])
def test_the_selection_is_top_k_with_its_tie_rule(grid):
    """On a coarse grid every score is exact, so the kernel's and the
    oracle's are the same numbers, and many rows tie at the threshold
    (``lax.top_k`` keeps the lower positions)."""
    _, _, _, qi, ki, w = inputs(1, grid)
    ch, counts = kernel_chosen(qi, ki, w)
    want = chosen_by_top_k(scores(qi, ki, w), TOPK)
    np.testing.assert_array_equal(ch, want)
    # every row chose min(t + 1, topk), counted a kv tile at a time
    np.testing.assert_array_equal(counts.sum(-1), np.broadcast_to(
        np.minimum(np.arange(T) + 1, TOPK), (B, T)))
    np.testing.assert_array_equal(
        counts, want.reshape(B, T, T // TILE, TILE).sum(-1))
    idx = np.asarray(scores(qi, ki, w))
    split = 0
    for b in range(B):
        for t in range(TOPK, T):
            kept = idx[b, t, :t + 1][np.asarray(want[b, t, :t + 1])]
            split += (idx[b, t, :t + 1] == kept.min()).sum() > (
                kept == kept.min()).sum()
    assert split > 0, "the grid made no tie at a threshold to split"


def test_continuous_scores_choose_the_largest():
    """Float32 scores summed in another order may differ in the last bit
    from the kernel's: every chosen score is at least every unchosen
    one, to that bit."""
    _, _, _, qi, ki, w = inputs(1)
    ch, counts = kernel_chosen(qi, ki, w)
    np.testing.assert_array_equal(counts.sum(-1), np.broadcast_to(
        np.minimum(np.arange(T) + 1, TOPK), (B, T)))
    idx = np.asarray(scores(qi, ki, w))
    ch = np.asarray(ch)
    causal = np.tril(np.ones((T, T), bool))
    for b in range(B):
        for t in range(TOPK, T):
            lo = idx[b, t][ch[b, t]].min()
            hi = idx[b, t][causal[t] & ~ch[b, t]].max()
            assert lo >= hi - 1e-5 * abs(hi), (b, t)


def test_constructed_ties_go_to_the_lower_positions():
    """Every score equal: each row keeps its first topk keys; two bands of
    equal scores: the upper band whole, then the lower band's first."""
    qi = jnp.ones((B, T, J, E)) / 4
    ki = jnp.ones((B, T, E)) / 4
    w = jnp.ones((B, T, J))
    ch, _ = kernel_chosen(qi, ki, w)
    first = (np.arange(T)[None] < TOPK) & np.tril(np.ones((T, T), bool))
    np.testing.assert_array_equal(ch, np.broadcast_to(first, ch.shape))
    ki = ki.at[:, 3::4].set(0.5)          # a higher band every 4th key
    ch, _ = kernel_chosen(qi, ki, w)
    np.testing.assert_array_equal(
        ch, chosen_by_top_k(scores(qi, ki, w), TOPK))
    assert bool(ch[0, T - 1, 3]) and not bool(ch[0, T - 1, T - 2])


def test_rows_before_topk_are_dense_causal():
    _, _, _, qi, ki, w = inputs(2)
    ch, _ = kernel_chosen(qi, ki, w)
    head = np.tril(np.ones((TOPK, T), bool), 0)[:, :T]
    np.testing.assert_array_equal(ch[:, :TOPK], np.broadcast_to(
        head, (B, TOPK, T)))


def rows_apart(case):
    """Seed 3's inputs with the last q tile's rows split by the indexer
    between kv tiles (every key of a tile points one way, a row's query
    the way of the 8 keys it is to choose, which then score 2 and every
    other key 0):

    * ``"first_tile_holds_none"``: rows 24-27 choose kv tile 0, rows
      28-31 kv tile 2, so tile 0 is the first the q tile visits and holds
      none of rows 28-31's keys;
    * ``"a_visited_tile_holds_none"``: rows 24-27 choose keys 0-7, rows
      28-31 keys 0-3 and 16-19, so every row has keys in tile 0 and rows
      24-27 none in tile 2, which is visited.

    The last q tile's attention scores sit near -50, so that a mask
    that is not far below every score would move the row's running max
    off its keys."""
    q, k, v, qi, ki, w = inputs(3)
    u = np.eye(D, dtype=np.float32)[0]
    k = 0.1 * k + u
    q = q.at[:, 24:].set(0.1 * q[:, 24:] - 50 * np.sqrt(D) * u)
    one = np.eye(E, dtype=np.float32)
    keys = np.tile(one[3], (B, T, 1))      # no row below chooses them
    qrow = np.abs(np.asarray(qi))
    lo, hi = slice(24, 28), slice(28, 32)
    if case == "first_tile_holds_none":
        keys[:, 0:8], keys[:, 16:24] = one[0], one[2]
        qrow[:, lo], qrow[:, hi] = one[0], one[2]
    else:
        keys[:, 0:4], keys[:, 4:8], keys[:, 16:20] = one[0], one[1], one[2]
        qrow[:, lo], qrow[:, hi] = one[0] + one[1], one[0] + one[2]
    w = jnp.abs(w).at[:, 24:].set(1.0)
    return q, k, v, jnp.asarray(qrow), jnp.asarray(keys), w


@pytest.mark.parametrize("case", ["seeded", "first_tile_holds_none",
                                  "a_visited_tile_holds_none"])
def test_forward_matches_the_equations(case):
    """Seeded inputs, and the two edges of the one mask a score takes: a
    row with no key yet keeps its running max at ``NEG`` through a
    visited tile, and a row with none in a later visited tile adds
    nothing there."""
    args = inputs(3) if case == "seeded" else rows_apart(case)
    o, ce, stats = run(*args)
    o_ref, ce_ref, ch = plain(*args)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ce, ce_ref, rtol=1e-5, atol=1e-5)
    xo, xce, _ = jax.jit(lambda *a: dsa.dsa_attention_xla(
        *a, topk=TOPK, tile=TILE))(*args)
    np.testing.assert_allclose(o, xo, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ce, xce, rtol=1e-5, atol=1e-5)
    pairs = int(np.minimum(np.arange(T) + 1, TOPK).sum()) * B
    assert int(stats["chosen_pairs"]) == pairs == int(
        stats["attended_pairs"]) == int(np.sum(ch))
    n = T // TILE
    assert int(stats["tiles_causal"]) == B * n * (n + 1) // 2
    # the chosen keys of each row in each kv tile, and the tiles visited
    per = np.asarray(ch).reshape(B, T, n, TILE).sum(-1)
    visited = per.reshape(B, n, TILE, n).any(2)
    last = per[:, 24:]                      # the last q tile's rows
    first = int(np.argmax(visited[0, -1]))
    if case == "first_tile_holds_none":
        assert first == 0 and (last[:, :, first] == 0).any()
    elif case == "a_visited_tile_holds_none":
        assert (last[:, :, first] > 0).all()
        assert visited[:, -1, 2].all() and (last[:, :, 2] == 0).any()


def test_gradients_match_the_equations_and_stay_on_their_side():
    """The LM's gradient (through ``o``) reaches q, k and v alone; the
    indexer's (through ``ce``) the indexer's q, k and w alone."""
    args = inputs(4)
    ro = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, D))
    rc = jax.random.normal(jax.random.PRNGKey(10), (B, T))

    def loss(f, which):
        def g(*a):
            o, ce = f(*a)[:2]
            return (jnp.sum(o * ro) if which != "ce" else 0.0) + (
                jnp.sum(ce * rc) if which != "o" else 0.0)
        return _waited(jax.grad(g, argnums=tuple(range(6))))

    kern = lambda *a: dsa.dsa_attention(*a, topk=TOPK, tile=TILE)  # noqa
    for which in ("o", "ce", "both"):
        got = loss(kern, which)(*args)
        want = loss(plain, which)(*args)
        for name, g, r in zip(("q", "k", "v", "qi", "ki", "w"), got, want):
            np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5,
                                       err_msg=f"{which}: d{name}")
            if (which == "o" and name in ("qi", "ki", "w")) or (
                    which == "ce" and name in ("q", "k", "v")):
                assert not np.any(np.asarray(g)), (which, name)


def test_a_tile_nobody_chose_is_skipped():
    """Keys of the second kv tile score far below the rest: no query
    past it chooses them, the kernels skip the tile and the result is
    still the equations'."""
    q, k, v, qi, ki, w = inputs(5)
    w = jnp.abs(w)
    qi = jnp.abs(qi)
    ki = jnp.abs(ki).at[:, TILE:2 * TILE].set(0.0)
    # rows of the second q tile on choose those keys (their own tile),
    # so the skipped tiles are (q tile >= 2, kv tile 1)
    o, ce, stats = run(q, k, v, qi, ki, w)
    n = T // TILE
    _, _, ch = plain(q, k, v, qi, ki, w)
    visited = np.asarray(ch).reshape(B, n, TILE, n, TILE).any((2, 4))
    assert not visited[:, 2:, 1].any()
    assert int(stats["tiles_visited"]) == visited.sum() < B * n * (n + 1) // 2
    o_ref, ce_ref, _ = plain(q, k, v, qi, ki, w)
    np.testing.assert_allclose(o, o_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ce, ce_ref, rtol=1e-5, atol=1e-5)
    g = _waited(jax.grad(lambda *a: jnp.sum(dsa.dsa_attention(
        *a, topk=TOPK, tile=TILE)[0] ** 2), argnums=(0, 1)))
    gr = jax.jit(jax.grad(lambda *a: jnp.sum(plain(*a)[0] ** 2),
                          argnums=(0, 1)))
    for a, b in zip(g(q, k, v, qi, ki, w), gr(q, k, v, qi, ki, w)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", [3, 5])
def test_the_xla_path_is_the_kernels(seed):
    """``dsa_attention_xla`` (what the rehearsal and the threaded tests
    run) gives the kernels' outputs, counts and gradients."""
    args = inputs(seed)
    o, ce, stats = run(*args)
    xo, xce, xstats = jax.jit(lambda *a: dsa.dsa_attention_xla(
        *a, topk=TOPK, tile=TILE))(*args)
    np.testing.assert_allclose(xo, o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(xce, ce, rtol=1e-5, atol=1e-5)
    assert {k: int(v) for k, v in xstats.items()} == {
        k: int(v) for k, v in stats.items()}
    ro = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, D))

    def grad(f):
        return jax.grad(lambda *a: jnp.sum(f(*a)[0] * ro) + jnp.sum(
            f(*a)[1]), argnums=tuple(range(6)))

    got = _waited(grad(lambda *a: dsa.dsa_attention(
        *a, topk=TOPK, tile=TILE)))(*args)
    want = jax.jit(grad(lambda *a: dsa.dsa_attention_xla(
        *a, topk=TOPK, tile=TILE)))(*args)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5)
