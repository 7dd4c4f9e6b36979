"""LFM2-MoE through the program (ISSUE 35): the per-layer pattern, the
gated short convolution, grouped-query attention with q/k norms and
rotary positions, and one chip's share of a routed expert layer with no
capacity and no dropped token, each against the family's plain
reference (``benchmark/families/lfm2_moe/reference.py``, which imports
nothing of the program) on seeded random weights at tiny sizes; then a
stacked expert leaf through both kvstore tiers with its ``group`` on
the spans, and the ``moe.route`` span of a sampled round."""

import hashlib
import json
import threading
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import family
from geomx_tpu.models import transformer as tf
from geomx_tpu.parallel.moe import _routed_ffn, routed_ffn

ROOT = Path(__file__).resolve().parents[1]
FAMILY = family.load(ROOT, ["benchmark"], "lfm2_moe")
reference = FAMILY.reference
CONFIG = json.loads(
    (ROOT / "benchmark/configs/lfm2-24b-a2b-ep8-l5-1chip.json").read_text())
# the rehearsal's tiny sizes: the cut's 5-layer pattern (conv with the
# dense FFN, full_attention, conv, conv, conv with experts), 4 of 16
# experts held, top 4
TINY = {**{k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])},
        **FAMILY.needs["rehearsal"]}


def _tokens(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TINY["vocab"], (n, TINY["max_seq"])),
                       jnp.int32)


def _build(dtype, **over):
    init, grad_fn = FAMILY.system.build({**TINY, **over}, dtype)
    return jax.jit(init)(jax.random.PRNGKey(3)), grad_fn


def _worst(grads, ref_grads):
    """{leaf: |g - ref| / |ref|} and the loss-free worst of them."""
    out = {}
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        norm = float(jnp.linalg.norm(r))
        out[jax.tree_util.keystr(path)] = (
            float(jnp.linalg.norm(g - r)) / norm if norm else
            float(jnp.linalg.norm(g)))
    return out


def test_the_cut_runs_the_published_pattern_from_the_last_dense_layer():
    cfg = FAMILY.system.config(TINY, "float32")
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    assert [cfg.is_routed(i) for i in range(5)] == [False] + [True] * 4
    params, _ = _build("float32")
    assert "pos" not in params                      # rotary positions
    assert set(params["layers"][0]) == {"ln1", "ln2", "w_in", "conv",
                                        "w_out", "w1", "w2", "w3"}
    assert set(params["layers"][1]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                        "q_norm", "k_norm", "router",
                                        "expert_bias", "experts"}
    assert params["layers"][1]["wk"].shape == (32, 2, 8)   # 2 of 4 heads
    assert params["layers"][2]["experts"]["w1"].shape == (4, 32, 16)
    assert params["layers"][2]["router"].shape == (32, 16)


def test_float32_matches_the_reference_in_loss_and_every_gradient_leaf():
    """Float32 compute on both sides: what is left is the order of the
    sums (the system's sorted grouped products against the reference's
    masked dense loop; its top_k against a threshold).  1e-4 of a
    leaf's norm is 25 times what was seen (3.9e-6) and a thousandth of
    what bfloat16 compute leaves (next test)."""
    params, grad_fn = _build("float32")
    x = _tokens()
    loss, _acc, grads, extra = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x))
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    worst = _worst(grads, ref_grads)
    assert len(worst) == 2 + 8 + 13 + 3 * 10     # every leaf compared
    assert max(worst.values()) < 1e-4, worst
    # the expert bias selects and never weighs: no gradient on either side
    assert not np.any(np.asarray(grads["layers"][1]["expert_bias"]))
    route = extra["moe_route"]
    assert route["rows"].shape == (4, 4) and route["rows"].dtype == jnp.int32
    assert np.array_equal(np.asarray(route["rows"]).sum(1),
                          np.asarray(route["held_pairs"]))


def test_bfloat16_stays_near_the_reference_and_fails_the_float32_tolerance():
    """The configuration's compute dtype.  bfloat16 activations move a
    router score by up to 2^-8 of itself, which flips the fourth choice
    of a token whose fourth and fifth scores are that close, and a
    flipped token moves a whole expert's contribution: the expert and
    router leaves differ by tenths of their norm at these sizes (32
    wide, 96 tokens; seen: 0.31 at worst), the loss by under 0.01.  The
    float32 tolerance must fail here: a bfloat16 computation cannot
    pass for the float32 one."""
    params, grad_fn = _build("bfloat16")
    x = _tokens()
    loss, _acc, grads, _ = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x))
    assert float(loss) == pytest.approx(float(ref_loss), abs=0.03)
    worst = _worst(grads, ref_grads)
    assert 1e-4 < max(worst.values()) < 0.6, worst
    dense = [v for k, v in worst.items()
             if "experts" not in k and "router" not in k]
    assert max(dense) < 0.3, worst


def _routed_layer(seed, d=16, fe=8, e_all=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "router": jax.random.normal(ks[0], (d, e_all)) / np.sqrt(d),
        "expert_bias": 0.002 * jax.random.normal(ks[1], (e_all,)),
        "experts": {
            "w1": jax.random.normal(ks[2], (e_all, d, fe)) / np.sqrt(d),
            "w3": jax.random.normal(ks[3], (e_all, d, fe)) / np.sqrt(d),
            "w2": jax.random.normal(ks[4], (e_all, fe, d)) / np.sqrt(fe)},
    }, jax.random.normal(ks[5], (2, 24, d))


def _share(layer, h, first, held, **kw):
    experts = {n: w[first:first + held] for n, w in layer["experts"].items()}
    return routed_ffn(h, layer["router"], layer["expert_bias"], experts,
                      first=first, k=4, compute_dtype=jnp.float32, **kw)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each, every share through the PROGRAM's
    held-experts layer: their partial results sum to what the reference
    gives for the whole 64-expert layer, and between them they are
    handed every (token, choice) pair exactly once."""
    layer, h = _routed_layer(0)
    whole = reference.expert_share(layer, h, first=0)    # all 64 held
    shares = [_share(layer, h, 8 * s, 8) for s in range(8)]
    np.testing.assert_allclose(sum(y for y, _ in shares), whole,
                               rtol=1e-5, atol=1e-6)
    assert sum(int(r["held_pairs"]) for _, r in shares) == 2 * 24 * 4
    assert sum(int(r["rows"].sum()) for _, r in shares) == 2 * 24 * 4
    # and one share is the reference's share, not only their sum
    np.testing.assert_allclose(
        shares[3][0], reference.expert_share(
            {**layer, "experts": {n: w[24:32] for n, w in
                                  layer["experts"].items()}}, h, first=24),
        rtol=1e-5, atol=1e-6)


def test_leaving_the_expert_bias_out_fails_parity():
    """The bias is small where it acts (0.002 in score, the spread
    ``init_params`` draws it with), and still chooses: over 4,096
    tokens it changes some token's fourth expert, the held experts'
    rows and the layer's result by far more than the float32
    tolerance."""
    layer, _ = _routed_layer(4)
    h = jax.random.normal(jax.random.PRNGKey(8), (1, 4096, 16))
    y, route = _share(layer, h, 0, 8)
    bare = {**layer, "expert_bias": jnp.zeros(64)}
    y0, route0 = _share(bare, h, 0, 8)
    assert not np.array_equal(np.asarray(route["rows"]),
                              np.asarray(route0["rows"]))
    whole = reference.expert_share(
        {**layer, "experts": {n: w[:8] for n, w in
                              layer["experts"].items()}}, h, first=0)
    np.testing.assert_allclose(y, whole, rtol=1e-5, atol=1e-6)
    assert float(jnp.max(jnp.abs(y0 - whole))) > 1e-2


def test_no_token_is_dropped_when_every_token_routes_to_one_held_expert():
    """The load a capacity would cut: all 48 tokens choose expert 3 (and
    three experts held elsewhere).  Every row reaches expert 3's group,
    none is dropped, and the result is still the reference's."""
    layer, h = _routed_layer(1)
    layer["expert_bias"] = layer["expert_bias"].at[
        jnp.array([3, 40, 41, 42])].add(10.0)
    y, route = _share(layer, h, 0, 8)
    assert np.asarray(route["rows"]).tolist() == [0, 0, 0, 48, 0, 0, 0, 0]
    assert int(route["held_pairs"]) == 48 and int(route["empty_tokens"]) == 0
    held = {**layer, "experts": {n: w[:8] for n, w in
                                 layer["experts"].items()}}
    np.testing.assert_allclose(y, reference.expert_share(held, h, first=0),
                               rtol=1e-5, atol=1e-6)
    # the gradient of the crowded expert's stack too
    def loss(fn):
        return lambda e: jnp.sum(fn({**held, "experts": e}) ** 2)
    g = jax.grad(loss(lambda l: routed_ffn(
        h, l["router"], l["expert_bias"], l["experts"], first=0, k=4,
        compute_dtype=jnp.float32)[0]))(held["experts"])
    r = jax.grad(loss(lambda l: reference.expert_share(l, h)))(
        held["experts"])
    for n in ("w1", "w2", "w3"):
        np.testing.assert_allclose(g[n], r[n], rtol=1e-4, atol=1e-5)
        assert not np.any(np.asarray(g[n][4]))       # an idle expert


# 512 tokens, top 4 of 64, 8 held: an even router sends 256 of the 2,048
# pairs, and the sorted buffer is 512 rows, a chunk of the pairs at a time
LOADS = {
    "even router: half a buffer": (lambda b: b, 1),
    # test_no_token_is_dropped's router: every token's first choice is
    # expert 3 and its others are held elsewhere, 512 pairs: one buffer
    # filled to its last row
    "one crowded expert: one buffer, full": (
        lambda b: b.at[jnp.array([3, 40, 41, 42])].add(10.0), 1),
    "held experts favoured: two chunks": (lambda b: b.at[:8].add(0.15), 2),
    "held experts favoured more: three chunks": (lambda b: b.at[:8].add(0.3),
                                                 3),
    # every choice of every token is held: the worst case, four full
    # chunks, tokens x k rows in all
    "every pair held: four chunks, full": (
        lambda b: b.at[jnp.array([3, 4, 5, 6])].add(10.0), 4),
}


@pytest.mark.parametrize("case", sorted(LOADS))
def test_however_many_chunks_the_load_takes_the_layer_is_the_references(case):
    """The held pairs go through the sorted buffer in as many chunks as
    they fill (``buffer_rows`` says how many rows that was), a group of
    rows split wherever a chunk ends, and however many it takes the
    layer is the reference's: its result and the gradient of every leaf
    (tokens, router, the three stacks; the bias has none), with no pair
    dropped."""
    from geomx_tpu.parallel.moe import chunk_rows

    bias, chunks = LOADS[case]
    layer, _ = _routed_layer(1)
    h = jax.random.normal(jax.random.PRNGKey(11), (2, 256, 16))
    held = {"router": layer["router"],
            "expert_bias": bias(layer["expert_bias"]),
            "experts": {n: w[:8] for n, w in layer["experts"].items()}}
    assert chunk_rows(2 * 256 * 4, 8, 64) == 512

    def program(l, h):
        y, route = routed_ffn(h, l["router"], l["expert_bias"], l["experts"],
                              first=0, k=4, compute_dtype=jnp.float32)
        return jnp.sum(y ** 2), (y, route)

    def plain(l, h):
        y = reference.expert_share(l, h, first=0)
        return jnp.sum(y ** 2), y

    (_, (y, route)), g = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(held, h)
    (_, y_ref), g_ref = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(held, h)
    assert (int(route["chunks"]), int(route["buffer_rows"])) == (
        chunks, 512 * chunks)
    pairs = int(route["held_pairs"])
    assert 512 * (chunks - 1) < pairs <= 512 * chunks
    assert int(route["rows"].sum()) == pairs            # none dropped
    if "full" in case:
        assert pairs == 512 * chunks
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    assert len(jax.tree_util.tree_leaves(g)) == 6
    for (path, a), r in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    assert not np.any(np.asarray(g[0]["expert_bias"]))


def test_a_chip_that_holds_every_expert_has_one_buffer_and_no_loop():
    """Where the stacks are the whole layer the worst case is the
    expected case: one chunk of tokens x k rows, and the lowered
    gradient holds no loop over chunks; one chip's share of eight has
    one a pass (the sort brings its own loops, in both)."""
    from geomx_tpu.parallel.moe import chunk_rows

    layer, h = _routed_layer(0)

    def lowered(held, h):
        return jax.jit(jax.grad(lambda e: jnp.sum(_share(
            {**layer, "experts": e}, h, 0, held)[0] ** 2))).lower(
            {n: w[:held] for n, w in layer["experts"].items()}).as_text()

    assert chunk_rows(2 * 24 * 4, 64, 64) == 192
    tall = jnp.tile(h, (1, 16, 1))                       # 768 tokens
    assert chunk_rows(2 * 384 * 4, 8, 64) == 768
    whole, share = lowered(64, h), lowered(8, tall)
    assert (share.count("stablehlo.while") - whole.count("stablehlo.while")
            == 2)                                        # forward, backward
    _, route = _share(layer, tall, 0, 64)
    assert int(route["chunks"]) == 1 and int(route["buffer_rows"]) == 3072


def test_the_megablox_path_is_the_ragged_path():
    """``expert_impl="gmm"``, what the chip runs, under the TPU
    interpreter against ``lax.ragged_dot``, forward and the stacks'
    gradient: its rows past the last group are uninitialised and have
    to be masked out of both.  (The layer itself, without its
    ``jax.checkpoint``, which the interpreter's callbacks cannot pass.)"""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    layer, _ = _routed_layer(2, d=128, fe=128, e_all=16)
    h = jax.random.normal(jax.random.PRNGKey(9), (1, 64, 128))

    def run(impl):
        def f(experts, h):
            y, _ = _routed_ffn(h, layer["router"], layer["expert_bias"],
                               experts, first=4, k=4, scale=1.0, impl=impl,
                               compute_dtype=jnp.float32)
            return jnp.sum(y ** 2), y
        experts = {n: w[4:8] for n, w in layer["experts"].items()}
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(
            experts, h)

    (_, y_r), (ge_r, gh_r) = run("ragged")
    with force_tpu_interpret_mode():
        (_, y_g), (ge_g, gh_g) = jax.tree_util.tree_map(
            np.asarray, run("gmm"))
    np.testing.assert_allclose(y_g, y_r, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gh_g, gh_r, rtol=1e-3, atol=1e-3)
    for n in ("w1", "w2", "w3"):
        np.testing.assert_allclose(ge_g[n], ge_r[n], rtol=1e-3, atol=1e-3)


def test_the_short_convolution_is_causal_and_the_references():
    cfg = FAMILY.system.config(TINY, "float32")
    params, _ = _build("float32")
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))
    y = tf._short_conv(cfg, layer, h)
    np.testing.assert_allclose(y, reference.short_conv(layer, h),
                               rtol=1e-5, atol=1e-6)
    later = h.at[:, 20:].add(jax.random.normal(jax.random.PRNGKey(6),
                                               (2, 12, 32)))
    y2 = tf._short_conv(cfg, layer, later)
    assert np.array_equal(np.asarray(y[:, :20]), np.asarray(y2[:, :20]))
    assert not np.allclose(y[:, 20:], y2[:, 20:])
    # the first outputs see zeros left of the sequence, not a wrap
    alone = tf._short_conv(cfg, layer, h[:, :1])
    np.testing.assert_allclose(alone, y[:, :1], rtol=1e-5, atol=1e-6)


def test_grouped_query_attention_with_norms_and_rotary_positions():
    """4 q heads over 2 k/v heads, q and k normed per head, rotary
    positions: the program's operator (all-float32 attention) against
    the reference's, which repeats k and v its own way."""
    cfg = FAMILY.system.config({**TINY, "attn_impl": "dense"}, "float32")
    params, _ = _build("float32")
    layer = dict(params["layers"][1])
    layer["q_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                    (8,))
    layer["k_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2),
                                                    (8,))
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 32))
    y = tf._attention(cfg, layer, h,
                      lambda q, k, v: tf._single_device_attention(cfg, q, k, v))
    np.testing.assert_allclose(y, reference.attention(layer, h),
                               rtol=2e-5, atol=2e-6)
    # the positions are relative: a rotation is undone by none, so the
    # same tokens later in the sequence attend alike only causally
    assert not np.allclose(tf._rope(h.reshape(2, 32, 4, 8), 1e6)[:, 1:],
                           h.reshape(2, 32, 4, 8)[:, 1:])
    np.testing.assert_array_equal(
        tf._rope(h.reshape(2, 32, 4, 8), 1e6)[:, 0],
        h.reshape(2, 32, 4, 8)[:, 0])


def test_flash_at_the_cuts_head_size_matches_dense_interpret():
    """The cut calls jax's flash kernels at a head of 64 (the flagship:
    128): the interpreted kernel, tiled by ``_flash_block_sizes(256,
    64)``, against the all-float32 attention, forward and backward."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from geomx_tpu.parallel.ring_attention import dense_attention

    cfg = tf.TransformerConfig(attn_impl="flash")
    sizes = tf._flash_block_sizes(8192, 64)
    assert (sizes.block_q, sizes.block_k_major_dkv, sizes.block_q_dq) == (
        512, 1024, 1024)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(key, (1, 256, 2, 64)) for key in ks)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    flash = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: tf._single_device_attention(cfg, q, k, v)),
        argnums=(0, 1, 2)))
    with force_tpu_interpret_mode():
        lf, gf = jax.tree_util.tree_map(np.asarray, flash(q, k, v))
    lr, gr = jax.value_and_grad(loss(
        lambda q, k, v: dense_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2))(q, k, v)
    assert float(lf) == pytest.approx(float(lr), rel=1e-4)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


# the flagship's gradient program as the parent commit lowered it (jax
# 0.9.0, CPU): the new config fields are all off there, and off must
# mean the same operations in the same order.  (sha256 of the lowering's
# text, its lines; regenerate with the loop below on a commit whose
# flagship is meant to differ.)
FLAGSHIP_LOWERINGS = {
    "dense": ("f046cb20fb045a7f", 756),
    "top-k capacity MoE": ("5df580ce889aea36", 967),
}


@pytest.mark.parametrize("kind", sorted(FLAGSHIP_LOWERINGS))
def test_the_flagships_lowered_gradient_program_is_unchanged(kind):
    moe = dict(moe_every=2, n_experts=4, moe_top_k=2) if "MoE" in kind else {}
    cfg = tf.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                               d_ff=64, max_seq=32, attn_impl="fast", **moe)
    p = jax.eval_shape(lambda k: tf.init_params(cfg, k), jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        grad_fn = tf.make_lm_grad_fn(cfg)
    text = grad_fn.lower(p, x, x).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text.splitlines())) == FLAGSHIP_LOWERINGS[kind]
    assert len(jax.eval_shape(grad_fn, p, x, x)) == 3    # no fourth value


# ---------------------------------------------------------------------------
# through the kvstore: a stacked expert leaf, its group, moe.route
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def trained():
    """Two FSA steps of the tiny model on ``Trainer.fit`` through both
    tiers (jax merge backend, global Adam), rounds sampled every second:
    step 0 is traced, step 1 is not."""
    from geomx_tpu import training
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    params, grad_fn = _build("float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    reads = []
    route_args = training._route_args

    def counted(route, tokens):
        reads.append(threading.current_thread().name)
        return route_args(route, tokens)

    training._route_args = counted
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        merge_backend="jax", trace_sample_every=2))
    out = {}
    try:
        def work(party):
            x = np.asarray(_tokens(2, seed=party))
            trainer = training.Trainer(
                sim.worker(party, 0), params, grad_fn,
                optimizer={"type": "adam", "lr": 0.01})
            hist = trainer.fit(iter([(x, x)] * 2), 2)
            out[party] = (trainer.params, hist)

        threads = [threading.Thread(target=work, args=(p,), daemon=True)
                   for p in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert len(out) == 2, "a worker hung or failed"
        sim.flush_traces()
        events = sim.trace_collector.merged_events()
    finally:
        training._route_args = route_args
        sim.shutdown()
    return params, out, events, reads


def test_a_stacked_expert_leaf_goes_through_both_tiers_with_party_parity(
        trained):
    params, out, _events, _ = trained
    a, b = (jax.tree_util.tree_leaves(out[p][0]) for p in range(2))
    assert all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))
    before = params["layers"][2]["experts"]["w1"]
    after = np.asarray(out[0][0]["layers"][2]["experts"]["w1"])
    assert after.shape == before.shape == (4, 32, 16)
    # Adam moved the stack, by a step of lr a round at most
    assert 0 < np.max(np.abs(after - before)) <= 2 * 0.01 * 1.001
    # the expert bias took no gradient: Adam left it where it was
    np.testing.assert_array_equal(
        np.asarray(out[0][0]["layers"][2]["expert_bias"]),
        params["layers"][2]["expert_bias"])
    assert out[0][1][1][0] < out[0][1][0][0]          # the loss fell


def test_every_span_that_carries_a_key_carries_its_group(trained):
    from geomx_tpu.kvstore.keys import leaf_groups

    params, _out, events, _ = trained
    groups = leaf_groups(params)
    assert groups.count("expert") == 12 and groups.count("dense") == 41
    keyed = [e for e in events if "key" in e["args"]]
    assert keyed and all(e["args"].get("group") in ("expert", "dense")
                         for e in keyed)
    for name in ("edge.d2h", "worker.push", "worker.pull", "local.push",
                 "global.push", "global.opt", "be.h2d"):
        mine = [e["args"] for e in keyed if e["name"] == name]
        experts = [a for a in mine if a["group"] == "expert"]
        # 12 stacks a worker (edge, worker.*) or a party (servers), in
        # the one sampled round
        assert len(experts) % 12 == 0 and experts, (name, len(experts))
        assert len(mine) > len(experts)
    # a worker's spans name the tensor id: the stacks are leaves 14-16
    # of layer 1 onwards, 4 x 32 x 16 floats each
    tids = {a["key"] for e in keyed if e["name"] == "worker.push"
            for a in [e["args"]] if a["group"] == "expert"}
    assert tids == {i for i, g in enumerate(groups) if g == "expert"}
    sizes = {e["args"]["nbytes"] for e in keyed
             if e["name"] == "edge.d2h" and e["args"]["group"] == "expert"}
    assert sizes == {4 * 4 * 32 * 16}


def test_moe_route_is_recorded_in_the_sampled_round_only(trained):
    _params, _out, events, reads = trained
    routes = [e for e in events if e["name"] == "moe.route"]
    # one a worker in step 0; in step 1 (unsampled) no span AND no read
    # of the counts to the host
    assert len(routes) == 2 and len(reads) == 2
    assert {e["pid"].split(":")[0] for e in routes} == {"worker"}
    for e in routes:
        a = e["args"]
        assert a["dropped"] == 0
        assert 0 < a["rows"] <= 4 * 64 * 4      # 4 layers x 64 tokens x 4
        assert a["max_over_mean"] >= 1.0
        assert 0.0 <= a["empty_pct"] <= 100.0
        # 64 tokens x 4 choices, 4 of 16 held: 128 rows would hold twice
        # an even router's load, less than one row tile of 256, so the
        # buffer is the 256 rows of all the pairs, one chunk a layer
        assert a["buffer_rows"] == 4 * 256 and a["chunks_max"] == 1
        assert a["fill_pct"] == pytest.approx(100.0 * a["rows"] / (4 * 256))
