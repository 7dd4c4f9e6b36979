"""Laguna through the program: the ``sliding_attention`` layer kind
(each query over the 512 keys ending at its own, ``ops/window_attention.py``)
among full attention layers, head counts by layer kind, a sigmoid gate
on each head's output, YaRN over half of each head on the full layers
and a whole default rotary on the windowed ones, a softmax router with
one shared expert; each against the family's plain reference
(``benchmark/families/laguna/reference.py``, which imports nothing of
the program) on seeded random weights at the rehearsal's sizes with a
window of 8 of 32 positions; the window kernels under the TPU
interpreter against masked dense attention; the shares of heads and
experts against the uncut layer; then one party through both kvstore
tiers against the reference's Adam steps, and the ``attn.window`` span
of a sampled round."""

import json
import math
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from benchmark.lib import family
from geomx_tpu import training
from geomx_tpu.models import transformer as tf
from geomx_tpu.ops import window_attention as wa
from geomx_tpu.parallel import moe

ROOT = Path(__file__).resolve().parents[1]
CELL = "laguna-s-2.1-ep32-tp4-l5-1chip"
FAMILY = family.load(ROOT, ["benchmark"], "laguna")
reference = FAMILY.reference
counts = FAMILY.counts
CONFIG = json.loads((ROOT / f"benchmark/configs/{CELL}.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}
WINDOW = 8
# the rehearsal's tiny sizes with a window below the sequence: 5 layers
# (dense + full, sliding x 3, full), 4 of 16 experts held, top 10, 12 /
# 18 q heads and 2 k/v heads of 8, 32 positions
TINY = {**CUT, **FAMILY.needs["rehearsal"], "sliding_window": WINDOW}


def _tokens(n=2, seed=0, T=None):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TINY["vocab"],
                                    (n, T or TINY["max_seq"])), jnp.int32)


def _interpreted(f):
    """``f`` with its kernels under the TPU interpreter, its result
    waited for before anything else is dispatched (the interpreter's
    callbacks deadlock against dispatches made while they are in
    flight)."""
    def call(*a):
        with force_tpu_interpret_mode():
            return jax.block_until_ready(f(*a))
    return call


def _build(dtype, **over):
    init, grad_fn = FAMILY.system.build({**TINY, **over}, dtype)
    return jax.jit(init)(jax.random.PRNGKey(3)), _interpreted(grad_fn)


def _worst(grads, ref_grads):
    out = {}
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        norm = float(jnp.linalg.norm(r))
        out[jax.tree_util.keystr(path)] = (
            float(jnp.linalg.norm(g - r)) / norm if norm else
            float(jnp.linalg.norm(g)))
    return out


def test_the_cut_holds_one_chips_share_at_the_published_widths():
    # at the published sizes: shapes alone, nothing is built
    cfg = FAMILY.system.config(CUT, "bfloat16")
    assert cfg.layer_types == (("full_attention",)
                               + ("sliding_attention",) * 3
                               + ("full_attention",))
    assert (cfg.n_heads, cfg.window_heads, cfg.kv_heads, cfg.head_dim) == (
        12, 18, 2, 128)
    assert (cfg.window, cfg.window_rope_theta, cfg.rope_theta,
            cfg.rope_dim) == (512, 1e4, 5e5, 64)
    assert cfg.yarn == tf.Yarn(128.0, 8192, 32.0, 1.0, 1.4852030263919618)
    assert (cfg.router_experts, cfg.n_experts, cfg.moe_top_k,
            cfg.routed_scale, cfg.router_score, cfg.n_shared_experts,
            cfg.n_dense_layers) == (256, 8, 10, 2.5, "softmax", 1, 1)
    assert cfg.head_gate and not cfg.tied_head and not cfg.router_grad
    tree = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    first, window = tree["layers"][0], tree["layers"][1]
    assert first["wq"].shape == (3072, 12, 128)
    assert first["wg"].shape == (3072, 12) and first["w1"].shape == (3072,
                                                                     12288)
    assert window["wq"].shape == (3072, 18, 128)
    assert window["wo"].shape == (18, 128, 3072)
    assert window["wk"].shape == (3072, 2, 128)
    assert window["experts"]["w1"].shape == (8, 3072, 1024)
    assert window["shared"]["w2"].shape == (1024, 3072)
    assert "expert_bias" not in window and "pos" not in tree
    assert tree["head"].shape == tree["embed"].shape == (12544, 3072)
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == counts.n_params(CUT) == 602_680_320
    # at 20.7 bytes a parameter (one party's peak, PERF.md): 12.48 GB
    assert 20.7 * count / 1e9 == pytest.approx(12.48, abs=0.005)


def test_the_counts_at_the_cut():
    """31 of the 136 causal 512 x 512 tiles at 8,192 positions; the
    attention layers a third of the forward FLOPs."""
    assert wa.tiles(8192, 512, 512) == (31, 136)
    assert counts.window_pairs(CUT) == wa.pairs(8192, 512) == (
        512 * 513 // 2 + (8192 - 512) * 512)
    fwd = counts.train_flops_per_token(CUT) / 3
    layers = counts._layers(CUT)
    attention = sum(2 * counts._attention_params(CUT, h)
                    + counts.attention_flops_per_token(CUT, k)
                    for k, h, _ in layers)
    assert attention / fwd == pytest.approx(0.334, abs=1e-3)
    # a windowed layer's core: 4.6 MFLOP a token where the causal
    # triangle would be 37.8
    assert counts.attention_flops_per_token(CUT, "sliding_attention") == (
        pytest.approx(4.57e6, rel=1e-3))
    assert 4 * 18 * 128 * (8192 + 1) / 2 == pytest.approx(37.75e6, rel=1e-3)


def _plain(q, k, v, window):
    """Grouped-query attention under the window's mask, float32 at
    ``highest``, every pair computed."""
    T, D = q.shape[1], q.shape[-1]
    kk, vv = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bthd,bshd->bhts", q, kk, precision="highest") / np.sqrt(D)
    t = jnp.arange(T)
    ok = (t[None] <= t[:, None]) & (t[None] > t[:, None] - window)
    a = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhts,bshd->bthd", a, vv, precision="highest")


@pytest.mark.parametrize("window", [5, 8, 12, 100])
def test_the_window_kernels_match_masked_dense_attention(window):
    """Forward and backward, tiles of 8 over 32 positions, 6 q heads over
    2 k/v heads: a window inside a tile, one tile wide, past one, and
    past the sequence (the causal triangle).  Float32 on both sides; what
    is left is the order of the sums."""
    B, T, H, G, D, tile = 2, 32, 6, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k, v = (jax.random.normal(key, (B, T, G, D)) for key in ks[1:3])
    do = jax.random.normal(ks[3], (B, T, H, D))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) * do)

    kernel = lambda q, k, v: wa.window_attention(   # noqa: E731
        q, k, v, window=window, tile=tile)
    o = _interpreted(jax.jit(kernel))(q, k, v)
    grads = _interpreted(jax.jit(jax.grad(loss(kernel), argnums=(0, 1, 2))))(
        q, k, v)
    np.testing.assert_allclose(o, _plain(q, k, v, window), atol=2e-6)
    want = jax.grad(loss(lambda q, k, v: _plain(q, k, v, window)),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(grads, want):
        np.testing.assert_allclose(g, w, atol=1e-5)
    visited, causal = wa.tiles(T, window, tile)
    assert causal == 10 and visited == {5: 7, 8: 7, 12: 9, 100: 10}[window]


def test_yarn_and_the_partial_rotary_are_the_formulas():
    """YaRN's 32 frequencies for Laguna's full layers written out from
    HF's definition (correction dims 9.04 -> 9 and 17.49 -> 18); the
    program's rotation turns the first 64 channels by them, cos and sin
    times the attention factor 0.1 ln 128 + 1, and passes the other 64;
    a sliding layer's turns all 128 at theta 1e4."""
    yarn = FAMILY.system.config(CUT, "float32").yarn
    assert yarn.attention_factor == pytest.approx(0.1 * math.log(128) + 1)
    dim, theta = 64, 5e5
    corr = [dim * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(theta))
            for r in (32, 1)]
    assert corr == pytest.approx([9.04, 17.49], abs=0.01)
    want = []
    for i in range(dim // 2):
        extra = theta ** (-2 * i / dim)
        ramp = min(max((i - 9) / (18 - 9), 0.0), 1.0)
        want.append(extra * (1 - ramp) + extra / 128 * ramp)
    np.testing.assert_allclose(yarn.inv_freq(theta, dim), want, rtol=1e-12)
    np.testing.assert_allclose(reference.yarn_inv_freq(dim), want,
                               rtol=1e-12)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 40, 3, 128))
    out = tf._rope(x, theta, dim, yarn)
    # float32 angles up to 40 radians and unit inputs: 2e-5 is a few
    # of float32's steps of them
    t = jnp.arange(40, dtype=jnp.float32)[:, None, None]
    ang = t * jnp.asarray(want, jnp.float32)
    c, s = (yarn.attention_factor * f(ang) for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :32], x[..., 32:64]
    np.testing.assert_allclose(out[..., :32], x1 * c - x2 * s, atol=2e-5)
    np.testing.assert_allclose(out[..., 32:64], x2 * c + x1 * s, atol=2e-5)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    whole = tf._rope(x, 1e4)
    ang = t * 1e4 ** (-jnp.arange(64, dtype=jnp.float32) / 64)
    np.testing.assert_allclose(
        whole[..., :64],
        x[..., :64] * jnp.cos(ang) - x[..., 64:] * jnp.sin(ang), atol=2e-5)
    np.testing.assert_allclose(
        reference.rotate(x, 1e4 ** (-np.arange(0, 128, 2) / 128)), whole,
        atol=2e-5)


@pytest.mark.parametrize("impl", ["fast", "flash"])
def test_float32_matches_the_reference_in_loss_and_every_gradient_leaf(impl):
    """Float32 compute on both sides, a window of 8: the dense path under
    the layers' checkpoint over 32 positions, or the kernels (jax's
    flash on the full layers, the window kernels on the sliding ones)
    under the TPU interpreter over 128, the least jax's flash tiles
    take.  2e-4 of a leaf's norm is the Kimi-Linear and Keye-VL
    families' tolerance; the gates take a gradient on both sides."""
    T = 128 if impl == "flash" else 32
    params, grad_fn = _build("float32", attn_impl=impl, max_seq=T,
                             remat=impl == "fast")
    x = _tokens(T=T)
    loss, _acc, grads, extra = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x),
                                          window=WINDOW)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    worst = _worst(grads, ref_grads)
    # embed, head, ln_f; the dense layer: 2 norms, 5 attention matrices
    # and 3 FFN ones; a routed layer: 2 norms, 5 attention matrices, the
    # router and 2 x 3 stacks
    assert len(worst) == 3 + 10 + 4 * 14
    assert max(worst.values()) < 2e-4, worst
    for g in (grads, ref_grads):
        layer = g["layers"][1]
        assert not np.any(np.asarray(layer["router"]))  # router_grad off
        assert float(jnp.linalg.norm(layer["wg"])) > 0
    att = extra["attn_window"]
    assert att["window"].tolist() == [0, WINDOW, WINDOW, WINDOW, 0]
    pairs = 2 * wa.pairs(T, WINDOW)
    assert att["pairs"].tolist() == [0, pairs, pairs, pairs, 0]
    assert att["gates"].tolist() == [2 * T * h for h in (12, 18, 18, 18, 12)]
    assert extra["moe_route"]["rows"].shape == (4, 4)


def test_bfloat16_stays_near_the_reference():
    """The configuration's compute dtype at 32 channels: a rounding moves
    a router's near-tie, and a flipped choice moves a whole expert's
    contribution; what holds at any size is that the loss is near and
    the float32 tolerance fails."""
    params, grad_fn = _build("bfloat16")
    x = _tokens()
    loss, _acc, grads, _ = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x),
                                          window=WINDOW)
    assert float(loss) == pytest.approx(float(ref_loss), abs=0.03)
    worst = _worst(grads, ref_grads)
    assert 2e-4 < max(worst.values()) < 1.6, worst


# ---------------------------------------------------------------------------
# the shares
# ---------------------------------------------------------------------------

def _uncut_layer(seed, d=16, dh=4, kv=8, experts=64, fe=8):
    """One routed layer of the whole deployment at a small size: 8 k/v
    heads (4 shares of 2), 64 experts (32 shares of 2), a shared
    expert."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 20))
    n = lambda shape, fan: (jax.random.normal(next(ks), shape)  # noqa: E731
                            / np.sqrt(fan))
    return lambda heads: {
        "ln1": 1 + 0.1 * n((d,), 1), "ln2": 1 + 0.1 * n((d,), 1),
        "wq": n((d, heads, dh), d), "wk": n((d, kv, dh), d),
        "wv": n((d, kv, dh), d), "wo": n((heads, dh, d), heads * dh),
        "wg": n((d, heads), d), "router": n((d, experts), d),
        "experts": {"w1": n((experts, d, fe), d), "w3": n((experts, d, fe), d),
                    "w2": n((experts, fe, d), fe)},
        "shared": {"w1": n((d, fe), d), "w3": n((d, fe), d),
                   "w2": n((fe, d), fe)}}


@pytest.mark.parametrize("kind, heads", [("sliding_attention", 72),
                                         ("full_attention", 48)])
def test_the_4_head_shares_and_32_expert_shares_are_the_uncut_layer(
        kind, heads):
    """A layer of the deployment over 32 chips: attention 4-way by k/v
    head groups (a quarter of the q heads and of the k/v heads each),
    experts 32-way.  The program's attention on each head share (its
    heads' part of ``wo``'s sum) and its routed layer on each expert
    share add up, with the shared expert counted once, to what the
    uncut reference gives for the whole block."""
    layer = _uncut_layer(1 if kind == "full_attention" else 2)(heads)
    T, q_share, kv_share = 32, heads // 4, 2
    x = jax.random.normal(jax.random.PRNGKey(7), (2, T, 16))
    i = 0 if kind == "full_attention" else 1
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x: reference.block(layer, x, i,
                                                  window=WINDOW))(x)
    cfg = tf.TransformerConfig(
        vocab=8, d_model=16, d_head=4, n_heads=q_share, window_heads=q_share,
        n_kv_heads=kv_share, n_layers=1, max_seq=T, layer_types=(kind,),
        window=WINDOW, window_rope_theta=reference.WINDOW_THETA,
        rope_theta=reference.FULL_THETA, rope_dim=2,
        yarn=FAMILY.system.config(CUT, "float32").yarn, head_gate=True,
        attn_impl="dense", compute_dtype=jnp.float32)

    def program(x):
        h = reference._rms(x, layer["ln1"])
        parts = []
        for r in range(4):
            q, kv = slice(r * q_share, (r + 1) * q_share), slice(
                2 * r, 2 * r + 2)
            share = {"wq": layer["wq"][:, q], "wk": layer["wk"][:, kv],
                     "wv": layer["wv"][:, kv], "wo": layer["wo"][q],
                     "wg": layer["wg"][:, q]}
            parts.append(tf._attention(
                cfg, share, h,
                lambda q, k, v: tf._single_device_attention(cfg, q, k, v),
                kind=kind))
        mid = x + sum(parts)
        h2 = reference._rms(mid, layer["ln2"])
        routed = [moe.routed_ffn(
            h2, layer["router"], None,
            {n: w[2 * s:2 * s + 2] for n, w in layer["experts"].items()},
            first=2 * s, k=reference.EXPERTS_PER_TOKEN,
            scale=reference.ROUTED_SCALE, compute_dtype=jnp.float32,
            score="softmax")[0] for s in range(32)]
        return (mid + sum(routed)
                + moe.shared_ffn(h2, layer["shared"], jnp.float32), parts[0],
                mid)

    with jax.default_matmul_precision("highest"):
        out, part, mid = jax.jit(program)(x)
    # float32 at ``highest`` on both sides: what is left is the order
    # of the sums (4 head shares and 32 expert shares against one)
    np.testing.assert_allclose(out, whole, rtol=1e-4, atol=2e-5)
    # one share alone is not the layer's attention
    attn = mid - x
    assert float(jnp.max(jnp.abs(part - attn))) > 0.1 * float(
        jnp.max(jnp.abs(attn)))


def test_a_window_needs_its_size_and_no_ring():
    with pytest.raises(ValueError, match="needs a window"):
        tf.TransformerConfig(n_layers=1, layer_types=("sliding_attention",))
    with pytest.raises(ValueError, match="do not divide"):
        tf.TransformerConfig(n_heads=4, n_kv_heads=2, window_heads=3,
                             window=4)
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2, 1),
                ("dp", "sp", "tp"))
    with pytest.raises(ValueError, match="no sliding_attention"):
        tf.make_apply(tf.TransformerConfig(
            n_layers=1, layer_types=("sliding_attention",), window=4), mesh)


# ---------------------------------------------------------------------------
# one party through both tiers; attn.window
# ---------------------------------------------------------------------------

LR = 3e-4       # the cell's


@pytest.fixture(scope="module")
def trained():
    """Two FSA steps of the tiny model on ``Trainer.fit`` through both
    tiers, ONE party of one worker (the cell's topology; jax merge
    backend, global Adam), rounds sampled every second: step 0 is
    traced, step 1 is not.  Attention as masked dense einsums, as the
    rehearsal runs it: the servers' threads dispatch while the worker's
    step runs."""
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    params, grad_fn = _build("float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    reads = []
    window_args = training._window_args

    def counted(att):
        reads.append(threading.current_thread().name)
        return window_args(att)

    training._window_args = counted
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        merge_backend="jax", trace_sample_every=2))
    batches = [np.asarray(_tokens(2, seed=s)) for s in range(2)]
    out = {}
    try:
        def work():
            trainer = training.Trainer(
                sim.worker(0, 0), params, grad_fn,
                optimizer={"type": "adam", "lr": LR})
            hist = trainer.fit(iter([(x, x) for x in batches]), 2)
            out["params"], out["hist"] = trainer.params, hist

        t = threading.Thread(target=work, daemon=True)
        t.start()
        t.join(300)
        assert not t.is_alive() and "hist" in out, "the worker hung or failed"
        sim.flush_traces()
        events = sim.trace_collector.merged_events()
    finally:
        training._window_args = window_args
        sim.shutdown()
    return params, batches, out, events, reads


def test_one_party_through_both_tiers_ends_at_the_references_adam_steps(
        trained):
    params, batches, out, _events, _ = trained
    losses, after = reference.train(params, batches, lr=LR,
                                    return_params=True, window=WINDOW)
    mine = [float(loss) for loss, _acc in out["hist"]]
    assert mine == pytest.approx(losses, abs=1e-4)
    flat = jax.tree_util.tree_flatten_with_path(out["params"])[0]
    for (path, a), b, w in zip(flat, jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(params)):
        # as in the Kimi-Linear family's test: Adam's first steps are lr
        # x the gradient's sign, and an element whose gradient is
        # rounding alone may go either way
        a, b = np.asarray(a), np.asarray(b)
        moved = np.linalg.norm(b - w)
        assert np.linalg.norm(a - b) <= 0.1 * moved + 1e-9, (
            jax.tree_util.keystr(path), np.linalg.norm(a - b), moved)
        assert np.max(np.abs(a - w)) <= 2 * LR * 1.001


def test_attn_window_is_recorded_in_the_sampled_round_only(trained):
    _params, _batches, _out, events, reads = trained
    spans = [e for e in events if e["name"] == "attn.window"]
    assert len(spans) == 1 and len(reads) == 1
    assert spans[0]["pid"].split(":")[0] == "worker"
    a = spans[0]["args"]
    assert (a["layers_window"], a["layers_full"], a["window"]) == (3, 2, 8)
    # the dense path computes every causal tile: one of 32 positions
    assert (a["tiles_visited"], a["tiles_causal"]) == (3 * 2, 3 * 2)
    assert a["tiles_visited_pct"] == 100.0
    assert a["pairs_in_window"] == 3 * 2 * wa.pairs(32, 8)
    assert 0 < a["gate_mean"] < 1 and 0 <= a["gate_closed_pct"] < 100


def test_window_args_of_skipped_tiles():
    """A full layer and a windowed one that computed 31 of 136 tiles;
    gates of 0.5 and 0.01."""
    row = lambda *v: jnp.asarray(v, jnp.int32)          # noqa: E731
    args = training._window_args({
        "window": row(0, 512), "tiles_visited": row(0, 31),
        "tiles_causal": row(0, 136), "pairs": row(0, 4_063_488),
        "gate_sum": jnp.asarray([2.0, 0.02]), "gate_closed": row(0, 2),
        "gates": row(4, 2)})
    assert args == {"layers_window": 1, "layers_full": 1, "window": 512,
                    "tiles_visited": 31, "tiles_causal": 136,
                    "tiles_visited_pct": pytest.approx(100 * 31 / 136),
                    "pairs_in_window": 4_063_488,
                    "gate_mean": pytest.approx(2.02 / 6),
                    "gate_closed_pct": pytest.approx(100 * 2 / 6)}
    assert args["tiles_visited_pct"] == pytest.approx(22.79, abs=0.01)
