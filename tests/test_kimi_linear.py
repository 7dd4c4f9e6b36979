"""Kimi-Linear through the program (ISSUE 37): the delta-rule mixer and
latent attention among the layer types, no positions, an untied head, a
shared expert beside one chip's share of the routed ones, each against
the family's plain reference (``benchmark/families/kimi_linear/
reference.py``, which imports nothing of the program and steps the scan
a token at a time) on seeded random weights at tiny sizes; then one
party through both kvstore tiers against the reference's Adam step, and
the ``kda.scan`` span of a sampled round.  ISSUE 38: what the layer's
checkpoint keeps of the scan, against a plain ``jax.checkpoint`` and
against no checkpoint at all."""

import contextlib
import hashlib
import json
import subprocess
import sys
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import family
from geomx_tpu.models import transformer as tf
from geomx_tpu.parallel import moe

ROOT = Path(__file__).resolve().parents[1]
CELL = "kimi-linear-48b-a3b-ep32-l5-1chip"
FAMILY = family.load(ROOT, ["benchmark"], "kimi_linear")
reference = FAMILY.reference
CONFIG = json.loads((ROOT / f"benchmark/configs/{CELL}.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}
# the rehearsal's tiny sizes: the cut's 5 layers (kda with the dense
# FFN, kda, kda, mla, kda with experts), 4 of 16 experts held, top 8
TINY = {**CUT, **FAMILY.needs["rehearsal"]}


def _tokens(n=3, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TINY["vocab"], (n, TINY["max_seq"])),
                       jnp.int32)


def _build(dtype, **over):
    init, grad_fn = FAMILY.system.build({**TINY, **over}, dtype)
    return jax.jit(init)(jax.random.PRNGKey(3)), grad_fn


def _worst(grads, ref_grads):
    out = {}
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(ref_grads)):
        norm = float(jnp.linalg.norm(r))
        out[jax.tree_util.keystr(path)] = (
            float(jnp.linalg.norm(g - r)) / norm if norm else
            float(jnp.linalg.norm(g)))
    return out


KDA_LEAVES = {"wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a", "f_b",
              "dt_bias", "A_log", "w_beta", "g_a", "g_b", "o_norm", "wo"}
MLA_LEAVES = {"wq", "w_kv_a", "kv_norm", "w_kv_b", "wo"}
ROUTED = {"router", "expert_bias", "experts", "shared"}


def test_the_cut_runs_published_layers_1_to_5():
    # at the published sizes: shapes alone, nothing is built
    cfg = FAMILY.system.config(CUT, "bfloat16")
    assert cfg.layer_types == ("kda", "kda", "kda", "mla", "kda")
    assert [cfg.is_routed(i) for i in range(5)] == [False] + [True] * 4
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_kernel) == (32, 128,
                                                                      4)
    tree = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    assert "pos" not in tree                          # no positions
    assert tree["head"].shape == tree["embed"].shape == (20480, 2304)
    norms = {"ln1", "ln2"}
    assert set(tree["layers"][0]) == norms | KDA_LEAVES | {"w1", "w2", "w3"}
    assert set(tree["layers"][1]) == norms | KDA_LEAVES | ROUTED
    assert set(tree["layers"][3]) == norms | MLA_LEAVES | ROUTED
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    mixer = lambda i, names: count(                              # noqa: E731
        {n: tree["layers"][i][n] for n in names})
    assert mixer(1, KDA_LEAVES) == 39_514_272          # ISSUE 37
    assert mixer(3, MLA_LEAVES) == 29_114_880
    assert tree["layers"][3]["wq"].shape == (2304, 32, 192)
    assert tree["layers"][3]["w_kv_b"].shape == (512, 32, 256)
    assert tree["layers"][2]["experts"]["w1"].shape == (8, 2304, 1024)
    assert tree["layers"][2]["shared"]["w2"].shape == (1024, 2304)
    assert tree["layers"][2]["router"].shape == (2304, 256)
    assert count(tree) == 602_433_408 + 4 * 256
    # the seeded decays are the family's: A in [1, 16], steps in
    # [0.001, 0.1] through the inverse softplus
    params, _ = _build("float32")
    layer = params["layers"][0]
    assert 0.0 <= float(layer["A_log"].min()) <= float(
        layer["A_log"].max()) <= np.log(16.0)
    dt = np.asarray(jax.nn.softplus(layer["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


def test_float32_matches_the_reference_in_loss_and_every_gradient_leaf():
    """Float32 compute on both sides: what is left is the order of the
    sums (the scan in chunks of 16 against a token at a time, sorted
    grouped products against a masked loop).  2e-4 of a leaf's norm is
    five times what was seen (3.7e-5, the first layer's ``o_norm``) and
    a hundredth of what bfloat16 compute leaves (next test)."""
    params, grad_fn = _build("float32")
    x = _tokens()
    loss, _acc, grads, extra = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x))
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    worst = _worst(grads, ref_grads)
    # embed, head, ln_f; 2 norms a layer; 4 kda + 1 mla mixers; one
    # dense FFN; 4 routed FFNs of router, bias, 3 stacks, 3 shared
    assert len(worst) == 3 + 5 * 2 + 4 * 15 + 5 + 3 + 4 * 8
    assert max(worst.values()) < 2e-4, worst
    assert not np.any(np.asarray(grads["layers"][1]["expert_bias"]))
    # one share's part of the router's gradient is not stepped on
    for g in (grads, ref_grads):
        assert not np.any(np.asarray(g["layers"][1]["router"]))
    assert float(jnp.linalg.norm(grads["head"])) > 0     # untied: its own
    scan = extra["kda_scan"]
    assert scan["chunks"].tolist() == [3] * 4             # 48 / 16
    assert scan["chunk"].tolist() == [16] * 4
    assert np.all(np.asarray(scan["log_decay_min"]) < 0)
    assert extra["moe_route"]["rows"].shape == (4, 4)


def test_bfloat16_stays_near_the_reference_and_fails_the_float32_tolerance():
    """The configuration's compute dtype.  As in LFM2's family a router
    score moved by 2^-8 flips a token's eighth choice (of 16 here), and
    a flipped token moves a whole expert's contribution; at 32 channels
    a bfloat16 rounding is a large share of a sum, and four scans deep
    the leaves of the first layer differ by their whole norm (seen: 1.17
    at worst, 0.39 at 128 channels, 0.02 for one kda layer alone there;
    the scan itself is within 0.5% at the published head size:
    ``tests/test_kda.py``).  What holds at any size: the loss is near,
    and the float32 tolerance fails."""
    params, grad_fn = _build("bfloat16")
    x = _tokens()
    loss, _acc, grads, _ = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x))
    assert float(loss) == pytest.approx(float(ref_loss), abs=0.03)
    worst = _worst(grads, ref_grads)
    assert 2e-4 < max(worst.values()) < 1.6, worst
    assert float(np.median(list(worst.values()))) < 0.7, worst


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------

def _routed_layer(seed, d=16, fe=8, e_all=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    n = lambda key, shape, fan: (jax.random.normal(key, shape)    # noqa: E731
                                 / np.sqrt(fan))
    return {
        "router": n(ks[0], (d, e_all), d),
        "expert_bias": 0.002 * jax.random.normal(ks[1], (e_all,)),
        "experts": {"w1": n(ks[2], (e_all, d, fe), d),
                    "w3": n(ks[3], (e_all, d, fe), d),
                    "w2": n(ks[4], (e_all, fe, d), fe)},
        "shared": {"w1": n(ks[5], (d, fe), d), "w3": n(ks[6], (d, fe), d),
                   "w2": n(ks[7], (fe, d), fe)},
    }, jax.random.normal(ks[8], (2, 24, d))


def test_the_32_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """32 chips hold one expert of 32 each; a token's top 8 lie on 8 of
    them.  The routed parts of all 32 shares, and the shared expert
    counted ONCE (every chip computes it alike), add up to what the
    reference gives for the whole layer with every expert held."""
    layer, h = _routed_layer(0)
    whole = (reference.expert_share(layer, h, first=0)
             + reference.shared_expert(layer, h))
    share = lambda i, shared: moe.routed_ffn(               # noqa: E731
        h, layer["router"], layer["expert_bias"],
        {n: w[i:i + 1] for n, w in layer["experts"].items()}, first=i,
        k=reference.EXPERTS_PER_TOKEN, scale=reference.ROUTED_SCALE,
        compute_dtype=jnp.float32, shared=shared)
    parts = [share(i, None) for i in range(32)]
    routed = sum(y for y, _ in parts)
    shared = moe.shared_ffn(h, layer["shared"], jnp.float32)
    np.testing.assert_allclose(routed + shared, whole, rtol=2e-5, atol=2e-6)
    # every (token, choice) pair landed on exactly one share
    assert sum(int(r["held_pairs"]) for _, r in parts) == 2 * 24 * 8
    # a share with the shared expert is its routed part plus the shared
    y0, _ = share(0, layer["shared"])
    np.testing.assert_allclose(y0, parts[0][0] + shared, rtol=2e-5,
                               atol=2e-6)
    # leaving the shared expert out fails parity, by its whole size
    assert float(jnp.max(jnp.abs(routed - whole))) > 0.1
    # and summing it on every share would count it 32 times
    assert not np.allclose(routed + 32 * shared, whole, atol=1e-2)


def test_the_shared_expert_takes_a_gradient_through_the_checkpoint():
    layer, h = _routed_layer(1, e_all=8)
    held = {n: w[:4] for n, w in layer["experts"].items()}

    def mine(shared, experts):
        y, _ = moe.routed_ffn(h, layer["router"], layer["expert_bias"],
                              experts, first=0, k=4, scale=2.446,
                              compute_dtype=jnp.float32, shared=shared)
        return jnp.sum(y ** 2)

    def ref(shared, experts):
        lay = {**layer, "shared": shared, "experts": experts}
        w = reference.router_weights.__globals__
        old = w["EXPERTS_PER_TOKEN"]
        w["EXPERTS_PER_TOKEN"] = 4
        try:
            y = (reference.expert_share(lay, h, first=0)
                 + reference.shared_expert(lay, h))
        finally:
            w["EXPERTS_PER_TOKEN"] = old
        return jnp.sum(y ** 2)

    a = jax.grad(mine, argnums=(0, 1))(layer["shared"], held)
    b = jax.grad(ref, argnums=(0, 1))(layer["shared"], held)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("router_grad", [True, False])
def test_routing_weights_as_constants_of_the_backward_pass(router_grad):
    """``router_grad=False``: the same layer forward, no gradient for the
    router and none into ``x`` through the scores: what the reference
    gives, whose weights are constants of the backward pass.  The
    default still trains the router and differs."""
    layer, h = _routed_layer(2)
    held = {n: w[:8] for n, w in layer["experts"].items()}

    def loss(h, router, **kw):
        y, _ = moe.routed_ffn(
            h, router, layer["expert_bias"], held, first=0,
            k=reference.EXPERTS_PER_TOKEN, scale=reference.ROUTED_SCALE,
            compute_dtype=jnp.float32, shared=layer["shared"], **kw)
        return jnp.sum(y ** 2)

    def ref(h, router):
        lay = {**layer, "router": router, "experts": held}
        return jnp.sum((reference.expert_share(lay, h, first=0)
                        + reference.shared_expert(lay, h)) ** 2)

    assert float(loss(h, layer["router"])) == float(
        loss(h, layer["router"], router_grad=False))
    dh, dr = jax.grad(loss, argnums=(0, 1))(h, layer["router"],
                                            router_grad=router_grad)
    ref_dh, ref_dr = jax.grad(ref, argnums=(0, 1))(h, layer["router"])
    assert not np.any(np.asarray(ref_dr))
    assert bool(np.any(np.asarray(dr))) == router_grad
    gap = float(jnp.max(jnp.abs(dh - ref_dh)))
    assert (gap > 1e-2) if router_grad else (gap < 2e-5), gap


@pytest.mark.parametrize("field", [
    {"no_positions": True},
    {"layer_types": ("conv", "full_attention")},
    {"router_experts": 8, "n_experts": 2, "d_expert": 16, "moe_top_k": 2},
])
def test_the_staged_model_refuses_what_its_stages_do_not_run(field):
    """``make_staged``'s embedding stage adds learned positions and its
    layer stages are attention with a dense FFN: a config composed
    otherwise is refused, not run as something else."""
    cfg = tf.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                               d_ff=64, max_seq=16, **field)
    with pytest.raises(ValueError, match="make_staged"):
        tf.make_staged(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the mixers on their own
# ---------------------------------------------------------------------------

def test_the_kda_mixer_matches_the_reference_and_is_causal():
    cfg = FAMILY.system.config(TINY, "float32")
    params, _ = _build("float32")
    layer = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32))
    y, scan = tf._kda(cfg, layer, h)
    np.testing.assert_allclose(y, reference.kda(layer, h), rtol=2e-4,
                               atol=2e-5)
    assert int(scan["chunks"]) == 3 and float(scan["state_bytes"]) == (
        2 * 4 * 8 * 8 * 4)
    later = h.at[:, 30:].add(1.0)
    y2, _ = tf._kda(cfg, layer, later)
    np.testing.assert_allclose(y[:, :30], y2[:, :30], rtol=1e-5, atol=1e-6)
    assert not np.allclose(y[:, 30:], y2[:, 30:])
    # the reference's scan in blocks is the scan: any block, one result
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 21, 2, 4))
    g = -jax.random.uniform(jax.random.PRNGKey(7), (1, 21, 2, 4))
    beta = jax.nn.sigmoid(q[..., 0])
    whole = reference.delta_scan(q, q, q, g, beta, block=21)
    for block in (4, 8):
        np.testing.assert_allclose(
            reference.delta_scan(q, q, q, g, beta, block=block), whole,
            rtol=1e-5, atol=1e-6)


def test_latent_attention_matches_the_reference():
    cfg = FAMILY.system.config({**TINY, "attn_impl": "dense"}, "float32")
    params, _ = _build("float32")
    layer = dict(params["layers"][3])
    layer["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                                     (16,))
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 48, 32))
    y = tf._latent_attention(
        cfg, layer, h, lambda q, k, v: tf._single_device_attention(cfg, q, k,
                                                                   v))
    np.testing.assert_allclose(y, reference.latent_attention(layer, h,
                                                             block=16),
                               rtol=2e-5, atol=2e-6)


def test_flash_with_192_wide_q_k_and_128_wide_v_matches_dense_interpret():
    """The latent layer's head widths through jax's flash kernels, which
    take one width, a multiple of 128 past 128: q, k and v padded with
    zeros to 256 (``_single_device_attention``), tiled by
    ``_flash_block_sizes(., 256)``, the softmax scale 192^-1/2; the
    interpreted kernels against all-float32 attention, forward and
    backward."""
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from geomx_tpu.parallel.ring_attention import dense_attention

    cfg = tf.TransformerConfig(attn_impl="flash")
    sizes = tf._flash_block_sizes(8192, 256)
    assert (sizes.block_q, sizes.block_k_major_dkv, sizes.block_q_dq) == (
        512, 1024, 1024)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k = (jax.random.normal(key, (1, 256, 2, 192)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, 256, 2, 128))

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
    assert [g.shape[-1] for g in gf] == [192, 192, 128]
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("shape, tiling", [
    ((8192, 2048, 1536), (256, 2048, 768)),      # LFM2's, as swept (PR 35)
    ((8192, 1536, 2048), (256, 2048, 768)),      # its w2: one tiling a layer
    ((4096, 2304, 1024), (256, 1152, 1024)),     # Kimi-Linear's
    ((4096, 1024, 2304), (256, 1152, 1024)),
    ((96, 32, 16), (32, 32, 16)),                # under a tile: the shape
])
def test_the_grouped_products_tiling_follows_the_shape(shape, tiling):
    assert moe.gmm_tiling(*shape) == tiling
    # the buffer stays twice an even router's rows (PR 36's rule)
    assert moe.chunk_rows(8192 * 8, 8, 256) == 4096
    assert moe.chunk_rows(8192 * 4, 8, 64) == 8192      # LFM2's


# LFM2's gradient program as the parent commit lowered it (jax 0.9.0,
# CPU; the rehearsal's sizes): the fields this PR adds are all off
# there, and off must mean the same operations in the same order.
LFM2_LOWERINGS = {"float32": ("b1272196f5804ea5", 5697),
                  "bfloat16": ("9dd2c8384f80d371", 6011)}


@pytest.mark.parametrize("dtype", sorted(LFM2_LOWERINGS))
def test_lfm2s_lowered_gradient_program_is_unchanged(dtype):
    lfm2 = family.load(ROOT, ["benchmark"], "lfm2_moe")
    config = json.loads((ROOT / "benchmark/configs/"
                         "lfm2-24b-a2b-ep8-l5-1chip.json").read_text())
    tiny = {**{k: config[k] for k in (*family.MODEL_KEYS,
                                      *lfm2.needs["keys"])},
            **lfm2.needs["rehearsal"]}
    init, grad_fn = lfm2.system.build(tiny, dtype)
    p = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((2, tiny["max_seq"]), jnp.int32)
    text = grad_fn.lower(p, x, x).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()[:16],
            len(text.splitlines())) == LFM2_LOWERINGS[dtype]


# ---------------------------------------------------------------------------
# what the layer's checkpoint keeps (ISSUE 38)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _plain_checkpoint():
    """``jax.checkpoint`` without whatever policy it is given: the
    layer's checkpoint as it was before the scan named anything."""
    checkpoint = jax.checkpoint
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "checkpoint",
                      lambda f, policy=None, **kw: checkpoint(f, **kw))
        yield


LONG = 256      # 16 chunks of 16: four blocks of BLOCK_CHUNKS a scan


def _loops(jaxpr, depth=0):
    """``[(depth, equations in the body)]`` of every ``scan`` and
    ``while`` of a jaxpr, by how many loops it lies inside: each
    occurrence counts (the lowered text holds a function once however
    often it is called)."""
    out = []
    for eqn in jaxpr.eqns:
        loop = eqn.primitive.name in ("scan", "while")
        if loop:
            body = eqn.params.get("jaxpr", eqn.params.get("body_jaxpr"))
            out.append((depth, len(body.jaxpr.eqns)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _loops(sub, depth + loop)
    return out


@pytest.fixture(scope="module")
def long_grads():
    """Loss, gradient, the scans' counts, the gradient program's loops
    and the ``stablehlo.while`` of its lowered text, of the tiny model over 256 positions in
    float32, by what surrounds a layer: ``make_apply``'s checkpoint with
    its policy (``remat`` true, the cell's), a plain ``jax.checkpoint``
    of the layer, and nothing (``remat`` false)."""
    x = jnp.asarray(np.random.default_rng(0).integers(
        0, TINY["vocab"], (2, LONG)), jnp.int32)
    out = {}

    def run(name, remat):
        params, grad_fn = _build("float32", max_seq=LONG, remat=remat)
        traced = grad_fn.trace(params, x, x)        # traced once
        lowered = traced.lower()
        loss, _acc, grads, extra = lowered.compile()(params, x, x)
        out[name] = {
            "loss": float(loss), "grads": grads, "scan": extra["kda_scan"],
            "loops": _loops(traced.jaxpr.jaxpr),
            "whiles": lowered.as_text().count("stablehlo.while")}

    run("policy", True)
    run("none", False)
    with _plain_checkpoint():
        run("plain", True)
    return out


def test_the_kept_scan_is_the_recomputed_one_to_the_bit(long_grads):
    """The saved output and states are the values the layer's recompute
    would have produced again: against a plain ``jax.checkpoint`` of the
    layer the loss and every gradient leaf are equal to the bit."""
    mine, plain = long_grads["policy"], long_grads["plain"]
    assert mine["loss"] == plain["loss"]
    flat = jax.tree_util.tree_flatten_with_path(mine["grads"])[0]
    assert len(flat) == 113
    for (path, g), r in zip(flat, jax.tree_util.tree_leaves(plain["grads"])):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=jax.tree_util.keystr(path))


def test_remat_leaves_the_gradient_within_the_files_tolerance(long_grads):
    mine, free = long_grads["policy"], long_grads["none"]
    assert mine["loss"] == pytest.approx(free["loss"], abs=2e-6)
    worst = _worst(mine["grads"], free["grads"])
    assert max(worst.values()) < 2e-4, worst


def test_the_scan_runs_twice_a_layer_under_the_layers_checkpoint(long_grads):
    """The loops of the gradient program.  A KDA layer's forward scan is
    a loop over blocks with a loop over a block's chunks inside; its
    backward scan a loop over blocks whose body runs the block again (a
    chunk loop) and then backward (another).  A plain checkpoint's
    recompute of the layer adds the forward scan a third time; with the
    states and the output kept, what is left of it is a loop over blocks
    with an empty body.  Beside them a loop forward and a loop backward
    a routed layer."""
    loops = {k: v["loops"] for k, v in long_grads.items()}
    total = {k: len(v) for k, v in loops.items()}
    assert total == {"none": 4 * 5 + 8, "plain": 4 * 7 + 8,
                     "policy": 4 * 6 + 8}                   # 28, 36, 32
    chunk_loops = {k: sum(d == 1 for d, _ in v) for k, v in loops.items()}
    assert chunk_loops == {"none": 4 * 3, "plain": 4 * 4, "policy": 4 * 3}
    empty = {k: sum(n == 0 for _, n in v) for k, v in loops.items()}
    assert empty == {"none": 0, "plain": 0, "policy": 4}
    # the lowered text: one loop fewer a KDA layer than under the plain
    # checkpoint (the block's checkpointed function is lowered once)
    assert long_grads["policy"]["whiles"] == long_grads["plain"]["whiles"] - 4
    # what is kept: a float32 state a block and the output, a layer
    kept = 4 * 2 * 4 * 8 * 8 * 4 + 2 * LONG * 4 * 8 * 4
    for name, want in (("policy", kept), ("plain", kept), ("none", 0)):
        scan = long_grads[name]["scan"]
        assert scan["kept_bytes"].tolist() == [want] * 4
        assert scan["state_bytes"].tolist() == [4 * 2 * 4 * 8 * 8 * 4] * 4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_without_kda_layers_the_policy_is_a_plain_checkpoint(dtype):
    """LFM2's family (short convolutions, grouped-query attention, a
    dense and four routed FFNs: no ``kda`` layer) with ``remat`` true:
    the names match nothing, so the lowered gradient program is, text
    for text, the one under a plain ``jax.checkpoint`` of the layer."""
    lfm2 = family.load(ROOT, ["benchmark"], "lfm2_moe")
    config = json.loads((ROOT / "benchmark/configs/"
                         "lfm2-24b-a2b-ep8-l5-1chip.json").read_text())
    tiny = {**{k: config[k] for k in (*family.MODEL_KEYS,
                                      *lfm2.needs["keys"])},
            **lfm2.needs["rehearsal"], "remat": True}
    x = jax.ShapeDtypeStruct((2, tiny["max_seq"]), jnp.int32)

    def lowered():
        init, grad_fn = lfm2.system.build(tiny, dtype)
        return grad_fn.lower(jax.eval_shape(init, jax.random.PRNGKey(0)),
                             x, x).as_text()

    with_policy = lowered()
    with _plain_checkpoint():
        assert lowered() == with_policy
    assert "optimization_barrier" in with_policy     # it is checkpointed


@pytest.mark.parametrize("remat, kept", [
    (True, 2 * 4 * 8 * 8 * 4 + 2 * 48 * 4 * 8 * 4), (False, 0)])
def test_the_scans_counts_say_what_the_layers_checkpoint_keeps(remat, kept):
    """``_kda``'s ``kept_bytes`` and the ``kda.scan`` span's ``kept_MB``:
    the float32 states (one block of 3 chunks, 2 sequences, 4 heads of 8
    x 8) and the output (2 x 48 x 4 x 8 float32) under ``remat``, 0
    without; ``state_MB`` is the scan's own either way."""
    from geomx_tpu import training

    cfg = FAMILY.system.config({**TINY, "remat": remat}, "float32")
    params, _ = _build("float32")
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 48, 32))
    _, scan = tf._kda(cfg, params["layers"][1], h)
    assert float(scan["kept_bytes"]) == kept
    args = training._scan_args(jax.tree_util.tree_map(
        lambda a: jnp.stack([a, a]), scan))
    assert args["layers"] == 2
    assert args["kept_MB"] == pytest.approx(kept / 1e6)
    assert args["state_MB"] == pytest.approx(2 * 4 * 8 * 8 * 4 / 1e6)


# ---------------------------------------------------------------------------
# one party through both tiers; kda.scan
# ---------------------------------------------------------------------------

LR = 3e-4       # the cell's


@pytest.fixture(scope="module")
def trained():
    """Two FSA steps of the tiny model on ``Trainer.fit`` through both
    tiers, ONE party of one worker (the cell's topology; jax merge
    backend, global Adam), rounds sampled every second: step 0 is
    traced, step 1 is not."""
    from geomx_tpu import training
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    params, grad_fn = _build("float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    reads = []
    scan_args = training._scan_args

    def counted(scan):
        reads.append(threading.current_thread().name)
        return scan_args(scan)

    training._scan_args = counted
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
        assert "hist" in out, "the worker hung or failed"
        sim.flush_traces()
        events = sim.trace_collector.merged_events()
    finally:
        training._scan_args = scan_args
        sim.shutdown()
    return params, batches, out, events, reads


def test_one_party_through_both_tiers_ends_at_the_references_adam_steps(
        trained):
    params, batches, out, _events, _ = trained
    losses, after = reference.train(params, batches, lr=LR,
                                    return_params=True)
    mine = [float(loss) for loss, _acc in out["hist"]]
    # the second loss is after an Adam step, which turns rounding in a
    # small gradient into a whole step of lr (seen: 2.2e-5)
    assert mine == pytest.approx(losses, abs=1e-4)
    flat = jax.tree_util.tree_flatten_with_path(out["params"])[0]
    for (path, a), b, w in zip(flat, jax.tree_util.tree_leaves(after),
                               jax.tree_util.tree_leaves(params)):
        # Adam's first steps are lr x the gradient's sign: an element
        # whose gradient is rounding alone may go either way, so a leaf
        # is held by its whole move, of which a tenth may differ (seen: 3.7%)
        a, b = np.asarray(a), np.asarray(b)
        moved = np.linalg.norm(b - w)
        assert np.linalg.norm(a - b) <= 0.1 * moved + 1e-9, (
            jax.tree_util.keystr(path), np.linalg.norm(a - b), moved)
        assert np.max(np.abs(a - w)) <= 2 * LR * 1.001
    # the expert bias took no gradient: Adam left it where it was
    np.testing.assert_array_equal(
        np.asarray(out["params"]["layers"][2]["expert_bias"]),
        params["layers"][2]["expert_bias"])
    moved = np.abs(np.asarray(out["params"]["layers"][0]["A_log"])
                   - params["layers"][0]["A_log"])
    assert 0 < moved.max() <= 2 * LR * 1.001


def test_kda_scan_is_recorded_in_the_sampled_round_only(trained):
    _params, _batches, _out, events, reads = trained
    scans = [e for e in events if e["name"] == "kda.scan"]
    # one worker, step 0 sampled; in step 1 no span AND no read of the
    # counts to the host
    assert len(scans) == 1 and len(reads) == 1
    assert scans[0]["pid"].split(":")[0] == "worker"
    a = scans[0]["args"]
    assert (a["layers"], a["chunk"], a["chunks"]) == (4, 16, 3)
    # one float32 state of 4 heads x 8 x 8 a block (3 chunks: one block)
    # and sequence (2)
    assert a["state_MB"] == pytest.approx(2 * 4 * 8 * 8 * 4 / 1e6)
    # the cell's remat: the layer's checkpoint keeps those and the output
    assert a["kept_MB"] == pytest.approx(
        (2 * 4 * 8 * 8 * 4 + 2 * 48 * 4 * 8 * 4) / 1e6)
    assert a["log_decay_min"] < 0
    routes = [e for e in events if e["name"] == "moe.route"]
    assert len(routes) == 1 and routes[0]["args"]["dropped"] == 0
    # the leaves' groups: twelve stacks are 'expert', the shared
    # expert's and the mixers' many small leaves 'dense'
    from geomx_tpu.kvstore.keys import leaf_groups

    groups = leaf_groups(_params)
    assert groups.count("expert") == 12
    assert groups.count("dense") == 113 - 12


def test_the_cell_rehearses_on_the_cpu():
    """``benchmark/run.py --rehearse`` of the new cell: the harness's
    control flow at the family's tiny sizes, the reference's check
    included."""
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL + ".fsa",
         "--seed", "3000000019", "--seconds", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failures"] == []
    assert line["tokens_per_step"] == 48                  # one party
    assert set(line["metrics"]) >= {"rehearsal_wan_MB_per_step",
                                    "rehearsal_setup_s"}
