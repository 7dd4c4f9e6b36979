"""Keye-VL-2.0's language model through the program (ISSUE 42): the
``dsa`` layer kind (grouped-query attention over the keys a lightning
indexer chose, ``ops/dsa.py``) with its indexer's KL term, a softmax
router renormalised over the chosen experts, an untied head, each
against the family's plain reference (``benchmark/families/keye_vl/
reference.py``, which imports nothing of the program and selects with
``lax.top_k``) on seeded random weights at the rehearsal's sizes with
``topk`` 8 of 32 positions; then one party through both kvstore tiers
against the reference's Adam steps, and the ``dsa.select`` span of a
sampled round."""

import json
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
from geomx_tpu.parallel import moe

ROOT = Path(__file__).resolve().parents[1]
CELL = "keye-vl-2.0-30b-a3b-ep8-l4-1chip"
FAMILY = family.load(ROOT, ["benchmark"], "keye_vl")
reference = FAMILY.reference
counts = FAMILY.counts
CONFIG = json.loads((ROOT / f"benchmark/configs/{CELL}.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}
TOPK = 8
# the rehearsal's tiny sizes with a topk below the sequence: 4 layers,
# 4 of 16 experts held, top 8, 32 positions in tiles of 8; the sparse
# attention as plain XLA, or its kernels under the TPU interpreter
TINY = {**CUT, **FAMILY.needs["rehearsal"]}
TINY["sa_config"] = {**TINY["sa_config"], "topk": TOPK}
# (the interpreter's callbacks are effects, which jax.checkpoint refuses)
KERNELS = {"dsa_impl": "pallas", "remat": False}
INDEXER = {"index_wq", "index_wk", "index_k_norm", "index_k_bias",
           "index_w"}


def _tokens(n=2, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TINY["vocab"], (n, TINY["max_seq"])),
                       jnp.int32)


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
    assert cfg.layer_types == ("dsa",) * 4
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 4, 128)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.index_tile) == (16, 64, 2048, 512)
    assert (cfg.router_experts, cfg.n_experts, cfg.moe_top_k,
            cfg.router_score) == (128, 16, 8, "softmax")
    tree = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    layer = tree["layers"][0]
    assert set(layer) == ({"ln1", "ln2", "wq", "wk", "wv", "wo", "q_norm",
                           "k_norm", "router", "experts"} | INDEXER)
    assert "expert_bias" not in layer and "pos" not in tree
    assert tree["head"].shape == tree["embed"].shape == (18992, 2048)
    assert layer["wq"].shape == (2048, 32, 128)
    assert layer["wk"].shape == (2048, 4, 128)
    assert layer["experts"]["w1"].shape == (16, 2048, 768)
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    assert count(layer) == 96_899_456                  # ISSUE 42
    assert count({n: layer[n] for n in INDEXER}) == 2_261_120
    assert count(tree) == 465_391_104 == counts.n_params(CUT)


def test_the_counts_at_the_cut():
    """23.4% of the causal pairs chosen at 16k; the indexer and the
    sparse attention near half a layer's forward FLOPs."""
    assert counts.chosen_pairs(CUT) / counts.causal_pairs(CUT) == (
        pytest.approx(0.234, abs=5e-4))
    d, T = 2048, 16384
    index = 2 * 16 * 64 * counts.causal_pairs(CUT)
    attend = 4 * 32 * 128 * counts.chosen_pairs(CUT)
    dense = 2 * T * (2 * d * 32 * 128 + 2 * d * 4 * 128
                     + 16 * 64 * d + 64 * d + 16 * d + 128 * d
                     + 3 * d * 768)
    assert (index + attend) / (index + attend + dense) == pytest.approx(
        0.48, abs=0.01)
    # at the tiny sizes every row past the 8th keeps 8
    assert counts.chosen_pairs(TINY) == 36 + 24 * 8


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_float32_matches_the_reference_in_loss_and_every_gradient_leaf(impl):
    """Float32 compute on both sides (the kernels under the TPU
    interpreter, or the plain XLA path): what is left is the order of
    the sums.  2e-4 of a leaf's norm is the tolerance of the Kimi-Linear
    family's same test; the indexer's leaves take the KL's gradient on
    both sides."""
    params, grad_fn = _build("float32", dsa_impl=impl,
                             remat=impl == "xla")
    x = _tokens()
    loss, _acc, grads, extra = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x), topk=TOPK)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-6)
    worst = _worst(grads, ref_grads)
    # embed, head, ln_f; a layer: 2 norms, 4 attention matrices, 2
    # q/k norms, 5 indexer leaves, the router and 3 stacks
    assert len(worst) == 3 + 4 * 17
    assert max(worst.values()) < 2e-4, worst
    for g in (grads, ref_grads):
        layer = g["layers"][1]
        assert not np.any(np.asarray(layer["router"]))  # router_grad off
        assert all(float(jnp.linalg.norm(layer[n])) > 0 for n in INDEXER)
    select = extra["dsa_select"]
    assert select["chosen_pairs"].tolist() == [2 * 228] * 4
    assert select["attended_pairs"].tolist() == [2 * 228] * 4
    assert select["tiles_causal"].tolist() == [2 * 10] * 4
    assert np.all(np.asarray(select["indexer_loss"]) > 0)
    assert extra["moe_route"]["rows"].shape == (4, 4)


def test_the_lm_loss_never_reaches_the_indexer_and_the_kl_nothing_else():
    """The program's two terms apart: the LM loss's gradient is zero on
    every indexer leaf, the indexer loss's zero everywhere else."""
    one = {**TINY, **KERNELS, "num_hidden_layers": 1}
    cfg = FAMILY.system.config(one, "float32")
    params = jax.jit(FAMILY.system.build(one, "float32")[0])(
        jax.random.PRNGKey(3))
    x = _tokens(1)
    apply_fn = tf.make_apply(cfg, return_route=True)

    def terms(p):
        logits, _route, seen = apply_fn(p, x)
        return (tf.token_cross_entropy(logits, x),
                jnp.sum(seen["dsa_select"]["indexer_loss"]))

    lm = _interpreted(jax.jit(jax.grad(lambda p: terms(p)[0])))(params)
    kl = _interpreted(jax.jit(jax.grad(lambda p: terms(p)[1])))(params)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(lm)[0],
                            jax.tree_util.tree_leaves(kl)):
        name = jax.tree_util.keystr(path)
        is_indexer = any(f"'{n}'" in name for n in INDEXER)
        assert not np.any(np.asarray(a if is_indexer else b)), name
        if is_indexer:
            assert float(jnp.linalg.norm(b)) > 0, name


def test_bfloat16_stays_near_the_reference():
    """The configuration's compute dtype at 32 channels: a rounding moves
    a router's or an indexer's near-tie, and a flipped choice moves a
    whole expert's or key's contribution; what holds at any size is
    that the loss is near and the float32 tolerance fails."""
    params, grad_fn = _build("bfloat16")
    x = _tokens()
    loss, _acc, grads, _ = grad_fn(params, x, x)
    ref_loss, ref_grads = reference.grads(params, np.asarray(x), topk=TOPK)
    assert float(loss) == pytest.approx(float(ref_loss), abs=0.03)
    worst = _worst(grads, ref_grads)
    assert 2e-4 < max(worst.values()) < 1.6, worst


def test_the_reference_one_precision_lower_fails_the_cells_tolerance():
    """``fsa-routed``'s ``loss_tol`` 0.008 against the reference's three
    warm-up losses: the reference with its weights and normed
    activations in float8 e4m3 (the precision below the configuration's
    bfloat16) is outside it, the float32 reference inside it of
    itself."""
    tol = json.loads((ROOT / "benchmark/traffic/fsa-routed.json")
                     .read_text())["correct"]["loss_tol"]
    params, _ = _build("float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    batches = [np.asarray(_tokens(2, seed=s)) for s in range(3)]
    exact = reference.train(params, batches, lr=3e-4, topk=TOPK)
    f8 = lambda a: jnp.asarray(a).astype(jnp.float8_e4m3fn).astype(  # noqa
        jnp.float32)
    rms = reference._rms
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(reference, "_rms", lambda x, s: f8(rms(x, s)))
        jax.clear_caches()
        lower = reference.train(jax.tree_util.tree_map(f8, params), batches,
                                lr=3e-4, topk=TOPK)
    jax.clear_caches()
    gaps = [abs(a - b) for a, b in zip(lower, exact)]
    assert max(gaps) > tol, gaps


# ---------------------------------------------------------------------------
# the router and the shares
# ---------------------------------------------------------------------------

def _routed_layer(seed, d=16, fe=8, e_all=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    n = lambda key, shape, fan: (jax.random.normal(key, shape)    # noqa: E731
                                 / np.sqrt(fan))
    return {
        "router": n(ks[0], (d, e_all), d),
        "experts": {"w1": n(ks[1], (e_all, d, fe), d),
                    "w3": n(ks[2], (e_all, d, fe), d),
                    "w2": n(ks[3], (e_all, fe, d), fe)},
    }, jax.random.normal(ks[4], (2, 24, d))


def test_the_softmax_router_renormalises_over_the_chosen():
    layer, h = _routed_layer(1)
    x = h.reshape(-1, 16)
    idx, weights = moe.route_topk(x, layer["router"], None, 8,
                                  score="softmax")
    r = np.asarray(jax.nn.softmax(x @ layer["router"], axis=-1))
    for t in range(x.shape[0]):
        top = np.argsort(-r[t])[:8]
        assert set(np.asarray(idx[t])) == set(top)
        np.testing.assert_allclose(
            weights[t], r[t, np.asarray(idx[t])] / r[t, top].sum(),
            rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    w = np.asarray(reference.router_weights(layer, x))
    np.testing.assert_allclose(
        np.take_along_axis(w, np.asarray(idx), -1), weights, rtol=1e-5)
    with pytest.raises(ValueError, match="selects by its scores alone"):
        moe.route_topk(x, layer["router"], jnp.zeros(32), 8,
                       score="softmax")


def test_the_8_shares_of_a_softmax_router_are_the_uncut_layer():
    """8 chips hold 4 of 32 experts each; a token's top 8 lie on up to 8
    of them.  The routed parts of all 8 shares add up to what the
    reference gives for the whole layer with every expert held (no
    shared expert: nothing is computed alike on every chip)."""
    layer, h = _routed_layer(0)
    whole = reference.expert_share(layer, h, first=0)
    parts = [moe.routed_ffn(
        h, layer["router"], None,
        {n: w[4 * i:4 * i + 4] for n, w in layer["experts"].items()},
        first=4 * i, k=reference.EXPERTS_PER_TOKEN,
        compute_dtype=jnp.float32, score="softmax") for i in range(8)]
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, rtol=2e-5,
                               atol=2e-6)
    # every (token, choice) pair landed on exactly one share
    assert sum(int(r["held_pairs"]) for _, r in parts) == 2 * 24 * 8
    # one share alone is not the layer
    assert float(jnp.max(jnp.abs(parts[0][0] - whole))) > 0.1


def test_the_sigmoid_router_is_the_default_and_keeps_its_bias():
    cfg = FAMILY.system.config(TINY, "float32")
    sigmoid = tf.TransformerConfig(**{
        **{f: getattr(cfg, f) for f in ("vocab", "max_seq", "d_model",
                                       "n_heads", "router_experts",
                                       "n_experts", "moe_top_k",
                                       "d_expert")},
        "n_layers": 1})
    assert sigmoid.router_score == "sigmoid"
    tree = jax.eval_shape(lambda k: tf.init_params(sigmoid, k),
                          jax.random.PRNGKey(0))
    assert tree["layers"][0]["expert_bias"].shape == (16,)
    with pytest.raises(ValueError, match="router_score"):
        tf.TransformerConfig(router_score="relu")


# ---------------------------------------------------------------------------
# the layer's checkpoint
# ---------------------------------------------------------------------------

def test_the_recompute_does_not_select_again():
    """Under ``remat`` the layer's checkpoint keeps the selection and
    what the forward kernel returned that the backward kernels read
    (``ops/dsa.py`` ``KEPT_NAMES``): the gradient program selects once a
    layer, runs the attention forward once (the recompute needs no
    kernel call, and jax's dead-code elimination drops it) and each
    backward kernel once.  Counted in the program's jaxpr, which needs
    no chip."""
    init, grad_fn = FAMILY.system.build(
        {**TINY, "dsa_impl": "pallas", "remat": True}, "float32")
    p = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, TINY["max_seq"]), jnp.int32)
    text = str(jax.make_jaxpr(grad_fn)(p, x, x))
    calls = {n: text.count(f"name={n}") for n in (
        "dsa_index", "dsa_attn_fwd", "dsa_attn_bwd_dq", "dsa_attn_bwd_dkv")}
    assert calls == {"dsa_index": 4, "dsa_attn_fwd": 4,
                     "dsa_attn_bwd_dq": 4, "dsa_attn_bwd_dkv": 4}, calls
    with pytest.raises(ValueError, match="dsa_impl"):
        FAMILY.system.config({**TINY, "dsa_impl": "interpret"}, "float32")


@pytest.mark.parametrize("impl, remat", [
    ("pallas", True), ("pallas", False), ("xla", True)])
def test_the_selects_counts_say_what_the_layers_checkpoint_keeps(impl, remat):
    """``_sparse_attention``'s ``kept_bytes`` and the ``dsa.select``
    span's ``kept_MB``: under ``remat`` the bytes of every value the
    layer's program names for the checkpoint (found in its jaxpr: the
    selection, ``o``, ``lse`` and ``lsei``), 0 without; the plain XLA
    path names nothing and keeps nothing."""
    from jax._src.ad_checkpoint import name_p

    from geomx_tpu.ops import dsa

    cfg = FAMILY.system.config({**TINY, "dsa_impl": impl, "remat": remat},
                               "float32")
    params, _ = _build("float32")
    layer = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 32))

    def select(layer, h):
        return tf._sparse_attention(cfg, layer, h)[1]

    named = {}
    for eqn in jax.make_jaxpr(select)(layer, h).eqns:
        if eqn.primitive is name_p and eqn.params["name"] in dsa.KEPT_NAMES:
            aval = eqn.outvars[0].aval
            named[eqn.params["name"]] = aval.size * aval.dtype.itemsize
    assert set(named) == (set(dsa.KEPT_NAMES) if impl == "pallas" else set())
    # o [2, 4, 32, 8] float32, lse [2, 32, 4], lsei, tau, sigma [2, 32,
    # 1], the tiles visited [2 x 4 x 4]
    assert sum(named.values()) == (
        4 * 2 * (4 * 32 * 8 + 32 * 4 + 3 * 32 + 16) if named else 0)
    kept = sum(named.values()) if remat else 0
    got = _interpreted(jax.jit(select))(layer, h)
    assert float(got["kept_bytes"]) == kept
    args = training._select_args(jax.tree_util.tree_map(
        lambda a: jnp.stack([a, a]), got))
    assert args["layers"] == 2
    assert args["kept_MB"] == pytest.approx(kept / 1e6)


# ---------------------------------------------------------------------------
# one party through both tiers; dsa.select
# ---------------------------------------------------------------------------

LR = 3e-4       # the cell's


@pytest.fixture(scope="module")
def trained():
    """Two FSA steps of the tiny model on ``Trainer.fit`` through both
    tiers, ONE party of one worker (the cell's topology; jax merge
    backend, global Adam), rounds sampled every second: step 0 is
    traced, step 1 is not.  The sparse attention as plain XLA, as the
    rehearsal runs it: the servers' threads dispatch while the worker's
    step runs."""
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    params, grad_fn = _build("float32")
    params = jax.tree_util.tree_map(np.asarray, params)
    reads = []
    select_args = training._select_args

    def counted(select):
        reads.append(threading.current_thread().name)
        return select_args(select)

    training._select_args = counted
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
        training._select_args = select_args
        sim.shutdown()
    return params, batches, out, events, reads


def test_one_party_through_both_tiers_ends_at_the_references_adam_steps(
        trained):
    params, batches, out, _events, _ = trained
    losses, after = reference.train(params, batches, lr=LR,
                                    return_params=True, topk=TOPK)
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
    # the indexer learned from its KL term through both tiers
    assert np.abs(np.asarray(out["params"]["layers"][0]["index_wq"])
                  - params["layers"][0]["index_wq"]).max() > 0


def test_dsa_select_is_recorded_in_the_sampled_round_only(trained):
    _params, _batches, _out, events, reads = trained
    spans = [e for e in events if e["name"] == "dsa.select"]
    assert len(spans) == 1 and len(reads) == 1
    assert spans[0]["pid"].split(":")[0] == "worker"
    a = spans[0]["args"]
    assert a["layers"] == 4
    assert a["chosen_pairs"] == a["attended_pairs"] == 4 * 2 * 228
    assert 0 < a["tiles_visited_pct"] <= 100.0
    assert a["indexer_loss"] > 0
    assert a["kept_MB"] == 0         # the plain XLA path names nothing
    routes = [e for e in events if e["name"] == "moe.route"]
    assert len(routes) == 1 and routes[0]["args"]["dropped"] == 0


def test_select_args_of_skipped_tiles():
    """Half the causal tiles skipped in one of two layers reads 75%."""
    row = lambda *v: jnp.asarray(v, jnp.int32)          # noqa: E731
    args = training._select_args({
        "chosen_pairs": row(10, 12), "attended_pairs": row(10, 12),
        "tiles_visited": row(10, 5), "tiles_causal": row(10, 10),
        "indexer_loss": jnp.asarray([1.5, 2.0]),
        "kept_bytes": jnp.asarray([2.5e6, 2.5e6], jnp.float32)})
    assert args == {"layers": 2, "chosen_pairs": 22, "attended_pairs": 22,
                    "tiles_visited_pct": 75.0, "indexer_loss": 3.5,
                    "kept_MB": 2.5}
