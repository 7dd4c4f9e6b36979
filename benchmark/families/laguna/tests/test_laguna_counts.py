"""Laguna's counts against hand counts, against a parameter
tree that the family's ``system.py`` builds from the seed, and the
roofline's arithmetic on the kernel functions."""

import json
from pathlib import Path

import jax
import pytest

from benchmark.lib import family, roofline

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "laguna")
counts = FAMILY.counts
CONFIG = json.loads((ROOT / "benchmark/configs/"
                     "laguna-s-2.1-ep32-tp4-l5-1chip.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_count_of_the_cut():
    # embedding and head 2 x 38,535,168 + final norm 3,072 + 5 x 6,144 of
    # layer norms; attention (wq, wo, gate, wk, wv) 11,046,912 on a full
    # layer (12 q heads) and 15,777,792 on a sliding one (18); the dense
    # FFN 113,246,208; 4 routed FFNs of 786,432 (router) + 75,497,472 (8
    # experts) + 9,437,184 (shared) = 602,680,320
    full, slide = 11_046_912, 15_783_936
    assert counts._attention_params(CUT, 12) == full
    assert counts._attention_params(CUT, 18) == slide
    routed = 3072 * 256 + 9 * 3 * 3072 * 1024
    total = (2 * 12544 * 3072 + 3072 + 5 * 6144 + 2 * full + 3 * slide
             + 3 * 3072 * 12288 + 4 * routed)
    assert counts.n_params(CUT) == total == 602_680_320
    # the sixteen stacks: half of it
    assert counts.n_expert_params(CUT) == 4 * 8 * 3 * 3072 * 1024
    # the model up and down the WAN once a step, one party
    assert 2 * 4 * counts.n_params(CUT) / 1e6 == pytest.approx(4821.44,
                                                               abs=0.01)


@pytest.mark.parametrize("sizes", [
    {"rehearsal": True}, {"rehearsal": True, "num_experts": 2,
                          "first_expert": 6},
    {"rehearsal": True, "num_hidden_layers": 9, "first_layer": 0},
    {"rehearsal": True, "num_hidden_layers": 4, "first_layer": 3},
    {}], ids=["rehearsal", "another-share", "two-periods", "from-layer-3",
              "the-cell"])
def test_parameter_count_is_the_tree_that_system_builds(sizes):
    sizes = dict(sizes)
    model = {**CUT, **(FAMILY.needs["rehearsal"]
                       if sizes.pop("rehearsal", False) else {}), **sizes}
    init, _ = FAMILY.system.build(model, "float32")
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
    assert counts.n_params(model) == sum(
        x.size for x in jax.tree_util.tree_leaves(tree))
    stacks = sum(x.size for x in jax.tree_util.tree_leaves(
        [layer.get("experts", {}) for layer in tree["layers"]]))
    assert counts.n_expert_params(model) == stacks


def test_train_flops_per_token():
    d, T = 3072, 8192
    attn = {12: 11_046_912, 18: 15_783_936}
    pairs = {12: T * (T + 1) / 2, 18: 512 * 513 / 2 + (T - 512) * 512}
    layer = {h: 6 * attn[h] + 3 * 4 * h * 128 * pairs[h] / T
             for h in attn}
    # the router's 256 outputs forward only; 10 x 8 / 256 of a held
    # expert a token and the shared expert whole
    routed = 2 * d * 256 + 6 * (0.3125 + 1) * 3 * d * 1024
    want = (6 * 12544 * d + 2 * layer[12] + 3 * layer[18]
            + 6 * 3 * d * 12288 + 4 * routed)
    assert counts.train_flops_per_token(CUT) == pytest.approx(want, rel=1e-12)
    # about 14.9 TFLOP a step of 8,192 tokens
    assert counts.train_flops_per_token(CUT) * T / 1e12 == pytest.approx(
        14.93, abs=0.01)


def test_the_window_kernels_count_the_pairs_in_the_window():
    """A sliding layer's forward call: 4 x 18 heads x 128 FLOPs at each
    of the 4,063,488 pairs in the window; far under the causal
    triangle's; bound by FLOPs on the v5e."""
    fl, by = counts.window_attn_fwd(CUT, 1)
    assert counts.window_pairs(CUT) == 4_063_488
    assert fl == 4 * 18 * 128 * 4_063_488
    # q, o (18 heads), k, v (2 heads) in bf16, the log-sum-exp a head
    assert by == 8192 * (2 * 128 * (2 * 18 + 2 * 2) + 4 * 18)
    assert roofline.least_seconds(fl, by, V5E)[1] == "flops"
    dq, dkv = counts.window_attn_bwd_dq(CUT, 1), counts.window_attn_bwd_dkv(
        CUT, 1)
    assert (dq[0], dkv[0]) == (1.5 * fl, 2 * fl)
    full = counts.flash_fwd(CUT, 1)[0]
    assert full == 4 * 12 * 128 * 8192 * 8193 / 2
    assert fl / full == pytest.approx(18 / 12 * 4_063_488 / 33_558_528)


def test_expert_gmm_counts_the_expected_rows():
    fl, by = counts.expert_gmm(CUT, 1)
    rows = 8192 * 10 * 8 / 256
    assert rows == 2560
    assert fl == 2 * rows * 3072 * 1024
    assert by == 2 * (rows * 3072 + rows * 1024 + 8 * 3072 * 1024)
