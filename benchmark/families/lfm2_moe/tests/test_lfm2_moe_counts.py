"""LFM2-MoE's counts against the issue's hand counts, against a
parameter tree that the family's ``system.py`` builds, and the
roofline's arithmetic on the kernel functions."""

import json
from pathlib import Path

import jax
import pytest

from benchmark.lib import family, roofline

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "lfm2_moe")
counts = FAMILY.counts
CONFIG = json.loads(
    (ROOT / "benchmark/configs/lfm2-24b-a2b-ep8-l5-1chip.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}


def test_parameter_count_of_the_cut():
    # embedding 16,777,216 + final norm 2,048 + the dense conv layer
    # 89,139,200 + the attention expert layer 86,118,528 + 3 conv expert
    # layers of 92,416,000 = 469,284,992 trained parameters (ISSUE 35),
    # and 64 expert-bias constants in each of the 4 routed layers
    assert counts.n_params(CUT) == 469_284_992 + 4 * 64
    # the twelve stacks: 64.4% of it
    assert counts.n_expert_params(CUT) == 12 * 8 * 2048 * 1536 == 301_989_888


@pytest.mark.parametrize("sizes", [
    {}, {"num_experts": 2, "first_expert": 6},
    {"num_hidden_layers": 9, "num_dense_layers": 2, "first_layer": 0}],
    ids=["rehearsal", "another-share", "two-dense-from-layer-0"])
def test_parameter_count_is_the_tree_that_system_builds(sizes):
    model = {**CUT, **FAMILY.needs["rehearsal"], **sizes}
    init, _ = FAMILY.system.build(model, "float32")
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert counts.n_params(model) == sum(x.size for x in leaves)
    stacks = sum(x.size for x in jax.tree_util.tree_leaves(
        [layer.get("experts", {}) for layer in tree["layers"]]))
    assert counts.n_expert_params(model) == stacks


def test_train_flops_per_token():
    d = 2048
    conv = 4 * d * d                               # w_in 3 d^2, w_out d^2
    attn = 2 * d * 32 * 64 + 2 * d * 8 * 64        # wq wo, wk wv
    dense_ffn = 3 * d * 11776
    # the router's 64 outputs and HALF an expert a token: 4 x 8 / 64
    routed = d * 64 + 0.5 * 3 * d * 1536
    matmul = (8192 * d + 4 * conv + attn + dense_ffn + 4 * routed)
    # one attention layer: 2 matmuls x 2 x 2048 FLOPs a pair x 8193 / 2
    attn_fwd = 2 * (2 * d) * 8193 / 2
    assert counts.train_flops_per_token(CUT) == 6 * matmul + 3 * attn_fwd
    assert counts.train_flops_per_token(CUT) == 1_217_409_024 == pytest.approx(1.2174e9,
                                                              rel=1e-3)
    # a chip that held every expert would count four experts a token
    whole = dict(CUT, num_experts=64)
    assert (counts.train_flops_per_token(whole)
            - counts.train_flops_per_token(CUT)) == 6 * 4 * 3.5 * 3 * d * 1536


def test_flash_kernel_counts_at_the_cuts_shape():
    pairs = 32 * 8192 * 8193 // 2                  # 1 sequence, 32 heads
    tile, rows = 32 * 8192 * 64, 32 * 8192
    assert counts.flash_fwd(CUT, 1) == (2 * 2 * 64 * pairs,
                                        4 * 2 * tile + 2 * 4 * rows)
    assert counts.flash_bwd_dkv(CUT, 1) == (4 * 2 * 64 * pairs,
                                            6 * 2 * tile + 3 * 4 * rows)
    assert counts.flash_bwd_dq(CUT, 1) == (3 * 2 * 64 * pairs,
                                           5 * 2 * tile + 3 * 4 * rows)


def test_expert_gmm_counts_the_expected_rows():
    fl, by = counts.expert_gmm(CUT, 1)
    assert fl == 2 * 4096 * 2048 * 1536            # 8192 x 4 x 8 / 64 rows
    assert by == 2 * (4096 * 2048 + 4096 * 1536 + 8 * 2048 * 1536)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = roofline.least_seconds(fl, by, peaks)
    assert bound == "flops" and t == pytest.approx(130.8e-6, rel=1e-3)
