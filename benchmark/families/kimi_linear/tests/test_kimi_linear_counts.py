"""Kimi-Linear's counts against the issue's hand counts, against a
parameter tree that the family's ``system.py`` builds from the seed,
and the roofline's arithmetic on the kernel functions."""

import json
from pathlib import Path

import jax
import pytest

from benchmark.lib import family, roofline

ROOT = Path(__file__).resolve().parents[4]
FAMILY = family.load(ROOT, ["benchmark"], "kimi_linear")
counts = FAMILY.counts
CONFIG = json.loads((ROOT / "benchmark/configs/"
                     "kimi-linear-48b-a3b-ep32-l5-1chip.json").read_text())
CUT = {k: CONFIG[k] for k in (*family.MODEL_KEYS, *FAMILY.needs["keys"])}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_count_of_the_cut():
    # embedding and head 2 x 47,185,920 + final norm 2,304 + 5 x 4,608
    # of layer norms + 4 KDA mixers of 39,514,272 + the MLA mixer
    # 29,114,880 + the dense FFN 63,700,992 + 4 routed FFNs of 589,824
    # (router) + 56,623,104 (8 experts) + 7,077,888 (shared) =
    # 602,433,408 trained parameters (ISSUE 37), and 256 expert-bias
    # constants in each of the 4 routed layers
    assert counts.n_params(CUT) == 602_433_408 + 4 * 256 == 602_434_432
    # the twelve stacks: 37.6% of it
    assert counts.n_expert_params(CUT) == 12 * 8 * 2304 * 1024 == 226_492_416
    assert counts.n_expert_params(CUT) / counts.n_params(CUT) == (
        pytest.approx(0.376, abs=5e-4))
    # every party's model up and down the WAN once a step, one party
    assert 2 * 1 * 4 * counts.n_params(CUT) / 1e6 == pytest.approx(4819.475,
                                                                   abs=1e-3)


@pytest.mark.parametrize("sizes", [
    {"rehearsal": True}, {"rehearsal": True, "num_experts": 2,
                          "first_expert": 6},
    {"rehearsal": True, "num_hidden_layers": 8, "first_layer": 1},
    {"rehearsal": True, "num_hidden_layers": 3, "first_layer": 3,
     "num_shared_experts": 2},
    {}], ids=["rehearsal", "another-share", "two-periods", "from-layer-3",
              "the-cell"])
def test_parameter_count_is_the_tree_that_system_builds(sizes):
    sizes = dict(sizes)
    model = {**CUT, **(FAMILY.needs["rehearsal"]
                       if sizes.pop("rehearsal", False) else {}), **sizes}
    init, _ = FAMILY.system.build(model, "float32")
    tree = jax.eval_shape(init, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(tree)
    assert counts.n_params(model) == sum(x.size for x in leaves)
    stacks = sum(x.size for x in jax.tree_util.tree_leaves(
        [layer.get("experts", {}) for layer in tree["layers"]]))
    assert counts.n_expert_params(model) == stacks


def test_train_flops_per_token():
    d, hk, k = 2304, 4096, 128
    kda = 4 * d * hk + 2 * (d * k + k * hk) + d * 32
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 4096 * d
    assert kda == 39_514_272 - (3 * hk * 4 + hk + 32 + k)    # less the taps,
    assert mla == 29_114_880 - 512                           # biases, norms
    dense_ffn = 3 * d * 9216
    # the router's 256 outputs, a QUARTER of a held expert a token
    # (8 x 8 / 256) and the shared expert whole
    routed = d * 256 + (0.25 + 1) * 3 * d * 1024
    matmul = 20480 * d + 4 * kda + mla + dense_ffn + 4 * routed
    # latent attention: QK^T at 192 and PV at 128, 32 heads, 8193 / 2
    # pairs a token; the scan: 32 heads x 2 (64 x 5 x 128 / 2 + 3 x 128^2)
    attn_fwd = 2 * 32 * (192 + 128) * 8193 / 2
    scan_fwd = 32 * 2 * (64 * 5 * 128 / 2 + 3 * 128 * 128)
    assert counts.kda_flops_per_token(CUT) == scan_fwd == 4_456_448
    assert counts.train_flops_per_token(CUT) == (
        6 * matmul + 3 * (attn_fwd + 4 * scan_fwd))
    assert counts.train_flops_per_token(CUT) == pytest.approx(2.3187e9,
                                                              rel=1e-4)
    # a chip that held every expert would count eight experts a token
    whole = dict(CUT, num_experts=256)
    assert (counts.train_flops_per_token(whole)
            - counts.train_flops_per_token(CUT)) == (
        6 * 4 * 7.75 * 3 * d * 1024)


def test_kernel_functions_and_their_rooflines():
    # latent attention, one sequence: 32 heads x 8192 x 8193 / 2 pairs
    pairs = 32 * 8192 * 8193 / 2
    fl, by = counts.flash_fwd(CUT, 1)
    assert fl == 2 * (192 + 128) * pairs
    assert by == 2 * 32 * 8192 * (2 * 192 + 2 * 128) + 2 * 4 * 32 * 8192
    assert counts.flash_bwd_dkv(CUT, 1)[0] == 2 * (2 * 192 + 2 * 128) * pairs
    assert counts.flash_bwd_dq(CUT, 1)[0] == 2 * (2 * 192 + 128) * pairs
    t, bound = roofline.least_seconds(fl, by, V5E)
    assert bound == "flops" and t == pytest.approx(3.49e-3, rel=1e-2)
    # a grouped product: 2,048 expected rows (256 an expert)
    fl, by = counts.expert_gmm(CUT, 1)
    assert fl == 2 * 2048 * 2304 * 1024
    assert by == 2 * (2048 * 2304 + 2048 * 1024 + 8 * 2304 * 1024)
    t, bound = roofline.least_seconds(fl, by, V5E)
    # at 256 rows an expert the stacks' bytes bound it, not the FLOPs
    assert bound == "bytes" and t == pytest.approx(62.7e-6, rel=1e-2)
