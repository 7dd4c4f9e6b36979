"""The flagship's counts against hand counts, and the roofline's
arithmetic on them."""

from pathlib import Path

import pytest

from benchmark.lib import family, roofline

ROOT = Path(__file__).resolve().parents[4]
flops = family.load(ROOT, ["benchmark"], "flagship").counts

L4 = {"vocab": 8192, "d_model": 2048, "n_heads": 16, "n_layers": 4,
      "d_ff": 8192, "max_seq": 2048}


def test_parameter_count():
    # embed 16,777,216 + pos 4,194,304 + ln_f 2,048
    # + 4 x (wq wk wv wo 16,777,216 + w1 w2 33,554,432 + ln1 ln2 4,096)
    assert flops.n_params(L4) == 222_316_544
    assert flops.n_params(dict(L4, n_layers=8)) == 423_659_520


def test_train_flops_per_token():
    # matmul parameters: 4 x 50,331,648 + tied head 16,777,216
    dense = 6 * 218_103_808
    # attention forward: 4 layers x 2 matmuls x 2 x 2048 FLOPs a pair x
    # 2049 / 2 pairs a token = 33,570,816; x 3 with the backward pass
    attn = 3 * 33_570_816
    assert flops.train_flops_per_token(L4) == dense + attn == 1_409_335_296


def test_causal_half_is_counted_once():
    full = 3 * 4 * 2 * (2 * 2048) * 2048    # every (q, k) pair, fwd + bwd
    got = flops.train_flops_per_token(L4) - 6 * 218_103_808
    assert got == pytest.approx(full / 2, rel=1e-3)


def test_flash_kernel_counts():
    pairs = 4 * 16 * 2048 * 2049 // 2            # 134,283,264 at batch 4
    tile = 4 * 16 * 2048 * 128                   # 16,777,216 elements
    rows = 4 * 16 * 2048
    fl, by = flops.flash_fwd(L4, 4)
    assert fl == 2 * 2 * 128 * pairs == 68_753_031_168
    assert by == 4 * 2 * tile + 2 * 4 * rows == 135_266_304
    assert flops.flash_bwd_dkv(L4, 4) == (4 * 2 * 128 * pairs,
                                          6 * 2 * tile + 3 * 4 * rows)
    assert flops.flash_bwd_dq(L4, 4) == (3 * 2 * 128 * pairs,
                                         5 * 2 * tile + 3 * 4 * rows)


def test_roofline_says_which_bound():
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = roofline.least_seconds(*flops.flash_fwd(L4, 4), peaks)
    assert bound == "flops" and t == pytest.approx(68_753_031_168 / 197e12)
    t, bound = roofline.least_seconds(1e6, 819e9, peaks)
    assert bound == "bytes" and t == pytest.approx(1.0)
