"""The seeded generator, and the traffic mixes' ``correct`` rules on
losses with a known verdict.  (A family's plain reference is tested
beside it: ``families/<family>/tests``.)"""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark.lib import data


def test_the_generator_is_seeded_and_learnable():
    spec = {"order": 0.85, "pool_steps": 3}
    a = data.batch_pool(spec, 7, workers=2, batch=4, seq=32, vocab=64)
    b = data.batch_pool(spec, 7, workers=2, batch=4, seq=32, vocab=64)
    c = data.batch_pool(spec, 8, workers=2, batch=4, seq=32, vocab=64)
    assert a.shape == (3, 2, 4, 32) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0 <= a.min() and a.max() < 64
    follows = a[..., 1:] == (5 * a[..., :-1] + 17) % 64
    assert 0.8 < follows.mean() < 0.92      # order 0.85 + chance hits


MATCH = {"mode": "match_reference", "loss_tol": 0.002,
         "require_falling": True}
# the committed MPQ rule, so that what it lets through is pinned here
BAND = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                   / "mpq.json").read_text())["correct"]
REF = [9.4166, 9.4124, 9.2845]


@pytest.mark.parametrize("rule, losses, wrong, bad", [
    (MATCH, [9.4166, 9.4123, 9.2846, 9.05], 0, set()),
    (MATCH, [9.4166, 9.4123, 9.2900, 9.05], 1, {2}),      # step 2 is off
    (MATCH, [9.4166, 9.4123, 9.2846, 9.45], 1, set()),    # did not fall
    (MATCH, [float("nan"), 9.4123, 9.2846, 9.05], 2, {0}),
    (BAND, [9.4166, 9.3571, 9.3500, 9.25], 0, set()),     # seen on the chip
    (BAND, [9.4300, 9.3571, 9.3500, 9.25], 1, {0}),       # forward pass off
    (BAND, [9.4166, 9.3571, 9.2500, 9.25], 1, {2}),       # below the band
    (BAND, [9.4166, 9.3571, 9.4500, 9.25], 1, {2}),       # worse than none
    # updates dropped, staled or zeroed: the loss stays where it began
    # (the band's upper end is 9.4166 - 0.2 * 0.1321 + 0.002 = 9.3922),
    # or wanders there for the whole run
    (BAND, [9.4166, 9.4160, 9.4150, 9.25], 1, {2}),
    (BAND, [9.4166, 9.4100, 9.3930, 9.25], 1, {2}),
    (BAND, [9.4166, 9.3571, 9.3500, 9.42], 1, set()),     # did not fall
])
def test_compare_losses(rule, losses, wrong, bad):
    from benchmark.lib.harness import compare_losses

    failures, steps, compared = compare_losses(losses, REF, rule, warmup=3)
    assert len(failures) == wrong and steps == bad, failures
    # every number compared stands beside its limit or limits
    assert compared["loss_gap_step0"] == [
        pytest.approx(abs(losses[0] - REF[0]), nan_ok=True), 0.002]
    assert compared["loss_fall_over_run"][1] == 0.0
    assert ("loss_step2_in_band" in compared) == (rule is BAND)
    assert ("loss_gap_step2" in compared) == (rule is MATCH)
