"""The Bi-Sparse encoder's compaction kernel (``ops/compact.py``) under
the TPU interpreter, against the plain-XLA compaction it replaces on the
chip: the same frame (value words and ascending indices) and the same
velocity and accumulator states, bit for bit, over two encodes.

The interpreter runs a kernel op by op on this thread, DMAs and
semaphores included; nothing else dispatches while it does."""

import numpy as np
import pytest

from geomx_tpu.ops import compact

LANES, ROWS, SLOTS = compact.LANES, compact.ROWS, compact.SLOTS


def _one_tile_of_rows(rng):
    # every selected element in three rows, far inside one visit's rows
    n = LANES * 512
    u = rng.standard_normal(n).astype(np.float32) * 1e-3
    u[100 * LANES:103 * LANES] = rng.uniform(1.0, 2.0, 3 * LANES)
    return u, 3 * LANES - 20


def _run_longer_than_a_visit(rng):
    # slot 0 at row 0, the other slots of its tile thousands of rows on
    n = LANES * 2048
    u = rng.standard_normal(n).astype(np.float32) * 1e-3
    u[5] = 50.0
    u[-3 * LANES:] = rng.uniform(1.0, 2.0, 3 * LANES)
    return u, 1 + 3 * LANES


def _ties_across_boundaries(rng):
    # the first tile runs from row 0 into ties at t over rows 255..262:
    # they cross row ROWS, where its first visit ends, and slot SLOTS,
    # where it ends; the cut falls past the next tile's end
    n = LANES * 1024
    u = rng.standard_normal(n).astype(np.float32) * 1e-3
    u[rng.choice(255 * LANES, 100, replace=False)] = 5.0
    u[255 * LANES:263 * LANES] = np.float32(-1.5)
    return u, 100 + 2 * SLOTS + 321


def _special_values(rng):
    n = LANES * 300
    u = rng.standard_normal(n).astype(np.float32)
    at = rng.choice(n, 400, replace=False)
    u[at[:20]] = np.nan
    u[at[20:40]] = -np.nan
    u[at[40:60]] = np.inf
    u[at[60:80]] = -np.inf
    u[at[80:200]] = -0.0
    u[at[200:]] = np.float32(1e-39) * rng.standard_normal(200)
    return u, n // 100


def _nan_payloads_tie_at_the_kth(rng):
    # the k-th largest is a NaN: every NaN ties at 0x7FFFFFFF, whatever
    # its payload or sign, and the lowest positions are taken
    n = LANES * 300
    u = rng.standard_normal(n).astype(np.float32)
    payloads = np.array([0x7F800001, 0x7FC00000, 0x7FFFFFFF, 0xFFA00000,
                         0xFFC12345], np.uint32)
    at = np.sort(rng.choice(n, 50, replace=False))
    u.view(np.uint32)[at] = payloads[np.arange(50) % len(payloads)]
    return u, 30


def _k_is_one(rng):
    return rng.standard_normal(LANES * 40).astype(np.float32), 1


def _k_is_n(rng):
    n = LANES * 40 + 17
    return rng.standard_normal(n).astype(np.float32), n


def _ragged_n(rng):
    n = 100_003
    return rng.standard_normal(n).astype(np.float32), 1000


CASES = {"one_tile_of_rows": _one_tile_of_rows,
         "run_longer_than_a_visit": _run_longer_than_a_visit,
         "ties_across_boundaries": _ties_across_boundaries,
         "nan_inf_signed_zero_denormals": _special_values,
         "nan_payloads_tie_at_the_kth": _nan_payloads_tie_at_the_kth,
         "k_is_1": _k_is_one,
         "k_is_n": _k_is_n,
         "n_not_a_multiple_of_128_or_the_tile": _ragged_n}


@pytest.mark.parametrize("case", list(CASES))
def test_compaction_kernel_matches_the_xla_compaction_bitwise(case):
    import jax
    from jax.experimental.pallas.tpu import force_tpu_interpret_mode

    from geomx_tpu.kvstore import jax_backend as jb

    rng = np.random.default_rng(sorted(CASES).index(case))
    u, k = CASES[case](rng)
    n = u.shape[0]
    v = rng.standard_normal(n).astype(np.float32) * 1e-2
    g = rng.standard_normal(n).astype(np.float32) * 1e-2
    m = np.float32(0.9)
    kernel = jax.jit(jb._bsc_step(jb._topk_frame_on_chip),
                     static_argnums=(4,))
    plain = jax.jit(jb._bsc_step(jb._topk_frame), static_argnums=(4,))
    state = {"kernel": (v, u), "plain": (v, u)}
    for step in range(2):
        out = {}
        for name, enc in (("kernel", kernel), ("plain", plain)):
            with force_tpu_interpret_mode():
                wire, v1, u1 = jax.block_until_ready(
                    enc(*state[name], g, m, k))
            state[name] = (v1, u1)
            out[name] = [np.asarray(x).view(np.int32) for x in (wire, v1, u1)]
        wire = out["plain"][0]
        assert wire.shape == (2 * k,)
        idx = wire[k:]
        assert np.all(np.diff(idx) > 0) and idx[0] >= 0 and idx[-1] < n
        for what, a, b in zip(("frame", "velocity", "accumulator"),
                              out["kernel"], out["plain"]):
            bad = np.flatnonzero(a != b)
            assert bad.size == 0, (step, what, bad[:8], a[bad[:4]],
                                   b[bad[:4]])
