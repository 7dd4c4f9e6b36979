"""Pallas on-chip codec kernels under TPU interpret mode on CPU; the
same kernels are compiled uninterpreted and re-checked against numpy by
``chip_smoke.py`` on the chip."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas.tpu import force_tpu_interpret_mode

from geomx_tpu.ops import dequantize_2bit_tpu, dgc_update_tpu, quantize_2bit_tpu


@pytest.fixture(autouse=True)
def _interpret_kernels():
    with force_tpu_interpret_mode():
        yield


def test_quantize_2bit_roundtrip_and_residual():
    rng = np.random.default_rng(0)
    n = 5000  # forces padding
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    r0 = jnp.zeros(n, jnp.float32)
    packed, r1 = quantize_2bit_tpu(g, r0, threshold=0.5)
    assert packed.dtype == jnp.uint8
    dec = dequantize_2bit_tpu(packed, n, threshold=0.5)

    gn = np.asarray(g)
    expected = np.zeros(n, np.float32)
    expected[gn > 0.5] = 0.5
    expected[gn < -0.5] = -0.5
    np.testing.assert_allclose(np.asarray(dec), expected)
    # residual feedback: r1 = g - emitted
    np.testing.assert_allclose(np.asarray(r1), gn - expected, rtol=1e-6)
    # mass conservation across repeated rounds
    total = np.asarray(dec).copy()
    r = r1
    for _ in range(5):
        packed, r = quantize_2bit_tpu(jnp.zeros(n, jnp.float32), r,
                                      threshold=0.5)
        total += np.asarray(dequantize_2bit_tpu(packed, n, threshold=0.5))
    resid = np.asarray(r)
    np.testing.assert_allclose(total + resid, gn, atol=1e-5)


def test_wire_size_is_16x():
    from geomx_tpu.ops.quantize import LANES, _QROWS

    n = _QROWS * LANES  # one full block: no padding overhead
    g = jnp.ones(n, jnp.float32)
    packed, _ = quantize_2bit_tpu(g, jnp.zeros(n, jnp.float32))
    assert packed.nbytes == n // 4  # 2 bits/elem = 16x vs f32


def test_dgc_update_matches_reference():
    rng = np.random.default_rng(1)
    n = 3000
    v = jnp.asarray(rng.standard_normal(n), jnp.float32)
    u = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    vo, uo = dgc_update_tpu(v, u, g, momentum=0.9)
    v_ref = 0.9 * np.asarray(v) + np.asarray(g)
    np.testing.assert_allclose(np.asarray(vo), v_ref, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(uo), np.asarray(u) + v_ref,
                               rtol=1e-4, atol=1e-6)
