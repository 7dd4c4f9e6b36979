"""On-TPU codec kernels (pallas).

The C++ codecs (geomx_tpu/native) run on the server hosts; these pallas
kernels are the *worker-side* equivalents so gradients can be compressed
on-chip before the device→host handoff at the slice edge — the payload
crossing PCIe/DCN is then already 16x smaller (cf. the EQuARX idea of
quantizing inside the collective; PAPERS.md).

Layout note: the on-chip packer uses a **strided** 2-bit layout
(byte ``i`` holds codes for elements ``i, i+n/4, i+2n/4, i+3n/4``) —
packing along the lane dimension would need cross-lane shuffles, packing
across rows is a pure elementwise shift-or.  ``dequantize_2bit_tpu``
mirrors it; the host codecs keep their own (consecutive) layout, so the
two formats are distinguished by the ``compr`` tags "2bit" (host) and
"2bit-tpu" (this kernel).

All kernels operate on flat float32 arrays padded to a multiple of
4*1024; shapes inside the kernel are (rows, 1024) blocks aligned to the
(8, 128) float32 tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 1024  # 8 sublanes x 128 lanes worth of elements per row


def _pad_to(x: jax.Array, mult: int) -> jax.Array:
    pad = (-x.shape[0]) % mult
    if pad:
        x = jnp.pad(x, (0, pad))
    return x


def _quant_kernel(g_ref, r_ref, thr_ref, packed_ref, newr_ref):
    thr = thr_ref[0, 0]
    r = r_ref[:] + g_ref[:]
    pos = r > thr
    neg = r < -thr
    # avoid small-int→float casts (unsupported on TPU pallas): pure selects
    q = jnp.where(pos, 1, jnp.where(neg, 2, 0))  # int32: 0 / 1 / 2
    newr_ref[:] = r - jnp.where(pos, thr, 0.0) + jnp.where(neg, thr, 0.0)
    # strided pack: rows are the quarter-strides
    quarter = q.shape[0] // 4
    packed = (q[0 * quarter:1 * quarter]
              | (q[1 * quarter:2 * quarter] << 2)
              | (q[2 * quarter:3 * quarter] << 4)
              | (q[3 * quarter:4 * quarter] << 6))
    packed_ref[:] = packed.astype(jnp.uint8)


# rows per grid step: 128 input rows → 32 packed uint8 rows (the uint8
# min sublane tile is 32); keeps each step's VMEM footprint ~2.5 MB
_QROWS = 128


@jax.jit
def _quantize_padded(g2d, r2d, thr):
    from jax.experimental import pallas as pl

    rows = g2d.shape[0]
    grid = (rows // _QROWS,)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_QROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((_QROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((_QROWS // 4, LANES), lambda i: (i, 0)),
            pl.BlockSpec((_QROWS, LANES), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows // 4, LANES), jnp.uint8),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ),
    )(g2d, r2d, thr)


def quantize_2bit_tpu(grad: jax.Array, residual: jax.Array,
                      threshold: float = 0.5):
    """Residual-feedback 2-bit quantization on-chip.

    Returns (packed uint8 [ceil(n/4*LANES)*LANES...], new_residual [n]).
    """
    n = grad.shape[0]
    g = _pad_to(grad.astype(jnp.float32), _QROWS * LANES)
    r = _pad_to(residual.astype(jnp.float32), _QROWS * LANES)
    rows = g.shape[0] // LANES
    thr = jnp.full((1, 1), threshold, jnp.float32)
    packed, newr = _quantize_padded(
        g.reshape(rows, LANES), r.reshape(rows, LANES), thr)
    return packed.reshape(-1), newr.reshape(-1)[:n]


def _dequant_kernel(packed_ref, thr_ref, out_ref):
    thr = thr_ref[0, 0]
    b = packed_ref[:].astype(jnp.int32)
    quarter = out_ref.shape[0] // 4

    def decode(q):
        return jnp.where(q == 1, thr, jnp.where(q == 2, -thr, 0.0))

    out_ref[0 * quarter:1 * quarter] = decode(b & 3)
    out_ref[1 * quarter:2 * quarter] = decode((b >> 2) & 3)
    out_ref[2 * quarter:3 * quarter] = decode((b >> 4) & 3)
    out_ref[3 * quarter:4 * quarter] = decode((b >> 6) & 3)


@jax.jit
def _dequantize_padded(p2d, thr):
    from jax.experimental import pallas as pl

    rows = p2d.shape[0] * 4
    grid = (p2d.shape[0] // (_QROWS // 4),)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((_QROWS // 4, LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_QROWS, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
    )(p2d, thr)


def dequantize_2bit_tpu(packed: jax.Array, n: int,
                        threshold: float = 0.5) -> jax.Array:
    prows = packed.shape[0] // LANES
    thr = jnp.full((1, 1), threshold, jnp.float32)
    out = _dequantize_padded(packed.reshape(prows, LANES), thr)
    return out.reshape(-1)[:n]


def _dgc_kernel(v_ref, u_ref, g_ref, m_ref, vout_ref, uout_ref):
    m = m_ref[0, 0]
    v = m * v_ref[:] + g_ref[:]
    vout_ref[:] = v
    uout_ref[:] = u_ref[:] + v


@jax.jit
def _dgc_padded(v2d, u2d, g2d, m):
    from jax.experimental import pallas as pl

    rows = v2d.shape[0]
    grid = (rows // _QROWS,)
    spec = pl.BlockSpec((_QROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _dgc_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=(spec, spec),
        out_shape=(
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ),
    )(v2d, u2d, g2d, m)


def dgc_update_tpu(velocity: jax.Array, accum: jax.Array, grad: jax.Array,
                   momentum: float = 0.9):
    """Fused DGC momentum-correction update (v = m·v + g; u += v) on-chip
    (the BSC inner loop, ref: gradient_compression.cc:191-269)."""
    n = grad.shape[0]
    v = _pad_to(velocity.astype(jnp.float32), _QROWS * LANES)
    u = _pad_to(accum.astype(jnp.float32), _QROWS * LANES)
    g = _pad_to(grad.astype(jnp.float32), _QROWS * LANES)
    rows = v.shape[0] // LANES
    m = jnp.full((1, 1), momentum, jnp.float32)
    vo, uo = _dgc_padded(v.reshape(rows, LANES), u.reshape(rows, LANES),
                         g.reshape(rows, LANES), m)
    return vo.reshape(-1)[:n], uo.reshape(-1)[:n]
