"""Device-resident WAN codecs (ISSUE 20): the JAX backend's codec stage.

Contracts pinned here:

- CROSS-DECODE PARITY: the numpy codecs are the bit-compat wire
  reference.  fp16 and 2bit device ENCODERS emit byte-identical frames
  for identical state; the BSC device encoder may pick a different
  (equally legal) support, the exact top-k (pinned against a sorting
  oracle, ties, NaN and five rounds of state), but every legal frame —
  device- or numpy-encoded — reconstructs BITWISE identically under
  both families' decoders, f32 and f16-sourced, with integer-valued
  gradients surviving exactly where the codec is lossless on them;
- DONATION SAFETY: ``compress`` never donates the gradient input — it
  may alias an in-flight view (a pull response, a store snapshot), so
  its bits must be untouched after encode; only stage-private state
  (residuals, momentum) is donated;
- STEADY-STATE RESIDENCY: 5 training rounds under device codecs + the
  device optimizer move the LOCAL tier's ``d2h_bytes`` by exactly
  nothing and the codec stage's full-tensor host counter by exactly
  nothing — the only D2H is the wire-ready compressed payload
  (``codec_d2h_bytes``), and the GLOBAL tier re-stages nothing
  (``h2d_bytes`` flat: decoded grads land as device arrays);
- FUZZ: the PR 17 damage model (truncations, seeded bit flips) against
  the DEVICE decoders lands the same typed :class:`CodecError`, never
  an out-of-bounds scatter or a mis-shaped tensor;
- SELECTION: ``resolve_codec_device`` — default on under the jax
  backend, env/config off-switches honored, deterministic mode forces
  the numpy reference, numpy backend never offers the stage.

Runs on the virtual 8-device CPU mesh (conftest)."""

import numpy as np
import pytest

from geomx_tpu.compression import (BscCodec, Fp16Codec, MpqSelector,
                                   TwoBitCodec, decompress_payload)
from geomx_tpu.compression.codecs import CodecError, unpack_sparse
from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.backend import NumpyBackend, resolve_codec_device


def _cfg(**kw):
    return Config(topology=Topology(), **kw)


def _stage(**cfg_kw):
    from geomx_tpu.kvstore.jax_backend import JaxBackend

    cfg = _cfg(**cfg_kw)
    stage = JaxBackend(cfg).make_codec_stage(cfg)
    assert stage is not None
    return stage


def _grad(n=4096, seed=0, dtype=np.float32, integer=False):
    rng = np.random.default_rng(seed)
    if integer:
        return rng.integers(-8, 9, n).astype(dtype)
    return (rng.standard_normal(n) * 2.0).astype(dtype)


def _host(x):
    out = np.asarray(x)
    assert out.dtype == np.float32
    return out


# ---------------------------------------------------------------------------
# cross-decode bitwise parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("src_dtype", [np.float32, np.float16],
                         ids=["f32", "f16"])
def test_fp16_frames_byte_identical_and_cross_decode(src_dtype):
    """fp16 is stateless: device and numpy encoders must emit the SAME
    bytes (XLA's f32→f16 cast is the same round-to-nearest-even), and
    each frame decodes bitwise identically under both decoders."""
    stage = _stage()
    n = 4096
    g = _grad(n, seed=1, dtype=src_dtype).astype(np.float32)
    dev_frame = _stage().make_push_codec({"type": "fp16"}).compress(1, g)
    np_frame = Fp16Codec().compress(1, g.copy())
    assert np.asarray(dev_frame).tobytes() == np.asarray(np_frame).tobytes()
    ref = decompress_payload("fp16", 1, np.asarray(np_frame), n)
    for frame in (dev_frame, np_frame):
        out_dev = _host(stage.decode("fp16", 1, np.asarray(frame), n))
        out_np = decompress_payload("fp16", 1, np.asarray(frame), n)
        assert out_dev.tobytes() == ref.tobytes()
        assert out_np.tobytes() == ref.tobytes()


def test_2bit_frames_byte_identical_across_rounds():
    """2bit carries a per-key residual; feeding IDENTICAL gradients to
    both engines must produce byte-identical frames every round (the
    quantize decisions are exact f32 compares on IEEE-identical sums),
    and the cross-decode matrix stays bitwise-green per round."""
    stage = _stage()
    dev = stage.make_push_codec({"type": "2bit", "threshold": 0.5})
    ref = TwoBitCodec(threshold=0.5)
    n = 2048
    for r in range(4):
        g = _grad(n, seed=10 + r)
        dev_frame = np.asarray(dev.compress(7, g))
        np_frame = np.asarray(ref.compress(7, g.copy()))
        assert dev_frame.tobytes() == np_frame.tobytes(), f"round {r}"
        want = decompress_payload("2bit", 7, np_frame, n,
                                  threshold=0.5).tobytes()
        assert _host(stage.decode("2bit", 7, dev_frame, n,
                                  0.5)).tobytes() == want
        assert decompress_payload("2bit", 7, dev_frame, n,
                                  threshold=0.5).tobytes() == want


def test_2bit_integer_grads_are_exact():
    """Integer-valued gradients with an integer threshold: every
    emitted ±t is exact on both engines and the residuals stay
    integer-valued — the decoded tensors match bitwise AND equal the
    direct {−t,0,+t} quantization."""
    stage = _stage()
    dev = stage.make_push_codec({"type": "2bit", "threshold": 1.0})
    n = 512
    g = _grad(n, seed=3, integer=True)
    frame = np.asarray(dev.compress(2, g))
    out = _host(stage.decode("2bit", 2, frame, n, 1.0))
    want = np.where(g > 1.0, np.float32(1.0),
                    np.where(g < -1.0, np.float32(-1.0), np.float32(0.0)))
    assert out.tobytes() == want.tobytes()
    assert decompress_payload("2bit", 2, frame, n,
                              threshold=1.0).tobytes() == want.tobytes()


def test_bsc_cross_decode_bitwise_both_directions():
    """BSC frames are ``[f32 values ‖ int32 indices bit-cast to f32]``.
    The device encoder's exact top-k may pick a different support than
    the reference's sampled-threshold scan, so frames need not match —
    but EVERY legal frame must reconstruct bitwise identically under
    both decoders, and the transmitted values must be exact f32 bits
    of the accumulated mass (integer grads → integer values)."""
    stage = _stage()
    n = 4096
    g = _grad(n, seed=5, integer=True)
    dev = stage.make_push_codec(
        {"type": "bsc", "ratio": 0.05, "momentum": 0.0})
    ref = BscCodec(ratio=0.05, momentum=0.0, sample_rate=1.0, seed=0)
    for frame in (np.asarray(dev.compress(9, g)),
                  np.asarray(ref.compress(9, g.copy()))):
        out_dev = _host(stage.decode("bsc", 9, frame, n))
        out_np = decompress_payload("bsc", 9, frame, n)
        assert out_dev.tobytes() == out_np.tobytes()
        vals, idx = unpack_sparse(frame)
        # integer grads + momentum 0: the round's accumulated mass is
        # integer-exact, so every transmitted value is a whole number
        assert np.all(vals == np.round(vals))
        np.testing.assert_array_equal(out_np[idx], vals)


def _support_cases():
    """(id, accumulated mass u, k[, the support where numpy's sort
    cannot say]) — what the device encoder's selection must survive."""
    rng = np.random.default_rng(31)

    def normal(n):
        return rng.standard_normal(n).astype(np.float32)

    cases = [(f"n{n}", normal(n), max(1, int(0.01 * n)))
             for n in (7, 2048, 4096, 100_003, 1_048_576)]
    few = np.zeros(5000, np.float32)
    few[rng.choice(5000, 20, replace=False)] = normal(20)
    spread = normal(4096)
    spread[[5, 9]] = np.inf, -np.inf
    return cases + [
        ("n_under_a_block", normal(200), 5),
        ("k1", normal(4096), 1),
        ("k_is_n", normal(300), 300),
        ("all_zero", np.zeros(5000, np.float32), 50),
        ("fewer_nonzeros_than_k", few, 50),
        ("ties_at_the_threshold",
         rng.integers(-3, 4, 100_003).astype(np.float32), 1000),
        ("all_negative", -np.abs(normal(4096)) - 1, 40),
        ("inf", spread, 40),
        # what jax.lax.top_k did before PR 31, on the CPU and on the
        # v5e: a NaN of either sign ranks above inf, lowest index first
        ("nan", np.array([1, np.nan, 3, np.inf, -np.nan, 2, np.nan],
                         np.float32), 3, [1, 4, 6]),
        ("nan_then_inf", np.array([1, np.nan, 3, np.inf, -np.inf, 2],
                                  np.float32), 2, [1, 3]),
    ]


@pytest.mark.parametrize("case", _support_cases(), ids=lambda c: c[0])
def test_bsc_support_is_the_exact_topk(case):
    """The device encoder selects without a sort (a counted threshold
    and a compaction); the support must still be the one a stable
    descending sort of |u| picks (which is ``lax.top_k``'s set), emitted
    ascending, with the exact bits of the accumulated mass, and the
    state zeroed exactly there."""
    import jax
    import jax.numpy as jnp

    _, u, k, *pinned = case
    n = len(u)
    want = (np.asarray(pinned[0]) if pinned else
            np.sort(np.argsort(-np.abs(u), kind="stable")[:k]))
    _, topk = jax.lax.top_k(jnp.abs(jnp.asarray(u)), k)
    np.testing.assert_array_equal(np.sort(np.asarray(topk)), want)
    codec = _stage().make_push_codec(
        {"type": "bsc", "ratio": (k + 0.5) / n, "momentum": 0.0})
    frame = np.asarray(codec.compress(3, u.copy()))
    assert frame.dtype == np.float32 and frame.shape == (2 * k,)
    idx = frame[k:].view(np.int32)
    np.testing.assert_array_equal(idx, want)  # unique, ascending, in range
    assert frame[:k].tobytes() == u[idx].tobytes()
    rest = u.copy()
    rest[idx] = 0.0
    for state in (codec._velocity[3], codec._accum[3]):
        assert np.asarray(state).tobytes() == rest.tobytes()


def test_bsc_five_rounds_match_the_sorting_encoder_bitwise():
    """Velocity, accumulator and frame over five rounds at momentum 0.9
    against a plain transcription of the encoder as it was before PR 31
    (a full ``lax.top_k``, two scatters): the same state bit for bit and
    the same index-value pairs, in whatever order."""
    import jax
    import jax.numpy as jnp

    n, m = 20_000, np.float32(0.9)
    k = max(1, int(0.01 * n))

    @jax.jit
    def sorting_enc(v, u, g):
        v = m * v + g
        u = u + v
        _, idx = jax.lax.top_k(jnp.abs(u), k)
        vals = u[idx]
        return idx, vals, v.at[idx].set(0.0), u.at[idx].set(0.0)

    codec = _stage().make_push_codec(
        {"type": "bsc", "ratio": 0.01, "momentum": 0.9})
    v = u = jnp.zeros(n, jnp.float32)
    for r in range(5):
        g = _grad(n, seed=40 + r)
        idx, vals, v, u = sorting_enc(v, u, jnp.asarray(g))
        frame = np.asarray(codec.compress(6, g))
        order = np.argsort(np.asarray(idx))
        assert (frame[k:].view(np.int32).tobytes()
                == np.asarray(idx)[order].tobytes()), f"round {r}"
        assert frame[:k].tobytes() == np.asarray(vals)[order].tobytes()
        assert np.asarray(codec._velocity[6]).tobytes() == \
            np.asarray(v).tobytes(), f"round {r}"
        assert np.asarray(codec._accum[6]).tobytes() == \
            np.asarray(u).tobytes(), f"round {r}"


def test_bsc_frame_leaves_the_device_as_integer_words():
    """XLA:TPU lowers a float32 concatenate to ``maximum(pad, pad)``,
    which flushed the indices next to the seam of the two halves to 0
    (they are denormals as float32; seen on the v5e in PR 31).  The
    encoder therefore emits int32 words and the float32 view is taken
    on the host; the bytes on the wire are the same, and every decoder
    rebuilds the same dense tensor from them."""
    import jax.numpy as jnp

    stage = _stage()
    codec = stage.make_push_codec(
        {"type": "bsc", "ratio": 0.01, "momentum": 0.0})
    n, k = 4096, 40
    g = _grad(n, seed=2)
    wire, _, _ = codec._enc(jnp.zeros(n), jnp.zeros(n), jnp.asarray(g),
                            np.float32(0.0), k)
    assert wire.dtype == jnp.int32 and wire.shape == (2 * k,)
    frame = codec.compress(4, g)
    assert frame.dtype == np.float32
    assert frame.tobytes() == np.asarray(wire).tobytes()
    dense = np.zeros(n, np.float32)
    idx = np.asarray(wire)[k:]
    dense[idx] = g[idx]
    for out in (_host(stage.decode("bsc", 4, frame, n)),
                _host(codec.decompress(4, frame, n)),
                decompress_payload("bsc", 4, frame, n),
                BscCodec(ratio=0.01).decompress(4, frame, n)):
        assert out.tobytes() == dense.tobytes()


def test_bsc_encoder_is_one_program_a_length_for_the_process():
    """``enc`` is pure, so it is jitted once for the process: two codecs
    (two local servers) share it, and a key's first encode runs the
    program every later one runs (the zero state is placed like the
    state a call returns).  Eight fetches a run were two lengths x two
    servers x two placements (PR 31's set-up; ISSUE 32)."""
    from geomx_tpu.kvstore import jax_backend

    a = _stage().make_push_codec({"type": "bsc", "ratio": 0.01})
    b = _stage().make_push_codec({"type": "bsc", "ratio": 0.01})
    assert a._enc is b._enc is jax_backend._bsc_encoder()
    # lengths no other test of this file encodes
    before = a._enc._cache_size()
    for n_th, n in enumerate((30_011, 70_003), start=1):
        for r in range(3):
            a.compress(n, _grad(n, seed=r))
            assert a._enc._cache_size() == before + n_th, (n, r)
        b.compress(n, _grad(n, seed=9))
        assert a._enc._cache_size() == before + n_th


def test_bsc_encoder_lowers_to_a_compact_module_named_enc():
    """What a process pays on the host for each length: the radix search
    is ONE loop body, so the module at the flagship's largest tensor is
    523 StableHLO operations (PR 31's unrolled search: 1,425; the
    ``lax.top_k`` encoder: 43).  ISSUE 32 asked for 400; the rest are
    scalars, which cost the host 0.05 s a length and spare the device
    an operation each (tests/test_tpu_compile.py counts those: they are
    what set-up pays for).  ``jit_enc`` is the name the benchmark's
    ``codec_dev_ms_per_step`` finds the program by."""
    import re

    import jax
    import jax.numpy as jnp

    from geomx_tpu.kvstore.jax_backend import _bsc_encoder

    n = 2048 * 8192
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    text = _bsc_encoder().lower(
        vec, vec, vec, jax.ShapeDtypeStruct((), jnp.float32),
        int(0.01 * n)).as_text()
    ops = re.findall(r"\bstablehlo\.(\w+)", text)
    assert len(ops) <= 600
    assert "sort" not in ops and ops.count("while") == 1
    assert re.match(r"module @jit_enc\b", text), text[:80]


def test_bsc_kernel_encodes_counts_only_the_kernels_compactions(
        monkeypatch):
    """``codec_bsc_kernel_encodes`` counts the Bi-Sparse encodes whose
    compaction the TPU kernel did, which is those whose state lives on
    a TPU: none here.  Told that a tensor's state lives on one, the
    backend counts that tensor's Bi-Sparse encodes and nothing else (not
    the fp16 rung of MPQ, not the other tensor)."""
    from geomx_tpu.kvstore import jax_backend

    stage = _stage()
    mpq = stage.make_push_codec({"type": "mpq", "size_bound": 1000})
    small, large, other = 512, 4096, 6000
    for r in range(2):
        for n in (small, large, other):
            mpq.select(n).compress(n, _grad(n, seed=r))
    stats = stage._be.stats()
    assert stats["codec_bsc_kernel_encodes"] == 0
    assert stats["codec_device_ms"] > 0

    lives_on_tpu = jax_backend._lives_on_tpu
    monkeypatch.setattr(jax_backend, "_lives_on_tpu",
                        lambda arr: arr.shape[0] == large
                        or lives_on_tpu(arr))
    for r in range(3):
        for n in (small, large, other):
            mpq.select(n).compress(n, _grad(n, seed=r))
    assert stage._be.stats()["codec_bsc_kernel_encodes"] == 3


def test_mpq_selector_is_isinstance_compatible_and_splits():
    """The device MPQ subclasses the numpy selector (the server's
    isinstance dispatch and QUERY_STATS counters must keep working)
    and swaps both rungs for device implementations."""
    from geomx_tpu.kvstore.jax_backend import (DeviceBscCodec,
                                               DeviceFp16Codec,
                                               DeviceMpqSelector)

    sel = _stage().make_push_codec({"type": "mpq", "size_bound": 100})
    assert isinstance(sel, DeviceMpqSelector)
    assert isinstance(sel, MpqSelector)
    assert isinstance(sel.select(50), DeviceFp16Codec)
    assert isinstance(sel.select(100), DeviceBscCodec)


def test_make_push_codec_parity_with_reference_factory():
    stage = _stage()
    assert stage.make_push_codec({"type": "none"}) is None
    with pytest.raises(ValueError):
        stage.make_push_codec({"type": "zstd"})


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("body", [
    {"type": "fp16"},
    {"type": "2bit", "threshold": 0.5},
    {"type": "bsc", "ratio": 0.05, "momentum": 0.9},
], ids=lambda b: b["type"])
def test_compress_never_corrupts_aliased_device_input(body):
    """The gradient handed to ``compress`` may alias an in-flight view
    (a pull response being serialized, a white-box snapshot).  The jit
    kernels donate only stage-private state — after two encodes (the
    second reusing donated residual buffers) the input's bits must be
    untouched."""
    import jax.numpy as jnp

    stage = _stage()
    codec = stage.make_push_codec(body)
    g = jnp.asarray(_grad(2048, seed=8))
    before = np.asarray(g).tobytes()
    codec.compress(4, g)
    codec.compress(4, g)  # residual/velocity now donated buffers
    assert np.asarray(g).tobytes() == before, (
        f"{body['type']}: encode mutated an aliased input")


# ---------------------------------------------------------------------------
# steady-state residency: the geo-round never touches host numpy
# ---------------------------------------------------------------------------

def test_steady_state_rounds_zero_host_copies(monkeypatch):
    """THE acceptance assertion: 5 compressed training rounds under
    device codecs + device optimizer pay ZERO merge-plane D2H on the
    local tier and ZERO re-staging H2D on the global tier — the only
    device→host traffic in the codec stage is the wire-ready
    compressed payload, billed to ``codec_d2h_bytes``, and the global
    tier's D2H is exactly the per-round weight serve (pulls), nothing
    else."""
    monkeypatch.setenv("GEOMX_MERGE_BACKEND", "jax")
    monkeypatch.setenv("GEOMX_CODEC_DEVICE", "1")
    n = 20000
    sim = Simulation(Config(topology=Topology(num_parties=2,
                                              workers_per_party=1)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(n, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.05})
        for p in range(2):
            sim.worker(p, 0).set_gradient_compression(
                {"type": "bsc", "ratio": 0.05})

        def one_round():
            g = np.ones(n, np.float32)
            for w in ws:
                w.push(0, g)
            return [w.pull_sync(0) for w in ws]

        one_round()  # warmup: jit compile + first-touch residency

        def counters(servers):
            return [(be.d2h_bytes, be.h2d_bytes, be.codec_host_bytes,
                     be.codec_d2h_bytes)
                    for be in (s._backend for s in servers)]

        loc0 = counters(sim.local_servers)
        glob0 = counters(sim.global_servers)
        for _ in range(5):
            one_round()
        # k = ratio*n per key per round: [vals ‖ idx] = 2k f32
        wire = 5 * 2 * max(1, int(0.05 * n)) * 4
        for (d0, h0, c0, w0), (d1, h1, c1, w1) in zip(
                loc0, counters(sim.local_servers)):
            assert d1 - d0 == 0, f"local merge plane paid D2H: {d1 - d0}"
            assert c1 - c0 == 0, f"full-tensor host copy in codec: {c1 - c0}"
            assert w1 - w0 == wire, (w1 - w0, wire)
            # worker pushes arrive as host frames: staging them is the
            # one H2D the local tier legitimately pays
            assert h1 - h0 == 5 * n * 4
        for (d0, h0, c0, _), (d1, h1, c1, _) in zip(
                glob0, counters(sim.global_servers)):
            assert h1 - h0 == 0, f"global tier re-staged grads: {h1 - h0}"
            assert c1 - c0 == 0
            # each round's pull is ONE weight materialization, no more
            assert d1 - d0 == 5 * n * 4, (d1 - d0, 5 * n * 4)
        # and the replicas actually trained
        outs = one_round()
        assert outs[0].mean() < -0.05
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# fuzz: the PR 17 damage model against the device decoders
# ---------------------------------------------------------------------------

def _fuzz_decode(decode, orig_len):
    """Same oracle as tests/test_integrity.py: a (possibly damaged)
    payload may only land a typed CodecError or a right-shaped f32
    tensor — struct.error / IndexError / OOB scatter / short arrays
    are the bug class this exists to catch."""
    try:
        out = decode()
    except CodecError:
        return "typed-reject"
    out = np.asarray(out)
    assert out.shape == (orig_len,), f"wrong shape {out.shape}"
    assert out.dtype == np.float32
    return "decoded"


@pytest.mark.parametrize("codec_name", ["bsc", "fp16", "2bit"])
def test_device_decoder_fuzz_truncate_bitflip(codec_name):
    rng = np.random.default_rng(abs(hash("dev" + codec_name)) % (2 ** 32))
    n = 4096
    grad = rng.standard_normal(n).astype(np.float32) * 2.0
    stage = _stage()
    body = {"bsc": {"type": "bsc", "ratio": 0.05},
            "fp16": {"type": "fp16"},
            "2bit": {"type": "2bit", "threshold": 0.5}}[codec_name]
    codec = stage.make_push_codec(body)
    payload = np.asarray(codec.compress(1, grad))
    tag = codec.name

    # clean roundtrip: deterministic, right-shaped, device-resident
    out1 = _host(stage.decode(tag, 1, payload, n))
    out2 = _host(stage.decode(tag, 1, payload.copy(), n))
    assert out1.shape == (n,)
    assert out1.tobytes() == out2.tobytes()

    raw = payload.tobytes()
    item = payload.dtype.itemsize

    def decode_bytes(b):
        arr = (np.frombuffer(b, dtype=payload.dtype)
               if len(b) % item == 0
               else np.frombuffer(b, dtype=np.uint8))
        return stage.decode(tag, 1, arr, n)

    # truncations: every cut point is a typed reject or right-shaped
    rejects = 0
    for cut in rng.choice(max(1, len(raw) - 1), size=48, replace=False):
        rejects += _fuzz_decode(
            lambda: decode_bytes(raw[:int(cut)]), n) == "typed-reject"
    assert rejects > 0, "no truncation was ever rejected"

    # seeded bit flips: never crash, never mis-shape, never OOB-scatter
    for _ in range(96):
        dam = bytearray(raw)
        pos = int(rng.integers(len(dam) * 8))
        dam[pos // 8] ^= 1 << (pos % 8)
        _fuzz_decode(lambda: decode_bytes(bytes(dam)), n)


def test_device_decoder_rejects_unknown_tag_and_bad_geometry():
    stage = _stage()
    with pytest.raises(CodecError, match="unknown"):
        stage.decode("zstd9", 1, np.ones(4, np.float32), 4)
    with pytest.raises(CodecError):
        stage.decode("fp16", 1, np.ones(3, np.float16), 4)  # short
    with pytest.raises(CodecError):
        stage.decode("2bit", 1, np.zeros(2, np.uint8), 64)  # short
    with pytest.raises(CodecError):  # odd sparse frame
        stage.decode("bsc", 1, np.ones(3, np.float32), 16)


def test_device_sparse_scatter_indices_are_fenced():
    """A flipped int32 index turns negative or huge; jax's scatter
    would silently DROP or WRAP it.  The device decode path runs the
    reference bounds gate BEFORE any device work."""
    from geomx_tpu.compression.codecs import pack_sparse

    stage = _stage()
    vals = np.array([1.0, 2.0], np.float32)
    for idx in ([-3, 0], [0, 10 ** 6]):
        payload = pack_sparse(vals, np.array(idx, np.int64))
        with pytest.raises(CodecError, match="index"):
            stage.decode("bsc", 5, payload, 16)


# ---------------------------------------------------------------------------
# selection rules
# ---------------------------------------------------------------------------

def test_codec_stage_selection_rules(monkeypatch):
    from geomx_tpu.kvstore.jax_backend import JaxBackend

    monkeypatch.delenv("GEOMX_CODEC_DEVICE", raising=False)
    cfg = _cfg()
    assert resolve_codec_device(cfg) is True
    assert JaxBackend(cfg).make_codec_stage(cfg) is not None
    # deterministic mode forces the numpy reference (replayable wires)
    det = _cfg(deterministic=True)
    assert resolve_codec_device(det) is False
    assert JaxBackend(det).make_codec_stage(det) is None
    # config field off wins without the env
    off = _cfg(codec_device=False)
    assert resolve_codec_device(off) is False
    # env off-switch for directly-constructed configs
    monkeypatch.setenv("GEOMX_CODEC_DEVICE", "0")
    assert resolve_codec_device(_cfg()) is False
    monkeypatch.setenv("GEOMX_CODEC_DEVICE", "1")
    assert resolve_codec_device(_cfg()) is True
    # the numpy backend never offers the stage
    assert NumpyBackend(_cfg()).make_codec_stage(_cfg()) is None
