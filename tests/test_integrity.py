"""End-to-end data-integrity plane (ISSUE 17).

Four layers, each pinned here:

- *wire*: checksum-stamped frames (``GEOMX_INTEGRITY_WIRE``) — flag
  off is bit-for-bit the legacy encoding, stamped frames detect every
  single-bit flip as :class:`WireCorruption`, and the in-proc fabric's
  corruption tap proves detect → NACK → resend keeps training
  byte-identical to an uncorrupted run;
- *gradient hygiene*: the server-side finiteness screen zeroes poisoned
  pushes, answers with a typed error, and QUARANTINES (never evicts)
  a repeat offender;
- *durable state*: checkpoint blobs carry a format stamp + whole-blob
  and per-slab CRCs; restore falls back through N generations; a
  corrupt replication snapshot is rejected without the word "fenced"
  (the Replicator reads fence-flavored replies as deposition);
- *codecs*: every WAN codec (bsc / fp16 / 2bit / mpq) survives a
  seeded fuzz of truncations and bit flips — typed ``CodecError`` or a
  right-shaped tensor, never a crash or a silently wrong shape.

The real-TCP operator tour is ``scripts/run_integrity_demo.sh``.
"""

import os
import random
import struct
import time

import numpy as np
import pytest

from geomx_tpu.compression.codecs import (BscCodec, CodecError, Fp16Codec,
                                          MpqSelector, TwoBitCodec,
                                          decompress_payload, pack_rows,
                                          pack_sparse, scatter_sparse,
                                          unpack_rows, unpack_sparse)
from geomx_tpu.core.config import Config, NodeId, Role, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore import checkpoint as ckpt
from geomx_tpu.transport import message as message_mod
from geomx_tpu.transport.message import Message, WireCorruption


def _msg(elems=256, seed=3):
    rng = np.random.default_rng(seed)
    return Message(
        sender=NodeId(Role.SERVER, 0, 0),
        recipient=NodeId(Role.GLOBAL_SERVER, 0, None),
        request=True, push=True, timestamp=11, msg_sig=77,
        keys=np.array([4], np.int64),
        vals=rng.standard_normal(elems).astype(np.float32),
        lens=np.array([elems], np.int64))


# ---------------------------------------------------------------------------
# wire integrity
# ---------------------------------------------------------------------------

def test_flag_off_is_bit_for_bit_legacy(monkeypatch):
    """The whole plane is opt-in: with the flag off the encoder output
    is byte-identical to the legacy frame — no marker, no CRC block —
    so a mixed-version rollout can upgrade either side first."""
    m = _msg()
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", False)
    off = bytes(m.to_bytes())
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", True)
    on = bytes(m.to_bytes())
    # the stamp is exactly the 8-byte CRC block; the marker byte flips
    # inside the (same-size) header
    assert len(on) - len(off) == 8
    assert off[4 + Message._INTEGRITY_BYTE] == 0
    assert on[4 + Message._INTEGRITY_BYTE] == 1
    # both decode to the same message
    for raw in (off, on):
        back = Message.from_bytes(raw)
        np.testing.assert_array_equal(back.vals, m.vals)
        assert back.msg_sig == m.msg_sig
    # and a stamped frame re-encoded with the flag off is the legacy
    # bytes again (decoder state never leaks into the encoder)
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", False)
    assert bytes(Message.from_bytes(on).to_bytes()) == off


def test_stamped_frame_detects_every_bit_flip(monkeypatch):
    """Random single-bit-flip sweep: every flip in a stamped frame must
    raise a typed error or fail framing — zero silently-wrong
    deliveries."""
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", True)
    m = _msg(64)
    raw = bytearray(m.to_bytes())
    ref = m.vals.tobytes()
    rng = np.random.default_rng(5)
    silent = 0
    for pos in rng.choice(len(raw) * 8, size=400, replace=False):
        byte, bit = int(pos) // 8, int(pos) % 8
        raw[byte] ^= 1 << bit
        try:
            out = Message.from_bytes(bytes(raw))
            if out.vals is None or out.vals.tobytes() != ref \
                    or out.msg_sig != m.msg_sig:
                silent += 1
        except Exception:
            pass  # detected (WireCorruption or a framing ValueError)
        finally:
            raw[byte] ^= 1 << bit
    assert silent == 0


def test_wire_corruption_carries_sender_identity(monkeypatch):
    """A payload-CRC mismatch still has a VERIFIED meta span, so the
    error names the sender — that identity is what the receiving
    fabric's NACK path needs to trigger the immediate resend."""
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", True)
    m = _msg(64)
    raw = bytearray(m.to_bytes())
    raw[-3] ^= 0x10  # damage payload bytes, far from header + meta
    with pytest.raises(WireCorruption) as ei:
        Message.from_bytes(bytes(raw))
    assert ei.value.sender == str(m.sender)
    assert ei.value.msg_sig == m.msg_sig


def test_legacy_frame_delivers_flip_silently(monkeypatch):
    """The behavior the stamp exists to close, pinned so the soak's
    with/without comparison stays honest: an unstamped frame with a
    payload flip decodes fine and returns WRONG numbers."""
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", False)
    m = _msg(64)
    raw = bytearray(m.to_bytes())
    off = raw.find(m.vals.tobytes())
    assert off > 0
    raw[off + 5] ^= 0x10
    out = Message.from_bytes(bytes(raw))
    assert out.vals.tobytes() != m.vals.tobytes()


def _tiny_cfg(**kw):
    kw.setdefault("topology", Topology(num_parties=2, workers_per_party=1))
    kw.setdefault("enable_flight", False)
    kw.setdefault("lightweight", True)
    kw.setdefault("resend_timeout_ms", 200)
    return Config(**kw)


def _init_model(sim, elems):
    ws = sim.all_workers()
    for w in ws:
        w.init(0, np.zeros(elems, np.float32))
    ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
    return ws


def _push_rounds(ws, rounds, elems):
    g = np.ones(elems, np.float32)
    for _ in range(rounds):
        for w in ws:
            w.push(0, g)
        for w in ws:
            w.wait_all()
    return ws[0].pull_sync(0)


def test_corrupt_link_detect_nack_resend_parity(monkeypatch):
    """The tentpole soak in miniature: a seeded bit-flip tap corrupts a
    WAN uplink; with stamps on, EVERY damaged frame is detected (none
    dropped as framing noise, none silently delivered), the NACK resend
    path re-delivers, and the final model is byte-identical to an
    uncorrupted run's."""
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", True)
    elems, rounds = 2048, 6
    sim = Simulation(_tiny_cfg())
    try:
        clean = _push_rounds(_init_model(sim, elems), rounds, elems)
    finally:
        sim.shutdown()
    sim = Simulation(_tiny_cfg())
    try:
        ws = _init_model(sim, elems)  # bring-up on a healthy fabric
        src = str(sim.local_servers[0].po.node)
        dst = str(sim.global_servers[0].po.node)
        sim.corrupt_link(src, dst, rate=0.3, mode="bitflip", seed=23)
        final = _push_rounds(ws, rounds, elems)
        fab = sim.fabric
        assert fab.corrupt_injected > 0, "tap never fired — dead soak"
        assert fab.corrupt_detected == fab.corrupt_injected
        assert fab.corrupt_delivered == 0
        assert fab.corrupt_dropped == 0
        np.testing.assert_array_equal(final, clean)
    finally:
        sim.shutdown()


def test_unstamped_corrupt_link_is_not_detected(monkeypatch):
    """Control experiment: with stamps OFF the same tap yields zero
    detections — every damaged frame is either silently delivered or
    dropped as framing noise.  The ledger's distinction is what makes
    the soak's detected == injected assertion meaningful."""
    monkeypatch.setattr(message_mod, "WIRE_INTEGRITY", False)
    sim = Simulation(_tiny_cfg())
    try:
        ws = _init_model(sim, 64)
        src = str(sim.local_servers[0].po.node)
        dst = str(sim.global_servers[0].po.node)
        sim.corrupt_link(src, dst, rate=1.0, mode="bitflip", seed=29)
        for w in ws:
            w.push(0, np.ones(64, np.float32))
        fab = sim.fabric
        deadline = time.monotonic() + 10.0
        while fab.corrupt_injected == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        sim.heal_corrupt(src, dst)
        assert fab.corrupt_injected > 0
        assert fab.corrupt_detected == 0  # nothing to detect them with
        assert fab.corrupt_delivered + fab.corrupt_dropped \
            == fab.corrupt_injected
        for w in ws:
            w.wait_all()  # the healed link serves the resends
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# gradient hygiene: poison screen + quarantine
# ---------------------------------------------------------------------------

def test_poison_screen_quarantines_not_evicts():
    """A worker pushing NaN gradients strikes out after
    ``poison_quarantine_n`` rejects and is QUARANTINED — reversibly
    folded out via the PR-16 machinery, never evicted — while the
    healthy worker's training math stays exactly right."""
    cfg = _tiny_cfg(
        topology=Topology(num_parties=1, workers_per_party=2),
        integrity_push_screen=True, poison_quarantine_n=2)
    sim = Simulation(cfg)
    try:
        w_ok, w_bad = _init_model(sim, 128)
        ls = sim.local_servers[0]
        bad = np.full(128, np.nan, np.float32)
        for _strike in (1, 2):
            # both members contribute before either waits: the typed
            # error rides the sync round's ack
            w_bad.push(0, bad)
            w_ok.push(0, np.ones(128, np.float32))
            with pytest.raises(RuntimeError, match="poisoned push"):
                w_bad.wait_all()
            w_ok.wait_all()
        assert ls.integrity_poison_rejects == 2
        assert ls.poison_quarantines == 1
        bad_s = str(w_bad.po.node)
        assert bad_s in ls._quarantined_members
        assert bad_s not in ls._members
        assert bad_s not in ls._evicted, "quarantine escalated to EVICT"
        # the healthy worker trains on alone (quarantine shrank the
        # round quorum) and zero poison ever reached the merge
        w_ok.push(0, np.ones(128, np.float32))
        w_ok.wait_all()
        final = w_ok.pull_sync(0)
        assert np.isfinite(final).all()
        assert final.min() < 0  # sgd actually applied clean gradients
        st = ls.stats()
        assert st["integrity_poison_rejects"] == 2
        assert st["poison_quarantines"] == 1
        assert st["quarantined_workers"] == 1
    finally:
        sim.shutdown()


def test_magnitude_screen_rejects_blowup():
    """poison_mag_max > 0 extends the screen beyond NaN/Inf: a finite
    but exploded gradient is rejected the same way — and with
    ``poison_quarantine_n=0`` the strike never escalates."""
    cfg = _tiny_cfg(
        topology=Topology(num_parties=1, workers_per_party=1),
        integrity_push_screen=True, poison_quarantine_n=0,
        poison_mag_max=1e3)
    sim = Simulation(cfg)
    try:
        (w,) = _init_model(sim, 32)
        w.push(0, np.full(32, 1e6, np.float32))
        with pytest.raises(RuntimeError, match="poisoned push"):
            w.wait_all()
        ls = sim.local_servers[0]
        assert ls.integrity_poison_rejects == 1
        assert ls.poison_quarantines == 0  # n=0 disables the escalation
        assert str(w.po.node) in ls._members
        w.push(0, np.ones(32, np.float32))
        w.wait_all()  # a clean push after the reject still merges
        assert np.isfinite(w.pull_sync(0)).all()
    finally:
        sim.shutdown()


def test_integrity_plane_off_by_default():
    cfg = Config()
    assert cfg.integrity_push_screen is False
    if "GEOMX_INTEGRITY_WIRE" not in os.environ:
        assert message_mod.WIRE_INTEGRITY is False


# ---------------------------------------------------------------------------
# verified durable state
# ---------------------------------------------------------------------------

def _state(seed=0):
    rng = np.random.default_rng(seed)
    store = {0: rng.standard_normal(64).astype(np.float32),
             3: rng.standard_normal(16).astype(np.float32)}
    return store, {"optimizer": {"type": "sgd", "lr": 0.1}}, {"boot": seed}


def test_checkpoint_stamped_roundtrip_and_legacy():
    store, opt, meta = _state()
    for integrity in (False, True):
        blob = ckpt.dumps_server_state(store, opt, meta,
                                       integrity=integrity)
        assert blob.startswith(b"GXCK") is integrity
        s2, o2, m2 = ckpt.loads_server_state(blob)
        assert o2 == opt and m2 == meta
        for k in store:
            np.testing.assert_array_equal(s2[k], store[k])


def test_checkpoint_corruption_detected_and_typed():
    store, opt, meta = _state()
    blob = ckpt.dumps_server_state(store, opt, meta, integrity=True)
    # whole-blob flip
    dam = bytearray(blob)
    dam[len(dam) // 2] ^= 0x40
    with pytest.raises(ckpt.CheckpointCorruption):
        ckpt.loads_server_state(bytes(dam))
    # truncation — mid-blob and mid-header
    with pytest.raises(ckpt.CheckpointCorruption):
        ckpt.loads_server_state(blob[:len(blob) // 2])
    with pytest.raises(ckpt.CheckpointCorruption):
        ckpt.loads_server_state(blob[:7])
    # unknown format version
    ver = bytearray(blob)
    ver[4:6] = struct.pack("<H", 99)
    with pytest.raises(ckpt.CheckpointCorruption, match="version"):
        ckpt.loads_server_state(bytes(ver))


def test_generation_rotation_and_fallback(tmp_path):
    """Three saves under keep=3 retain three generations; rotting the
    newest makes the restore scan fall back to the previous one."""
    path = str(tmp_path / "ck.npz")
    for gen in range(3):
        ckpt.rotate_generations(path, keep=3)
        store, opt, meta = _state(seed=gen)
        ckpt.save_server_state(path, store, opt, meta, integrity=True)
    assert ckpt.restore_candidates(path) == [path, f"{path}.1",
                                             f"{path}.2"]
    # newest verifies → wins
    _, _, m = ckpt.load_server_state(path)
    assert m["boot"] == 2
    # rot the newest: the fallback scan lands on generation 1
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0x01
    open(path, "wb").write(bytes(raw))
    got = None
    for cand in ckpt.restore_candidates(path):
        try:
            got = ckpt.load_server_state(cand)
            break
        except ckpt.CheckpointCorruption:
            continue
    assert got is not None and got[2]["boot"] == 1


def test_server_load_checkpoint_falls_back(tmp_path):
    """The live GlobalServer restore path: newest generation rotted on
    disk → the previous one is installed, the reject is counted, and
    serving continues from verified state."""
    sim = Simulation(_tiny_cfg(
        topology=Topology(num_parties=1, workers_per_party=1)))
    try:
        gs = sim.global_servers[0]
        path = str(tmp_path / "gs.npz")
        good_store, opt, meta = _state(seed=7)
        ckpt.save_server_state(path, good_store, opt, meta,
                               integrity=True)
        ckpt.rotate_generations(path, keep=2)
        ckpt.save_server_state(path, *_state(seed=8), integrity=True)
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0x08
        open(path, "wb").write(bytes(raw))
        gs.load_checkpoint(path)
        assert gs.integrity_ckpt_rejects == 1
        np.testing.assert_array_equal(
            np.asarray(gs.store[0]), good_store[0])
    finally:
        sim.shutdown()


def test_corrupt_replication_snapshot_reply_never_says_fenced():
    """A rotted REPLICATE frame must be rejected WITHOUT fence-flavored
    wording — the primary's Replicator reads 'fenced' replies as a
    deposition signal, and one bad frame must not depose a healthy
    primary."""
    sim = Simulation(_tiny_cfg(
        topology=Topology(num_parties=1, workers_per_party=1)))
    try:
        gs = sim.global_servers[0]
        probe = Message(sender=NodeId(Role.GLOBAL_SERVER, 1, None),
                        recipient=gs.po.node, request=True)
        with gs._mu:
            err = gs._reject_corrupt_snapshot_locked(
                ckpt.CheckpointCorruption("blob CRC mismatch"), probe)
        assert "fenced" not in err["error"]
        assert gs.integrity_ckpt_rejects == 1
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# codec fuzz: typed errors, right shapes, no crashes
# ---------------------------------------------------------------------------

def _fuzz_decode(decode, orig_len):
    """Decode a (possibly damaged) payload: the ONLY acceptable
    outcomes are a typed CodecError or a right-shaped float32 tensor.
    Anything else — struct.error, IndexError, a short array — is the
    bug class this suite exists to catch."""
    try:
        out = decode()
    except CodecError:
        return "typed-reject"
    out = np.asarray(out)
    assert out.shape == (orig_len,), f"wrong shape {out.shape}"
    assert out.dtype == np.float32
    return "decoded"


@pytest.mark.parametrize("codec_name", ["bsc", "fp16", "2bit", "mpq"])
def test_codec_fuzz_roundtrip_truncate_bitflip(codec_name):
    rng = np.random.default_rng(abs(hash(codec_name)) % (2 ** 32))
    n = 4096
    grad = rng.standard_normal(n).astype(np.float32) * 2.0
    codec = {"bsc": lambda: BscCodec(ratio=0.05),
             "fp16": Fp16Codec,
             "2bit": TwoBitCodec,
             "mpq": lambda: MpqSelector(size_bound=n // 2)}[codec_name]()
    if codec_name == "mpq":
        codec = codec.select(n)  # n >= size_bound → the bsc member
    payload = np.asarray(codec.compress(1, grad))
    tag = codec.name

    # 1. clean roundtrip: deterministic decode with the right shape
    out1 = codec.decompress(1, payload, n)
    out2 = codec.decompress(1, payload.copy(), n)
    assert out1.shape == (n,) and out1.dtype == np.float32
    np.testing.assert_array_equal(out1, out2)

    raw = payload.tobytes()
    item = payload.dtype.itemsize

    def decode_bytes(b):
        arr = (np.frombuffer(b, dtype=payload.dtype)
               if len(b) % item == 0
               else np.frombuffer(b, dtype=np.uint8))
        return decompress_payload(tag, 1, arr, n)

    # 2. truncations: every cut point is a typed reject or right-shaped
    rejects = 0
    for cut in rng.choice(max(1, len(raw) - 1), size=64, replace=False):
        rejects += _fuzz_decode(
            lambda: decode_bytes(raw[:int(cut)]), n) == "typed-reject"
    assert rejects > 0, "no truncation was ever rejected"

    # 3. seeded bit flips: never crash, never mis-shape
    for _ in range(128):
        dam = bytearray(raw)
        pos = int(rng.integers(len(dam) * 8))
        dam[pos // 8] ^= 1 << (pos % 8)
        _fuzz_decode(lambda: decode_bytes(bytes(dam)), n)


def test_sparse_index_bounds_are_fenced():
    """A flipped int32 scatter index turns negative or huge; numpy
    fancy indexing would silently WRAP the negative ones into valid
    slots.  The sparse decoders refuse out-of-range ids instead."""
    vals = np.array([1.0, 2.0], np.float32)
    for idx in ([-3, 0], [0, 10 ** 6]):
        payload = pack_sparse(vals, np.array(idx, np.int64))
        with pytest.raises(CodecError, match="index"):
            scatter_sparse(payload, 16, key=5)
    # row-sparse geometry gates
    rows = np.ones((2, 4), np.float32)
    packed = pack_rows(np.array([0, 1], np.int64), rows)
    ids, back = unpack_rows(packed, 4)
    np.testing.assert_array_equal(back, rows)
    np.testing.assert_array_equal(ids, [0, 1])
    with pytest.raises(CodecError):
        unpack_rows(packed[:-1], 4)  # ragged payload
    with pytest.raises(CodecError):
        unpack_rows(packed, 0)  # nonsensical geometry


def test_unpack_sparse_rejects_odd_and_unknown_tag():
    with pytest.raises(CodecError):
        unpack_sparse(np.ones(3, np.float32))
    with pytest.raises(CodecError, match="unknown"):
        decompress_payload("zstd9", 1, np.ones(4, np.float32), 4)


# ---------------------------------------------------------------------------
# chaos plumbing + atomic_write
# ---------------------------------------------------------------------------

def test_netfault_corrupt_phase_validation_and_seed():
    from geomx_tpu.chaos.netfault import NetFaultPhase, _corrupt_seed

    ph = NetFaultPhase(at_s=1.0, duration_s=2.0, kind="corrupt",
                       src="server:0@p0", dst="global_server:0",
                       rate=0.5, corrupt_mode="truncate")
    # the per-link tape seed is stable and link-distinct
    assert _corrupt_seed(7, ph) == _corrupt_seed(7, ph)
    ph2 = NetFaultPhase(at_s=1.0, duration_s=2.0, kind="corrupt",
                        src="server:0@p1", dst="global_server:0")
    assert _corrupt_seed(7, ph) != _corrupt_seed(7, ph2)
    with pytest.raises(ValueError):
        NetFaultPhase(at_s=0, duration_s=1, kind="corrupt",
                      src="a", dst="b", rate=0.0)
    with pytest.raises(ValueError):
        NetFaultPhase(at_s=0, duration_s=1, kind="corrupt",
                      src="a", dst="b", corrupt_mode="scramble")
    with pytest.raises(ValueError):
        NetFaultPhase(at_s=0, duration_s=1, kind="corrupt", dst="b")


def test_corrupt_bytes_deterministic_per_seed():
    from geomx_tpu.transport.van import corrupt_bytes

    blob = bytes(range(256)) * 8
    a = corrupt_bytes(blob, random.Random(13), "bitflip")
    b = corrupt_bytes(blob, random.Random(13), "bitflip")
    assert a == b and a != blob and len(a) == len(blob)
    t = corrupt_bytes(blob, random.Random(13), "truncate")
    assert len(t) < len(blob)


def test_atomic_write_durable_and_no_droppings(tmp_path):
    from geomx_tpu.utils.io import atomic_write

    p = tmp_path / "slab.bin"
    with atomic_write(str(p)) as f:
        f.write(b"x" * 1024)
    assert p.read_bytes() == b"x" * 1024
    leftovers = [q for q in tmp_path.iterdir() if q.name != "slab.bin"]
    assert not leftovers, f"tmp droppings: {leftovers}"


# ---------------------------------------------------------------------------
# health rule
# ---------------------------------------------------------------------------

def test_health_rule_data_corruption_pages_and_recovers():
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        enable_obs=True, obs_interval_s=0.0,  # manual tick
        obs_window=8, obs_corruption_events=5,
        enable_flight=False, lightweight=True))
    try:
        mc, eng = sim.metrics_collector, sim.health
        node = "server:0@p9"  # synthetic foreign node

        def sample(t, wire, poison, quar, who=node):
            mc.ingest({"node": who, "boot": 1, "t_mono": float(t),
                       "metrics": {},
                       "stats": {"integrity_wire_rejects": wire,
                                 "integrity_poison_rejects": poison,
                                 "poison_quarantines": quar}})

        for i in range(3):
            sample(i, wire=i * 4, poison=i, quar=0)
        recs = eng.tick(now=5.0)
        fired = [r for r in recs if r["rule"] == "data_corruption"
                 and r["subject"] == node]
        assert fired and fired[0]["state"] == "firing"
        assert fired[0]["severity"] == "warn"  # no quarantine involved
        # flat counters → window deltas decay to zero → recovery (the
        # obs_window=8 ring ages the reject burst out)
        for i in range(3, 12):
            sample(i, wire=8, poison=2, quar=0)
        recs = eng.tick(now=20.0)
        rec = [r for r in recs if r["rule"] == "data_corruption"
               and r["subject"] == node]
        assert rec and rec[0]["state"] == "recovered"
        # a burst that includes a quarantine pages at critical severity
        node2 = "server:0@p8"
        for i in range(2):
            sample(i, wire=0, poison=i * 6, quar=i, who=node2)
        recs = eng.tick(now=25.0)
        crit = [r for r in recs if r["rule"] == "data_corruption"
                and r["subject"] == node2]
        assert crit and crit[0]["state"] == "firing"
        assert crit[0]["severity"] == "critical"
    finally:
        sim.shutdown()
