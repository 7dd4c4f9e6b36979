"""Pluggable merge backend (ISSUE 10): numpy stays the bit-identical
default, the jax backend merges identically on CPU (f32 exact for
integer-valued gradients, the same arrival-order fold), the donation /
promotion / deterministic contracts hold, and the whole kvstore round
machinery runs green with the lanes forced onto jax
(``scripts/run_backend_smoke.sh`` runs the broader sweep).

Runs on the virtual 8-device CPU mesh (conftest), so the mesh psum
party-aggregation path and the opt-in quantized rung are exercised for
real — one pre-reduced buffer per device, reduced by ``shard_map`` +
``psum`` at round close."""

import os
import tracemalloc

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.backend import (NumpyBackend, make_merge_backend,
                                       resolve_merge_backend)
from geomx_tpu.kvstore.common import make_merge_lanes, resolve_server_shards


def _jax_backend(**cfg_kw):
    from geomx_tpu.kvstore.jax_backend import JaxBackend

    return JaxBackend(Config(topology=Topology(), **cfg_kw))


# ---- selection rules ---------------------------------------------------------

def test_auto_resolves_numpy_on_cpu_host(monkeypatch):
    # the suite pins JAX_PLATFORMS=cpu (conftest): auto must pick the
    # host reference path without so much as importing jax.  Clear the
    # env fallback — run_backend_smoke.sh runs this very test under
    # GEOMX_MERGE_BACKEND=jax
    monkeypatch.delenv("GEOMX_MERGE_BACKEND", raising=False)
    cfg = Config(topology=Topology())
    assert cfg.merge_backend == "auto"
    assert resolve_merge_backend(cfg) == "numpy"
    assert isinstance(make_merge_backend(cfg), NumpyBackend)


def test_deterministic_forces_numpy():
    cfg = Config(topology=Topology(), merge_backend="jax",
                 deterministic=True)
    assert resolve_merge_backend(cfg) == "numpy"


def test_env_fallback_shakes_directly_constructed_configs(monkeypatch):
    monkeypatch.setenv("GEOMX_MERGE_BACKEND", "jax")
    cfg = Config(topology=Topology())  # merge_backend left at "auto"
    assert resolve_merge_backend(cfg) == "jax"
    # an explicit field wins over the env fallback
    assert resolve_merge_backend(
        Config(topology=Topology(), merge_backend="numpy")) == "numpy"


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="merge_backend"):
        resolve_merge_backend(Config(topology=Topology(),
                                     merge_backend="cuda"))


def test_jax_backend_caps_lanes():
    cfg = Config(topology=Topology(), server_shards=8)
    be = _jax_backend(server_shards=8)
    mu, shards = make_merge_lanes(cfg, "test", be)
    try:
        assert mu.n == shards.n == min(resolve_server_shards(cfg),
                                       be.max_lanes)
    finally:
        shards.stop()


# ---- merge contracts ---------------------------------------------------------

def test_donated_adopt_no_hidden_copy_on_numpy_path():
    """The zero-copy recv view flows straight into the accumulator: a
    donated writeable f32 buffer IS adopted (same object), and the seed
    allocates nothing of the payload's size — the tracemalloc guard
    that keeps a 200 MB push from silently costing 400 MB."""
    be = NumpyBackend(Config(topology=Topology()))
    v = np.ones(1 << 20, np.float32)  # 4 MB
    tracemalloc.start()
    try:
        acc = be.seed(v, donated=True)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert acc is v, "donated buffer must be adopted, not copied"
    assert peak < v.nbytes // 2, f"hidden copy on the adopt path: {peak}"
    # the defensive half of the contract: non-donated payloads are
    # copied (the sender may still be aliasing the buffer)
    assert be.seed(v, donated=False) is not v
    frozen = np.ones(16, np.float32)
    frozen.flags.writeable = False
    adopted = be.seed(frozen, donated=True)
    assert adopted is not frozen and adopted.flags.writeable


def test_donated_adopt_honored_by_jax_backend():
    """The jax path's adopt contract is the single staged H2D copy:
    h2d_bytes counts exactly one staging of every payload, and the wire
    buffer is never aliased by live round state (mutating it after the
    push must not change the merge)."""
    be = _jax_backend()
    v1 = np.ones(1024, np.float32)
    v2 = np.full(1024, 2.0, np.float32)
    acc = be.seed(v1, donated=True)
    acc = be.accumulate(acc, v2)
    v1[:] = 99.0  # the donated buffer is ours again after staging
    v2[:] = 99.0
    out = be.materialize(acc)
    np.testing.assert_array_equal(out, np.full(1024, 3.0, np.float32))
    assert be.stats()["h2d_bytes"] == v1.nbytes + v2.nbytes
    assert be.stats()["merge_device_ms"] > 0


def test_f16_promotion_rule_pinned_across_backends():
    """A float16 push promotes to a float32 accumulator on the FIRST
    touch, and both backends produce bit-identical f32 — the dtype
    promotion half of the MergeBackend contract."""
    rng = np.random.default_rng(7)
    v16 = rng.standard_normal(4096).astype(np.float16)
    w16 = rng.standard_normal(4096).astype(np.float16)
    outs = {}
    for name, be in (("numpy", NumpyBackend(Config(topology=Topology()))),
                     ("jax", _jax_backend())):
        acc = be.seed(v16.copy(), donated=True)
        acc = be.accumulate(acc, w16.copy())
        out = be.materialize(acc)
        assert out.dtype == np.float32
        outs[name] = out.tobytes()
    assert outs["numpy"] == outs["jax"]


def test_f32_merge_exact_parity_numpy_vs_jax():
    """Integer-valued f32 gradients make float accumulation exact in
    any order, so the two backends must agree BIT-identically."""
    rng = np.random.default_rng(3)
    pushes = [rng.integers(-64, 64, 8192).astype(np.float32)
              for _ in range(8)]
    results = {}
    for name, be in (("numpy", NumpyBackend(Config(topology=Topology()))),
                     ("jax", _jax_backend())):
        acc = be.seed(pushes[0].copy(), donated=True)
        for p in pushes[1:]:
            acc = be.accumulate(acc, p.copy())
        results[name] = be.materialize(acc).tobytes()
    assert results["numpy"] == results["jax"]


def test_mesh_psum_party_aggregation(monkeypatch):
    """With the 8-device mesh and a big tensor the jax backend parks
    one pre-reduced part per device slot and the round close reduces
    across them as one shard_map+psum collective — same exact sum."""
    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_MESH_MIN_ELEMS", 1024)
    be = _jax_backend()
    if len(be._devices) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    pushes = [np.full(4096, float(i + 1), np.float32) for i in range(5)]
    acc = be.seed(pushes[0], donated=True)
    for p in pushes[1:]:
        acc = be.accumulate(acc, p)
    assert acc.spread and len(acc.parts) > 1, "mesh path not engaged"
    out = be.materialize(acc)
    np.testing.assert_array_equal(out, np.full(4096, 15.0, np.float32))


def test_quantized_rung_error_bounded(monkeypatch):
    """The opt-in EQuARX rung routes the mesh collective through the
    int8 block-quantized psum: the party sum is recovered within the
    documented per-element bound (each element quantized at most twice
    per leg at <= blockmax/127)."""
    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_MESH_MIN_ELEMS", 1024)
    be = _jax_backend(merge_quantized=True)
    if len(be._devices) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    rng = np.random.default_rng(11)
    pushes = [rng.standard_normal(4096).astype(np.float32)
              for _ in range(4)]
    acc = be.seed(pushes[0], donated=True)
    for p in pushes[1:]:
        acc = be.accumulate(acc, p)
    out = be.materialize(acc)
    exact = np.sum(pushes, axis=0)
    k = len(pushes)
    bound = 2.0 * k * max(np.abs(p).max() for p in pushes) / 127.0
    assert np.max(np.abs(out - exact)) <= bound
    assert be.stats()["merge_quantized"] is True


# ---- e2e: the kvstore round machinery on the jax lanes -----------------------

def _train_rounds(steps=2, lr=0.1, **cfg_kw):
    cfg = Config(topology=Topology(num_parties=2, workers_per_party=2),
                 **cfg_kw)
    sim = Simulation(cfg)
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(2048, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": lr})
        for _ in range(steps):
            for i, w in enumerate(ws):
                w.push(0, np.full(2048, float(i + 1), np.float32))
            for w in ws:
                w.pull_sync(0)
                w.wait_all()
        out = ws[0].pull_sync(0)
        ls = sim.local_servers[0]
        return np.array(out), ls._backend.name, ls.stats()
    finally:
        sim.shutdown()


def test_e2e_jax_backend_matches_numpy_bitwise():
    """The acceptance bar: a full two-tier FSA round trip under
    GEOMX_MERGE_BACKEND=jax lands bit-identical weights to the numpy
    default (integer-valued grads — exact under any fold order), and
    the servers actually ran the jax lanes (stats say so, with the
    device counters moving)."""
    w_np, be_np, _ = _train_rounds(merge_backend="numpy")
    w_jx, be_jx, st = _train_rounds(merge_backend="jax")
    assert (be_np, be_jx) == ("numpy", "jax")
    assert st["merge_backend"] == "jax"
    assert st["h2d_bytes"] > 0
    assert w_np.tobytes() == w_jx.tobytes()


def test_jax_backend_registry_gauges_set():
    from geomx_tpu.utils.metrics import system_snapshot

    _, _, st = _train_rounds(merge_backend="jax", steps=1)
    snap = system_snapshot()
    keyed = {k for k in snap if k.endswith(".merge_device_ms")
             or k.endswith(".h2d_bytes")}
    assert keyed, f"merge gauges missing from the registry: {sorted(snap)[:8]}"


def test_deterministic_suite_unaffected():
    """deterministic + jax request = numpy lanes, single stripe — the
    replayable debug mode cannot be put on a device dispatch order."""
    w_a, be_a, _ = _train_rounds(merge_backend="jax", deterministic=True)
    w_b, be_b, _ = _train_rounds(merge_backend="numpy", deterministic=True)
    assert be_a == be_b == "numpy"
    assert w_a.tobytes() == w_b.tobytes()


# ---- the round close: one D2H, handed on frozen (ISSUE 27) -------------------

class _FreshFrozenPart:
    """What an accelerator's device array is to ``np.asarray``: every
    D2H lands in a fresh, read-only host buffer (jax freezes it on
    every platform; only the CPU client hands out a view instead)."""

    def __init__(self, v):
        self._v = np.array(v, np.float32)
        self.nbytes = self._v.nbytes
        self.handed_out = []

    def copy_to_host_async(self):
        """The copy a round close starts before it reads (ISSUE 41)."""

    def __array__(self, dtype=None, copy=None):
        out = self._v.copy()
        out.flags.writeable = False
        self.handed_out.append(out)
        return out


def _as_accelerator(be):
    """Steer a jax backend down its accelerator branch on the CPU
    client: what it decides by is the platform it observed."""
    be._platform = "tpu"
    return be


def _materialize_fresh_frozen():
    from geomx_tpu.kvstore.jax_backend import _DeviceAccum

    be = _as_accelerator(_jax_backend())
    part = _FreshFrozenPart(np.arange(1024))
    out = be.materialize(_DeviceAccum(part, 1024, False, key=7))
    # the D2H result itself, frozen: no second copy of the round
    assert len(part.handed_out) == 1 and out is part.handed_out[0]
    assert not out.flags.writeable
    st = be.stats()
    assert (st["d2h_bytes"], st["cow_bytes"]) == (out.nbytes, 0)
    # a consumer that builds in the round copies once, and is counted
    from geomx_tpu.kvstore.server import _mutable_round

    own = _mutable_round(be, out)
    assert own is not out and own.flags.writeable
    own += 1.0  # (writing into ``out`` would raise: numpy enforces it)
    np.testing.assert_array_equal(out, np.arange(1024, dtype=np.float32))
    assert be.stats()["cow_bytes"] == out.nbytes
    assert _mutable_round(be, own) is own
    assert be.stats()["cow_bytes"] == out.nbytes


def _materialize_cpu_view_isolated():
    # on the CPU client device_put aliases an aligned host buffer and
    # np.asarray is a view of it: the materialized round could be the
    # sender's own (non-donated) memory, so the isolation copy stays
    be = _jax_backend()
    assert be._platform == "cpu"
    v = np.arange(4096, dtype=np.float32)
    out = be.materialize(be.seed(v, donated=False))
    assert out.flags.writeable and not np.shares_memory(out, v)
    v[:] = -1.0
    np.testing.assert_array_equal(out, np.arange(4096, dtype=np.float32))
    assert be.stats()["cow_bytes"] == 0  # isolation is not a COW
    # a host-seeded (row-sparse) round passes through, as ever
    host = np.ones(8, np.float32)
    assert be.materialize(host) is host


def _local_server_forwards_frozen():
    # the local tier only READS the round (WAN pack, push-up): it goes
    # up frozen, as it came off the device, and nothing is copied
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        merge_backend="jax"))
    try:
        ls = sim.local_servers[0]
        _as_accelerator(ls._backend)
        sent = []
        zpush = ls.up.zpush
        ls.up.zpush = lambda kvs, **kw: (sent.append(kvs.vals),
                                         zpush(kvs, **kw))[1]
        w = sim.all_workers()[0]
        w.init(0, np.zeros(2048, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        sent.clear()  # the init went up too
        for r in range(3):
            w.push(0, np.full(2048, float(r + 1), np.float32))
            w.wait_all()
            np.testing.assert_array_equal(
                w.pull_sync(0),
                np.full(2048, -sum(range(1, r + 2)), np.float32))
        assert len(sent) == 3
        assert not any(v.flags.writeable for v in sent)
        st = ls.stats()
        assert st["cow_bytes"] == 0
        assert st["d2h_bytes"] == st["h2d_bytes"] == 3 * 2048 * 4
    finally:
        sim.shutdown()


@pytest.mark.parametrize("case", [
    _materialize_fresh_frozen, _materialize_cpu_view_isolated,
    _local_server_forwards_frozen], ids=lambda f: f.__name__.strip("_"))
def test_materialize_hands_on_one_copy(case):
    case()


def _host_opt_rounds(opt, frozen, **cfg_kw):
    """Three two-party rounds closed by the HOST optimizer at the
    global tier; returns (weights, the global server's stats)."""
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1), **cfg_kw))
    try:
        gs = sim.global_servers[0]
        if frozen:
            _as_accelerator(gs._backend)
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(2048, np.float32))
        ws[0].set_optimizer(opt)
        rng = np.random.default_rng(5)
        for _ in range(3):
            for w in ws:
                w.push(0, rng.integers(-8, 8, 2048).astype(np.float32))
            for w in ws:
                w.pull_sync(0)
                w.wait_all()
        return np.array(ws[0].pull_sync(0)), gs.stats()
    finally:
        sim.shutdown()


@pytest.mark.parametrize("frozen", [True, False],
                         ids=["frozen-d2h", "cpu-copy"])
@pytest.mark.parametrize("opt", [
    {"type": "sgd", "lr": 0.1},          # builds new_w IN the round
    {"type": "dcasgd", "lr": 0.1},       # scales the round in place
    {"type": "adam", "lr": 0.1},
], ids=lambda o: o["type"])
def test_host_optimizer_close_copies_on_write(opt, frozen):
    """The global tier's host-optimizer close (device stage off, or an
    optimizer it does not cover) is the consumer that WRITES into the
    materialized round (``update_scaled``: accum is donated).  A frozen
    D2H result is copied there, once, and counted; the trajectory is
    the numpy backend's bit for bit, and no frozen round is written
    into (numpy would raise "assignment destination is read-only")."""
    w_np, _ = _host_opt_rounds(opt, False, merge_backend="numpy")
    w_jx, st = _host_opt_rounds(opt, frozen, merge_backend="jax",
                                merge_opt_device=False)
    assert st["merge_backend"] == "jax" and st["opt_device"] == ""
    assert w_np.tobytes() == w_jx.tobytes()
    assert st["d2h_bytes"] == 3 * 2048 * 4
    assert st["cow_bytes"] == (st["d2h_bytes"] if frozen else 0)


@pytest.mark.parametrize("frozen", [True, False],
                         ids=["frozen-d2h", "cpu-copy"])
def test_row_sparse_scatter_into_device_round_copies_on_write(frozen):
    """The other writer: a row-sparse push meeting a round that a dense
    push seeded on the device scatters (``np.add.at``) into the
    materialized round, through the same gate."""
    from geomx_tpu.kvstore.server import _mutable_round

    be = _jax_backend()
    if frozen:
        _as_accelerator(be)
    dense = np.arange(64, dtype=np.float32)
    host = _mutable_round(be, be.materialize(be.seed(dense, True)))
    np.add.at(host.reshape(-1, 8), [1, 1], np.ones((2, 8), np.float32))
    want = dense.copy().reshape(-1, 8)
    want[1] += 2.0
    np.testing.assert_array_equal(host, want.ravel())
    assert be.stats()["cow_bytes"] == (host.nbytes if frozen else 0)


def test_payload_overwritten_after_ack_does_not_reach_global_tier():
    """The aliasing contract (``WorkerKVStore.push``): a non-donated
    payload aliases the caller's buffer until the ack, never after.
    The round close's D2H runs outside the server's lock but BEFORE the
    ack, and on the CPU client its copy is what cuts the alias chain
    (payload -> staged device buffer -> np.asarray view -> the global
    tier's staged buffer): a buffer reused right after the ack must not
    change the round the global tier merges."""
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        merge_backend="jax"))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4096, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        for r in range(3):
            buf = np.full(4096, 3.0, np.float32)
            ws[0].push(0, buf)
            ws[0].wait_all()          # acked: the buffer is ours again
            buf[:] = 1e6              # ... while party 1's round is open
            ws[1].push(0, np.full(4096, 5.0, np.float32))
            ws[1].wait_all()
            for w in ws:
                np.testing.assert_array_equal(
                    w.pull_sync(0),
                    np.full(4096, -4.0 * (r + 1), np.float32))  # mean of 3, 5
    finally:
        sim.shutdown()


def test_membership_fold_that_closes_a_round_still_ships_it():
    """The fold path (a leave lowers the target under the all-stripes
    barrier and the open round completes without the leaver) detaches
    AND materializes under the barrier, as before: the round is
    shipped, not lost between the two halves of the close."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        merge_backend="jax"))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(256, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        w3 = sim.add_worker(0)
        w3.init(0, np.zeros(256, np.float32))
        g = np.ones(256, np.float32)
        for w in ws + [w3]:
            w.push(0, g)
        for w in ws + [w3]:
            np.testing.assert_array_equal(w.pull_sync(0), -3.0 * g)
            w.wait_all()
        ls = sim.local_servers[0]
        d2h = ls.stats()["d2h_bytes"]
        ws[0].push(0, g)
        ws[1].push(0, g)              # 2 of 3: the round stalls ...
        for w in ws:
            w.wait_all()
        assert w3.leave_party()["num_workers"] == 2   # ... and folds
        for w in ws:
            np.testing.assert_array_equal(w.pull_sync(0), -5.0 * g)
        st = ls.stats()
        assert st["d2h_bytes"] == d2h + g.nbytes and st["cow_bytes"] == 0
        assert all(s.accum is None and s.in_flight == 0
                   for s in ls._keys.values())
    finally:
        sim.shutdown()
