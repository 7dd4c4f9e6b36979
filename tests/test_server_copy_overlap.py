"""The server tier's copies off the chip in flight together (ISSUE 41).

A local server on the jax backend STARTS a closed round's copy to the
host where it decided the round (``JaxBackend.materialize_async``) and
goes on to the next message; its closer waits for each copy to land in
the order the rounds were decided and only then acks and ships.  The
global server's ``DeviceWeight`` starts its copy at the round close
where the handle it replaces was read.  Driven here on the CPU client
with the landing of each key's copy held on an event."""

import threading
import time
from collections import defaultdict

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.common import Cmd, Ctrl
from geomx_tpu.ps import KVPairs
from geomx_tpu.transport.message import Control, Domain, Message
from geomx_tpu.utils import get_profiler

N = 64          # elements a key: 256 bytes


def _sim(keys=3, workers=1, **cfg):
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=workers),
        merge_backend="jax", **cfg))
    ws = sim.all_workers()
    ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
    for w in ws:
        for t in range(keys):
            w.init(t, np.zeros(N, np.float32))
    return sim


def _keys(w, n):
    return [w.plan.parts(t, N)[0].ps_key for t in range(n)]


def _until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


class _Landing:
    """A copy on its way whose FIRST landing runs ``before(copy)`` (the
    closer lands it; the ack's turn asks again and is handed the
    value, as with ``_HostCopy`` itself)."""

    def __init__(self, copy, before):
        self._copy, self._before = copy, before

    def land(self):
        if self._before is not None:
            before, self._before = self._before, None
            before(self._copy)
        return self._copy.land()


class _Gate:
    """Holds the landing of each key's copy on an event, and logs what
    the server does around it: ``issue`` (the copy started), ``land``,
    ``ack`` (the push's response) and ``up`` (``_push_up``), by key."""

    def __init__(self, ls):
        self.ls = ls
        self.log = []
        self.open = defaultdict(threading.Event)
        self.bytes_seen = []
        be = ls._backend
        start, gate = be.materialize_async, self

        def held(copy):
            assert gate.open[copy.key].wait(20)
            gate.log.append(("land", copy.key))

        def issue(acc):
            copy = start(acc)
            gate.bytes_seen.append(be._bytes_in_flight)
            gate.log.append(("issue", acc.key))
            return _Landing(copy, held)

        be.materialize_async = issue
        response, push_up = ls.server.response, ls._push_up

        def acked(req, *a, **kw):
            if req.push:
                gate.log.append(("ack", int(req.keys[0])))
            return response(req, *a, **kw)

        def shipped(kvs, **kw):
            for k in kvs.keys:
                gate.log.append(("up", int(k)))
            return push_up(kvs, **kw)

        ls.server.response, ls._push_up = acked, shipped

    def release(self, *keys):
        for k in keys:
            self.open[k].set()

    def release_all(self):
        self.open.default_factory = lambda: _SET
        self.release(*self.open)

    def of(self, kind):
        return [k for what, k in self.log if what == kind]


_SET = threading.Event()
_SET.set()


# ---- the local round close ---------------------------------------------------

def test_two_closes_in_flight_and_a_third_push_staged_meanwhile():
    """Keys A and B close while neither copy has landed, and the push
    channel still takes C's push and stages it (its H2D beside their
    D2H).  Nothing is acked or shipped before its OWN copy landed, and
    they leave in the order the rounds were decided: B's copy landing
    first moves nothing until A's has."""
    sim = _sim(keys=3)
    try:
        w = sim.all_workers()[0]
        ls = sim.local_servers[0]
        be = ls._backend
        ka, kb, kc = _keys(w, 3)
        gate = _Gate(ls)
        h2d0 = be.stats()["h2d_bytes"]
        for t in range(3):
            w.push(t, np.full(N, float(t + 1), np.float32))
        assert _until(lambda: gate.of("issue") == [ka, kb, kc])
        # three copies in flight at once, the third push staged meanwhile
        assert be._copies_in_flight == 3
        assert be.stats()["h2d_bytes"] - h2d0 == 3 * 4 * N
        # with the closer: each key's landing, and its ack-and-ship turn
        assert ls._closer.pending == 6
        assert not gate.of("ack") and not gate.of("up")
        for k in (ka, kb, kc):       # each key's own pull stays parked
            assert ls._keys[k].in_flight == 1
        gate.release(kb)             # B lands first: A still holds it
        time.sleep(0.1)
        assert not gate.of("land") and not gate.of("ack")
        gate.release(ka)
        assert _until(lambda: gate.of("up") == [ka, kb])
        time.sleep(0.05)
        assert gate.log[3:] == [
            ("land", ka), ("ack", ka), ("up", ka),
            ("land", kb), ("ack", kb), ("up", kb)]
        gate.release(kc)
        w.wait_all()
        assert gate.log[-3:] == [("land", kc), ("ack", kc), ("up", kc)]
        for t in range(3):
            np.testing.assert_array_equal(
                w.pull_sync(t), np.full(N, -float(t + 1), np.float32))
        assert be._copies_in_flight == 0 and be._bytes_in_flight == 0
        assert ls._closer.pending == 0
    finally:
        sim.shutdown()


def test_the_byte_bound_holds_and_a_close_past_it_waits(monkeypatch):
    """With room for two keys' copies the third close waits, on the
    push channel with no lock held, until one has landed; a key larger
    than the whole bound is admitted alone."""
    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_COPIES_IN_FLIGHT_BYTES", 2 * 4 * N)
    sim = _sim(keys=4)
    try:
        w = sim.all_workers()[0]
        ls = sim.local_servers[0]
        be = ls._backend
        ks = _keys(w, 4)
        gate = _Gate(ls)
        for t in range(4):
            w.push(t, np.ones(N, np.float32))
        assert _until(lambda: gate.of("issue") == ks[:2])
        time.sleep(0.2)
        assert gate.of("issue") == ks[:2], "a close passed the bound"
        assert be._bytes_in_flight == 2 * 4 * N
        # the third round is decided and detached, its stripe free: the
        # server still serves what does not wait for it
        st = ls._keys[ks[2]]
        assert st.accum is None and st.in_flight == 1
        assert ls._mu.stripe(ks[2]).acquire(False)
        ls._mu.stripe(ks[2]).release()
        gate.release(ks[0])
        assert _until(lambda: gate.of("issue") == ks[:3])
        time.sleep(0.1)
        assert gate.of("issue") == ks[:3]
        gate.release_all()
        w.wait_all()
        assert gate.of("up") == ks and gate.of("ack") == ks
        assert max(gate.bytes_seen) <= 2 * 4 * N
        assert be._bytes_in_flight == 0
        # larger than the bound: alone, and at once
        big = be.materialize_async(
            be.seed(np.ones(4 * N, np.float32), False, key=99))
        assert be._bytes_in_flight == 4 * 4 * N
        np.testing.assert_array_equal(big.land(), np.ones(4 * N, np.float32))
        assert be._bytes_in_flight == 0 and be._copies_in_flight == 0
    finally:
        sim.shutdown()


def test_three_lanes_close_under_a_one_key_bound_in_order_and_balanced(
        monkeypatch):
    """Stress: merge lanes as threads (three stripes) decide rounds of
    three keys at once, room for ONE key's copy, the landings jittered,
    the interpreter switching threads every 10 us: every key's rounds
    reach ``_push_up`` in the order they closed, the bytes in flight
    never pass the bound, and the counts come back to zero."""
    import sys

    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_COPIES_IN_FLIGHT_BYTES", 4 * N)
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        server_shards=3, merge_backend="jax"), lightweight=False)
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        w = sim.all_workers()[0]
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        for t in range(3):
            w.init(t, np.zeros(N, np.float32))
        ls = sim.local_servers[0]
        be = ls._backend
        ks = _keys(w, 3)
        assert len({id(ls._mu.stripe(k)) for k in ks}) == 3
        naps = iter(np.random.default_rng(0).uniform(0, 0.003, 90))
        start, seen = be.materialize_async, []

        def issue(acc):
            copy = start(acc)
            seen.append(be._bytes_in_flight)
            return _Landing(copy, lambda copy: time.sleep(next(naps)))

        be.materialize_async = issue
        shipped = {k: [] for k in ks}
        push_up = ls._push_up

        def logged(kvs, **kw):
            for k, v in kvs.slices():
                shipped[int(k)].append(float(v[0]))
            return push_up(kvs, **kw)

        ls._push_up = logged
        for r in range(30):
            for t in range(3):
                w.push(t, np.full(N, float(r + 1), np.float32))
        w.wait_all()
        for t in range(3):
            np.testing.assert_array_equal(
                w.pull_sync(t), np.full(N, -465.0, np.float32))
        for k in ks:
            assert shipped[k] == [float(r + 1) for r in range(30)], k
        assert len(seen) == 90 and max(seen) <= 4 * N
        assert be._bytes_in_flight == 0 and be._copies_in_flight == 0
        assert _until(lambda: ls._closer.pending == 0)
    finally:
        sys.setswitchinterval(was)
        sim.shutdown()


@pytest.mark.parametrize("lanes", ["inline", "threads"])
def test_one_message_of_many_keys_past_the_bound_is_acked(monkeypatch, lanes):
    """P3 packs every part of a tensor into ONE push message: four keys
    close on it with room for two copies.  Each copy goes to the closer
    as it is issued, so the message's later keys wait for room that its
    earlier ones give back, and never on copies only its own ack's turn
    would land (that wait had no end)."""
    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_COPIES_IN_FLIGHT_BYTES", 2 * 4 * N)
    threads = lanes == "threads"
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        merge_backend="jax", enable_p3=True, p3_slice_elems=N,
        **({"server_shards": 3} if threads else {})),
        lightweight=not threads)
    try:
        w = sim.all_workers()[0]
        ls = sim.local_servers[0]
        be = ls._backend
        assert ls._shards.inline is (not threads)
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        w.init(0, np.zeros(4 * N, np.float32))
        messages, seen = [], []
        handle, start = ls._handle_push, be.materialize_async

        def counted(msg, kvs):
            messages.append(len(kvs.keys))
            return handle(msg, kvs)

        def issue(acc):
            copy = start(acc)
            seen.append(be._bytes_in_flight)
            return _Landing(copy, lambda copy: time.sleep(0.02))

        ls._handle_push, be.materialize_async = counted, issue
        w.push(0, np.ones(4 * N, np.float32))
        waiter = threading.Thread(target=w.wait_all, daemon=True)
        waiter.start()
        waiter.join(10)
        if waiter.is_alive():       # fail, not hang: let the channel go
            with be._room:
                be._bytes_in_flight = -(1 << 30)
                be._room.notify_all()
            pytest.fail("the push was never acked")
        assert messages == [4] and len(seen) == 4
        assert max(seen) <= 2 * 4 * N
        np.testing.assert_array_equal(w.pull_sync(0),
                                      -np.ones(4 * N, np.float32))
        assert be._bytes_in_flight == 0 and be._copies_in_flight == 0
        assert _until(lambda: ls._closer.pending == 0)
    finally:
        sim.shutdown()


def _overwrite_init(sim, w, ls):
    k = _keys(w, 1)[0]
    m = Message(sender=w.po.node, recipient=ls.po.node, app_id=0,
                customer_id=w.worker.customer.customer_id,
                timestamp=10_000, request=True, push=True, cmd=Cmd.INIT, keys=np.array([k], np.int64),
                vals=np.full(N, 5.0, np.float32),
                lens=np.array([N], np.int64), body={"overwrite": True})
    ls._handle_init(m, KVPairs(m.keys, m.vals, m.lens))


def _set_compression(sim, w, ls):
    ls._on_cmd(Message(sender=w.po.node, recipient=ls.po.node, app_id=0,
                       customer_id=w.worker.customer.customer_id,
                       timestamp=10_001, request=True,
                       cmd=Ctrl.SET_COMPRESSION, body={"type": "fp16"}))


def _fold(sim, w, ls):
    # a leave takes the all-stripes barrier to fold the member out
    ls._on_add_node(Message(
        sender=w.po.node, recipient=ls.po.node, control=Control.ADD_NODE,
        domain=Domain.LOCAL, request=True,
        body={"action": "leave", "node": "worker:9@p0", "token": "t"}))


def _stop(sim, w, ls):
    ls.stop()
    sim.local_servers.remove(ls)    # not again at ``sim.shutdown()``


@pytest.mark.parametrize("drain", [_overwrite_init, _set_compression, _fold,
                                   _stop],
                         ids=["overwrite_init", "set_compression",
                              "all_stripes_barrier", "shutdown"])
def test_what_quiesces_the_lanes_quiesces_the_closer(drain):
    """Everything that drains the merge lanes before it changes the
    state they would land on waits for the closer too, and returns
    with nothing pending: no copy in flight, no close unshipped."""
    sim = _sim(keys=2)
    try:
        w = sim.all_workers()[0]
        ls = sim.local_servers[0]
        be = ls._backend
        ks = _keys(w, 2)
        gate = _Gate(ls)
        for t in range(2):
            w.push(t, np.ones(N, np.float32))
        assert _until(lambda: gate.of("issue") == ks)
        done = threading.Event()

        def run():
            drain(sim, w, ls)
            done.set()

        th = threading.Thread(target=run, daemon=True)
        th.start()
        assert not done.wait(0.3), "returned with closes pending"
        assert ls._closer.pending >= 4 and be._copies_in_flight == 2
        gate.release_all()
        assert done.wait(10)
        assert ls._closer.pending == 0 and be._copies_in_flight == 0
        assert gate.of("up") == ks and gate.of("ack") == ks
    finally:
        sim.shutdown()


def test_a_handler_at_the_timeout_says_so_and_the_rounds_keep_their_order(
        monkeypatch, capsys):
    """A landing that will not come: the handler that quiesces before a
    fold waits ``_QUIESCE_S`` and no longer, says so loudly and goes
    on.  What the fold then completes leaves BEHIND the rounds still
    with the closer, so nothing is reordered by going on.  A poison
    strike that folds nobody out is on the push ingest path and waits
    for nothing."""
    import geomx_tpu.kvstore.server as srv

    monkeypatch.setattr(srv, "_QUIESCE_S", 0.2)
    sim = _sim(keys=2, workers=2)
    try:
        w0, w1 = sim.all_workers()
        ls = sim.local_servers[0]
        ka, kb = _keys(w0, 2)
        gate = _Gate(ls)
        w0.push(0, np.ones(N, np.float32))
        w1.push(0, np.ones(N, np.float32))       # closes A: wedged
        assert _until(lambda: gate.of("issue") == [ka])
        w0.push(1, np.full(N, 2.0, np.float32))  # B waits for worker 1
        assert _until(lambda: ls._keys[kb].count == 1)
        t0 = time.monotonic()
        ls._poison_strike("worker:7@p0")         # a strike, no fold
        assert time.monotonic() - t0 < 0.15
        assert not ls._quiesce()
        assert "NOT quiesced after 0.2s" in capsys.readouterr().out
        t0 = time.monotonic()
        ls._on_add_node(Message(                 # worker 1 leaves: B closes
            sender=w1.po.node, recipient=ls.po.node,
            control=Control.ADD_NODE, domain=Domain.LOCAL, request=True,
            body={"action": "leave", "node": str(w1.po.node),
                  "token": "t"}))
        assert 0.2 <= time.monotonic() - t0 < 5.0
        assert "NOT quiesced" in capsys.readouterr().out
        assert ls._keys[kb].accum is None, "the fold did not go ahead"
        time.sleep(0.1)
        assert not gate.of("up"), "the fold's round overtook the wedged one"
        gate.release_all()
        assert _until(lambda: gate.of("up") == [ka, kb])
        assert ls._quiesce()
    finally:
        sim.shutdown()


def test_deterministic_and_numpy_close_inline():
    """``deterministic`` resolves the numpy backend, which has no copy
    to wait for: no closer, no thread, every close on the deciding
    thread as before."""
    for cfg in (dict(deterministic=True), dict(merge_backend="numpy")):
        sim = Simulation(Config(
            topology=Topology(num_parties=1, workers_per_party=1),
            **{"merge_backend": "jax", **cfg}))
        try:
            ls = sim.local_servers[0]
            assert ls._closer is None and not ls._backend.async_copies
            assert not [t for t in threading.enumerate()
                        if t.name.startswith(f"close-{ls.po.node}")]
            w = sim.all_workers()[0]
            w.set_optimizer({"type": "sgd", "lr": 1.0})
            w.init(0, np.zeros(N, np.float32))
            w.push(0, np.ones(N, np.float32))
            w.wait_all()
            np.testing.assert_array_equal(w.pull_sync(0),
                                          -np.ones(N, np.float32))
        finally:
            sim.shutdown()


def test_a_row_sparse_round_leaves_behind_an_earlier_dense_one():
    """The row-sparse close takes the dense push's tail: its round (a
    host accumulator, nothing to wait for) is shipped behind a dense
    round of the same server that is still with the closer."""
    from geomx_tpu.compression.codecs import pack_rows

    sim = _sim(keys=2)
    try:
        w = sim.all_workers()[0]
        ls = sim.local_servers[0]
        ka, kb = _keys(w, 2)
        gate = _Gate(ls)
        w.push(0, np.ones(N, np.float32))
        assert _until(lambda: gate.of("issue") == [ka])
        rows = np.ones((2, 8), np.float32)
        m = Message(sender=w.po.node, recipient=ls.po.node, app_id=0,
                    customer_id=w.worker.customer.customer_id,
                    timestamp=20_000, request=True, push=True,
                    cmd=Cmd.ROW_SPARSE_PUSH, keys=np.array([kb], np.int64),
                    vals=pack_rows(np.array([0, 3], np.int64), rows),
                    lens=np.array([0], np.int64), body={"rs_cols": 8})
        m.lens[0] = len(m.vals)
        ls._handle_push_row_sparse(m, KVPairs(m.keys, m.vals, m.lens))
        time.sleep(0.1)     # its round is host bytes: no copy is issued
        assert gate.of("issue") == [ka]
        assert not gate.of("up"), "the row-sparse round overtook"
        gate.release_all()
        assert _until(lambda: gate.of("up") == [ka, kb])
        w.wait_all()
    finally:
        sim.shutdown()


# ---- the global server's weight for the pulls --------------------------------

def test_the_copy_starts_at_the_close_only_where_the_last_handle_was_read():
    from geomx_tpu.kvstore.jax_backend import JaxBackend

    be = JaxBackend(Config(topology=Topology(), merge_backend="jax"))
    opt = be.make_device_optimizer({"type": "sgd", "lr": 1.0})
    g = np.ones(N, np.float32)

    def step(raw):
        return opt.step(0, raw, be.seed(g, False, key=0), 1.0)

    d2h = lambda: be.stats()["d2h_bytes"]  # noqa: E731
    h1 = step(np.zeros(N, np.float32))      # replaces a host array
    assert not h1._inflight and not h1.was_read
    h2 = step(h1)                           # h1 replaced unread
    assert not h2._inflight and d2h() == 0 and be._copies_in_flight == 0
    np.testing.assert_array_equal(h2.host(), np.full(N, -2.0, np.float32))
    assert h2.was_read and d2h() == 4 * N and be._copies_in_flight == 0
    h3 = step(h2)                           # h2 was pulled: h3 will be
    assert h3._inflight == 1 and be._copies_in_flight == 1
    assert d2h() == 2 * 4 * N               # billed when it starts
    np.testing.assert_array_equal(h3.host(), np.full(N, -3.0, np.float32))
    assert h3.host() is h3.host() and d2h() == 2 * 4 * N
    assert be._copies_in_flight == 0
    h4 = step(h3)                           # started, and never read
    assert be._copies_in_flight == 1
    h5 = step(h4)                           # h4 replaced unread:
    assert be._copies_in_flight == 0        # it left the count there
    assert not h5._inflight and d2h() == 3 * 4 * N
    h4.host()                               # a late reader of the old one
    assert d2h() == 3 * 4 * N and be._copies_in_flight == 0
    # the HFA delta close follows the same rule
    assert opt.add_delta(h5, be.seed(g, False, key=0))._inflight == 0
    h5.host()
    assert opt.add_delta(h5, be.seed(g, False, key=0))._inflight == 1


def test_a_pulls_copy_does_not_hold_the_version_lock():
    """``_weight_wv`` pairs the handle with its version under
    ``_wv_mu`` and waits for the copy with it released, so a round
    close's swap never waits for a copy; the copy itself was started at
    the close (the last handle was pulled), two readers of one handle
    share it, and it is billed once."""
    sim = _sim(keys=1)
    try:
        w = sim.all_workers()[0]
        gs = sim.global_servers[0]
        be = gs._backend
        k = _keys(w, 1)[0]
        w.push(0, np.ones(N, np.float32))
        w.wait_all()
        np.testing.assert_array_equal(w.pull_sync(0),
                                      -np.ones(N, np.float32))
        h = gs.store.raw(k)
        assert h.was_read            # by the local server's pull-down
        entered, release = threading.Event(), threading.Event()
        land = be._land

        def held(dev, sp, inflight):
            entered.set()
            assert release.wait(20)
            return land(dev, sp, inflight)

        be._land = held
        w.push(0, np.ones(N, np.float32))
        assert entered.wait(5)       # the pull-down waits for the copy
        h2 = gs.store.raw(k)
        assert h2 is not h and h2._inflight >= 1 and not h2.was_read
        assert gs._wv_mu.acquire(False), "the copy holds _wv_mu"
        gs._wv_mu.release()
        d2h = be.stats()["d2h_bytes"]
        got = []
        other = threading.Thread(
            target=lambda: got.append(gs._weight_wv(k)))
        other.start()
        time.sleep(0.05)
        assert not got               # one copy: the second reader waits
        release.set()
        other.join(5)
        assert got and got[0][0] is h2.host()
        assert be.stats()["d2h_bytes"] == d2h   # billed when it started
        w.wait_all()
        np.testing.assert_array_equal(w.pull_sync(0),
                                      np.full(N, -2.0, np.float32))
        assert _until(lambda: be._copies_in_flight == 0)
    finally:
        sim.shutdown()


# ---- the counter it brings ---------------------------------------------------

def test_every_d2h_span_says_how_many_were_in_flight():
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        trace_sample_every=1, trace_batch_events=16, merge_backend="jax"))
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "adam", "lr": 0.01})
        for w in ws:
            for t in range(3):
                w.init(t, np.zeros(N, np.float32))

        for node in sim.offices:    # earlier tests' nodes of these names
            get_profiler(node).reset()

        def loop(kv):
            for r in range(4):
                with kv.trace_round(r):
                    for t in range(3):
                        kv.push(t, np.full(N, 0.1, np.float32))
                        kv.pull(t, lambda t, a: None)
                    kv.wait_all()

        threads = [threading.Thread(target=loop, args=(kv,)) for kv in ws]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        sim.flush_traces()
        events = [e for node in sim.offices
                  for e in list(get_profiler(node)._events)]
        spans = [e for e in events if e["name"] == "be.d2h"]
    finally:
        sim.shutdown()
    roles = {str(e["pid"]).split(":")[0] for e in spans}
    assert {"server", "global_server"} <= roles
    for e in spans:
        a = e["args"]
        assert a["inflight"] >= 1 and "wait_us" in a, a
    # a local round's copy is waited for on the closer, under its turn
    turns = {e["args"]["span"]: e for e in events
             if e["name"] == "local.land"}
    assert turns and all("queued_us" in e["args"] for e in turns.values())
    local = [e for e in spans if str(e["pid"]).startswith("server:")]
    assert local and all(e["tid"].startswith("close-") for e in local)


def test_the_wait_for_room_is_a_d2h_span_of_the_thread_it_held(monkeypatch):
    """``be.d2h`` sums every second a thread was held for a landing: a
    close past the bound waits for room inside a ``be.d2h`` of the push
    channel's own, with no ``inflight`` (nothing was issued), so the
    median of ``inflight`` reads the copies alone."""
    import geomx_tpu.kvstore.jax_backend as jb

    monkeypatch.setattr(jb, "_COPIES_IN_FLIGHT_BYTES", 4 * N)
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        trace_sample_every=1, trace_batch_events=16, merge_backend="jax"))
    try:
        w = sim.all_workers()[0]
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        for t in range(3):
            w.init(t, np.zeros(N, np.float32))
        ls = sim.local_servers[0]
        start = ls._backend.materialize_async
        ls._backend.materialize_async = lambda acc: _Landing(
            start(acc), lambda copy: time.sleep(0.05))
        get_profiler(str(ls.po.node)).reset()   # earlier tests' servers
        with w.trace_round(0):
            for t in range(3):
                w.push(t, np.ones(N, np.float32))
            w.wait_all()
        sim.flush_traces()
        spans = [e for e in list(get_profiler(str(ls.po.node))._events)
                 if e["name"] == "be.d2h"]
    finally:
        sim.shutdown()
    copies = [e for e in spans if "inflight" in e["args"]]
    rooms = [e for e in spans if "inflight" not in e["args"]]
    assert len(copies) == 3 and len(rooms) == 2
    assert all(e["args"]["inflight"] == 1 for e in copies)
    assert all(e["tid"].startswith("close-") for e in copies)
    assert not any(e["tid"].startswith("close-") for e in rooms)
    assert all(e["dur"] >= 0.03e6 for e in rooms)


# ---- the parity oracle -------------------------------------------------------

def _fsa(backend, closer=True, rounds=3):
    """Two parties of two workers, three keys, integer-valued gradients
    and a power-of-two step: every merge and update is exact, so the
    weights must agree bit for bit whatever closes the rounds."""
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=2),
        merge_backend=backend, enable_flight=False))
    try:
        if not closer:
            for ls in sim.local_servers:    # the parent's inline close
                ls._closer.stop()
                ls._closer = None
        ws = sim.all_workers()
        for w in ws:
            for t in range(3):
                w.init(t, np.zeros(256, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.5})
        grads = np.random.default_rng(41).integers(
            -8, 8, size=(rounds, len(ws), 3, 256)) * 4.0
        out = {}
        for r in range(rounds):
            for i, w in enumerate(ws):
                for t in range(3):
                    w.push(t, grads[r, i, t].astype(np.float32))
            for i, w in enumerate(ws):
                w.wait_all()
                out[i] = [np.array(w.pull_sync(t), copy=True)
                          for t in range(3)]
        return out
    finally:
        sim.shutdown()


def test_fsa_weights_bit_identical_on_every_worker_and_equal_to_the_parents():
    with_closer = _fsa("jax")
    for i in range(1, 4):
        for a, b in zip(with_closer[0], with_closer[i]):
            assert a.tobytes() == b.tobytes(), f"worker {i} parted"
    for name, other in (("inline close", _fsa("jax", closer=False)),
                        ("numpy backend", _fsa("numpy"))):
        for a, b in zip(with_closer[0], other[0]):
            assert a.tobytes() == b.tobytes(), name
    assert np.any(with_closer[0][0] != 0.0)
