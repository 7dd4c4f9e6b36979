"""Key-sharded parallel server merge (PR 5 tentpole).

The servers' per-key state now lives behind N lock stripes with N
serial merge lanes (``kvstore.common.StripedRLock`` /
``ShardExecutor``); membership folds, fences and snapshots take the
all-stripes barrier.  These tests pin:

- the primitives' contracts (per-key FIFO, barrier atomicity, drain);
- merge DETERMINISM under 8 concurrent pushers over disjoint AND
  overlapping keys — sharded and single-lock accumulators bit-identical
  (integer-valued gradients make float accumulation order-independent);
- end-to-end training parity: a sharded deployment converges to exactly
  the single-lock deployment's weights;
- pull serving is not head-of-line blocked behind another key's merge
  (the split pull lane + stripe independence together).
"""

import threading
import time

import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.common import Cmd, ShardExecutor, StripedRLock
from geomx_tpu.ps.kv_app import KVPairs
from geomx_tpu.transport.message import Message


def test_striped_lock_barrier_excludes_stripe_holder():
    lk = StripedRLock(4)
    held = threading.Event()
    release = threading.Event()

    def holder():
        with lk.stripe(2):
            held.set()
            release.wait(5)

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(2)
    entered = []

    def barrier():
        with lk:
            entered.append(True)

    b = threading.Thread(target=barrier)
    b.start()
    time.sleep(0.1)
    assert not entered, "all-stripes barrier entered past a held stripe"
    release.set()
    b.join(5); t.join(5)
    assert entered
    # re-entrancy: under the barrier, any stripe may be re-taken
    with lk:
        with lk.stripe(0), lk.stripe(3):
            pass


def test_shard_executor_keeps_per_key_fifo():
    ex = ShardExecutor(4)
    try:
        order = {k: [] for k in range(8)}
        for i in range(50):
            for k in range(8):
                ex.submit(k, lambda k=k, i=i: order[k].append(i))
        assert ex.drain(10)
        for k, seen in order.items():
            assert seen == list(range(50)), f"lane {k % 4} reordered key {k}"
    finally:
        ex.stop()


def _push_stress(shards: int, pushers: int = 8, pushes: int = 12,
                 elems: int = 2048):
    """Drive the LocalServer's push handler from ``pushers`` threads:
    each pusher hits its own key (disjoint) AND a shared key
    (overlapping).  Returns {key: accumulated sum} once the lanes
    drain.  Integer-valued gradients keep float accumulation exact, so
    the sums are bit-identical whatever the interleaving."""
    cfg = Config(topology=Topology(num_parties=1,
                                   workers_per_party=pushers),
                 server_shards=shards)
    sim = Simulation(cfg)
    try:
        ls = sim.local_servers[0]
        ls._workers_target = 1 << 30   # rounds must never complete here
        ls.server.response = lambda *a, **k: None  # merge only, no wire
        workers = sim.topology.workers(0)
        shared_key = 1000

        def pusher(i):
            for t in range(pushes):
                for k in (i, shared_key):
                    m = Message(sender=workers[i], recipient=ls.po.node,
                                push=True, request=True,
                                timestamp=t * 2 + (k == shared_key),
                                cmd=Cmd.DEFAULT,
                                keys=np.array([k], np.int64),
                                vals=np.full(elems, float(i + 1),
                                             np.float32),
                                lens=np.array([elems], np.int64))
                    ls._handle_push(m, KVPairs(m.keys, m.vals, m.lens))

        threads = [threading.Thread(target=pusher, args=(i,))
                   for i in range(pushers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert ls._shards.drain(20)
        out = {}
        with ls._mu:
            for k, st in ls._keys.items():
                assert st.accum is not None, f"key {k} lost its accum"
                out[int(k)] = st.accum.tobytes()
                # every pusher's every push must be counted
                expect = pushes * (pushers if k == 1000 else 1)
                assert st.count == expect, (k, st.count, expect)
        return out
    finally:
        sim.shutdown()


def test_sharded_merge_bit_identical_to_single_lock():
    single = _push_stress(shards=1)
    sharded = _push_stress(shards=8)
    assert single.keys() == sharded.keys()
    for k in single:
        assert single[k] == sharded[k], f"key {k} sum diverged"


def test_sharded_e2e_training_parity():
    """A sharded deployment must train to EXACTLY the single-lock
    deployment's weights (4 workers, multi-key model, integer-valued
    gradients pre-scaled by 1/4 stay exact in float32)."""

    def run(shards):
        cfg = Config(topology=Topology(num_parties=1,
                                       workers_per_party=4),
                     server_shards=shards)
        sim = Simulation(cfg)
        try:
            ws = sim.all_workers()
            ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
            for w in ws:
                for k in range(3):
                    w.init(k, np.zeros(256, np.float32))
            rng = np.random.default_rng(42)
            grads = rng.integers(-8, 8, size=(3, 4, 3, 256)) * 4.0
            for r in range(3):
                for i, w in enumerate(ws):
                    for k in range(3):
                        w.push(k, grads[r, i, k].astype(np.float32))
                for w in ws:
                    w.wait_all()
                for w in ws:
                    for k in range(3):
                        w.pull_sync(k)
            # tensor ids map to sharded ps-keys; snapshot the whole store
            return {int(k): np.array(v)
                    for k, v in sim.global_servers[0].store.items()}
        finally:
            sim.shutdown()

    w1 = run(1)
    w8 = run(8)
    assert w1.keys() == w8.keys() and len(w1) == 3
    for k in w1:
        assert np.array_equal(w1[k], w8[k]), f"key {k} weights diverged"


def test_pull_not_blocked_behind_other_keys_merge():
    """Head-of-line independence under sharding: while key B's merge
    lane is stuck, a pull of key A must still be served (split pull
    lane routes it around the push queue; stripes keep A's state free).
    This is the sharded half of the split_pull_queue guarantee — the
    single-lock half lives in test_robustness.py.  lightweight=False:
    lightweight mode runs merge lanes inline with server_shards forced
    to 1 — the sharded configuration under test doesn't exist there."""
    # 3 stripes, not 4: a tensor's ps key is ``tensor_id << 20``
    # (kvstore/keys.py CHUNK_SPACE) and a stripe is ``key % n``, so under
    # any power-of-two count EVERY key shares stripe 0 and this test was
    # a race between the pull and the wedged lane for that one stripe
    # (lost under load).  2**20 % 3 == 1: keys 0 and 1 differ here.
    cfg = Config(topology=Topology(num_parties=1, workers_per_party=2),
                 server_shards=3)
    sim = Simulation(cfg, lightweight=False)
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        for w in ws:
            w.init(0, np.zeros(64, np.float32))
            w.init(1, np.zeros(64, np.float32))
        ls = sim.local_servers[0]
        k0, k1 = (ws[0].plan.parts(t, 64)[0].ps_key for t in (0, 1))
        assert ls._mu.stripe(k0) is not ls._mu.stripe(k1)
        block = threading.Event()
        from geomx_tpu.native import bindings as nb
        orig = nb.accumulate

        def slow_accumulate(acc, v, threads=0):
            block.wait(5)  # key B's merge wedged mid-accumulate
            orig(acc, v, threads)

        # wedge key 1's round: first push seeds the accum, second push
        # (the patched accumulate) blocks its lane
        ws[0].push(1, np.ones(64, np.float32))
        ws[0].wait_all()
        import geomx_tpu.kvstore.server as server_mod

        server_mod._native_accumulate = slow_accumulate
        try:
            ws[1].push(1, np.ones(64, np.float32))  # blocks on a lane
            t0 = time.monotonic()
            got = ws[1].pull_sync(0)  # DIFFERENT key: must not wait
            assert time.monotonic() - t0 < 2.0, (
                "pull starved behind another key's merge")
            assert got.shape == (64,)
        finally:
            block.set()
            for w in ws:
                w.wait_all()
            server_mod._native_accumulate = orig
    finally:
        sim.shutdown()


def test_deterministic_mode_forces_single_shard():
    from geomx_tpu.kvstore.common import resolve_server_shards

    cfg = Config(topology=Topology(), server_shards=8, deterministic=True)
    assert resolve_server_shards(cfg) == 1
    cfg2 = Config(topology=Topology(), server_shards=6)
    assert resolve_server_shards(cfg2) == 6


# ---- the round close off the server's lock (ISSUE 27) ------------------------

def _before_landing(be, hook):
    """Run ``hook(key)`` where a closed round's copy off the chip is
    waited for (``_HostCopy.land``, on the server's closer since
    ISSUE 41; the copy itself was started by ``materialize_async``)."""
    start = be.materialize_async

    class _Hooked:
        def __init__(self, copy):
            self._copy, self._hook = copy, hook

        def land(self):     # the closer lands it; the ack's turn re-reads
            if self._hook is not None:
                self._hook, first = None, self._hook
                first(self._copy.key)
            return self._copy.land()

    be.materialize_async = lambda acc: _Hooked(start(acc))


def test_round_close_d2h_holds_no_stripe():
    """The stripe is held to DECIDE and DETACH a round, never to wait
    for the device or to copy the model.  Under the reactor default a
    server has ONE stripe: wedge the landing (the round close's
    D2H) of key B and everything else that takes the lock must go on
    — a worker's pull of key A, a pull-down of key A — while key B's
    own pull stays parked behind its round (``in_flight``, set at the
    detach), and is served with the new weights once the round is
    through."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1),
        merge_backend="jax"))
    try:
        w = sim.all_workers()[0]
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        for t in (0, 1):
            w.init(t, np.zeros(64, np.float32))
        ls = sim.local_servers[0]
        assert ls._mu.n == 1, "the reactor default is one lock a server"
        ka, kb = (w.plan.parts(t, 64)[0].ps_key for t in (0, 1))
        wedged, release = threading.Event(), threading.Event()

        def wedge(key):
            if key == kb:
                wedged.set()
                assert release.wait(20)

        _before_landing(ls._backend, wedge)
        try:
            w.push(1, np.ones(64, np.float32))   # one worker: closes B
            assert wedged.wait(5)
            # B's round is detached and launched, its stripe free
            st = ls._keys[kb]
            assert st.accum is None and st.in_flight == 1
            t0 = time.monotonic()
            np.testing.assert_array_equal(w.pull_sync(0),
                                          np.zeros(64, np.float32))
            fresh = np.full(64, 7.0, np.float32)
            down = threading.Thread(target=ls._on_pull_down, args=(
                KVPairs(np.array([ka], np.int64), fresh,
                        np.array([64], np.int64)),))
            down.start()
            down.join(5)
            assert not down.is_alive(), "pull-down waited for the D2H"
            np.testing.assert_array_equal(w.pull_sync(0), fresh)
            assert time.monotonic() - t0 < 5.0
            # B's own pull parks: fresher weights are owed to it (sent
            # past the client, which holds a tensor's pull back until
            # its push is acked — and that ack follows the D2H)
            got = []
            ts = w.worker.zpull([kb], cb=got.append, cmd=Cmd.DEFAULT)
            deadline = time.monotonic() + 5
            while not st.parked_pulls and time.monotonic() < deadline:
                time.sleep(0.01)
            assert st.parked_pulls and not got, "served a stale B"
        finally:
            release.set()
        w.worker.wait(ts)
        w.wait_all()
        np.testing.assert_array_equal(got[0].vals,
                                      -np.ones(64, np.float32))
    finally:
        sim.shutdown()


def test_pushes_up_keep_per_key_order_across_lanes():
    """With merge lanes as threads (three stripes, three keys) each
    lane detaches its key's rounds in turn and the closer lands and
    ships them in the order they were decided, so a key's rounds reach
    the global tier in the order they closed whatever the D2H of each
    took — 20 back-to-back rounds, the D2H jittered."""
    cfg = Config(topology=Topology(num_parties=1, workers_per_party=1),
                 server_shards=3, merge_backend="jax")
    sim = Simulation(cfg, lightweight=False)   # lanes are threads
    try:
        w = sim.all_workers()[0]
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        for t in range(3):
            w.init(t, np.zeros(64, np.float32))
        ls = sim.local_servers[0]
        keys = [w.plan.parts(t, 64)[0].ps_key for t in range(3)]
        assert len({id(ls._mu.stripe(k)) for k in keys}) == 3
        rng = np.random.default_rng(0)
        naps = {k: iter(rng.uniform(0, 0.01, 20)) for k in keys}
        _before_landing(ls._backend,
                        lambda key: time.sleep(next(naps[key])))
        shipped = {k: [] for k in keys}
        push_up = ls._push_up

        def logged(kvs, **kw):
            for k, v in kvs.slices():
                shipped[int(k)].append(float(v[0]))
            return push_up(kvs, **kw)

        ls._push_up = logged
        for r in range(20):
            for t in range(3):
                w.push(t, np.full(64, float(r + 1), np.float32))
        w.wait_all()
        for t in range(3):
            np.testing.assert_array_equal(
                w.pull_sync(t), np.full(64, -210.0, np.float32))
        for k in keys:
            assert shipped[k] == [float(r + 1) for r in range(20)], k
    finally:
        sim.shutdown()
