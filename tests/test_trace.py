"""Cross-tier distributed tracing (ISSUE 3 tentpole).

Covers: end-to-end causal-chain propagation over the HiPS tree (the
acceptance criterion: one round's push → local-merge → WAN →
global-merge → pull chain connected by parent/child span ids across
>= 3 node roles, critical-path report naming the dominant stage),
trace-context survival through the DGT multi-channel chunk path
(reordered + lost lossy chunks) and the KVWorker.retarget replay path,
round sampling, heartbeat-RTT clock metrics, the per-codec WAN byte
registry, and the disabled-path overhead guard (spans gated before
construction — no per-message allocation).
"""

import time

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.trace import context as tctx
from geomx_tpu.trace.recorder import _NULL_SPAN, Tracer, get_tracer
from geomx_tpu.transport.message import Control, Domain, Message
from geomx_tpu.utils.metrics import system_snapshot


def _trace_cfg(parties=2, workers=1, **kw):
    kw.setdefault("trace_sample_every", 1)
    return Config(topology=Topology(num_parties=parties,
                                    workers_per_party=workers), **kw)


def _run_rounds(sim, rounds, tid=0, n=64):
    """Drive FSA rounds the way the training loop does: every worker's
    push+pull issued under its round span, waits after all parties
    pushed (an FSA round only completes with every party's push)."""
    ws = sim.all_workers()
    for r in range(rounds):
        for w in ws:
            with w.trace_round(r):
                w.push(tid, np.full(n, 0.1, np.float32))
                w.pull(tid, lambda t, a: None)
        for w in ws:
            w.wait_all()


def _train_rounds(sim, rounds, tid=0, n=64):
    """The rounds as ``training.run_worker`` drives them: a thread a
    worker, the wait for the pulls under the round's root (a round ends
    when the worker holds what it pulled)."""
    import threading

    def loop(w):
        for r in range(rounds):
            with w.trace_round(r):
                w.push(tid, np.full(n, 0.1, np.float32))
                w.pull(tid, lambda t, a: None)
                w.wait_all()

    ths = [threading.Thread(target=loop, args=(w,))
           for w in sim.all_workers()]
    for t in ths:
        t.start()
    for t in ths:
        t.join(60)
    assert not any(t.is_alive() for t in ths)


def test_e2e_chain_across_three_roles_and_critical_path(tmp_path):
    """Acceptance: merged trace connects one round's chain across
    worker / local server / global server, and the critical-path report
    names a dominant stage per round: since ISSUE 39 the stage with the
    largest share of the round's BLOCKING CHAIN, not of the summed
    durations."""
    sim = Simulation(_trace_cfg())
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        for w in ws:
            w.init(0, np.zeros(64, np.float32))
        _train_rounds(sim, 3)
        assert sim.flush_traces() > 0
        evs = sim.trace_collector.merged_events()
        roles = {e["pid"].split(":")[0] for e in evs}
        assert {"worker", "server", "global_server"} <= roles
        # every recorded parent resolves to a recorded span — the chain
        # has no dangling edges
        ids = {e["args"]["span"] for e in evs}
        dangling = [e for e in evs
                    if e["args"]["parent"] and e["args"]["parent"] not in ids]
        assert not dangling, [e["name"] for e in dangling]
        # walk one global-merge span up to its worker root: the chain
        # must cross >= 3 distinct roles connected by parent ids
        by_span = {e["args"]["span"]: e for e in evs}
        gl = [e for e in evs if e["name"] == "global.push"]
        assert gl, "no global-server merge spans collected"
        e, chain_roles, chain_names = gl[0], set(), []
        while e is not None:
            chain_roles.add(e["pid"].split(":")[0])
            chain_names.append(e["name"])
            e = by_span.get(e["args"]["parent"])
        assert len(chain_roles) >= 3, (chain_roles, chain_names)
        assert chain_names[-1] == "round", chain_names
        # critical path: every sampled round reported, dominant named
        rep = sim.trace_report()
        rounds = {r["round"]: r for r in rep["rounds"]}
        assert {0, 1, 2} <= set(rounds)
        for r in rounds.values():
            # the chain covers the round exactly, reaches every role,
            # and the dominant stage is the one it spent longest in
            assert sum(r["path"].values()) == r["wall_us"] > 0
            assert r.get("chain_lost_at") is None
            assert {k.split(":")[0] for k in r["path"]} >= {
                "worker", "server", "global_server"}
            assert r["dominant_stage"] in (
                "compute", "edge", "lan_push", "local_merge", "codec",
                "wan", "global_merge", "pull_fanout", "barrier", "queue",
                "other", "unexplained")
            dom = r["stages"][r["dominant_stage"]]
            assert dom["worst_node"]
            assert dom["path_us"] == max(
                st["path_us"] for st in r["stages"].values())
            assert all(st["busy_us"] >= 0 for st in r["stages"].values())
        # the merged file dump is valid JSON with the same events (and a
        # ``round.path`` instant the collector's own node shipped since)
        out = sim.dump_trace(str(tmp_path / "trace.json"))
        assert len(out["traceEvents"]) >= len(evs)
    finally:
        sim.shutdown()


def test_round_sampling_every_n():
    """trace_sample_every=2: rounds 0 and 2 trace, rounds 1 and 3 add
    NOTHING — the sampling gate is the overhead contract when on."""
    sim = Simulation(_trace_cfg(trace_sample_every=2))
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        for w in ws:
            w.init(0, np.zeros(16, np.float32))
        _run_rounds(sim, 4, n=16)
        sim.flush_traces()
        traced = {r["round"] for r in sim.trace_report()["rounds"]}
        assert traced == {0, 2}
    finally:
        sim.shutdown()


def test_dgt_chunks_preserve_trace_context_under_reorder_and_loss():
    """Satellite: the trace context survives the DGT multi-channel UDP
    path — chunks arrive reordered and lossy-channel chunks go missing,
    and the reassembled logical message still carries the original
    trace/span/parent ids."""
    from geomx_tpu.transport.dgt import DgtReassembler, DgtSender

    cfg = Config(enable_dgt=1, dgt_block_size=8, dgt_k=0.25,
                 dgt_udp_channels=3)
    sender = DgtSender(cfg)
    msg = Message(
        recipient=None, domain=Domain.GLOBAL, app_id=0, customer_id=1,
        timestamp=7, request=True, push=True,
        keys=np.array([5], np.int64),
        vals=np.arange(64, dtype=np.float32),
        lens=np.array([64], np.int64),
        trace_id=4242, span_id=99, parent_span_id=55, sampled=True,
    )
    msg.sender = "worker:0@p0"
    chunks = sender.split(msg)
    assert len(chunks) > 2
    assert all(c.trace_id == 4242 and c.span_id == 99
               and c.parent_span_id == 55 and c.sampled for c in chunks)
    # drop one lossy chunk, deliver the rest in reverse order
    lossy = [c for c in chunks if c.channel >= 1]
    assert lossy, "k=0.25 must put chunks on lossy channels"
    dropped = lossy[0]
    arriving = [c for c in chunks if c is not dropped]
    arriving.reverse()
    reasm = DgtReassembler()
    whole = None
    for c in arriving:
        out = reasm.accept(c)
        if out is not None:
            assert whole is None, "reassembled twice"
            whole = out
    assert whole is not None
    assert whole.trace_id == 4242
    assert whole.span_id == 99
    assert whole.parent_span_id == 55
    assert whole.sampled
    # the dropped lossy chunk zero-filled, the rest intact
    assert len(whole.vals) == 64


def test_retarget_replay_keeps_original_trace_id():
    """Satellite: a request replayed through KVWorker.retarget (the
    PR 1 failover path) keeps its ORIGINAL trace_id — the replay shows
    up as part of the original round's trace, not as a fresh one."""
    from geomx_tpu.kvstore.common import APP_PS
    from geomx_tpu.ps import KVPairs, KVServer, KVWorker, Postoffice
    from geomx_tpu.ps.postoffice import split_range
    from geomx_tpu.transport import InProcFabric

    cfg = Config(topology=Topology(num_parties=1, workers_per_party=1,
                                   num_standby_globals=1),
                 request_retry_s=30.0)  # long: only retarget may resend
    topo = cfg.topology
    fabric = InProcFabric()
    offices = {str(n): Postoffice(n, topo, fabric, cfg)
               for n in topo.all_nodes()}
    for po in offices.values():
        po.start()
    old = topo.global_servers()[0]
    new = topo.standby_globals()[0]
    got = []

    def handle(msg, kvs, server):
        got.append((msg.trace_id, msg.parent_span_id, msg.span_id))
        server.response(msg)

    srv_old = KVServer(APP_PS, 0, offices[str(old)], lambda *a: None)
    srv_new = KVServer(APP_PS, 0, offices[str(new)], handle)
    wnode = topo.workers(0)[0]
    kw = KVWorker(APP_PS, 1, offices[str(wnode)], [old], split_range(1))
    tctx.activate()
    prev = tctx.swap(tctx.TraceContext(4321, 17))
    try:
        ts = kw.zpush(KVPairs(np.array([1], np.int64),
                              np.ones(4, np.float32), np.array([4])))
    finally:
        tctx.restore(prev)
    time.sleep(0.2)
    assert kw.customer.num_response(ts) == 0  # blackholed at old target
    assert kw.retarget(old, new) == 1
    kw.wait(ts)
    assert got, "replayed request never reached the new target"
    trace_id, parent, span = got[0]
    assert trace_id == 4321
    assert parent == 17
    assert span != 0  # assigned at first send, preserved by the replay
    kw.stop(); srv_old.stop(); srv_new.stop()
    for po in offices.values():
        po.stop()
    fabric.shutdown()


def _backend_sites_get_the_null_span():
    """Every site ISSUE 26 added to the jax merge backend and the merge
    lanes, with ``context.ACTIVE`` false: ``_NULL_SPAN`` each, and the
    operator gauges still fed from the site's own clock."""
    from geomx_tpu.kvstore.jax_backend import JaxBackend
    from geomx_tpu.kvstore.server import _ctx_bound

    class Spy(Tracer):
        def __init__(self):
            super().__init__("spy-node")
            self.seen = []

        def span(self, name, *a, **kw):
            got = super().span(name, *a, **kw)
            self.seen.append((name, got))
            return got

    spy = Spy()
    cfg = Config(topology=Topology(), merge_backend="jax")
    be = JaxBackend(cfg, tracer=spy)
    small = np.ones(64, np.float32)      # folded on one device
    be.materialize(be.accumulate(be.seed(small, False, key=2), small))
    v = np.ones(1 << 16, np.float32)     # one slot a device, reduced
    acc = be.accumulate(be.seed(v, donated=False, key=3), v)
    acc = be.scale(acc, 0.5)
    opt = be.make_device_optimizer({"type": "sgd", "lr": 0.1})
    opt.step(3, np.zeros(1 << 16, np.float32), acc, 1.0).host()
    stage = be.make_codec_stage(cfg)
    stage._wire(stage.make_push_codec({"type": "fp16"})._enc(
        stage._ensure_device(v)))
    assert {n for n, _ in spy.seen} == {
        "be.h2d", "be.add", "be.scale", "be.reduce", "be.d2h", "opt.step"}
    assert all(got is _NULL_SPAN for _, got in spy.seen)
    st = be.stats()
    assert st["merge_device_ms"] > 0 and st["opt_device_ms"] > 0
    fn = lambda: None  # noqa: E731
    assert _ctx_bound(fn, spy, 1) is fn


def test_disabled_wait_sites_measure_and_allocate_nothing():
    """ISSUE 40's sites with ``context.ACTIVE`` false, on whole rounds
    of a ``Simulation`` with tracing off: every span site (the new
    ``worker.grad``-side ones included) gets ``_NULL_SPAN``; no wait is
    measured (``await_device``), no lock is wrapped (``locked``), no
    message is stamped with its sender's span; and a disabled ``locked``
    / ``await_device`` site allocates nothing that stays."""
    import sys
    import threading

    was_active = tctx.ACTIVE
    tctx.ACTIVE = False
    try:
        _new_sites_do_nothing_unsampled()
        tr = Tracer("overhead-guard-waits")
        lock = threading.Lock()
        value = np.ones(4, np.float32)

        def site():
            # the two shapes the sites are written in (kvstore/server.py
            # ``merge_one``; kvstore/jax_backend.py ``DeviceWeight.host``)
            with (lock if not tctx.ACTIVE else tr.locked(lock)):
                pass
            with tr.span("be.d2h", key=1, nbytes=16) as sp:
                if sp is not _NULL_SPAN:
                    sp.await_device(value)

        for _ in range(100):
            site()
        before = sys.getallocatedblocks()
        for _ in range(10_000):
            site()
        assert sys.getallocatedblocks() - before < 50
    finally:
        tctx.ACTIVE = was_active


def _new_sites_do_nothing_unsampled():
    import threading

    from geomx_tpu.trace import recorder
    from geomx_tpu.training import _edge_to_host

    seen, stamped = [], []
    real_span = Tracer.span

    def span(self, name, *a, **kw):
        got = real_span(self, name, *a, **kw)
        seen.append((name, got))
        return got

    def never(*a, **kw):
        raise AssertionError("measured with tracing off")

    patched = [(Tracer, "span", span), (Tracer, "locked", never),
               (recorder._Span, "await_device", never),
               (recorder._Span, "waited", never),
               (recorder._Span, "add", never),
               (recorder._TimedLock, "__init__", never)]
    saved = [(o, n, getattr(o, n)) for o, n, _ in patched]
    for o, n, f in patched:
        setattr(o, n, f)
    sim = Simulation(Config(topology=Topology(num_parties=1,
                                              workers_per_party=1),
                            merge_backend="jax"))
    try:
        w = sim.all_workers()[0]
        w.set_optimizer({"type": "adam", "lr": 0.1})
        w.init(0, np.zeros(4096, np.float32))
        van_send = w.po.van.send

        def send(msg, *a, **kw):
            van_send(msg, *a, **kw)
            stamped.append((msg.sent_by, msg.sent_mono, msg.trace_id))

        w.po.van.send = send
        done = threading.Event()
        for r in range(2):
            with w.trace_round(r):
                w.push(0, _edge_to_host(w, 0, np.ones(4096, np.float32), 1.0))
                w.pull(0, lambda t, a: done.set())
                w.wait_all()
        assert done.is_set()
    finally:
        sim.shutdown()
        for o, n, f in saved:
            setattr(o, n, f)
    names = {n for n, _ in seen}
    assert {"edge.d2h", "worker.push", "worker.pull", "worker.wait",
            "worker.pull_decode", "local.push", "local.close", "be.d2h",
            "local.pull_down", "global.push", "global.close", "global.opt",
            "global.swap", "global.acks", "global.pull"} <= names, names
    assert all(got is _NULL_SPAN for _, got in seen)
    assert stamped and all(s == (0, 0.0, 0) for s in stamped)


def test_disabled_tracing_no_per_message_work():
    """Tier-1 overhead guard (satellite): with tracing off, spans are
    gated BEFORE construction (the factory returns one shared no-op
    object) and messages cross the van completely unstamped."""
    from geomx_tpu.ps import Postoffice
    from geomx_tpu.transport import InProcFabric

    was_active = tctx.ACTIVE
    tctx.ACTIVE = False
    try:
        tr = Tracer("overhead-guard-node")
        # no allocation: the identical shared null object every call
        assert tr.span("local.push") is _NULL_SPAN
        assert tr.span("anything") is tr.span("else")
        assert tr.round(0, 0) is _NULL_SPAN
        # ...whatever the site carries (ISSUE 26: key / nbytes / the
        # message itself, read only when a span is recorded)
        assert tr.span("be.add", key=1, nbytes=2) is _NULL_SPAN
        assert tr.span("handle", of=Message(), queued_us=None) is _NULL_SPAN
        tr.instant("evict.worker")  # gated: records nothing
        assert tr.pending() == 0
        _backend_sites_get_the_null_span()

        topo = Topology(num_parties=1, workers_per_party=1)
        fabric = InProcFabric()
        po = Postoffice(topo.workers(0)[0], topo, fabric, Config())
        po.start()
        try:
            msg = Message(recipient=topo.server(0), domain=Domain.LOCAL,
                          control=Control.HEARTBEAT)
            po.van.send(msg)
            assert msg.sent_mono == 0  # no queue stamp either
            assert msg.trace_id == 0
            assert msg.span_id == 0
            assert msg.parent_span_id == 0
            assert not msg.sampled
        finally:
            po.stop()
            fabric.shutdown()
    finally:
        tctx.ACTIVE = was_active


def test_response_inherits_request_trace():
    """reply_to: the response joins the request's trace as a child of
    the request message (the timestamp/Customer correlation)."""
    req = Message(request=True, trace_id=9, span_id=33,
                  parent_span_id=11, sampled=True)
    rep = req.reply_to()
    assert rep.trace_id == 9
    assert rep.parent_span_id == 33  # child of the request MESSAGE
    assert rep.span_id == 0          # fresh id assigned at send
    assert rep.sampled


def test_trace_fields_survive_wire_serialization():
    m = Message(request=True, push=True,
                keys=np.array([1], np.int64),
                vals=np.ones(3, np.float32), lens=np.array([3], np.int64),
                trace_id=77, span_id=88, parent_span_id=66, sampled=True)
    m.sender = None
    back = Message.from_bytes(m.to_bytes())
    assert back.trace_id == 77
    assert back.span_id == 88
    assert back.parent_span_id == 66
    assert back.sampled


def test_heartbeat_rtt_and_clock_offsets_in_registry():
    """Satellite: heartbeat pings are echoed; RTT + clock offset land in
    the system-metrics registry and Postoffice.clock_offsets — the same
    numbers the trace collector merges timestamps with."""
    sim = Simulation(_trace_cfg(heartbeat_interval_s=0.05,
                                enable_eviction=False))
    try:
        w = sim.all_workers()[0]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not w.po.clock_offsets():
            time.sleep(0.05)
        offs = w.po.clock_offsets()
        assert offs, "no heartbeat echo arrived"
        sched = str(sim.topology.scheduler(0))
        assert sched in offs
        # one host, one clock: offset within the RTT, RTT sane
        rtts = w.po.heartbeat_rtts()
        assert 0.0 <= rtts[sched] < 1.0
        assert abs(offs[sched]) <= max(rtts[sched], 0.05)
        snap = system_snapshot()
        assert snap.get(f"{w.po.node}.heartbeat_rtt_s", float("nan")) >= 0.0
        assert np.isfinite(snap.get(f"{w.po.node}.clock_offset_s",
                                    float("nan")))
        # local servers heartbeat BOTH tiers — the collector's chaining
        # input (worker->psched + server->psched + server->gsched)
        ls = sim.local_servers[0]
        deadline = time.monotonic() + 10
        while (time.monotonic() < deadline
               and len(ls.po.clock_offsets()) < 2):
            time.sleep(0.05)
        assert len(ls.po.clock_offsets()) == 2
    finally:
        sim.shutdown()


def test_wan_codec_bytes_in_registry():
    """Satellite: every GLOBAL-domain data send is ledgered per wire
    codec tag in the system-metrics registry (wan_bytes_vanilla /
    wan_bytes_fp16 / ...)."""
    base = system_snapshot()
    sim = Simulation(Config(topology=Topology(num_parties=2,
                                              workers_per_party=1)))
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        for w in ws:
            w.init(0, np.zeros(4096, np.float32))
        for p in range(2):
            sim.worker(p, 0).set_gradient_compression({"type": "fp16"})
        for w in ws:
            w.push(0, np.ones(4096, np.float32))
        for w in ws:
            w.pull_sync(0)
        snap = system_snapshot()

        def delta(suffix):
            return sum(v - base.get(k, 0) for k, v in snap.items()
                       if k.endswith(suffix))

        assert delta(".wan_bytes_fp16") > 0      # compressed push-ups
        assert delta(".wan_bytes_vanilla") > 0   # INIT forwarding
    finally:
        sim.shutdown()


def test_phase_tracer_artifact(tmp_path):
    """The soak-deflake helper: phases land as root spans in a dumpable
    Chrome-trace artifact."""
    from geomx_tpu.trace import PhaseTracer

    pt = PhaseTracer("unit")
    with pt.phase("setup"):
        time.sleep(0.01)
    pt.mark("kill", node="worker:0@p0")
    with pt.phase("recovery"):
        time.sleep(0.01)
    path = pt.dump(str(tmp_path / "phases.json"))
    import json

    events = json.load(open(path))["traceEvents"]
    names = [e["name"] for e in events]
    assert "phase.setup" in names
    assert "phase.recovery" in names
    assert "mark.kill" in names
    setup = next(e for e in events if e["name"] == "phase.setup")
    assert setup["dur"] >= 10_000  # microseconds
