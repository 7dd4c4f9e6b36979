"""The round's blocking chain (ISSUE 40).

``trace/collector.py`` ``blocking_chain`` as a pure function over
hand-built rounds with known answers; a 2-party ``Simulation`` on the
jax backend with a delay injected in turn into the local server's
materialize, the global optimizer's close and the worker's decode (the
chain's label and ``dominant_stage`` follow it, where the summed
durations do not); ``round.path`` under a ``jax.profiler`` session,
read back through ``benchmark/lib/spans.py`` with each new metric's own
file; the waits recorded on the sampled path.
"""

import itertools
import json
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.trace import context as tctx
from geomx_tpu.trace.collector import (PATH_FIELDS, UNEXPLAINED, WAIT_DEVICE,
                                       WAIT_LOCK, WAIT_QUEUE, WAN,
                                       blocking_chain, path_fields)
from geomx_tpu.trace.recorder import _HOLDERS, Tracer, get_tracer

ROOT = Path(__file__).resolve().parent.parent
W0, W1 = "worker:0@p0", "worker:0@p1"
S0, G = "server:0@p0", "global_server:0"
NEW_METRICS = (
    "path_worker_s_p50", "path_local_server_s_p50",
    "path_global_server_s_p50", "path_device_wait_s_p50",
    "path_queue_wait_s_p50", "path_unexplained_pct_p50",
    "server_d2h_wait_s_per_step", "host_rss_peak_GB")


# ---------------------------------------------------------------------------
# hand-built rounds
# ---------------------------------------------------------------------------

class Round:
    """Event dicts as the collector holds them, times in microseconds."""

    def __init__(self):
        self.events = []
        self._ids = itertools.count(1)

    def span(self, node, tid, name, ts, dur, parent=None, **args):
        ev = {"name": name, "pid": node, "tid": tid, "ts": float(ts),
              "dur": float(dur), "ph": "X" if dur else "i",
              "cat": "round" if name == "round" else "trace",
              "args": {"trace_id": 1, "span": next(self._ids),
                       "parent": parent["args"]["span"] if parent else 0,
                       **args}}
        self.events.append(ev)
        return ev

    def message(self, node, tid, by, ts, name="lan.send"):
        """The ``.send`` instant of a message sent at ``ts`` from inside
        the span ``by``; a ``handle`` names it as its parent."""
        return self.span(node, tid, name, ts, 0, parent=by,
                         by=by["args"]["span"])


def _one_worker_round(r, wait_from, end=1000):
    """A worker that computes to 100, pushes two keys, then waits."""
    root = r.span(W0, "main", "round", 0, end)
    r.span(W0, "main", "worker.grad", 0, 100, parent=root)
    p1 = r.span(W0, "main", "worker.push", 100, 10, parent=root)
    p2 = r.span(W0, "main", "worker.push", 110, 10, parent=root)
    r.span(W0, "main", "worker.wait", wait_from, end - wait_from,
           parent=root)
    return root, p1, p2


def _answered(r, by, sent, start, dur=80):
    """The worker's response thread handles the answer sent at ``sent``
    from inside ``by`` (a server's span) and decodes it."""
    m = r.message(by["pid"], by["tid"], by, sent)
    h = r.span(W0, "resp", "handle", start, dur, parent=m,
               queued_us=float(start - sent), lane="c0.0")
    r.span(W0, "resp", "worker.pull_decode", start + 10, dur - 20, parent=h)
    return h


def two_keys_one_channel():
    """Key 1 holds the server's one channel 110..510 (its ``be.d2h``
    waits 100 us for the device); key 2, sent at 115, waits behind it
    and is handled 510..900 on another pool thread of the same lane."""
    r = Round()
    _root, p1, p2 = _one_worker_round(r, wait_from=120)
    m1 = r.message(W0, "main", p1, 105)
    m2 = r.message(W0, "main", p2, 115)
    h1 = r.span(S0, "pool-1", "handle", 110, 400, parent=m1,
                queued_us=5.0, lane="c0.0")
    push1 = r.span(S0, "pool-1", "local.push", 120, 390, parent=h1)
    r.span(S0, "pool-1", "be.d2h", 150, 350, parent=push1, wait_us=100.0,
           waits=[["device", 0.0, 100.0, 0]])
    h2 = r.span(S0, "pool-2", "handle", 510, 390, parent=m2,
                queued_us=395.0, lane="c0.0")
    _answered(r, h2, sent=890, start=900)
    return r.events, {
        "worker:worker.grad": 100, "worker:worker.push": 15,   # to 115
        # key 2's 395 us in the queue: what the channel did meanwhile
        "server:handle": 5 + 380, "server:local.push": 30 + 10,
        WAIT_DEVICE: 100, "server:be.d2h": 250,
        WAIT_QUEUE: 10, "worker:handle": 20,
        "worker:worker.pull_decode": 60, UNEXPLAINED: 20}


def held_stripe(known_holder: bool):
    """The pull's handler waits 200 us for a stripe another thread of
    the server holds inside ``local.push``."""
    r = Round()
    _root, p1, _p2 = _one_worker_round(r, wait_from=120)
    m1 = r.message(W0, "main", p1, 105)
    other = r.span(S0, "pool-9", "handle", 90, 400, lane="c0.0p")
    holder = r.span(S0, "pool-9", "local.push", 100, 380, parent=other)
    h = r.span(S0, "pool-1", "handle", 110, 700, parent=m1, queued_us=5.0,
               lane="c0.0")
    r.span(S0, "pool-1", "local.pull", 200, 500, parent=h, lock_us=200.0,
           waits=[["lock", 50.0, 200.0,
                   holder["args"]["span"] if known_holder else 0]])
    _answered(r, h, sent=800, start=900)
    waited = {"server:local.push": 200} if known_holder else {WAIT_LOCK: 200}
    return r.events, {
        "worker:worker.grad": 100, "worker:worker.push": 5,
        "server:handle": 90 + 100, "server:local.pull": 300, **waited,
        WAIT_QUEUE: 5 + 100, "worker:handle": 20,
        "worker:worker.pull_decode": 60, UNEXPLAINED: 20}


def gap_with_no_span():
    """Between the gradient and the first push the worker's thread has
    only the round's root open for 300 us."""
    r = Round()
    root = r.span(W0, "main", "round", 0, 1000)
    r.span(W0, "main", "worker.grad", 0, 100, parent=root)
    p = r.span(W0, "main", "worker.push", 400, 20, parent=root)
    r.span(W0, "main", "worker.wait", 420, 570, parent=root)
    m = r.message(W0, "main", p, 410)
    h = r.span(S0, "pool-1", "handle", 415, 475, parent=m, queued_us=5.0,
               lane="c0.0")
    _answered(r, h, sent=880, start=900)
    return r.events, {
        "worker:worker.grad": 100, UNEXPLAINED: 300 + 20,
        "worker:worker.push": 10, WAIT_QUEUE: 5 + 20, "server:handle": 465,
        "worker:handle": 20, "worker:worker.pull_decode": 60}


def across_a_wire():
    """Two parties; the round closes on the party whose push crossed to
    the global server last.  No ``queued_us`` crosses a wire: the
    matched ``wan.send`` / ``wan.recv`` pair is the WAN's share."""
    r = Round()
    r.span(W1, "main", "round", 50, 500)          # closed long before
    root = r.span(W0, "main", "round", 0, 1000)
    r.span(W0, "main", "worker.grad", 0, 100, parent=root)
    p = r.span(W0, "main", "worker.push", 100, 20, parent=root)
    r.span(W0, "main", "worker.wait", 120, 880, parent=root)
    m = r.message(W0, "main", p, 110)
    h = r.span(S0, "pool-1", "handle", 115, 100, parent=m, queued_us=5.0,
               lane="c0.0")
    up = r.message(S0, "pool-1", h, 200, name="wan.send")
    r.span(G, "van", "wan.recv", 500, 0, parent=up)
    g = r.span(G, "pool-3", "handle", 520, 300, parent=up, lane="c0.0")
    r.span(G, "pool-3", "global.opt", 600, 200, parent=g)
    _answered(r, g, sent=810, start=900)
    return r.events, {
        "worker:worker.grad": 100, "worker:worker.push": 10, WAIT_QUEUE: 5
        + 20 + 90, "server:handle": 85, WAN: 300,
        "global_server:handle": 90, "global_server:global.opt": 200,
        "worker:handle": 20, "worker:worker.pull_decode": 60,
        UNEXPLAINED: 20}


def threaded_lane():
    """``server_shards > 1``: the merge runs as a ``lane`` item on a
    thread of its own, 150 us after the handler submitted it; the lane
    was busy with another key meanwhile."""
    r = Round()
    _root, p1, _p2 = _one_worker_round(r, wait_from=120)
    m1 = r.message(W0, "main", p1, 105)
    h = r.span(S0, "customer", "handle", 110, 60, parent=m1, queued_us=5.0,
               lane="c0.0")
    push = r.span(S0, "customer", "local.push", 120, 40, parent=h)
    busy = r.span(S0, "lane-1", "lane", 100, 200, queued_us=0.0)
    r.span(S0, "lane-1", "be.add", 100, 200, parent=busy)
    lane = r.span(S0, "lane-1", "lane", 300, 500, parent=push,
                  queued_us=150.0, key=1)
    _answered(r, lane, sent=790, start=900)
    return r.events, {
        "worker:worker.grad": 100, "worker:worker.push": 5,
        WAIT_QUEUE: 5 + 110, "server:handle": 10, "server:local.push": 30,
        # the 150 us the item waited: the lane's earlier key, then idle
        "server:be.add": 150, "server:lane": 490, "worker:handle": 20,
        "worker:worker.pull_decode": 60, UNEXPLAINED: 20}


ROUNDS = {
    "two_keys_one_channel": two_keys_one_channel,
    "held_stripe_holder_known": lambda: held_stripe(True),
    "held_stripe_holder_unknown": lambda: held_stripe(False),
    "gap_with_no_span": gap_with_no_span,
    "across_a_wire": across_a_wire,
    "threaded_lane": threaded_lane,
}


@pytest.mark.parametrize("name", sorted(ROUNDS))
def test_every_instant_goes_to_the_one_label_that_held_it(name):
    events, want = ROUNDS[name]()
    chain = blocking_chain(events)
    assert chain["lost"] is None
    assert chain["path"] == want
    # to the microsecond, and so do the fields of ``round.path``
    assert sum(chain["path"].values()) == chain["wall_us"] == 1000
    fields = path_fields(chain)
    assert sum(fields[f] for f in PATH_FIELDS) == fields["wall_us"]
    assert fields["unexplained_pct"] == pytest.approx(
        100.0 * want.get(UNEXPLAINED, 0) / 1000)
    # each field is its labels' sum: the working time by role...
    for role, field in (("worker", "worker_us"),
                        ("server", "local_server_us"),
                        ("global_server", "global_server_us")):
        assert fields[field] == sum(
            us for k, us in want.items() if k.split(":")[0] == role)


def test_times_that_are_no_whole_microseconds_still_sum_exactly():
    events, _ = two_keys_one_channel()
    for i, ev in enumerate(events):
        ev["ts"] += 0.37 * i
        if ev["dur"]:
            ev["dur"] += 0.21 * i
    chain = blocking_chain(events, detail=True)
    assert sum(chain["path"].values()) == chain["wall_us"]
    assert sum(us for _l, _n, us in chain["segments"]) == chain["wall_us"]


def test_a_chain_that_cannot_go_on_says_where_and_spreads_nothing():
    """The answer's sender never reached the collector: what is left of
    the round is ``unexplained``, on the node the chain was lost at."""
    events, _ = two_keys_one_channel()
    events = [e for e in events
              if not (e["name"] == "lan.send" and e["pid"] == S0)]
    chain = blocking_chain(events)
    assert chain["lost"] == W0
    assert chain["path"][UNEXPLAINED] == 20 + 890
    assert sum(chain["path"].values()) == 1000
    assert blocking_chain([e for e in events if e["name"] != "round"]) is None


# ---------------------------------------------------------------------------
# a Simulation: the waits on the sampled path, a delay followed
# ---------------------------------------------------------------------------

SIZES = (4096, 4096, 4096)
# 0.3 s a round over three keys: one 40 ms stall of a shared host (one
# run in thirty here) then leaves ``moved`` at 0.88 of ``added``, where
# 0.05 s a key left it under the 0.8 asked for below
DELAY_S = 0.1
# a server ships by ``trace_batch_events`` alone, and the collector
# gives a round out one round later where every server fills a batch a
# round, as the default 256 is under a real model's hundreds of spans a
# server a round: three keys make some forty
BATCH = 16


def _sim():
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        trace_sample_every=1, trace_batch_events=BATCH,
        merge_backend="jax"))
    ws = sim.all_workers()
    ws[0].set_optimizer({"type": "adam", "lr": 0.01})
    for w in ws:
        for tid, n in enumerate(SIZES):
            w.init(tid, np.zeros(n, np.float32))
    return sim


def _train(sim, rounds, lockstep=False, sizes=SIZES):
    """The rounds as ``run_worker`` drives them: a thread a worker,
    every push and pull and the wait for them under the round's root.
    ``lockstep``: the workers open each root together and make their
    gradient under ``worker.grad``, as workers that step on one global
    batch do (free-running threads drift apart, and the skew between
    their roots is ``unexplained`` by definition)."""
    together = threading.Barrier(len(sim.all_workers()))

    def loop(kv):
        for r in rounds:
            if lockstep:
                together.wait(60)
            with kv.trace_round(r):
                with kv.trace_span("worker.grad"):
                    grads = [np.full(n, 0.1, np.float32) for n in sizes]
                for tid, g in enumerate(grads):
                    kv.push(tid, g)
                    kv.pull(tid, lambda t, a: None)
                kv.wait_all()

    threads = [threading.Thread(target=loop, args=(kv,))
               for kv in sim.all_workers()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads), "a worker hung"


def _slow(fn):
    def slowed(*a, **kw):
        time.sleep(DELAY_S)
        return fn(*a, **kw)
    return slowed


def _delay_local_materialize(sim):
    for s in sim.local_servers:     # inside ``be.d2h``, where it lands
        s._backend._land = _slow(s._backend._land)


def _delay_global_close(sim):
    opt = sim.global_servers[0]._dev_opt    # inside ``global.opt``
    opt.step = _slow(opt.step)


def _delay_worker_decode(sim):
    for w in sim.all_workers():     # inside ``worker.pull_decode``
        w._decode = _slow(w._decode)


@pytest.mark.parametrize("inject,label,stage", [
    (_delay_local_materialize, "server:be.d2h", "local_merge"),
    (_delay_global_close, "global_server:global.opt", "global_merge"),
    (_delay_worker_decode, "worker:worker.pull_decode", "pull_fanout"),
], ids=["local_materialize", "global_close", "worker_decode"])
def test_the_chain_follows_an_injected_delay(inject, label, stage):
    sim = _sim()
    try:
        _train(sim, (0, 1, 2))          # 0 compiles; 1 and 2 are the base
        inject(sim)
        _train(sim, (3, 4))
        sim.flush_traces()
        rounds = {r["round"]: r for r in sim.trace_report()["rounds"]}
    finally:
        sim.shutdown()
    for r in rounds.values():
        assert sum(r["path"].values()) == r["wall_us"]
        assert r.get("chain_lost_at") is None
    base = min((rounds[1], rounds[2]), key=lambda r: r["wall_us"])
    for k in (3, 4):
        r = rounds[k]
        added = r["wall_us"] - base["wall_us"]
        assert added >= 0.8 * len(SIZES) * DELAY_S * 1e6
        moved = r["path"].get(label, 0) - base["path"].get(label, 0)
        assert moved >= 0.8 * added, (label, moved, added, r["path"])
        assert r["dominant_stage"] == stage
        assert r["stages"][stage]["path_us"] == max(
            st["path_us"] for st in r["stages"].values())
    if stage == "local_merge":
        # what the summed durations named before this PR: the stage with
        # the most thread time, with the delay as without it
        for r in (base, rounds[3], rounds[4]):
            assert max(r["stages"], key=lambda s: r["stages"][s][
                "busy_us"]) != "local_merge"


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Six training rounds of a 2-party Simulation under one profiler
    session; the program's spans as ``benchmark/lib/spans.py`` loads
    them, and the collector's own events."""
    import jax

    from benchmark.lib import spans

    trace_dir = str(tmp_path_factory.mktemp("profile"))
    sim = _sim()
    try:
        _train(sim, (0,))
        jax.profiler.start_trace(trace_dir)
        _train(sim, range(1, 7))
        sim.flush_traces()
        jax.profiler.stop_trace()
        events = sim.trace_collector.merged_events()
    finally:
        sim.shutdown()
    found = spans.load(trace_dir)
    return {"spans": found, "steps": 6,
            "t0": min(s.start for s in found),
            "t1": max(s.end for s in found)}, events


def test_round_path_lands_inside_the_session_with_its_fields(profiled):
    obs, events = profiled
    paths = [s for s in obs["spans"] if s.name == "round.path"]
    # a round is whole one round later: all but the last
    assert len(paths) >= 4
    assert {s.node.split(":")[0] for s in paths} == {"global_scheduler"}
    for s in paths:
        a = s.args
        assert sum(a[f] for f in PATH_FIELDS) == a["wall_us"] > 0
        assert a["unexplained_pct"] == pytest.approx(
            100.0 * a["unexplained_us"] / a["wall_us"])
    # the same instant is an event of the collector's own timeline (the
    # newest may not have been shipped back to it yet)
    shipped = {e["args"]["trace_id"]: e["args"] for e in events
               if e["name"] == "round.path"}
    on_clock = {s.args["trace_id"]: s.args for s in paths}
    assert shipped and set(shipped) <= set(on_clock)
    for tid, a in shipped.items():
        assert all(a[f] == on_clock[tid][f] for f in PATH_FIELDS)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_the_session_with_its_own_file(profiled, name):
    from benchmark.lib import spans

    obs, _events = profiled
    spec = json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{name}.json").read_text())
    value = spans.program_span(spec, obs)
    assert value is not None and value >= 0
    if name == "server_d2h_wait_s_per_step":
        whole = spans.program_span(
            dict(spec, field="duration", scale=1.0), obs)
        assert value <= whole
    if name.startswith("path_") and name.endswith("_s_p50"):
        wall = spans.program_span(dict(spec, field="wall_us"), obs)
        assert value <= wall


def test_the_waits_are_fields_of_the_spans_that_waited(profiled):
    _obs, events = profiled
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e["args"])
    for a in by_name["be.d2h"]:
        assert a["wait_us"] >= 0
    for name in ("local.push", "global.push", "local.pull_down",
                 "global.swap"):
        assert all(a["lock_us"] >= 0 for a in by_name[name]), name
    for name in ("worker.wait", "global.acks"):
        assert by_name[name]
    sends = by_name["lan.send"] + by_name["wan.send"]
    assert all("by" in a for a in sends)
    assert {a["lane"] for a in by_name["handle"]} >= {"c0.0", "c0.0p"}
    # a wait worth an interval carries its place inside the span
    for args in itertools.chain.from_iterable(by_name.values()):
        for kind, off, us, _holder in args.get("waits", ()):
            assert kind in ("device", "lock") and off >= 0 and us > 0


def test_a_timed_lock_names_its_holder_and_sums_the_wait():
    was = tctx.ACTIVE
    tctx.activate()
    try:
        tr = Tracer("chain-lock-node")
        lock = threading.RLock()
        assert tr.locked(lock) is lock      # no sampled span open
        entered, release = threading.Event(), threading.Event()
        seen = {}

        def holder():
            with tr.round(0, 1), tr.span("local.push") as sp:
                seen["holder"] = sp.span_id
                with tr.locked(lock):
                    entered.set()
                    release.wait(10)

        t = threading.Thread(target=holder)
        t.start()
        assert entered.wait(10)
        threading.Timer(0.05, release.set).start()
        with tr.round(0, 1), tr.span("local.pull") as sp:
            with tr.locked(lock):
                pass
            with tr.locked(lock):           # uncontended: nothing added
                pass
        t.join(10)
        assert sp.args["lock_us"] >= 0.04e6
        (kind, off, us, held_by), = sp.waits
        assert kind == "lock" and held_by == seen["holder"]
        assert us == pytest.approx(sp.args["lock_us"])
        assert id(lock) not in _HOLDERS     # no table of dead locks
    finally:
        tctx.ACTIVE = was


# ---------------------------------------------------------------------------
# ISSUE 40: what the tracer holds, and for how long
# ---------------------------------------------------------------------------

def _plain_sim(sizes=SIZES, **kw):
    """Two parties on the numpy merge backend (nothing compiles)."""
    kw.setdefault("trace_batch_events", BATCH)
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        trace_sample_every=1, **kw))
    ws = sim.all_workers()
    ws[0].set_optimizer({"type": "sgd", "lr": 0.01})
    for w in ws:
        for tid, n in enumerate(sizes):
            w.init(tid, np.zeros(n, np.float32))
    return sim


def _paths(sim):
    sim.flush_traces()
    sim.flush_traces()      # ...and the ``round.path`` instants back
    return {e["args"]["trace_id"] - 1: e["args"]
            for e in sim.trace_collector.merged_events()
            if e["name"] == "round.path"}


@pytest.mark.parametrize("mode", [
    {}, {"sync_global_mode": False}, {"use_hfa": True, "hfa_k2": 2},
], ids=["fsa", "mixed_sync", "hfa"])
def test_every_sync_mode_gets_one_path_a_round(mode):
    """Structure only: how much of a CPU round of test threads is
    ``unexplained`` is the machine's load, and is bounded where a delay
    is injected (``test_the_chain_follows_an_injected_delay``)."""
    sim = _plain_sim(**mode)
    try:
        _train(sim, range(6), lockstep=True)
        paths = _paths(sim)
        report = {r["round"]: r for r in sim.trace_report()["rounds"]}
    finally:
        sim.shutdown()
    # a round is whole one round later: every one but the last
    assert set(paths) == set(range(5))
    assert set(report) == set(range(6))
    for k, a in paths.items():
        assert sum(a[f] for f in PATH_FIELDS) == a["wall_us"] > 0
        assert report[k].get("chain_lost_at") is None
        assert sum(report[k]["path"].values()) == a["wall_us"]
        assert a["unexplained_pct"] == pytest.approx(
            100.0 * a["unexplained_us"] / a["wall_us"])


def test_the_collector_drops_a_round_once_its_path_is_out():
    """No ``trace_dir``: after ten sampled rounds the collector holds
    two rounds' events at most, ten reports, and the paths."""
    sim = _plain_sim()
    try:
        _train(sim, range(2))
        sim.flush_traces()
        coll = sim.trace_collector
        one_round = coll.events_received / 2
        _train(sim, range(2, 10))
        paths = _paths(sim)
        held = coll.held_events()
        with coll._mu:
            rounds_held = sorted(coll._by_round)
            kept = len(coll._events)
        report = sim.trace_report()
    finally:
        sim.shutdown()
    assert kept == 0
    assert rounds_held == [10]                  # trace id of round 9
    assert len(paths) == 9
    assert held <= 2 * one_round + len(paths) + 16
    assert coll.events_received >= 9 * one_round
    assert [r["round"] for r in report["rounds"]] == list(range(10))
    assert report["num_events"] == coll.events_received
    assert coll.late_events == coll.path_errors == 0


def test_rounds_no_worker_ever_closes_are_pushed_out():
    """Roots that never arrive (a worker left mid-round) must not make
    the rounds pile up: the oldest goes once ``MAX_HELD_ROUNDS`` wait."""
    from geomx_tpu.trace import collector as tc

    sim = _plain_sim()
    try:
        coll = sim.trace_collector
        for tid in range(1, 12):
            coll.ingest({"node": S0, "spans": [
                {"name": "handle", "pid": S0, "tid": "t", "ts": 0.0,
                 "dur": 5.0, "cat": "trace", "ph": "X",
                 "args": {"trace_id": tid, "span": tid, "parent": 0,
                          "t_mono_us": 1e6 * tid}}]})
        with coll._mu:
            held = sorted(coll._by_round)
        report = coll.critical_path()
        # an event of a round already pushed out is counted, not kept
        coll.ingest({"node": S0, "spans": [
            {"name": "handle", "pid": S0, "tid": "t", "ts": 0.0, "dur": 1.0,
             "args": {"trace_id": 2, "span": 99, "parent": 0}}]})
    finally:
        sim.shutdown()
    assert held == list(range(12 - tc.MAX_HELD_ROUNDS, 12))
    assert [r["round"] for r in report["rounds"]] == list(range(11))
    assert all(r["path"] == {} for r in report["rounds"])
    assert coll.late_events == 1 and coll.held_events() == len(held)


def test_a_lost_chain_waits_for_the_rest_of_its_round():
    """A server ships by the batch, and a node that sees only some
    rounds late (the global server under HFA with ``hfa_k2`` 2): the
    round is whole by its roots, its chain is lost, and it waits for the
    events instead of going out with the rest of itself ``unexplained``;
    it goes out as soon as they come."""
    events, want = two_keys_one_channel()           # round 0, trace id 1
    late = [e for e in events if e["pid"] == S0]

    def root(tid):
        return {"name": "round", "cat": "round", "pid": W0, "tid": "main",
                "ts": 2000.0 * tid, "dur": 10.0,
                "args": {"trace_id": tid, "span": 990 + tid, "parent": 0}}

    sim = _plain_sim()
    try:
        coll = sim.trace_collector
        coll.ingest({"node": W0, "spans": [
            e for e in events if e["pid"] != S0] + [root(2)]})
        assert set(coll._lost_at) == {1}    # tried when round 1 closed
        coll.ingest({"node": W0, "spans": []})      # nothing new: not again
        assert not coll._reports and _paths(sim) == {}
        coll.ingest({"node": S0, "spans": late})    # ...again now
        report = coll._reports[0]
        paths = _paths(sim)
    finally:
        sim.shutdown()
    assert report["path"] == dict(sorted(want.items(),
                                         key=lambda kv: -kv[1]))
    assert "chain_lost_at" not in report and 1 not in coll._lost_at
    assert paths[0]["wall_us"] == 1000
    assert paths[0]["unexplained_us"] == want[UNEXPLAINED]
    assert coll.late_events == 0


def test_a_dump_that_was_asked_for_keeps_the_timeline(tmp_path):
    """``Config.trace_dir``: the merged timeline stays (under its cap),
    and ``Simulation.shutdown`` leaves ``geomx_trace_report.json`` there
    as ``launch.py`` does for a deployment."""
    sim = _plain_sim(trace_dir=str(tmp_path / "out"))
    try:
        _train(sim, range(5))
        sim.flush_traces()
        coll = sim.trace_collector
        events = coll.merged_events()
        assert coll._events.maxlen
        assert len(events) >= coll.events_received - 8
        assert {e["args"]["trace_id"] for e in events
                if e["name"] == "round"} == {1, 2, 3, 4, 5}
    finally:
        sim.shutdown()
    report = json.loads(
        (tmp_path / "out" / "geomx_trace_report.json").read_text())
    assert [r["round"] for r in report["rounds"]] == list(range(5))
    for r in report["rounds"]:
        assert sum(r["path"].values()) == r["wall_us"] > 0
        assert r["dominant_stage"]


def test_a_round_root_says_what_the_process_has_held():
    """``rss_peak_MB`` on every worker's root, the one memory field
    (``host_rss_peak_GB`` reads it), and on no other span."""
    import resource

    sim = _plain_sim()
    try:
        _train(sim, range(2))
        sim.flush_traces()
        events = sim.trace_collector.merged_events()
    finally:
        sim.shutdown()
    roots = [e["args"] for e in events if e["name"] == "round"]
    assert roots      # of the round still held: a finished one is dropped
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e6
    for a in roots:
        assert 0 < a["rss_peak_MB"] <= peak
        assert not [k for k in a if k.startswith(("hbm_", "rss_"))
                    and k != "rss_peak_MB"]
    assert not [e for e in events if e["name"] != "round"
                and "rss_peak_MB" in e["args"]]


def test_nothing_a_span_recorded_keeps_a_pushed_array_alive():
    """ISSUE 40 B: spans, contexts and wait records hold ids and numbers.
    Once the round's spans have shipped, the gradient a worker pushed is
    garbage as soon as the worker lets go of it."""
    import gc
    import weakref

    sim = _sim()        # the jax backend: ``await_device`` sites run too
    refs = []
    try:
        def loop(kv):
            for r in range(3):
                with kv.trace_round(r):
                    for tid, n in enumerate(SIZES):
                        g = np.full(n, 0.1, np.float32)
                        refs.append(weakref.ref(g))
                        kv.push(tid, g)
                        del g
                        kv.pull(tid, lambda t, a: refs.append(
                            weakref.ref(a)))
                    kv.wait_all()

        threads = [threading.Thread(target=loop, args=(kv,))
                   for kv in sim.all_workers()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        sim.flush_traces()
        gc.collect()
        alive = [r for r in refs if r() is not None]
        assert len(refs) == 2 * 3 * len(SIZES) * 2
        assert not alive, f"{len(alive)} of {len(refs)} arrays still held"
        # and the event dicts carry nothing but plain values
        for e in sim.trace_collector.merged_events():
            for k, v in e["args"].items():
                assert isinstance(v, (int, float, str, list)), (e["name"], k)
    finally:
        sim.shutdown()


def test_a_flush_cannot_raise_once_its_node_is_gone():
    """``Simulation.shutdown`` and a worker's closing root flush a
    tracer whose postoffice, van or collector may be gone already."""
    was = tctx.ACTIVE
    tctx.activate()
    try:
        tr = Tracer("chain-gone-node")

        class Gone:
            topology = None

            def clock_offsets(self):
                raise RuntimeError("postoffice stopped")

        tr.attach(Gone())
        with tr.round(0, 1), tr.span("worker.push"):
            pass
        assert tr.flush() == 0 and tr.pending() == 2    # kept, not lost
        tr._cap = 1
        assert tr.flush() == 0 and tr.pending() == 1    # ...under the cap

        class Po:
            def clock_offsets(self):
                return {}

        class Broken:
            def ingest(self, body):
                raise ValueError("collector stopped")

        tr.attach(Po(), collector=Broken())
        assert tr.flush() == 0 and tr.pending() == 1
        tr.detach()
        assert tr.flush() == 0
    finally:
        tctx.ACTIVE = was


def test_a_worker_ships_at_its_root_and_a_server_by_the_batch_alone():
    """One rule: ``trace_batch_events`` is the only size trigger, and a
    worker's closing root the only other one.  With a batch no server
    fills, the servers send nothing (their vans' WAN bytes are the
    model's alone) until ``flush_traces`` asks."""
    sim = _plain_sim(trace_batch_events=50_000)
    try:
        _train(sim, range(3))
        coll = sim.trace_collector
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:      # the reports are messages
            with coll._mu:
                shipped = {e["pid"].split(":")[0]
                           for evs in coll._by_round.values() for e in evs}
                roots = dict(coll._newest_root)
            if set(roots.values()) == {3}:      # trace id of round 2
                break
            time.sleep(0.01)
        assert shipped == {"worker"} and set(roots.values()) == {3}
        pending = [n for n in (S0, G) if get_tracer(n).pending()]
        assert pending == [S0, G]
        assert sim.flush_traces() > 0
        rounds = sim.trace_report()["rounds"]
        assert [r["round"] for r in rounds] == [0, 1, 2]
        assert [bool(r.get("held")) for r in rounds] == [False, False, True]
        assert all(r.get("chain_lost_at") is None for r in rounds)
    finally:
        sim.shutdown()


def test_the_wan_policy_reads_the_newest_round_that_is_whole():
    """``WanSignals`` passes over a round the collector still holds (its
    chain may lack a server's part), and a congested link leads the
    chain as ``wan``, which the compute veto lets through, with the
    global merge second."""
    from geomx_tpu.control.policy import WanPolicyEngine
    from geomx_tpu.control.signals import SignalEstimator

    def report(*rounds):
        return {"rounds": [dict(r) for r in rounds]}

    whole = {"round": 3, "dominant_stage": "wan",
             "stages": {"wan": {"straggler_party": "p1"}}}
    held = {"round": 4, "dominant_stage": "local_merge", "held": True,
            "stages": {"local_merge": {"straggler_party": "p0"}}}
    fuser = SignalEstimator()
    sig = fuser.ingest(1.0, {}, report(whole, held))
    assert (sig.dominant_stage, sig.straggler_party) == ("wan", "p1")
    assert fuser.ingest(2.0, {}, report(held)).dominant_stage is None

    # the chain of a round whose wire is slower than its local merge
    events, _want = across_a_wire()
    from geomx_tpu.trace.collector import _round_report

    r, chain = _round_report(1, events)
    assert chain["lost"] is None and r["dominant_stage"] == "wan"
    assert 0 < r["stages"]["global_merge"]["path_us"] < \
        r["stages"]["wan"]["path_us"]
    now = [0.0]
    eng = WanPolicyEngine({"type": "none"}, budget_s=1.0, deadband=0.2,
                          cooldown_s=0.0, patience=1, clock=lambda: now[0])
    sig = fuser.ingest(3.0, {}, {"rounds": [r]})
    sig.round_time_s = 5.0
    now[0] = 1.0
    assert eng.observe(sig) is not None and eng.vetoes == 0
    r["dominant_stage"] = "global_merge"    # ...and what is vetoed still
    sig = fuser.ingest(4.0, {}, {"rounds": [r]})
    sig.round_time_s = 5.0
    now[0] = 2.0
    assert eng.observe(sig) is None and eng.vetoes == 1


def test_a_chain_that_cannot_be_computed_costs_the_round_nothing():
    """The collector's emit runs on the thread that delivered a report:
    events it cannot make sense of are counted, never raised."""
    sim = _plain_sim()
    try:
        coll = sim.trace_collector
        bad = {"name": "round", "cat": "round", "pid": W0, "tid": "t",
               "ts": "never", "dur": 5.0,
               "args": {"trace_id": 1, "span": 1, "parent": 0}}
        ok = {"name": "round", "cat": "round", "pid": W0, "tid": "t",
              "ts": 0.0, "dur": 5.0,
              "args": {"trace_id": 2, "span": 2, "parent": 0,
                       "t_mono_us": 10.0}}
        coll.ingest({"node": W0, "spans": [bad, ok]})
        assert coll.path_errors == 1
        assert coll.held_events() == 1
    finally:
        sim.shutdown()


def test_the_profilers_buffer_keeps_the_newest_events_only():
    from geomx_tpu.utils import profiler

    p = profiler.Profiler("chain-cap")
    for i in range(profiler.MAX_EVENTS + 10):
        p.add_event({"name": "x", "ph": "X", "dur": 1.0, "i": i})
    assert p.stats()["num_events"] == profiler.MAX_EVENTS
    assert p._events[0]["i"] == 10
