"""Cluster telemetry plane (ISSUE 7 tentpole): time-series metrics
shipping, the SLO health engine, and the live cluster-state console.

Covers: pump → collector e2e over the wire (ring series, boot-fenced
rates, Prometheus exposition, perfetto counter tracks in the merged
trace), the acceptance failover-visibility scenario (kill one global
shard's primary → ``cluster_state()`` flips the holder + term within a
collection interval, the health engine emits exactly one round-stall
alert for that shard followed by a recovery record), the disabled-path
guard (default config: no pump, no threads, no METRICS_REPORT frames on
a wire tap), the Ctrl.CLUSTER_STATE wire query, health-rule units over
synthetic series, QUERY_STATS uptime/boot, the NaN gauge fence, and the
registry reset fixture.
"""

import json
import threading
import time

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.common import Ctrl
from geomx_tpu.kvstore.keys import encode_tensor
from geomx_tpu.transport.message import Domain
from geomx_tpu.utils.metrics import (reset_system_metrics, system_counter,
                                     system_gauge, system_snapshot)


def _obs_cfg(parties=2, workers=1, **kw):
    kw.setdefault("enable_obs", True)
    kw.setdefault("obs_interval_s", 0.0)  # manual pump/tick
    return Config(topology=Topology(num_parties=parties,
                                    workers_per_party=workers), **kw)


def _wait_for(pred, timeout=15.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _run_rounds(sim, rounds, tids=(0,), n=32):
    ws = sim.all_workers()
    for _ in range(rounds):
        for w in ws:
            for t in tids:
                w.push(t, np.ones(n, np.float32))
        for w in ws:
            for t in tids:
                w.pull_sync(t)
            w.wait_all()


# ---------------------------------------------------------------------------
# pump -> collector e2e
# ---------------------------------------------------------------------------

def test_pump_collector_e2e_series_and_rates():
    """Every node's samples land in the collector's rings over the
    METRICS_REPORT wire path; stats carry the servers' QUERY_STATS dict
    and rates are computable from consecutive samples."""
    sim = Simulation(_obs_cfg())
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(32, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 2)
        sim.pump_metrics()
        mc = sim.metrics_collector
        # every role reported (workers, both tiers, schedulers)
        nodes = set(mc.nodes())
        assert {"worker:0@p0", "server:0@p0", "global_server:0",
                "global_scheduler:0"} <= nodes
        # server stats ARE the QUERY_STATS body
        assert mc.value("server:0@p0", "wan_push_rounds") == 2
        assert mc.value("global_server:0", "key_rounds") == 2
        _run_rounds(sim, 2)
        sim.pump_metrics()
        assert mc.value("global_server:0", "key_rounds") == 4
        r = mc.rate("server:0@p0", "wan_send_bytes")
        assert r is not None and r > 0
        # series are bounded rings
        for _ in range(12):
            sim.pump_metrics()
        assert len(mc.series("worker:0@p0", "send_bytes")) \
            <= sim.config.obs_window
    finally:
        sim.shutdown()


def test_prometheus_exposition_and_nan_fence():
    """The text exposition lists every reported family with a node
    label, and a never-set gauge (NaN) can never reach it — nor any
    shipped sample (JSON-invalid NaN is fenced at the pump)."""
    sim = Simulation(_obs_cfg(parties=1))
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(8, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 1, n=8)
        # a never-set gauge on a pumped node's prefix
        system_gauge("server:0@p0.test_unset_gauge")
        system_gauge("server:0@p0.test_set_gauge").set(1.5)
        sim.pump_metrics()
        pump = sim.metrics_pumps["server:0@p0"]
        body = pump.sample()
        json.dumps(body, allow_nan=False)  # raises on NaN leakage
        assert "server:0@p0.test_unset_gauge" not in body["metrics"]
        assert body["metrics"]["server:0@p0.test_set_gauge"] == 1.5
        sim.pump_metrics()
        txt = sim.metrics_collector.prometheus_text()
        assert 'geomx_test_set_gauge{node="server:0@p0"} 1.5' in txt
        assert "test_unset_gauge" not in txt
        assert 'geomx_key_rounds{node="global_server:0"}' in txt
        assert "NaN" not in txt and "nan" not in txt.lower().replace(
            "instance", "")
        # snapshot-level fence for direct registry readers
        snap = system_snapshot(skip_unset=True)
        assert "server:0@p0.test_unset_gauge" not in snap
        assert "server:0@p0.test_unset_gauge" in system_snapshot()
    finally:
        sim.shutdown()


def test_counter_tracks_merge_into_trace_json(tmp_path):
    """With tracing AND telemetry on, the merged trace JSON carries
    perfetto counter-track ("ph": "C") events from the collected series
    next to the round spans, on the same rebased timeline."""
    sim = Simulation(_obs_cfg(trace_sample_every=1))
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        for w in ws:
            w.init(0, np.zeros(32, np.float32))
        for r in range(2):
            for w in ws:
                with w.trace_round(r):
                    w.push(0, np.ones(32, np.float32))
                    w.pull(0, lambda t, a: None)
            for w in ws:
                w.wait_all()
        sim.pump_metrics()
        trace = sim.dump_trace(str(tmp_path / "t.json"))
        evs = trace["traceEvents"]
        counters = [e for e in evs if e.get("ph") == "C"]
        spans = [e for e in evs if e.get("ph") == "X"]
        assert counters and spans, (len(counters), len(spans))
        names = {e["name"] for e in counters}
        assert "metric.key_rounds" in names
        assert "metric.wan_send_bytes" in names
        # same rebased timeline: counter timestamps sit inside the
        # span timeline's range (all ts >= 0 after rebase)
        assert all(e["ts"] >= 0 for e in counters)
        with open(tmp_path / "t.json") as f:
            json.load(f)  # the dump stays valid JSON
    finally:
        sim.shutdown()


def test_cluster_state_wire_query():
    """Ctrl.CLUSTER_STATE answered over the wire: a worker-side command
    round trip returns the same merged state Simulation.cluster_state()
    composes."""
    sim = Simulation(_obs_cfg(parties=1))
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(8, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 1, n=8)
        sim.pump_metrics()
        kv = sim.worker(0, 0)
        ts = kv.worker.send_cmd(sim.topology.global_scheduler(),
                                Ctrl.CLUSTER_STATE, domain=Domain.GLOBAL,
                                wait=False)
        kv.worker.customer.wait(ts, timeout=10.0)
        state = kv.worker.cmd_response(ts)
        assert isinstance(state, dict)
        shards = {int(k): v for k, v in state["shards"].items()}
        assert shards[0]["holder"] == "global_server:0"
        assert state["topology"]["num_parties"] == 1
        assert state["telemetry"]["reports"] > 0
        # renders without blowing up, naming the holder
        from geomx_tpu.obs import render_text

        txt = render_text(state)
        assert "global_server:0" in txt
        assert sim.state_service.queries_served == 1
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# acceptance: live failover visibility
# ---------------------------------------------------------------------------

@pytest.mark.failover
def test_failover_visible_in_cluster_state_and_round_stall_alert():
    """Acceptance: kill one global shard's primary mid-training —
    cluster_state() reports the promoted holder + bumped term within
    one collection interval, and the health engine emits exactly one
    round-stall alert for that shard followed by a recovery record."""
    cfg = Config(
        topology=Topology(num_parties=2, workers_per_party=1,
                          num_global_servers=2, num_standby_globals=2),
        enable_obs=True, obs_interval_s=0.0,
        request_retry_s=0.4, heartbeat_interval_s=0.05,
        heartbeat_timeout_s=0.4, replicate_every=1, retry_backoff_cap=2,
        obs_stall_min_s=0.3, obs_stall_factor=2.0)
    sim = Simulation(cfg)
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(16, np.float32))
            w.init(1, np.zeros(16, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        for _ in range(3):
            # pump + tick per round: the stall rule arms a shard only
            # after OBSERVING its progress (an idle-since-boot shard
            # must never alert), so the series needs per-round samples
            _run_rounds(sim, 1, tids=(0, 1), n=16)
            sim.pump_metrics()
            sim.health.tick()
        st = sim.cluster_state()
        assert st["shards"][1]["holder"] == "global_server:1"
        assert st["shards"][1]["term"] == 0
        # wait for the standby to hold shard 1's state, then kill
        sb1 = sim.standby_globals[1]
        k1 = encode_tensor(1, 16, 2)[0].ps_key
        assert _wait_for(lambda: k1 in sb1.store), "replication stalled"
        sim.kill_global_server(1)
        # the surviving shard keeps completing rounds while shard 1 is
        # dark; pump + tick until the health engine calls the stall
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            _run_rounds(sim, 1, tids=(0,), n=16)
            sim.pump_metrics()
            sim.health.tick()
            if sim.health.active_alerts():
                break
            time.sleep(0.05)
        active = [(a["rule"], a["subject"])
                  for a in sim.health.active_alerts()]
        assert ("round_stall", "shard:1") in active, active
        # promotion lands; the console shows it within one collection
        # interval of the next sweep
        assert _wait_for(lambda: not sb1.is_standby), "promotion stalled"
        # (the state is collected on the next sweep: asserting at once
        # lost that race about one run in twelve)
        assert _wait_for(lambda: sim.cluster_state()["shards"][1]["holder"]
                         == "standby_global:1"), "console never showed it"
        st = sim.cluster_state()
        assert st["shards"][1]["holder"] == "standby_global:1"
        assert st["shards"][1]["term"] == 1
        assert st["shards"][1]["promoted"] is True
        assert st["shards"][0]["holder"] == "global_server:0"
        assert st["shards"][0]["term"] == 0
        # shard 1's stalled round replays at the standby; progress =
        # recovery record
        _run_rounds(sim, 1, tids=(1,), n=16)
        sim.pump_metrics()
        sim.health.tick()
        stall = [r for r in sim.health.alerts
                 if r["rule"] == "round_stall"
                 and r["subject"] == "shard:1"]
        assert [r["state"] for r in stall] == ["firing", "recovered"], \
            stall
        # exactly one alert for that shard; the surviving shard never
        # alerted
        assert not [r for r in sim.health.alerts
                    if r["rule"] == "round_stall"
                    and r["subject"] == "shard:0"]
        # alerts also landed in the registry
        snap = system_snapshot("global_scheduler:0.")
        assert snap["global_scheduler:0.health_alerts"] == 1
        assert snap["global_scheduler:0.health_recoveries"] == 1
        assert snap["global_scheduler:0.health_round_stall_alerts"] == 1
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# disabled-path guard
# ---------------------------------------------------------------------------

def test_disabled_obs_no_frames_no_threads():
    """Default config (GEOMX_OBS off): no collector, no pump, no
    telemetry threads, and a full training round puts zero
    METRICS_REPORT frames on the wire — the PR 3 trace-guard style
    'behavior unchanged' check."""
    sim = Simulation(Config(topology=Topology(num_parties=1,
                                              workers_per_party=1)))
    try:
        assert sim.metrics_collector is None
        assert sim.health is None
        assert not sim.metrics_pumps
        names = {t.name for t in threading.enumerate()}
        assert not any(n.startswith(("metrics-pump", "health-engine"))
                       for n in names), names
        seen = []
        orig = sim.fabric.deliver
        sim.fabric.deliver = lambda m: (seen.append(m), orig(m))[1]
        w = sim.worker(0, 0)
        w.init(0, np.zeros(32, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        w.push(0, np.ones(32, np.float32))
        w.pull_sync(0)
        w.wait_all()
        assert seen, "tap saw no traffic"
        assert not [m for m in seen
                    if m.cmd == int(Ctrl.METRICS_REPORT)]
        # the console itself stays available (costs nothing until
        # queried) but reports no telemetry
        st = sim.cluster_state()
        assert st["telemetry"] is None and st["health"] is None
    finally:
        sim.shutdown()


def test_obs_interval_runs_pump_and_health_threads():
    """obs_interval_s > 0: samples accumulate without manual pumping
    (the operator path the launcher uses)."""
    sim = Simulation(_obs_cfg(parties=1, obs_interval_s=0.05))
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(8, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 1, n=8)
        mc = sim.metrics_collector
        assert _wait_for(lambda: mc.reports_received >= 8, timeout=10)
        assert _wait_for(
            lambda: mc.value("global_server:0", "key_rounds") == 1,
            timeout=10)
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# restart discrimination (QUERY_STATS uptime/boot satellite)
# ---------------------------------------------------------------------------

def test_query_stats_uptime_and_boot_both_tiers():
    """QUERY_STATS now answers uptime_s/boot on both tiers, and a
    warm-booted replacement's counter reset is fenced by the collector
    (node_restarts bumps, no negative rates) instead of reading as a
    rate collapse."""
    cfg = _obs_cfg(parties=1, heartbeat_interval_s=0.05,
                   heartbeat_timeout_s=0.4, request_retry_s=0.4)
    sim = Simulation(cfg)
    try:
        kv = sim.worker(0, 0)
        kv.init(0, np.zeros(8, np.float32))
        kv.set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 2, n=8)
        ls_stats = kv.worker.send_cmd(sim.topology.server(0),
                                      Ctrl.QUERY_STATS,
                                      domain=Domain.LOCAL)
        gs_stats = kv.worker.send_cmd(sim.topology.global_servers()[0],
                                      Ctrl.QUERY_STATS,
                                      domain=Domain.GLOBAL)
        for st in (ls_stats, gs_stats):
            assert st["uptime_s"] >= 0.0
            assert st["boot"] > 0
        old_boot = ls_stats["boot"]
        sim.pump_metrics()
        sim.pump_metrics()
        mc = sim.metrics_collector
        # replace the local server (same identity, new boot)
        sim.kill_local_server(0)
        sim.restart_local_server(0)
        assert _wait_for(
            lambda: (sim.local_servers[0].po.van.boot != old_boot))
        sim.pump_metrics()
        assert mc.node_restarts.get("server:0@p0") == 1
        # the fenced ring restarts: rates need two fresh samples and
        # can never span the reset
        sim.pump_metrics()
        r = mc.rate("server:0@p0", "wan_send_bytes")
        assert r is None or r >= 0.0
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# health rules over synthetic series
# ---------------------------------------------------------------------------

def _synthetic_engine(**cfg_kw):
    """A live 1-party sim whose collector we feed synthetic foreign
    samples — rule units run against controlled series."""
    cfg_kw.setdefault("obs_window", 8)
    sim = Simulation(_obs_cfg(parties=1, **cfg_kw))
    return sim, sim.metrics_collector, sim.health


def test_health_rule_replication_lag_and_rtt():
    sim, mc, eng = _synthetic_engine()
    try:
        mc.ingest({"node": "global_server:9", "boot": 7, "t_mono": 1.0,
                   "metrics": {"global_server:9.replication_lag_s": 120.0,
                               "global_server:9.heartbeat_rtt_s": 2.5},
                   "stats": {}})
        recs = eng.tick(now=10.0)
        got = {(r["rule"], r["subject"], r["state"]) for r in recs}
        assert ("replication_lag", "global_server:9",
                "firing") in got, recs
        assert ("rtt_outlier", "global_server:9", "firing") in got
        # second tick: still firing -> NO duplicate records
        assert not eng.tick(now=11.0)
        mc.ingest({"node": "global_server:9", "boot": 7, "t_mono": 2.0,
                   "metrics": {"global_server:9.replication_lag_s": 0.5,
                               "global_server:9.heartbeat_rtt_s": 0.01},
                   "stats": {}})
        recs = eng.tick(now=12.0)
        got = {(r["rule"], r["subject"], r["state"]) for r in recs}
        assert ("replication_lag", "global_server:9",
                "recovered") in got
        assert ("rtt_outlier", "global_server:9", "recovered") in got
        assert not eng.active_alerts()
    finally:
        sim.shutdown()


def test_health_rule_goodput_collapse_and_fence_spike():
    sim, mc, eng = _synthetic_engine(obs_goodput_frac=0.1,
                                     obs_fence_spike=8)
    try:
        node = "server:0@p9"
        # healthy phase: 10 MB/s, rounds progressing
        for i in range(4):
            mc.ingest({"node": node, "boot": 3, "t_mono": float(i),
                       "metrics": {},
                       "stats": {"wan_send_bytes": i * 10_000_000,
                                 "wan_push_rounds": i,
                                 "eviction_fenced_pushes": 0}})
        assert not [r for r in eng.tick(now=4.0)
                    if r["subject"] == node]
        # collapse phase: bytes crawl while rounds still tick over, and
        # the fence counter spikes
        for i in range(4, 8):
            mc.ingest({"node": node, "boot": 3,
                       "t_mono": float(i * 10),
                       "metrics": {},
                       "stats": {"wan_send_bytes":
                                 40_000_000 + i * 1_000,
                                 "wan_push_rounds": i,
                                 "eviction_fenced_pushes": (i - 3) * 5}})
        recs = eng.tick(now=80.0)
        got = {(r["rule"], r["state"]) for r in recs
               if r["subject"] == node}
        assert ("goodput_collapse", "firing") in got, recs
        assert ("fence_spike", "firing") in got, recs
        # recovery: the ring refills with healthy samples
        for i in range(8, 16):
            mc.ingest({"node": node, "boot": 3,
                       "t_mono": 80.0 + (i - 8),
                       "metrics": {},
                       "stats": {"wan_send_bytes":
                                 50_000_000 + (i - 8) * 10_000_000,
                                 "wan_push_rounds": i,
                                 "eviction_fenced_pushes": 25}})
        recs = eng.tick(now=90.0)
        got = {(r["rule"], r["state"]) for r in recs
               if r["subject"] == node}
        assert ("goodput_collapse", "recovered") in got, recs
        assert ("fence_spike", "recovered") in got, recs
    finally:
        sim.shutdown()


def test_health_alert_log_jsonl(tmp_path):
    """Alert records are appended to the configured JSONL log, each
    line parseable (the NaN fence applies here too)."""
    log = tmp_path / "alerts.jsonl"
    sim, mc, eng = _synthetic_engine(obs_alert_log=str(log))
    try:
        mc.ingest({"node": "global_server:9", "boot": 1, "t_mono": 1.0,
                   "metrics": {"global_server:9.replication_lag_s":
                               float(10 ** 3)},
                   "stats": {}})
        eng.tick(now=5.0)
        mc.ingest({"node": "global_server:9", "boot": 1, "t_mono": 2.0,
                   "metrics": {"global_server:9.replication_lag_s": 0.1},
                   "stats": {}})
        eng.tick(now=6.0)
        lines = [json.loads(ln) for ln in
                 log.read_text().strip().splitlines()]
        assert [ln["state"] for ln in lines] == ["firing", "recovered"]
        assert lines[0]["rule"] == "replication_lag"
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# controller reads collected series
# ---------------------------------------------------------------------------

def test_adaptive_controller_reads_collected_series():
    """With the telemetry plane on, the adaptive-WAN controller serves
    its sweeps from the collector's rings instead of issuing its own
    QUERY_STATS round trips."""
    cfg = _obs_cfg(parties=1, adaptive_wan=True, adapt_interval_s=0.0)
    sim = Simulation(cfg)
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(8, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        _run_rounds(sim, 2, n=8)
        sim.pump_metrics()
        before = sim.wan_controller.metrics_samples
        sim.wan_controller.tick()
        assert sim.wan_controller.metrics_samples == before + 1
        # the sampled stats carried the real round counter
        sig = sim.wan_controller.signals
        assert sig._rounds["server:0@p0"]._q[-1][1] == 2.0
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# registry reset satellite
# ---------------------------------------------------------------------------

def test_reset_system_metrics_isolation():
    """reset_system_metrics wipes the registry; stale handles keep
    working without resurrecting their names — the autouse conftest
    fixture gives every test a clean slate."""
    c = system_counter("test_reset.counter")
    c.inc(5)
    system_gauge("test_reset.gauge").set(2.0)
    assert system_snapshot("test_reset.") == {
        "test_reset.counter": 5, "test_reset.gauge": 2.0}
    reset_system_metrics()
    assert system_snapshot("test_reset.") == {}
    c.inc()  # the orphan handle must not reappear in the registry
    assert system_snapshot("test_reset.") == {}
    # a re-registration starts from zero (no bleed from the orphan)
    assert system_counter("test_reset.counter").value == 0


def test_registry_clean_slate_between_simulations():
    """Regression for the cross-Simulation bleed: two sequential sims
    under resets see absolute counter values, not accumulations."""
    for _ in range(2):
        sim = Simulation(_obs_cfg(parties=1))
        try:
            w = sim.all_workers()[0]
            w.init(0, np.zeros(8, np.float32))
            w.set_optimizer({"type": "sgd", "lr": 0.1})
            _run_rounds(sim, 1, n=8)
            sim.pump_metrics()
            assert system_snapshot(
                "global_scheduler:0.")["global_scheduler:0.obs_reports"] \
                == sim.metrics_collector.reports_received
        finally:
            sim.shutdown()
        reset_system_metrics()
        assert system_snapshot("global_scheduler:0.") == {}
