"""Elastic membership under preemption churn (ISSUE 13): the graceful
drain protocol (Control.PREEMPT_NOTICE — notice → flush → leave →
immediate fold, never a heartbeat-expiry stall), the concurrent
membership-transition matrix (two parties folding in one global round,
a join landing during a drain, a notice racing its own heartbeat
expiry), ESync planner churn hygiene, the seeded churn orchestrator
(geomx_tpu/chaos), and the churn_storm health rule.  Fast tests are
tier-1 and run under BOTH the legacy threads harness and the
lightweight reactor dispatch path; the 24-party spot-churn soak with
loss parity against an uninterrupted control is slow + scale.
"""

import threading
import time

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Group, NodeId, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.transport.message import Control, Domain, Message
from geomx_tpu.utils.metrics import system_counter, system_gauge

pytestmark = pytest.mark.chaos

# the membership-transition tests shake under the thread-per-endpoint
# harness AND the shared-reactor serial-dispatch path — concurrency
# windows differ between them by construction
TRANSPORTS = [pytest.param(False, id="threads"),
              pytest.param(True, id="reactor")]


def _cfg(parties=1, workers=2, **kw):
    kw.setdefault("heartbeat_interval_s", 0.05)
    kw.setdefault("heartbeat_timeout_s", 2.0)
    kw.setdefault("enable_preempt", True)
    return Config(topology=Topology(num_parties=parties,
                                    workers_per_party=workers), **kw)


def _wait_for(pred, timeout=20.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _freeze_heartbeats(po):
    """Silence one node's heartbeat source (both transport modes) —
    the node stays functional, only its liveness signal stops."""
    if po._hb_task is not None:
        po._hb_task.cancel()
        po._hb_task = None
    if po._hb_thread is not None:
        po._hb_stop.set()


@pytest.mark.parametrize("lightweight", TRANSPORTS)
def test_notice_drains_worker_without_eviction(lightweight):
    """The drain protocol end to end: a noticed worker flushes, leaves,
    and is folded out IMMEDIATELY — drain latency a small fraction of
    the eviction timeout, the eviction monitor never fires, rounds and
    barriers continue on the survivor set."""
    sim = Simulation(_cfg(), lightweight=lightweight)
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(8, np.float32))
        w0.set_optimizer({"type": "sgd", "lr": 1.0})
        for w in (w0, w1):
            w.push(0, np.ones(8, np.float32))
        np.testing.assert_allclose(w0.pull_sync(0),
                                   -2 * np.ones(8, np.float32))
        for w in (w0, w1):
            w.wait_all()

        # a barrier entered while w1 is still a member must release
        # when the graceful leave drops it from barrier accounting
        released = []

        def barrier():
            w0.po.barrier(Group.WORKERS, timeout=30)
            released.append(True)

        th = threading.Thread(target=barrier)
        th.start()

        reply = sim.notice_worker(0, 1)
        assert reply and reply["ok"], reply
        # acceptance: notice→member-folded well under the eviction
        # window (the whole point — no heartbeat-expiry stall)
        timeout = sim.config.heartbeat_timeout_s
        assert reply["latency_s"] < 0.25 * timeout, reply
        ls = sim.local_servers[0]
        assert ls.left_workers == 1
        assert ls.evicted_workers == 0
        assert w1.preempt_drains == 1

        th.join(30)
        assert released, "graceful leave did not release the barrier"

        # the survivor's next round completes alone — no stall window
        w0.push(0, np.ones(8, np.float32))
        np.testing.assert_allclose(w0.pull_sync(0),
                                   -3 * np.ones(8, np.float32))
        # ... and the monitor stayed quiet the whole time
        time.sleep(3 * sim.config.heartbeat_interval_s)
        assert sim.eviction_monitors[0].evictions == 0
        assert "worker:1@p0" not in sim.eviction_monitors[0]._evicted
        # drain visible in the flight ring (postmortem attribution)
        evs = [e for e in w1.po.flight.events()
               if e["note"] == "preempt_drain"]
        assert evs, "drain left no flight event"
    finally:
        sim.shutdown()


@pytest.mark.parametrize("lightweight", TRANSPORTS)
def test_notice_races_own_heartbeat_expiry_notice_wins(lightweight):
    """The worker's liveness signal dies at notice time and the drain
    is SLOW (a parked pull holds it open past the heartbeat timeout):
    the draining-member hold must keep the eviction monitor quiet for
    the drain window, so the graceful leave — not an eviction — ends
    the membership.  The monitor must also not double-fold afterward."""
    sim = Simulation(_cfg(heartbeat_timeout_s=0.4, preempt_drain_s=1.2),
                     lightweight=lightweight)
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(4, np.float32))
        w0.set_optimizer({"type": "sgd", "lr": 1.0})
        for w in (w0, w1):
            w.push(0, np.ones(4, np.float32))
        w0.pull_sync(0)
        for w in (w0, w1):
            w.wait_all()
        # open a round only w1 contributes to, and park w1's pull on it
        # — the drain's flush now blocks until the leave itself folds
        # the round, holding the drain open across the expiry window
        w1.push(0, np.ones(4, np.float32))
        got = []
        w1.pull(0, lambda t, a: got.append(a))
        _freeze_heartbeats(w1.po)  # liveness dies WITH the notice

        t0 = time.monotonic()
        reply = sim.notice_worker(0, 1, timeout=10.0)
        drained_at = time.monotonic() - t0
        assert reply and reply["ok"], reply
        # the drain provably outlived the heartbeat timeout...
        assert drained_at > sim.config.heartbeat_timeout_s, drained_at
        ls = sim.local_servers[0]
        mon = sim.eviction_monitors[0]
        # ...yet the notice won: graceful leave, never an eviction
        assert ls.left_workers == 1
        assert ls.evicted_workers == 0
        assert mon.evictions == 0
        assert mon.notice_holds >= 1
        # the leave folded the round w1 held open — its pull serves
        assert _wait_for(lambda: bool(got), 5)

        # double-fold guard: a late EVICT for the already-left member
        # must not decrement the target again (the monitor's own RPC
        # machinery, so the reply routes like a real sweep's would)
        target_before = ls._workers_target
        reply = mon._rpc(sim.topology.server(0), Control.EVICT,
                         {"node": "worker:1@p0", "boot": 1},
                         Domain.LOCAL)
        assert reply is not None and reply["evicted"] is False, reply
        assert ls._workers_target == target_before
        assert ls.evicted_workers == 0
    finally:
        sim.shutdown()


@pytest.mark.parametrize("lightweight", TRANSPORTS)
def test_two_parties_fold_in_same_global_round(lightweight):
    """Concurrent party-level transitions: one local server drains on
    notice while another dies ungracefully — BOTH fold out of the same
    mid-flight global round, the surviving party's round completes,
    and both parties later rejoin (replacement warm boot → unfold)."""
    sim = Simulation(_cfg(parties=3, workers=1, heartbeat_timeout_s=0.5,
                          request_retry_s=0.5),
                     lightweight=lightweight)
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(8, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        for w in ws:
            w.push(0, np.ones(8, np.float32))
        np.testing.assert_allclose(ws[0].pull_sync(0),
                                   -np.ones(8, np.float32))
        for w in ws:
            w.wait_all()

        # open the next global round with only party 0's contribution
        ws[0].push(0, np.ones(8, np.float32))
        # party 1 drains gracefully; party 2 dies ungracefully — the
        # two folds land on the same open round
        reply = sim.notice_local_server(1)
        assert reply and reply["ok"], reply
        sim.kill_local_server(2)
        # the round completes on the lone survivor (notice fold is
        # immediate; party 2's fold lands after its expiry)
        np.testing.assert_allclose(ws[0].pull_sync(0),
                                   -2 * np.ones(8, np.float32))
        gs = sim.global_servers[0]
        assert _wait_for(lambda: gs.num_contributors == 1, 10)
        assert gs.party_folds == 2
        assert sim.recovery_monitor.preempt_folds == 1
        # only the crash; the monitor counts its fold once the global
        # tier has ACKed the RPC, i.e. just after gs.party_folds moved
        assert _wait_for(lambda: sim.recovery_monitor.party_folds == 1, 10)

        # the noticed party's host is reclaimed; replacements come up
        sim.kill_local_server(1)
        time.sleep(2.5 * sim.config.heartbeat_timeout_s)
        sim.restart_local_server(1)
        sim.restart_local_server(2)
        assert _wait_for(
            lambda: sim.recovery_monitor.party_unfolds == 2, 40), \
            "parties never folded back in"
        for w in ws:
            w.push(0, np.ones(8, np.float32))
        outs = [w.pull_sync(0) for w in ws]
        np.testing.assert_allclose(outs[0], -3 * np.ones(8, np.float32))
        np.testing.assert_allclose(outs[0], outs[1])
        np.testing.assert_allclose(outs[0], outs[2])
    finally:
        sim.shutdown()


@pytest.mark.parametrize("lightweight", TRANSPORTS)
def test_join_lands_during_anothers_drain(lightweight):
    """A worker joins while another member's drain is in flight (held
    open by a parked pull): the join and the leave serialize through
    the membership seq — the final target is exactly (survivors +
    joiner), and the joiner trains."""
    sim = Simulation(_cfg(preempt_drain_s=1.0), lightweight=lightweight)
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(4, np.float32))
        w0.set_optimizer({"type": "sgd", "lr": 1.0})
        for w in (w0, w1):
            w.push(0, np.ones(4, np.float32))
        w0.pull_sync(0)
        for w in (w0, w1):
            w.wait_all()
        # hold w1's drain open: a round only w1 contributed to
        w1.push(0, np.ones(4, np.float32))
        got = []
        w1.pull(0, lambda t, a: got.append(a))

        replies = []
        th = threading.Thread(target=lambda: replies.append(
            sim.notice_worker(0, 1, timeout=10)))
        th.start()
        time.sleep(0.05)  # let the notice land; the drain is now held
        #                   open by w1's parked pull
        # the join lands while the drain is still flushing
        wj = sim.add_worker(0)
        wj.init(0, np.zeros(4, np.float32))  # publish shapes (no-op
        #                                       server-side)
        th.join(15)
        assert replies and replies[0] and replies[0]["ok"], replies

        ls = sim.local_servers[0]
        assert ls._workers_target == 2  # w0 + joiner, never 1 or 3
        assert wj.num_workers == 2
        # whichever way the join/leave interleaved, close any round the
        # transition left partially counted before the clean round below
        st = ls._keys[0]
        if st.accum is not None:
            w0.push(0, np.ones(4, np.float32))
            assert _wait_for(lambda: st.accum is None, 10)
        # the post-transition group trains: both members' round lands
        for w in (w0, wj):
            w.push(0, np.ones(4, np.float32))
        a = w0.pull_sync(0)
        b = wj.pull_sync(0)
        np.testing.assert_allclose(a, b)
    finally:
        sim.shutdown()


def test_preempt_disabled_is_legacy_default():
    """Default-off guard: without ``enable_preempt`` no notice hook is
    registered anywhere — a PREEMPT_NOTICE on the wire is ignored, the
    member stays, and the legacy graceful-leave / eviction paths are
    untouched.  ``notice_worker`` refuses loudly."""
    sim = Simulation(Config(topology=Topology(num_parties=1,
                                              workers_per_party=2),
                            heartbeat_interval_s=0.05,
                            heartbeat_timeout_s=2.0))
    try:
        assert not sim.config.enable_preempt  # the default
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(4, np.float32))
        with pytest.raises(AssertionError, match="enable_preempt"):
            sim.notice_worker(0, 1)
        # raw wire notice: nothing consumes it, nothing drains
        sim.offices["scheduler:0@p0"].van.send(Message(
            recipient=NodeId.parse("worker:1@p0"),
            control=Control.PREEMPT_NOTICE, domain=Domain.LOCAL,
            request=True, body={"token": "t-guard"}))
        time.sleep(0.3)
        ls = sim.local_servers[0]
        assert "worker:1@p0" in ls._members
        assert ls.left_workers == 0
        assert not w1.preempt_noticed.is_set()
        # the legacy graceful leave still behaves exactly as before
        w1.leave_party()
        assert ls.left_workers == 1 and ls._workers_target == 1
    finally:
        sim.shutdown()


def test_esync_planner_forgets_departed_worker():
    """ESync churn hygiene: a departed straggler's stale step estimate
    must leave the reach-time target with it — before the fix it stayed
    in the max forever and pinned every survivor's assignment high."""
    from geomx_tpu.sched.esync import EsyncState

    st = EsyncState(min_steps=1, max_steps=64)
    st.report("fast", step_s=0.01, comm_s=0.0, max_steps=64)
    st.report("slow", step_s=0.50, comm_s=0.0, max_steps=64)
    # the straggler sets the target: the fast worker fills the window
    assert st.plan()["fast"] >= 40
    assert st.drop("slow") is True
    assert st.workers() == ["fast"]
    # target collapsed to the fast worker's own reach time
    assert st.plan()["fast"] == st.min_steps
    assert st.drop("slow") is False  # idempotent

    # server-level wiring: the graceful leave folds the member out of
    # the planner too (the fold IS the replan trigger)
    sim = Simulation(_cfg())
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(4, np.float32))
        assert w0.esync_report(0.01, 0.0, max_steps=64) >= 1
        assert w1.esync_report(0.50, 0.0, max_steps=64) == 1
        # the straggler inflates the fast worker's assignment...
        assert w0.esync_report(0.01, 0.0, max_steps=64) >= 40
        srv = sim.local_servers[0]
        assert sorted(srv._esync.workers()) == ["worker:0@p0",
                                                "worker:1@p0"]
        w1.leave_party()
        assert srv._esync.workers() == ["worker:0@p0"]
        # ...and the fold deflates it back to min_steps
        assert w0.esync_report(0.01, 0.0, max_steps=64) == 1
    finally:
        sim.shutdown()


def test_churn_orchestrator_scripted_seeded_and_attributed():
    """The orchestrator executes a SEEDED tape (same seed → same tape),
    respects the min-survivor floor, counts every injected event in the
    churn_* registry family, and stamps each into the flight recorder
    so postmortems can attribute stalls to injected faults."""
    from geomx_tpu.chaos import ChurnOrchestrator, ChurnPhase, ChurnPlan

    phases = (ChurnPhase(2.0, departure_rate=2.5, join_rate=1.5,
                         notice_fraction=1.0),)
    assert (ChurnPlan(phases=phases, seed=11).schedule()
            == ChurnPlan(phases=phases, seed=11).schedule())
    assert (ChurnPlan(phases=phases, seed=11).schedule()
            != ChurnPlan(phases=phases, seed=12).schedule())

    sim = Simulation(_cfg(parties=2, workers=2, heartbeat_timeout_s=0.6,
                          preempt_drain_s=2.0))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        plan = ChurnPlan(phases=phases, seed=11,
                         min_workers_per_party=1,
                         max_workers_per_party=3)
        orch = ChurnOrchestrator(sim, plan)
        orch.run()  # inline: deterministic completion
        stats = orch.stats()
        assert stats["transitions"] > 0, "the tape injected nothing"
        # counters match the executed tape exactly
        gsched = str(sim.topology.global_scheduler())
        assert (system_counter(f"{gsched}.churn_notices").value
                == stats["notices"])
        assert (system_counter(f"{gsched}.churn_joins").value
                == stats["joins"])
        # min-survivor floor held at every point
        for p, ranks in orch._alive.items():
            assert len(ranks) >= plan.min_workers_per_party, (p, ranks)
        assert stats["survivors"] >= 2 * plan.min_workers_per_party
        # every injected event is in the global scheduler's flight ring
        churn_evs = [e for e in sim.offices[gsched].flight.events()
                     if e["ev"] == "CHURN"]
        assert len(churn_evs) == len(orch.events)
        # a noticed departure is NEVER an eviction
        for mon in sim.eviction_monitors:
            assert not (set(mon._evicted) & orch.noticed), (
                mon._evicted, orch.noticed)
        # graceful drains measured and fast
        if stats["drain_latency_s"]:
            med = stats["drain_latency_s"][
                len(stats["drain_latency_s"]) // 2]
            assert med < 0.25 * sim.config.heartbeat_timeout_s
        orch.stop()
    finally:
        sim.shutdown()


def test_churn_storm_health_rule_fires_and_surfaces():
    """The churn_storm rule: transition rate over the collector window
    past the bound fires `cluster`; the orchestrator's survivor gauge
    at the floor fires `survivor_floor` (critical) — both visible in
    the status console's active-alert list."""
    cfg = _cfg(workers=1, heartbeat_interval_s=0.0, enable_obs=True,
               obs_interval_s=0.0, obs_churn_storm=10)
    sim = Simulation(cfg)
    try:
        gsched = str(sim.topology.global_scheduler())
        system_counter(f"{gsched}.churn_notices").inc(2)
        system_counter(f"{gsched}.churn_ungraceful_kills").inc(1)
        sim.pump_metrics()
        sim.health.tick()  # one series point: the rule stays quiet
        system_counter(f"{gsched}.churn_notices").inc(12)
        system_counter(f"{gsched}.churn_ungraceful_kills").inc(6)
        sim.pump_metrics()
        recs = sim.health.tick()
        storm = [r for r in recs if r["rule"] == "churn_storm"
                 and r["subject"] == "cluster"]
        assert storm and storm[0]["state"] == "firing", recs
        # survivor floor: gauges the orchestrator ships
        system_gauge(f"{gsched}.churn_survivors").set(2)
        system_gauge(f"{gsched}.churn_min_survivors").set(2)
        sim.pump_metrics()
        recs = sim.health.tick()
        floor = [r for r in recs if r["subject"] == "survivor_floor"]
        assert floor and floor[0]["severity"] == "critical", recs
        # surfaced in the live cluster state (python -m geomx_tpu.status)
        active = (sim.cluster_state().get("health") or {}).get("active")
        assert any(a["rule"] == "churn_storm" for a in active), active
    finally:
        sim.shutdown()


def test_training_loops_break_at_step_boundary_on_notice():
    """run_worker finishes the in-flight step and stops when the notice
    lands — the drain's 'finish your step, then flush' contract."""
    import jax

    from geomx_tpu.data import ShardedIterator, synthetic_classification
    from geomx_tpu.models import create_cnn_state
    from geomx_tpu.training import run_worker

    sim = Simulation(_cfg(workers=1, heartbeat_interval_s=0.0))
    try:
        kv = sim.all_workers()[0]
        x, y = synthetic_classification(n=64, shape=(8, 8, 1), seed=0)
        _, params, grad_fn = create_cnn_state(
            jax.random.PRNGKey(0), input_shape=(1, 8, 8, 1))
        it = ShardedIterator(x, y, 8, 0, 1)

        def log(step, _l, _a):
            if step == 2:
                kv.preempt_noticed.set()

        hist = run_worker(kv, params, grad_fn, it, 50,
                          barrier_init=False, log_fn=log)
        assert len(hist) == 3, "loop did not break at the boundary"
    finally:
        sim.shutdown()


# ---------------------------------------------------------------------------
# the 24-party spot-churn soak (slow + scale; pytest -m scale)
# ---------------------------------------------------------------------------


def _quad_loop(kv, name, target, state, stop_all, errs):
    """Free-running FSA round loop on a quadratic objective: push
    grad((w-t)^2)/n + per-worker noise, pull, record loss.  Bounded
    waits so a killed worker's thread exits instead of wedging."""
    rng = np.random.RandomState(abs(hash(name)) % (2 ** 31))
    w = state.get("w")
    if w is None:
        w = kv.pull_sync(0) if state.get("bootstrap") else \
            np.zeros_like(target)
    try:
        while not stop_all.is_set() and not kv.preempt_noticed.is_set():
            g = (w - target + rng.normal(0, 0.01, target.shape)
                 .astype(np.float32)) / kv.num_workers
            kv.push(0, g)
            got = []
            ts = kv.pull(0, lambda t, a: got.append(a))
            deadline = time.monotonic() + 120
            while not got:
                try:
                    kv.worker.customer.wait(ts, timeout=0.5)
                except TimeoutError:
                    if kv.po.van.killed:
                        raise RuntimeError("killed")
                    if time.monotonic() > deadline:
                        raise RuntimeError(f"{name}: round stuck >120s")
            w = got[0]
            state["w"] = w
            state["loss"] = float(np.mean((w - target) ** 2))
            state["rounds"] = state.get("rounds", 0) + 1
    except Exception as e:  # noqa: BLE001 — killed workers land here
        state["stopped"] = True  # pushes no more (the teardown's
        #                          per-party leave_global gate)
        errs.append((name, repr(e)))
        return
    state["stopped"] = True
    if not kv.preempt_noticed.is_set():
        # orderly end: leave so siblings' final rounds fold complete
        try:
            kv.wait_all()
        except Exception:
            pass
        try:
            kv.leave_party(timeout=15)
        except Exception as e:  # noqa: BLE001
            errs.append((name, f"leave: {e!r}"))


def _run_soak(parties, rounds_target, churn_plan=None):
    from geomx_tpu.chaos import ChurnOrchestrator

    cfg = _cfg(parties=parties, workers=2, heartbeat_timeout_s=0.6,
               request_retry_s=0.5, preempt_drain_s=5.0,
               lightweight=True,
               # at 24 parties the scheduler's ring sees ~1k message
               # heads/s — a soak-length window needs a deeper ring or
               # early injected events are overwritten before the
               # attribution check reads them
               flight_events=1 << 16)
    sim = Simulation(cfg, lightweight=True)
    dim = 128
    target = np.linspace(-1.0, 1.0, dim).astype(np.float32)
    stop_all = threading.Event()
    errs: list = []
    states = {}
    threads = []
    orch = None
    try:
        ws = sim.all_workers()
        for kv in ws:
            kv.init(0, np.zeros(dim, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.3})

        def start(kv, bootstrap=False):
            name = str(kv.po.node)
            st = states.setdefault(name, {"bootstrap": bootstrap})
            th = threading.Thread(
                target=_quad_loop, args=(kv, name, target, st,
                                         stop_all, errs),
                name=f"soak-{name}", daemon=True)
            threads.append(th)
            th.start()

        for kv in ws:
            start(kv)
        observer = "worker:0@p0"
        if churn_plan is not None:
            orch = ChurnOrchestrator(
                sim, churn_plan,
                spawn=lambda kv: (kv.init(0, np.zeros(dim, np.float32)),
                                  start(kv, bootstrap=True)),
                protect={observer}).start()
            orch.join(churn_plan.duration_s + 120)
            assert not orch._thread.is_alive(), "orchestrator wedged"
        # train until the protected observer saw rounds_target rounds
        assert _wait_for(
            lambda: states[observer].get("rounds", 0) >= rounds_target,
            timeout=300), (states[observer], errs)
        stop_all.set()
        # orderly wind-down: parties finish at DIFFERENT global rounds,
        # and a party that stopped pushing stalls the global FSA round
        # for everyone else — so as each party's workers exit their
        # loops, that party withdraws from the global tier
        # (leave_global), folding the survivors' final rounds complete
        left_global = set()
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            by_party = {}
            for name, st in states.items():
                p = int(name.split("@p")[1])
                by_party.setdefault(p, []).append(
                    st.get("stopped", False))
            for p, flags in by_party.items():
                if p not in left_global and all(flags):
                    left_global.add(p)
                    try:
                        sim.local_servers[p].leave_global(timeout=10)
                    except Exception:
                        pass  # dead/folded server: nothing to withdraw
            if not any(th.is_alive() for th in threads):
                break
            time.sleep(0.05)
        for th in threads:
            th.join(max(0.1, deadline - time.monotonic()))
        stuck = [th.name for th in threads if th.is_alive()]
        assert not stuck, f"permanently-stuck rounds: {stuck} ({errs})"
        return sim, orch, states, errs
    except BaseException:
        stop_all.set()
        if orch is not None:
            orch.stop()
        sim.shutdown()
        raise


@pytest.mark.slow
@pytest.mark.scale
def test_spot_churn_soak_24_parties_loss_parity():
    """Acceptance (ISSUE 13): a 24-party lightweight-reactor soak under
    a seeded Poisson ChurnPlan — mixed notices, ungraceful kills, joins
    and local-server preemptions, ≥20 membership transitions — must
    complete with loss parity vs an uninterrupted control, zero
    permanently-stuck rounds, every injected event attributable in the
    flight-recorder timeline, graceful drains well under the eviction
    window, and no noticed worker ever evicted."""
    from geomx_tpu.chaos import ChurnPhase, ChurnPlan

    parties, rounds_target = 24, 40

    # ---- control: same fleet, nobody preempted -------------------------
    sim, _, states, errs = _run_soak(parties, rounds_target)
    try:
        control_loss = states["worker:0@p0"]["loss"]
        assert not errs, errs
        assert np.isfinite(control_loss)
    finally:
        sim.shutdown()

    # ---- churn run -----------------------------------------------------
    plan = ChurnPlan(
        phases=(
            # a preemption wave: mostly-graceful departures + arrivals
            ChurnPhase(6.0, departure_rate=1.6, join_rate=1.0,
                       notice_fraction=0.6, server_kill_rate=0.15,
                       server_restart_s=1.5),
            # a harsher tail: more ungraceful kills
            ChurnPhase(6.0, departure_rate=1.4, join_rate=1.0,
                       notice_fraction=0.35),
        ),
        seed=13, min_workers_per_party=1, max_workers_per_party=3)
    sim, orch, states, errs = _run_soak(parties, rounds_target,
                                        churn_plan=plan)
    try:
        stats = orch.stats()
        churn_loss = states["worker:0@p0"]["loss"]
        # loss parity with the uninterrupted control: both runs sit at
        # the quadratic's noise floor — churn must not knock training
        # off it
        assert np.isfinite(churn_loss)
        assert abs(churn_loss - control_loss) < 0.05, (
            churn_loss, control_loss)
        assert churn_loss < 0.05, churn_loss
        # the plan actually churned: ≥20 executed transitions, mixed
        assert stats["transitions"] >= 20, stats
        assert stats["notices"] > 0 and stats["ungraceful_kills"] > 0 \
            and stats["joins"] > 0, stats
        # every injected event attributable in the flight timeline
        gsched = str(sim.topology.global_scheduler())
        churn_evs = [e for e in sim.offices[gsched].flight.events()
                     if e["ev"] == "CHURN"]
        assert len(churn_evs) == len(orch.events), (
            len(churn_evs), len(orch.events))
        by_note = {}
        for e in churn_evs:
            by_note[e["note"]] = by_note.get(e["note"], 0) + 1
        assert by_note.get("churn_notice", 0) == stats["notices"]
        assert by_note.get("churn_join", 0) == stats["joins"]
        # drain latency: notice→folded median a small fraction of the
        # eviction timeout, and a noticed worker NEVER fired the monitor
        drains = stats["drain_latency_s"]
        assert drains, "no graceful drain completed"
        med = drains[len(drains) // 2]
        assert med < 0.25 * sim.config.heartbeat_timeout_s, drains
        for mon in sim.eviction_monitors:
            overlap = set(mon._evicted) & orch.noticed
            assert not overlap, overlap
        # only killed workers errored out of their loops
        bad = [n for n, _ in errs
               if n not in orch.killed and n not in orch.noticed]
        assert not bad, (bad, errs)
        # the observer made continuous progress: zero stuck rounds
        assert states["worker:0@p0"]["rounds"] >= rounds_target
    finally:
        sim.shutdown()
