"""Dynamic worker join (VERDICT r3 item 7; ref: ADD_NODE runtime id
assignment + node-table broadcast, ps-lite van.cc:41-112).

The build's topology is a static plan (documented divergence), so the
party SERVER owns rank assignment and the aggregation count: a new
worker registers mid-training and is folded into each key's count at
that key's next fresh aggregation round — never mid-round.
"""

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation


def _round(workers, tid, grads):
    for w, g in zip(workers, grads):
        w.push(tid, g)
    outs = [w.pull_sync(tid) for w in workers]
    for w in workers:
        w.wait_all()
    return outs


def test_worker_joins_midtraining_and_count_shifts():
    """Start 2 workers, train, add a third: the server's round count
    shifts to 3 at the next round boundary and training continues with
    all three contributions aggregated."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)

        # round 1: two workers; server applies -lr * sum = -2
        outs = _round(ws, 0, [g, g])
        np.testing.assert_allclose(outs[0], -2.0 * np.ones(4))

        # join a third worker mid-training
        w3 = sim.add_worker(0)
        assert w3.num_workers == 3
        srv = sim.local_servers[0]
        assert srv.joined_workers == 1
        # the joiner initializes its replica (no-op server-side) and
        # pulls current weights before contributing
        w3.init(0, np.zeros(4, np.float32))
        np.testing.assert_allclose(w3.pull_sync(0), -2.0 * np.ones(4))

        # round 2: THREE workers must now complete the round — if the
        # server still counted to 2, the third push would leak into a
        # phantom next round and desync every later pull
        outs = _round(ws + [w3], 0, [g, g, g])
        for o in outs:
            np.testing.assert_allclose(o, -5.0 * np.ones(4))

        # round 3: still 3
        outs = _round(ws + [w3], 0, [g, g, g])
        for o in outs:
            np.testing.assert_allclose(o, -8.0 * np.ones(4))
    finally:
        sim.shutdown()


def test_join_mid_round_extends_open_round():
    """A join landing while a round is mid-aggregation EXTENDS that
    round's target: the joiner's first pushes land in whatever round is
    open, and completing it early at the old count would leak a static
    worker's push into the next round (advisor r4).  So the open round
    waits for all three — no contribution is lost or carried over."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)

        # first worker pushes: round is now mid-aggregation (1 of 2)
        ws[0].push(0, g)
        w3 = sim.add_worker(0)  # join lands mid-round -> target 3
        ws[1].push(0, g)        # 2 of 3: round still open
        w3.init(0, np.zeros(4, np.float32))
        w3.push(0, g)           # 3 of 3: completes with everyone
        np.testing.assert_allclose(ws[0].pull_sync(0), -3.0 * np.ones(4))
        for w in ws + [w3]:
            w.wait_all()

        # membership broadcast reached the static workers too: their
        # 1/num_workers gradient pre-scale must track the new size
        assert ws[0].num_workers == 3 and ws[1].num_workers == 3

        # next round counts all three as well
        outs = _round(ws + [w3], 0, [g, g, g])
        for o in outs:
            np.testing.assert_allclose(o, -6.0 * np.ones(4))
    finally:
        sim.shutdown()


def test_join_bootstrap_pull_does_not_deadlock():
    """Advisor r4 HIGH: the joiner's natural bootstrap order is pull
    the current model, THEN push.  Join raises the open round's target
    to include the joiner, so under the old serving rule (park any pull
    while count > 0) the joiner's own bootstrap pull parked behind a
    round that only its push could complete — a deadlock that also
    wedged the static workers.  Non-contributor pulls are now served
    from the last completed round, so the bootstrap pull returns
    immediately even with a round open and waiting for the joiner."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        _round(ws, 0, [g, g])                   # store = -2

        # the join lands first (target -> 3), THEN the static workers
        # push: the open round now waits for the joiner's contribution
        w3 = sim.add_worker(0)
        w3.init(0, np.zeros(4, np.float32))
        ws[0].push(0, g)
        ws[1].push(0, g)
        # the bootstrap pull: the open round (2 of 3) can only complete
        # with w3's own push — under the old serving rule this parked
        # forever (and the statics' pulls behind it).  Non-contributors
        # are now served the last completed round's weights.
        pulled = w3.pull_sync(0)                # old rule: hangs forever
        np.testing.assert_allclose(pulled, -2.0 * np.ones(4))

        # the joiner contributes: the waiting round completes for all
        w3.push(0, g)
        outs = [w.pull_sync(0) for w in ws + [w3]]
        for o in outs:
            np.testing.assert_allclose(o, -5.0 * np.ones(4))
        for w in ws + [w3]:
            w.wait_all()
    finally:
        sim.shutdown()


def test_lagging_worker_pull_serves_last_completed_round():
    """A worker one round behind (others already pushed round r+1) asks
    for round r's weights: it must get the store's last-completed value,
    not park behind the open r+1 round (which its own push feeds)."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        _round(ws, 0, [g, g])                   # round r completes: -2
        ws[0].push(0, g)                        # r+1 opens (1 of 2)
        # ws[1] has not contributed to r+1 — its pull gets round r
        np.testing.assert_allclose(ws[1].pull_sync(0), -2.0 * np.ones(4))
        ws[1].push(0, g)                        # r+1 completes: -4
        np.testing.assert_allclose(ws[0].pull_sync(0), -4.0 * np.ones(4))
        for w in ws:
            w.wait_all()
    finally:
        sim.shutdown()


def test_leave_and_push_completion_race_is_single():
    """Advisor r4 MEDIUM: a push deciding completion (outside the lock)
    racing a leave that lowers the target must not run _round_complete
    twice for one key — the second call would crash taking the
    already-None accumulator.  Hammer the interleaving: many rounds
    where the last static push and a leave/rejoin land back to back."""
    import threading

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=3)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(64, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.01})
        g = np.ones(64, np.float32)
        for _ in range(10):
            ws[0].push(0, g)
            ws[1].push(0, g)
            # racing pair: the completing third push vs a leave that
            # also sees count >= lowered target
            t_push = threading.Thread(target=ws[2].push, args=(0, g))
            t_leave = threading.Thread(target=ws[2].leave_party)
            t_push.start(); t_leave.start()
            t_push.join(); t_leave.join()
            # both statics can still pull (no crashed server thread)
            out = ws[0].pull_sync(0)
            assert np.isfinite(out).all()
            ws[0].wait_all(); ws[1].wait_all(); ws[2].wait_all()
            # rejoin for the next iteration
            ws[2].join_party()
        srv = sim.local_servers[0]
        assert srv.left_workers == 10 and srv.joined_workers == 10
    finally:
        sim.shutdown()


def test_leave_restores_count_and_releases_stalled_round():
    """Graceful leave: the target drops at the boundary, and a round the
    leaver never reached completes without it instead of stalling."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        w3 = sim.add_worker(0)
        w3.init(0, np.zeros(4, np.float32))

        outs = _round(ws + [w3], 0, [g, g, g])  # 3-way round: -3
        np.testing.assert_allclose(outs[0], -3.0 * np.ones(4))

        # the two static workers push the NEXT round (2 of 3) — it
        # stalls until the third contributor's fate resolves
        ws[0].push(0, g)
        ws[1].push(0, g)
        res = w3.leave_party()
        assert res["num_workers"] == 2
        assert sim.local_servers[0].left_workers == 1
        # the leave released the stalled round at count 2
        np.testing.assert_allclose(ws[0].pull_sync(0), -5.0 * np.ones(4))
        for w in ws:
            w.wait_all()

        # subsequent rounds count 2 again
        outs = _round(ws, 0, [g, g])
        np.testing.assert_allclose(outs[0], -7.0 * np.ones(4))
    finally:
        sim.shutdown()


def test_static_plan_worker_can_leave():
    """The membership registry is seeded with the static plan, so a PLAN
    worker's leave lowers the target too (advisor r4: it used to be
    silently treated as a replayed leave, stalling every later round)."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        _round(ws, 0, [g, g])
        res = ws[1].leave_party()
        assert res["num_workers"] == 1
        # worker 0 trains on alone — rounds complete at count 1
        ws[0].push(0, g)
        np.testing.assert_allclose(ws[0].pull_sync(0), -3.0 * np.ones(4))
        ws[0].wait_all()
    finally:
        sim.shutdown()


def test_join_under_wan_compression():
    """Join interplay with the WAN codec path: a joiner folds into a
    party whose push-ups ride BSC — the pull-direction compressor's
    per-subscriber tracked views and the join are independent, so
    training must continue and the WAN must stay compressed."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        compression="bsc"))
    try:
        ws = sim.all_workers()
        rng = np.random.default_rng(0)
        for w in ws:
            w.init(0, np.zeros(4096, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        ws[0].set_gradient_compression({"type": "bsc", "ratio": 0.05})
        g = rng.standard_normal(4096).astype(np.float32)
        _round(ws, 0, [g, g])
        base = sim.wan_bytes()["wan_send_bytes"]

        w3 = sim.add_worker(0)
        w3.init(0, np.zeros(4096, np.float32))
        outs = _round(ws + [w3], 0, [g, g, g])
        # all three replicas agree post-join
        np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5, atol=1e-6)
        # and the WAN hop stayed sparse (well under the dense 2x16KB
        # push+pull a vanilla round would cost)
        sent = sim.wan_bytes()["wan_send_bytes"] - base
        assert sent < 0.5 * (2 * 4096 * 4), sent
    finally:
        sim.shutdown()


def test_join_survives_drop_injection():
    """ADD_NODE is a control message outside the resender; the client
    RPC retries (and the server handler is idempotent by node id), so a
    join must succeed across a lossy fabric and must not double-count
    when a reply — not the request — was the drop."""
    from geomx_tpu.transport.van import FaultPolicy

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        resend_timeout_ms=100),  # recovers dropped DATA traffic; the
        #                          ADD_NODE rpc has its own retry
        fault=FaultPolicy(drop_rate=0.3, seed=7))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        w3 = sim.add_worker(0)  # retries under 30% drop
        assert w3.num_workers == 3
        srv = sim.local_servers[0]
        # idempotency: however many requests got through, ONE member
        assert srv._workers_target == 3, srv._workers_target
        assert srv.joined_workers >= 1
    finally:
        sim.shutdown()


def test_join_under_intra_ts():
    """VERDICT r4 item 6: join used to be rejected under the intra-party
    TS overlay (fixed member set).  The membership broadcast now updates
    the TsScheduler's dissemination targets and the TsPushScheduler's
    pairing threshold, so a joiner both receives overlay relays and
    participates in the merge tree."""
    import threading

    import jax

    from geomx_tpu.data import ShardedIterator, synthetic_classification
    from geomx_tpu.models import create_cnn_state
    from geomx_tpu.training import run_worker

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        enable_intra_ts=True))
    try:
        x, y = synthetic_classification(n=256, shape=(8, 8, 1), seed=0)
        _, params, grad_fn = create_cnn_state(
            jax.random.PRNGKey(0), input_shape=(1, 8, 8, 1))
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "adam", "lr": 0.01})
        hist = {}

        def train(kv, widx, nw, steps):
            it = ShardedIterator(x, y, 16, widx, nw, seed=1)
            hist[widx] = run_worker(kv, params, grad_fn, it, steps,
                                    barrier_init=False)

        ths = [threading.Thread(target=train, args=(w, i, 2, 2))
               for i, w in enumerate(ws)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert len(hist) == 2, "static TS round hung"

        w3 = sim.add_worker(0)
        # scheduler member sets tracked the join.  The membership
        # broadcast is asynchronous — join_party() returning only means
        # the SERVER folded the joiner in, not that every scheduler's
        # hook has run yet — so poll with a short deadline instead of
        # asserting immediately (advisor r5: flaky under load)
        import time as _time

        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline:
            if all(str(w3.po.node) in sched.members
                   for sched in sim.ts_schedulers):
                break
            _time.sleep(0.02)
        for sched in sim.ts_schedulers:
            assert str(w3.po.node) in sched.members
        ths = [threading.Thread(target=train, args=(w, i, 3, 2))
               for i, w in enumerate(ws + [w3])]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert len(hist) == 3, "post-join TS round hung"
        assert len(hist[2]) == 2  # the joiner trained full rounds
        assert np.isfinite([h[0] for h in hist[2]]).all()
    finally:
        sim.shutdown()


def test_join_under_hfa_renormalizes_weight_mean():
    """VERDICT r4 item 6: join under HFA.  Workers push weight/n; a
    transition round mixes denominators (statics at old n, joiner at
    new n) and a leave can complete a round short — either way the
    accumulated Σ w_i/n_i is renormalized by Σ 1/n_i (announced per
    push as hfa_n), so the party 'mean' stays a convex combination and
    the weights are never scale-inflated."""
    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        use_hfa=True, hfa_k2=1))
    try:
        ws = sim.all_workers()
        w_val = 6.0 * np.ones(4, np.float32)
        for w in ws:
            w.init(0, w_val.copy())
        # HFA round at n=2: both push w/2 with hfa_n=2 -> mean = 6
        for w in ws:
            w.push(0, w_val / 2, body={"hfa_n": 2})
        np.testing.assert_allclose(ws[0].pull_sync(0), w_val)
        for w in ws:
            w.wait_all()

        w3 = sim.add_worker(0)
        w3.init(0, w_val.copy())
        assert w3.num_workers == 3
        # transition round: statics still at n=2 (stale pre-scale),
        # joiner at n=3.  Unnormalized sum = 6/2+6/2+6/3 = 8 (a 1.33x
        # weight inflation); renormalized by S = 1/2+1/2+1/3 -> 6.
        ws[0].push(0, w_val / 2, body={"hfa_n": 2})
        ws[1].push(0, w_val / 2, body={"hfa_n": 2})
        w3.push(0, w_val / 3, body={"hfa_n": 3})
        np.testing.assert_allclose(ws[0].pull_sync(0), w_val, rtol=1e-6)
        for w in ws + [w3]:
            w.wait_all()

        # leave completes a round short: 2 of 3 pushed, leaver exits.
        # Σ w/3 * 2 = 4 would SHRINK the weights; renormalized -> 6.
        ws[0].push(0, w_val / 3, body={"hfa_n": 3})
        ws[1].push(0, w_val / 3, body={"hfa_n": 3})
        w3.leave_party()
        np.testing.assert_allclose(ws[0].pull_sync(0), w_val, rtol=1e-6)
        for w in ws:
            w.wait_all()
    finally:
        sim.shutdown()


def test_party_leave_lowers_global_tier_target():
    """VERDICT r4 item 6: graceful PARTY leave.  The global tier's
    aggregation target (num_global_workers) drops at the round
    boundary; a round the leaving party never reached completes with
    the remaining parties instead of stalling forever.  (The
    reference's global membership is static; recovery is a TODO at
    van.cc:224 — this goes beyond it.)"""
    sim = Simulation(Config(
        topology=Topology(num_parties=3, workers_per_party=1)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        # global tier applies the PARTY mean: -lr * (3g)/3 = -1
        outs = _round(ws, 0, [g, g, g])
        np.testing.assert_allclose(outs[0], -1.0 * np.ones(4))

        # parties 0 and 1 push the next round; party 2 leaves instead
        ws[0].push(0, g)
        ws[1].push(0, g)
        res = sim.local_servers[2].leave_global()
        for gs_reply in res.values():
            assert gs_reply["num_global_workers"] == 2
        # the stalled round completes with two parties: -(2g)/2 = -1
        np.testing.assert_allclose(ws[0].pull_sync(0), -2.0 * np.ones(4))
        ws[0].wait_all(); ws[1].wait_all()

        # subsequent rounds count 2 parties
        outs = _round(ws[:2], 0, [g, g])
        np.testing.assert_allclose(outs[0], -3.0 * np.ones(4))

        # replayed leave is idempotent
        res = sim.local_servers[2].leave_global()
        for gs_reply in res.values():
            assert gs_reply["num_global_workers"] == 2
    finally:
        sim.shutdown()


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["plain", "tsengine", "hfa"])
def test_worker_joins_over_real_tcp(mode):
    """Process-level join (the reference's ADD_NODE is inherently
    multi-process, van.cc:41-112): a full TCP topology trains while an
    out-of-plan worker process registers via --join --advertise, trains
    a couple of rounds, and leaves gracefully; everyone exits 0 and the
    server's exit stats show the join+leave.  Parametrized over the
    plain loop, the TS overlay (peers/scheduler must learn the joiner's
    out-of-plan ADDRESS from the membership broadcast — relays and ask
    replies dial it) and HFA (weight-mean renormalization)."""
    import os
    import re
    import subprocess
    import sys
    import time

    from tests.test_tcp import free_base_port

    flags = {"plain": [], "tsengine": ["--tsengine"], "hfa": ["--hfa"]}[mode]
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    topo = Topology(num_parties=1, workers_per_party=2)
    base = free_base_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_PLATFORM_NAME="cpu")

    def spawn(role, extra):
        return subprocess.Popen(
            [sys.executable, "-m", "geomx_tpu.launch", "--role", role,
             "--parties", "1", "--workers", "2",
             "--base-port", str(base)] + extra + flags,
            cwd=cwd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)

    procs = {str(n): spawn(str(n), ["--steps", "8"])
             for n in topo.all_nodes()}
    # the joiner: out-of-plan rank 2, binds past the plan's ports.
    # Launched immediately — it registers while the static workers are
    # still in jax compile, and runs fewer steps than they do so its
    # rounds are a prefix of theirs (leave covers the rest)
    join_role = "worker:2@p0"
    procs[join_role] = spawn(join_role, [
        "--steps", "2", "--join",
        "--advertise", f"127.0.0.1:{base + 40}"])
    try:
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs.values()):
                break
            time.sleep(0.5)
        outputs = {}
        for r, p in procs.items():
            if p.poll() is None:
                p.kill()
            outputs[r] = p.communicate()[0]
        for r, p in procs.items():
            assert p.returncode == 0, \
                f"{r} rc={p.returncode}: {outputs[r][-1000:]}"
        assert "joined as rank 2" in outputs[join_role], outputs[join_role]
        assert "left cleanly" in outputs[join_role], outputs[join_role]
        srv_out = outputs["server:0@p0"]
        m = re.search(r"joined=(\d+) left=(\d+)", srv_out)
        assert m and m.group(1) == "1" and m.group(2) == "1", srv_out
        for w in ("worker:0@p0", "worker:1@p0"):
            assert "steps=8" in outputs[w], outputs[w][-500:]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()


def test_joined_worker_trains_a_model():
    """End-to-end: CNN training continues across a join and the loss
    keeps improving with three contributors."""
    import jax

    from geomx_tpu.data import ShardedIterator, synthetic_classification
    from geomx_tpu.models import create_cnn_state
    from geomx_tpu.training import flatten_params, run_worker

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        x, y = synthetic_classification(n=256, shape=(8, 8, 1), seed=0)
        _, params, grad_fn = create_cnn_state(
            jax.random.PRNGKey(0), input_shape=(1, 8, 8, 1))
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "adam", "lr": 0.01})

        import threading

        hist = {}

        def train(kv, widx, nw, steps):
            it = ShardedIterator(x, y, 16, widx, nw)
            hist[widx] = run_worker(kv, params, grad_fn, it, steps,
                                    barrier_init=False)

        ths = [threading.Thread(target=train, args=(w, i, 2, 3))
               for i, w in enumerate(ws)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()

        w3 = sim.add_worker(0)
        ths = [threading.Thread(target=train, args=(w, i, 3, 3))
               for i, w in enumerate(ws + [w3])]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert len(hist[2]) == 3  # the joiner trained full rounds
        losses = [h[0] for h in hist[0]]
        assert np.isfinite(losses).all()
    finally:
        sim.shutdown()


def test_party_leave_under_hfa():
    """Party leave while the global tier is in HFA mode: accumulated
    milestone DELTAS must complete additively (not through the
    optimizer) when the leave lowers the target, and the surviving
    party trains on."""
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        use_hfa=True, hfa_k2=1))
    try:
        ws = sim.all_workers()
        w_val = 4.0 * np.ones(4, np.float32)
        for w in ws:
            w.init(0, w_val.copy())
        # one full HFA round: both parties push mean weights -> both
        # replicas equal the cross-party mean (still 4.0)
        for w in ws:
            w.push(0, w_val, body={"hfa_n": 1})
        for w in ws:
            np.testing.assert_allclose(w.pull_sync(0), w_val)
            w.wait_all()

        # party 0 pushes the next round; party 1 leaves instead of
        # pushing — the round must complete additively with party 0's
        # milestone delta alone
        ws[0].push(0, 6.0 * np.ones(4, np.float32), body={"hfa_n": 1})
        res = sim.local_servers[1].leave_global()
        for gs_reply in res.values():
            assert gs_reply["num_global_workers"] == 1
        out = ws[0].pull_sync(0)
        assert np.isfinite(out).all()
        ws[0].wait_all()

        # the surviving party keeps syncing rounds cleanly
        ws[0].push(0, 5.0 * np.ones(4, np.float32), body={"hfa_n": 1})
        out2 = ws[0].pull_sync(0)
        assert np.isfinite(out2).all()
        ws[0].wait_all()
    finally:
        sim.shutdown()


def _join_trains_under(cfg_kwargs, loop="plain"):
    """Shared driver: 2 static workers train, a third joins, everyone
    trains again; returns the joiner's history."""
    import threading

    import jax

    from geomx_tpu.data import ShardedIterator, synthetic_classification
    from geomx_tpu.models import create_cnn_state
    from geomx_tpu.training import ESync, Trainer, run_worker

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2),
        **cfg_kwargs))
    try:
        x, y = synthetic_classification(n=256, shape=(8, 8, 1), seed=0)
        _, params, grad_fn = create_cnn_state(
            jax.random.PRNGKey(0), input_shape=(1, 8, 8, 1))
        ws = sim.all_workers()
        if loop == "plain":
            ws[0].set_optimizer({"type": "adam", "lr": 0.01})
        hist = {}

        def train(kv, widx, nw, n):
            # ShardedIterator samples with replacement and never ends —
            # no cycling wrapper needed (esync draws rounds x local
            # steps batches from it)
            it = ShardedIterator(x, y, 16, widx, nw, seed=1)
            esync = ESync(max_local_steps=4) if loop == "esync" else None
            hist[widx] = run_worker(
                kv, params, grad_fn, it, n, barrier_init=False,
                schedule=Trainer.schedule_for(kv, esync=esync))

        ths = [threading.Thread(target=train, args=(w, i, 2, 2))
               for i, w in enumerate(ws)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=120)
        assert len(hist) == 2, "static phase hung"
        w3 = sim.add_worker(0)
        ths = [threading.Thread(target=train, args=(w, i, 3, 2))
               for i, w in enumerate(ws + [w3])]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=150)
        assert len(hist) == 3, "post-join phase hung"
        assert np.isfinite([h[0] for h in hist[2]]).all()
        return hist[2]
    finally:
        sim.shutdown()


def test_join_under_p3():
    """Join under P3 (sliced piggybacked push_pull): the joiner's
    sliced keys fold into the same per-key round machinery — membership
    is uniform across scheduling modes, like the reference's ADD_NODE."""
    _join_trains_under(dict(enable_p3=True, p3_slice_elems=5_000))


def test_join_under_esync():
    """Join under ESync: the state server's plan is report-keyed (no
    fixed member set), the HFA weight mean renormalizes via hfa_n —
    a joiner simply starts reporting and training."""
    _join_trains_under(dict(use_hfa=True), loop="esync")


def test_concurrent_joins_get_unique_ranks():
    """Two workers joining the same party simultaneously must receive
    DISTINCT ranks and both be counted — rank assignment and the target
    bump live under the server lock, but the test pins the end-to-end
    guarantee (the reference's scheduler serializes ADD_NODE the same
    way, van.cc:41-112)."""
    import threading

    sim = Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=2)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(4, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.ones(4, np.float32)
        _round(ws, 0, [g, g])

        joined = {}

        def join_one(slot):
            joined[slot] = sim.add_worker(0)

        ths = [threading.Thread(target=join_one, args=(i,))
               for i in range(2)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=60)
        assert len(joined) == 2, "a join hung"
        srv = sim.local_servers[0]
        assert srv._workers_target == 4
        ranks = sorted(srv._members.values())
        assert ranks == [0, 1, 2, 3], ranks  # unique, gapless

        # all four train a round together
        all_ws = ws + list(joined.values())
        for w in joined.values():
            w.init(0, np.zeros(4, np.float32))
            assert np.isfinite(w.pull_sync(0)).all()
        outs = _round(all_ws, 0, [g] * 4)
        for o in outs:
            np.testing.assert_allclose(o, outs[0])
    finally:
        sim.shutdown()


def test_party_leave_prunes_dcasgd_backups():
    """MixedSync + DCASGD keeps a previous-weight snapshot per SENDER
    (party server); a party's graceful leave must drop its snapshots or
    full-model copies stay pinned in global-server RAM for the run."""
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        sync_global_mode=False))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(8, np.float32))
        ws[0].set_optimizer({"type": "dcasgd", "lr": 0.1})
        g = np.ones(8, np.float32)
        for w in ws:
            w.push(0, g)
            w.pull_sync(0)
            w.wait_all()
        gs = sim.global_servers[0]
        senders = set()
        for st in gs.optimizer.state.values():
            senders |= set(st.get("prev", {}))
        assert len(senders) == 2, senders  # both party servers tracked

        res = sim.local_servers[1].leave_global()
        for reply in res.values():
            assert reply["num_global_workers"] == 1
        leaver = str(sim.local_servers[1].po.node)
        for st in gs.optimizer.state.values():
            assert leaver not in st.get("prev", {})
        # survivor keeps training
        ws[0].push(0, g)
        assert np.isfinite(ws[0].pull_sync(0)).all()
        ws[0].wait_all()
    finally:
        sim.shutdown()
