"""The worker's own thread: one slice edge and one loop for every sync
mode (ISSUE 30, ISSUE 33).

``_edge_to_host`` hands the push the copy off the device as it is
(``scale == 1``) or scales on the device before that copy; ``_exchange``
issues ``pull(i)`` before ``push(i + 1)``; ``run_worker`` runs it for
gradients every step (FSA), for weights every ``k1`` steps (HFA) or after
as many steps as the state server assigned (ESync), and the staged
overlap loop runs it a stage at a time.  The loops that did each of
these apart are kept below as the references the one loop is held to,
to the bit.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu import training
from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.kvstore.common import Cmd
from geomx_tpu.overlap import StagedModel, run_worker_overlapped
from geomx_tpu.training import (ESync, Schedule, Trainer, _edge_to_host,
                                flatten_params, run_worker, unflatten_params)

JOIN_S = 120

# what a cluster runs with, by the name the cases below go by
CLUSTER = {"plain": {}, "p3": {"enable_p3": True, "p3_slice_elems": 100},
           "hfa": {"use_hfa": True, "hfa_k1": 2, "hfa_k2": 1},
           "esync": {"use_hfa": True, "hfa_k2": 1}, "overlapped": {}}


def _sim(workers=1, parties=2, **kw):
    return Simulation(Config(
        topology=Topology(num_parties=parties, workers_per_party=workers),
        **kw))


def _mlp(seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = {"w1": jax.random.normal(k[0], (12, 32)) * 0.3,
              "b1": jnp.zeros(32),
              "w2": jax.random.normal(k[1], (32, 4)) * 0.3,
              "b2": jnp.zeros(4)}

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        logits = h @ p["w2"] + p["b2"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @jax.jit
    def grad_fn(p, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        return loss, jnp.float32(0.0), grads

    return params, grad_fn


def _staged_mlp(seed):
    """The same shapes as two stages (tensors 0-1 and 2-3)."""
    params, _ = _mlp(seed)
    stages = [{"b": params["b1"], "w": params["w1"]},
              {"b": params["b2"], "w": params["w2"]}]

    def ce(logits, y):
        logp = jax.nn.log_softmax(logits)
        return (-jnp.mean(jnp.take_along_axis(logp, y[:, None], 1)),
                jnp.float32(0.0))

    fns = [lambda p, x: jnp.tanh(x @ p["w"] + p["b"]),
           lambda p, x: x @ p["w"] + p["b"]]
    return StagedModel(fns, ce), stages


def _batches(widx, steps):
    rng = np.random.default_rng(100 + widx)
    return [(rng.normal(size=(16, 12)).astype(np.float32),
             rng.integers(0, 4, 16)) for _ in range(steps)]


def _loop_of(schedule):
    """``loop(kv, batches, params_out)`` running ONE exchange of the
    named schedule (the overlapped loop: one a stage)."""
    def loop(kv, batches, params_out=None):
        if schedule == "overlapped":
            model, stages = _staged_mlp(0)
            return run_worker_overlapped(kv, model, stages, batches[:1], 1,
                                         params_out=params_out)
        params, grad_fn = _mlp(0)
        esync = ESync() if schedule == "esync" else None
        sched = Trainer.schedule_for(kv, esync=esync)
        return run_worker(kv, params, grad_fn, batches, sched.k1,
                          params_out=params_out, schedule=sched)
    return loop


def _run_all(sim, main):
    """``main(kv, widx)`` on a thread a worker; raises what one raised."""
    errors = []

    def guarded(kv, widx):
        try:
            main(kv, widx)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append((widx, e))

    threads = [threading.Thread(target=guarded, args=(kv, i))
               for i, kv in enumerate(sim.all_workers())]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=JOIN_S)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads), "a worker hung"


def _record_pushes(kv, sent):
    """Append what every training push of ``kv`` hands the van."""
    for name in ("zpush", "push_pull"):
        inner = getattr(kv.worker, name)

        def recording(kvs, _inner=inner, **kw):
            if kw.get("cmd") == Cmd.DEFAULT:
                sent.append(kvs.vals)
            return _inner(kvs, **kw)

        setattr(kv.worker, name, recording)


@pytest.mark.parametrize("scale,divide", [
    (1.0, False), (0.5, False), (1.0 / 3.0, False), (3, True)],
    ids=["one", "half", "third", "by-three"])
def test_a_leaf_crosses_the_edge_in_one_pass(scale, divide):
    """What reaches the van is the copy off the device itself when there
    is nothing to scale, and ``np.asarray(g) * scale`` (a gradient) or
    ``np.asarray(w) / n`` (a weight) to the bit, the parents' two passes,
    when there is."""
    sim = _sim()
    try:
        kv = sim.worker(0, 0)
        g = jax.random.normal(jax.random.PRNGKey(7), (64, 48), jnp.float32)
        kv.init(0, np.zeros(g.shape, np.float32))
        sent = []
        _record_pushes(kv, sent)
        host = _edge_to_host(kv, 0, g, scale, divide)
        kv.push(0, host)
        for w in sim.all_workers()[1:]:   # the other party closes the round
            w.push(0, np.zeros(g.shape, np.float32))
        for w in sim.all_workers():
            w.wait_all()
        assert not host.flags.writeable
        assert np.shares_memory(sent[0], host)
        if scale == 1.0:
            assert np.shares_memory(sent[0], np.asarray(g))
        want = np.asarray(g) / scale if divide else np.asarray(g) * scale
        assert want.dtype == host.dtype == np.float32
        assert host.tobytes() == want.tobytes()
    finally:
        sim.shutdown()


@pytest.mark.parametrize("schedule,workers", [
    ("plain", 1), ("plain", 2), ("p3", 2), ("hfa", 2), ("esync", 2),
    ("overlapped", 2)])
def test_every_schedule_crosses_the_edge_in_one_pass(schedule, workers,
                                                     monkeypatch):
    """Whatever the loop and the sync mode, a leaf is copied off the
    device once an exchange, by ``_edge_to_host``, and what the van is
    handed is that copy (under P3 a slice of its float32 copy)."""
    crossed = {}   # kv -> [(leaf, scale, divide, host)]
    inner = training._edge_to_host

    def recording(kv, tid, g, scale, divide=False):
        host = inner(kv, tid, g, scale, divide)
        crossed.setdefault(kv, []).append((g, scale, divide, host))
        return host

    monkeypatch.setattr(training, "_edge_to_host", recording)
    sim = _sim(workers, **CLUSTER[schedule])
    try:
        sent = {kv: [] for kv in sim.all_workers()}
        for kv, vals in sent.items():
            _record_pushes(kv, vals)
        if not CLUSTER[schedule].get("use_hfa"):
            sim.worker(0, 0).set_optimizer({"type": "sgd", "lr": 0.1})
        loop = _loop_of(schedule)
        _run_all(sim, lambda kv, i: loop(kv, _batches(i, 2)))
        for kv in sim.all_workers():
            assert len(crossed[kv]) == 4, "a leaf an exchange"
            hosts = [h for *_, h in crossed[kv]]
            for g, scale, divide, host in crossed[kv]:
                assert divide == bool(CLUSTER[schedule].get("use_hfa"))
                assert scale == (workers if divide else 1.0 / workers)
                want = (np.asarray(g) / scale if divide
                        else np.asarray(g) * scale)
                assert host.tobytes() == want.tobytes()
                assert not host.flags.writeable
            if schedule == "p3":
                assert (b"".join(v.tobytes() for v in sent[kv])
                        == b"".join(h.tobytes() for h in hosts))
            else:
                assert len(sent[kv]) == 4
                for vals, host in zip(sent[kv], hosts):
                    assert np.shares_memory(vals, host)
    finally:
        sim.shutdown()


def _run_worker_pulling_last(kv, params, grad_fn, data_iter, steps,
                             params_out):
    """PR 29's plain loop: every push of the step, a host multiply by
    ``scale`` on each, then every pull."""
    leaves, treedef = flatten_params(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=True)
    params = unflatten_params(treedef, leaves)
    history, buf = [], [None] * len(leaves)
    for step, (x, y) in enumerate(data_iter):
        if step >= steps:
            break
        scale = 1.0 / kv.num_workers
        loss, acc, grads = grad_fn(params, x, y)
        g_leaves, _ = jax.tree_util.tree_flatten(grads)
        for tid, g in enumerate(g_leaves):
            kv.push(tid, np.asarray(g) * scale, priority=-tid)
        for tid in range(len(leaves)):
            kv.pull(tid, lambda t, arr: buf.__setitem__(t, arr),
                    priority=-tid)
        kv.wait_all()
        params = unflatten_params(treedef, buf)
        history.append((float(loss), float(acc)))
    params_out["params"] = params
    return history


def _run_worker_syncing_weights_apart(kv, params, grad_fn, data_iter, steps,
                                      params_out, k1=None):
    """PR 32's HFA loop (``k1`` local steps between syncs) and, with
    ``k1`` None, the first round of its ESync loop (one local step, the
    push acks waited for, the report), over the sync round they shared: a host divide by the party size on each weight,
    every push, then every pull."""
    import optax

    optimizer = optax.adam(1e-2)
    leaves, treedef = flatten_params(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=True)
    params = unflatten_params(treedef, leaves)
    opt_state = optimizer.init(params)
    history, buf = [], [None] * len(leaves)
    for step, (x, y) in enumerate(data_iter):
        if step >= steps:
            break
        loss, acc, grads = grad_fn(params, x, y)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if k1 is None or (step + 1) % k1 == 0:
            w_leaves, _ = jax.tree_util.tree_flatten(params)
            n = kv.num_workers
            push_ts = [kv.push(tid, np.asarray(w) / n, priority=-tid,
                               body={"hfa_n": n})
                       for tid, w in enumerate(w_leaves)]
            if k1 is None:
                for pts in push_ts:
                    kv.worker.wait(pts)
            for tid in range(len(leaves)):
                kv.pull(tid, lambda t, arr: buf.__setitem__(t, arr),
                        priority=-tid)
            kv.wait_all()
            params = unflatten_params(treedef, buf)
            if k1 is None:
                kv.esync_report(0.01, 0.01)
        history.append((float(loss), float(acc)))
    params_out["params"] = params
    return history


def _train(loop, workers, steps=3, **cluster):
    """[(losses, flat parameters, pushed payloads)] of every worker after
    ``steps`` steps of the MLP (FSA: under global Adam)."""
    sim = _sim(workers, **cluster)
    out = {}
    try:
        params, grad_fn = _mlp(0)

        def main(kv, widx):
            sent = []
            _record_pushes(kv, sent)
            if widx == 0 and not cluster.get("use_hfa"):
                kv.set_optimizer({"type": "adam", "lr": 0.05})
            kv.barrier()
            got = {}
            hist = loop(kv, params, grad_fn, _batches(widx, steps), steps,
                        params_out=got)
            out[widx] = ([h[0] for h in hist],
                         flatten_params(got["params"])[0],
                         [np.array(v) for v in sent])

        _run_all(sim, main)
        return [out[i] for i in range(2 * workers)]
    finally:
        sim.shutdown()


def _assert_same_to_the_bit(new, old):
    for (losses, leaves, sent), (old_losses, old_leaves, old_sent) in zip(
            new, old):
        assert losses == old_losses
        assert len(sent) == len(old_sent) > 0
        for a, b in zip(leaves + sent, old_leaves + old_sent):
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("workers,backend",
                         [(1, "numpy"), (1, "jax"), (2, "numpy")],
                         ids=["1w-numpy", "1w-jax", "2w-numpy"])
def test_run_worker_trains_to_the_bit_as_the_loop_that_pulled_last(
        workers, backend):
    new = _train(run_worker, workers, merge_backend=backend)
    old = _train(_run_worker_pulling_last, workers, merge_backend=backend)
    _assert_same_to_the_bit(new, old)
    assert all(losses[-1] < losses[0] for losses, _, _ in new)
    # FSA's oracle: every worker holds the same parameters after a step
    for _, leaves, _ in new[1:]:
        for a, b in zip(leaves, new[0][1]):
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("schedule,workers,steps",
                         [("hfa", 1, 5), ("hfa", 2, 5), ("esync", 2, 1)],
                         ids=["hfa-1w", "hfa-2w", "esync-2w"])
def test_weights_cross_to_the_bit_as_in_the_loops_that_synced_apart(
        schedule, workers, steps):
    """HFA over five steps (two syncs at ``k1`` 2 and a local step left
    over) lands the same weights, and an ESync round pushes and pulls
    the same arrays, as the parent's loops over ``_hfa_sync_round``.
    (Later ESync rounds follow the clock; with three workers a party the
    server's sum follows the order of arrival.)"""
    cluster = CLUSTER[schedule]

    def new_loop(kv, *args, **kw):
        sched = Trainer.schedule_for(
            kv, esync=ESync() if schedule == "esync" else None)
        return run_worker(kv, *args, schedule=sched, **kw)

    def old_loop(kv, *args, **kw):
        return _run_worker_syncing_weights_apart(
            kv, *args, k1=cluster.get("hfa_k1"), **kw)

    new = _train(new_loop, workers, steps, **cluster)
    old = _train(old_loop, workers, steps, **cluster)
    _assert_same_to_the_bit(new, old)
    assert len(new[0][2]) == 4 * (steps // cluster.get("hfa_k1", 1))


def _record_calls(kv, log):
    for name in ("push", "pull", "push_pull"):
        inner = getattr(kv, name)

        def recording(tid, *a, _inner=inner, _name=name, **kw):
            log.append((_name, tid))
            return _inner(tid, *a, **kw)

        setattr(kv, name, recording)


@pytest.mark.parametrize("schedule,workers", [
    ("plain", 1), ("plain", 2), ("p3", 1), ("hfa", 2), ("esync", 1),
    ("overlapped", 1)])
def test_a_tensors_pull_is_issued_before_the_next_tensors_push(
        schedule, workers, monkeypatch):
    """On every worker, whatever the loop and the sync mode: tensor i is
    copied off the device, pushed, and its pull issued before tensor
    i + 1 is touched (under P3 the one push_pull is both).  In the
    sampled round's spans ``worker.pull`` of tensor i starts before
    ``worker.push`` of tensor i + 1, and ``edge.scale`` is recorded only
    where a scaling is left."""
    logs = {}
    inner = training._edge_to_host

    def recording(kv, tid, *a, **kw):
        logs[kv].append(("edge", tid))
        return inner(kv, tid, *a, **kw)

    monkeypatch.setattr(training, "_edge_to_host", recording)
    sim = _sim(workers, trace_sample_every=1, **CLUSTER[schedule])
    try:
        for kv in sim.all_workers():
            logs[kv] = []
            _record_calls(kv, logs[kv])
        if not CLUSTER[schedule].get("use_hfa"):
            sim.worker(0, 0).set_optimizer({"type": "sgd", "lr": 0.1})
        loop = _loop_of(schedule)
        _run_all(sim, lambda kv, i: loop(kv, _batches(i, 2)))
        # the overlapped loop walks backward: the deeper stage first
        order = [2, 3, 0, 1] if schedule == "overlapped" else [0, 1, 2, 3]
        calls = ["push_pull"] if schedule == "p3" else ["push", "pull"]
        for kv in sim.all_workers():
            assert logs[kv] == [(c, t) for t in order
                                for c in ["edge"] + calls]
        assert sim.flush_traces() > 0
        evs = [e for e in sim.trace_collector.merged_events()
               if e["pid"].startswith("worker")]
        assert len({e["pid"] for e in evs}) == 2 * workers
        for pid in {e["pid"] for e in evs}:
            names = {e["name"] for e in evs if e["pid"] == pid}
            assert {"round", "edge.d2h"} <= names
            assert ("edge.scale" in names) == (workers > 1), (pid, names)
            if schedule == "p3":
                continue
            at = {(e["name"], e["args"]["key"]): e["ts"] for e in evs
                  if e["pid"] == pid and e["name"] in
                  ("worker.push", "worker.pull", "edge.d2h")}
            for tid, nxt in zip(order, order[1:]):
                assert (at["edge.d2h", tid] <= at["worker.push", tid]
                        <= at["worker.pull", tid]
                        < at["worker.push", nxt]), (pid, tid, at)
    finally:
        sim.shutdown()


@pytest.mark.parametrize("mode", ["fsa", "hfa", "esync"])
def test_trainer_fit_reaches_every_schedule(mode):
    """``Trainer`` picks the loop's schedule from the cluster's mode and
    the ``ESync`` it is handed, and ``fit`` runs it: under HFA a weight
    exchange every ``Config.hfa_k1`` steps, under ESync a report to the
    state server a round."""
    sim = _sim(parties=1, **CLUSTER.get(mode, {}))
    try:
        kv = sim.worker(0, 0)
        params, grad_fn = _mlp(0)
        rounds = []
        sent = []
        _record_pushes(kv, sent)
        trainer = Trainer(
            kv, params, grad_fn,
            optimizer={"type": "sgd", "lr": 0.1} if mode == "fsa" else None,
            hfa_k1=2 if mode == "hfa" else None,
            esync=ESync(max_local_steps=3, rounds_out=rounds)
            if mode == "esync" else None)
        if mode == "fsa":
            assert trainer.schedule == Schedule()
        else:
            assert trainer.schedule.optimizer is not None
            assert trainer.schedule.k1 == (2 if mode == "hfa" else 1)
            assert (trainer.schedule.esync is not None) == (mode == "esync")
        hist = trainer.fit(iter(_batches(0, 64)), 4)
        if mode == "esync":
            assert len(rounds) == 4 and len(hist) == sum(r for r, _ in rounds)
            assert all(1 <= r <= 3 for r, _ in rounds)
        else:
            assert len(hist) == 4
        assert len(sent) == 4 * (2 if mode == "hfa" else 4)
        flat = flatten_params(trainer.params)[0]
        assert all(np.isfinite(a).all() for a in flat)
    finally:
        sim.shutdown()


@pytest.mark.parametrize("cluster,kw", [
    ({}, {"hfa_k1": 2}),
    ({}, {"esync": ESync()}),
    ({"use_hfa": True, "hfa_k1": 2}, {"hfa_k1": 3})],
    ids=["hfa-on-fsa", "esync-on-fsa", "another-k1"])
def test_trainer_refuses_a_schedule_the_cluster_does_not_run(cluster, kw):
    sim = _sim(parties=1, **cluster)
    try:
        with pytest.raises(ValueError, match="use_hfa"):
            Trainer.schedule_for(sim.worker(0, 0), **kw)
    finally:
        sim.shutdown()
