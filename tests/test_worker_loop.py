"""The worker's own thread (ISSUE 30): a gradient leaf crosses the slice
edge in one pass, and each tensor's pull is issued with its push.

``_edge_to_host`` hands ``kv.push`` the copy off the device as it is
(``scale == 1.0``) or scales on the device before that copy; the plain
branch of ``run_worker`` issues ``pull(i)`` before ``push(i + 1)`` and
trains to the bit as the loop that pulled after the last push did (that
loop is kept below as the reference).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.training import (_edge_to_host, flatten_params, run_worker,
                                unflatten_params)

JOIN_S = 120


def _sim(workers=1, **kw):
    return Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=workers), **kw))


@pytest.mark.parametrize("scale", [1.0, 0.5, 1.0 / 3.0],
                         ids=["one", "half", "third"])
def test_a_leaf_crosses_the_edge_in_one_pass(scale):
    """What reaches the van is the copy off the device itself when there
    is nothing to scale, and ``np.asarray(g) * scale`` to the bit (the
    parent's two passes) when there is."""
    sim = _sim()
    try:
        kv = sim.worker(0, 0)
        g = jax.random.normal(jax.random.PRNGKey(7), (64, 48), jnp.float32)
        kv.init(0, np.zeros(g.shape, np.float32))
        sent = []
        zpush = kv.worker.zpush

        def recording_zpush(kvs, **kw):
            sent.append(kvs.vals)
            return zpush(kvs, **kw)

        kv.worker.zpush = recording_zpush
        host = _edge_to_host(kv, 0, g, scale)
        kv.push(0, host)
        for w in sim.all_workers()[1:]:   # the other party closes the round
            w.push(0, np.zeros(g.shape, np.float32))
        for w in sim.all_workers():
            w.wait_all()
        assert not host.flags.writeable
        assert np.shares_memory(sent[0], host)
        if scale == 1.0:
            assert np.shares_memory(sent[0], np.asarray(g))
        want = np.asarray(g) * scale
        assert want.dtype == host.dtype == np.float32
        assert host.tobytes() == want.tobytes()
    finally:
        sim.shutdown()


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


def _batches(widx, steps):
    rng = np.random.default_rng(100 + widx)
    return [(rng.normal(size=(16, 12)).astype(np.float32),
             rng.integers(0, 4, 16)) for _ in range(steps)]


def _run_worker_pulling_last(kv, params, grad_fn, data_iter, steps,
                             params_out):
    """The parent's plain loop: every push of the step, a host multiply
    by ``scale`` on each, then every pull."""
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


def _train(loop, workers, backend, steps=3):
    """[(losses, flat parameters)] of every worker after ``steps`` FSA
    steps of the MLP under global Adam."""
    sim = _sim(workers, merge_backend=backend)
    out, errors = {}, []
    try:
        params, grad_fn = _mlp(0)

        def main(party, rank, widx):
            try:
                kv = sim.worker(party, rank)
                if widx == 0:
                    kv.set_optimizer({"type": "adam", "lr": 0.05})
                kv.barrier()
                got = {}
                hist = loop(kv, params, grad_fn, _batches(widx, steps),
                            steps, params_out=got)
                out[widx] = ([h[0] for h in hist],
                             flatten_params(got["params"])[0])
            except Exception as e:  # noqa: BLE001 - re-raised below
                errors.append((widx, e))

        threads = [threading.Thread(target=main, args=(p, r, p * workers + r))
                   for p in range(2) for r in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=JOIN_S)
        assert not errors, errors
        assert len(out) == 2 * workers, "a worker hung"
        return [out[i] for i in range(2 * workers)]
    finally:
        sim.shutdown()


@pytest.mark.parametrize("workers,backend",
                         [(1, "numpy"), (1, "jax"), (2, "numpy")],
                         ids=["1w-numpy", "1w-jax", "2w-numpy"])
def test_run_worker_trains_to_the_bit_as_the_loop_that_pulled_last(
        workers, backend):
    new = _train(run_worker, workers, backend)
    old = _train(_run_worker_pulling_last, workers, backend)
    for (losses, leaves), (old_losses, old_leaves) in zip(new, old):
        assert losses == old_losses
        assert losses[-1] < losses[0]
        for a, b in zip(leaves, old_leaves):
            assert a.tobytes() == b.tobytes()
    # FSA's oracle: every worker holds the same parameters after a step
    for _, leaves in new[1:]:
        for a, b in zip(leaves, new[0][1]):
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("workers", [1, 2], ids=["1w", "2w"])
def test_a_tensors_pull_is_issued_before_the_next_tensors_push(workers):
    """In a sampled round ``worker.pull`` of tensor i starts before
    ``worker.push`` of tensor i + 1 on every worker, and ``edge.scale``
    is recorded only where a scaling is left."""
    sim = _sim(workers, trace_sample_every=1)
    try:
        params, grad_fn = _mlp(0)
        n_leaves = len(jax.tree_util.tree_leaves(params))
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        threads = [threading.Thread(
            target=run_worker,
            args=(w, params, grad_fn, _batches(i, 1), 1)) for i, w in
            enumerate(ws)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=JOIN_S)
        assert not any(t.is_alive() for t in threads), "a worker hung"
        assert sim.flush_traces() > 0
        evs = [e for e in sim.trace_collector.merged_events()
               if e["pid"].startswith("worker")]
        for pid in {e["pid"] for e in evs}:
            at = {(e["name"], e["args"]["key"]): e["ts"] for e in evs
                  if e["pid"] == pid and e["name"] in
                  ("worker.push", "worker.pull", "edge.d2h", "edge.scale")}
            for tid in range(n_leaves - 1):
                assert (at["edge.d2h", tid] <= at["worker.push", tid]
                        <= at["worker.pull", tid]
                        < at["worker.push", tid + 1]), (pid, tid, at)
            assert (("edge.scale", 0) in at) == (workers > 1), (pid, at)
    finally:
        sim.shutdown()
