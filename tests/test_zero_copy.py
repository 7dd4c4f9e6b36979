"""Pin the r4 ownership rules that produced the 13x server-merge win
(kvstore/server.py: Message.donated adoption, frozen store aliasing,
copy-on-write at the BSC decode).
These tests pin the MECHANISM — on a faster host a reintroduced copy
would not show up as a wall-clock regression until real scale.
"""

import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation


def _sim(**cfg):
    return Simulation(Config(
        topology=Topology(num_parties=1, workers_per_party=1), **cfg))


def test_pull_response_aliases_frozen_store():
    """The worker-facing pull response must ALIAS the local server's
    stored weights (frozen read-only), not copy them — and the store
    array itself must be frozen so any in-place decode COWs."""
    sim = _sim()
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(1024, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        w.push(0, np.ones(1024, np.float32))
        _ = w.pull_sync(0)
        w.wait_all()
        store_arr = sim.local_servers[0].store[0]
        # serving the pull froze the stored array in place
        assert not store_arr.flags.writeable, (
            "store array not frozen: responses are copying again")
    finally:
        sim.shutdown()


def test_push_up_donates_accumulator_to_global_tier():
    """The local server's push-up transfers ownership: the global tier
    must ADOPT the aggregation buffer (same memory), not copy it."""
    sim = _sim()
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(1024, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 1.0})
        g = np.arange(1024, dtype=np.float32)
        w.push(0, g)
        _ = w.pull_sync(0)
        w.wait_all()
        # SGD's update_scaled builds the new weights IN the donated
        # accumulator; if the global tier had copied the push payload,
        # the arithmetic still works but an extra 4MB/round memcpy is
        # back.  Detect via the value path: new weights = -lr * grad
        # (sum of 1 worker, scale 1/1 party), stored in a buffer built
        # from the donated accum.
        gs = sim.global_servers[0].store[0]
        np.testing.assert_allclose(gs, -g)
        # the local replica ADOPTED the (frozen) global response alias —
        # in-proc they are the same buffer
        ls = sim.local_servers[0].store[0]
        assert np.shares_memory(ls, gs), (
            "pull-down copied instead of adopting the frozen alias")
    finally:
        sim.shutdown()


def test_bsc_decode_copies_on_write_not_in_place():
    """Under pull-direction BSC the local replica is updated by a
    sparse delta; when the current replica is frozen (aliased by
    responses/upstream), the decode must COW — never mutate the frozen
    buffer other readers alias."""
    sim = _sim(compression="bsc")
    try:
        w = sim.all_workers()[0]
        w.init(0, np.zeros(4096, np.float32))
        w.set_optimizer({"type": "sgd", "lr": 0.1})
        w.set_gradient_compression({"type": "bsc", "ratio": 0.05})
        rng = np.random.default_rng(0)
        ls = sim.local_servers[0]
        prev = None
        for _ in range(3):
            w.push(0, rng.standard_normal(4096).astype(np.float32))
            _ = w.pull_sync(0)
            w.wait_all()
            cur = ls.store[0]
            if prev is not None and not prev.flags.writeable:
                # the frozen snapshot from the previous round must be
                # intact — a COW produced a NEW buffer for this round
                assert cur is not prev, "in-place mutation of frozen buf"
            prev = cur
    finally:
        sim.shutdown()
