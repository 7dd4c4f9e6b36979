#!/usr/bin/env bash
# Distributed-tracing demo + CI guard: a short in-proc HiPS simulation
# (2 parties x 2 workers, 1 global server) with trace_sample_every=1,
# training the demo CNN for a few rounds.  Asserts the merged trace is
# non-empty, spans from >= 3 node roles are causally connected, and the
# report gives every round its blocking chain (the labels sum to the
# round's wall time, the chain is never lost) and names the dominant
# stage by it — prints the chain, then leaves
# the artifacts in ${GEOMX_TRACE_DIR:-/tmp/geomx_trace_demo} for
# chrome://tracing / https://ui.perfetto.dev.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export JAX_PLATFORM_NAME=cpu
OUT="${GEOMX_TRACE_DIR:-/tmp/geomx_trace_demo}"
mkdir -p "$OUT"

python - "$OUT" <<'PY'
import sys

import jax
import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.data import ShardedIterator, synthetic_classification
from geomx_tpu.kvstore import Simulation
from geomx_tpu.models import create_cnn_state
from geomx_tpu.training import run_worker

out_dir = sys.argv[1]
sim = Simulation(Config(topology=Topology(num_parties=2,
                                          workers_per_party=2),
                        trace_sample_every=1,
                        # a dump is asked for: the collector keeps the
                        # merged timeline (and shutdown leaves the
                        # report here too)
                        trace_dir=out_dir))
try:
    ws = sim.all_workers()
    ws[0].set_optimizer({"type": "sgd", "lr": 0.05})
    x, y = synthetic_classification(n=256, shape=(8, 8, 1), seed=0)
    _, params, grad_fn = create_cnn_state(jax.random.PRNGKey(0),
                                          input_shape=(1, 8, 8, 1))
    import threading

    steps = 4
    ths = [threading.Thread(target=run_worker, args=(
        kv, params, grad_fn,
        ShardedIterator(x, y, 16, i, len(ws)), steps),
        kwargs={"barrier_init": False}) for i, kv in enumerate(ws)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(240)
    assert not any(t.is_alive() for t in ths), "training hung"

    n = sim.flush_traces()
    assert n > 0, "merged trace is EMPTY"
    trace = sim.dump_trace(f"{out_dir}/geomx_trace.json")
    evs = trace["traceEvents"]
    roles = {e["pid"].split(":")[0] for e in evs}
    assert {"worker", "server", "global_server"} <= roles, roles
    ids = {e["args"]["span"] for e in evs}
    dangling = [e for e in evs
                if e["args"]["parent"] and e["args"]["parent"] not in ids]
    assert not dangling, f"{len(dangling)} dangling parent edges"
    report = sim.trace_report()
    assert report["rounds"], "critical-path report has no rounds"
    for r in report["rounds"]:
        assert r["dominant_stage"], r
        assert sum(r["path"].values()) == r["wall_us"] > 0, r
        assert "chain_lost_at" not in r, r["chain_lost_at"]
    print(sim.trace_collector.report_text())
    print(f"OK: {len(evs)} events across {len(roles)} roles, "
          f"{len(report['rounds'])} rounds -> {out_dir}/geomx_trace.json")
finally:
    sim.shutdown()
PY
