"""Compute/communication overlap: the staged (P3-style) worker loop.

The reference's defining perf mechanism is that every kvstore push/pull
is a dependency-engine op with a per-layer priority, so round-r
communication overlaps round-r(+1) compute: layer-N's push starts the
moment its gradient exists mid-backward, and next-step forward begins
as soon as shallow layers' pulls land (ref: include/mxnet/engine.h:153-263
PushAsync w/ priority; kvstore_dist.h:355-363 P3 fake pull;
threadsafe_queue.h:49-58 priority send queue).

XLA has no cross-step engine — under ``jit`` the whole train step is one
compiled computation and gradients only become visible at its end.  The
TPU-native equivalent splits the model into **stages** (each a
jit-compiled segment) and chains their VJPs from Python:

- **forward walk**: stage *i* blocks only on *its own* pulled params, so
  shallow stages compute while deep params are still crossing the WAN;
- **backward walk**: stage *i*'s gradient is pushed the instant its VJP
  returns, so the uplink transmits deep grads while shallow VJPs are
  still computing, and under P3's priority queue shallow grads jump any
  queued deep slices at the end of backward.

The kvstore aggregates / pushes up / pulls down **per key** (explicit
per-key state machines in ``kvstore/server.py``), so stage granularity
propagates through both tiers end-to-end: each stage's round completes
independently of the others.

Backward segments recompute their stage's forward (rematerialization) —
the standard TPU trade of FLOPs for memory; gradients are bit-identical
to monolithic autodiff because chained VJPs *are* the chain rule.

Overlap is only measurable when transmissions contend: see
``FaultPolicy(wan_bandwidth_bps=...)`` which serializes each sender's
uplink in the simulator.
"""

from __future__ import annotations

import threading
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from geomx_tpu.kvstore.client import WorkerKVStore
from geomx_tpu.training import (_exchange, flatten_params,
                                unflatten_params)


class StagedModel:
    """A model split into jit-compiled forward/backward segments.

    ``stage_fns[i]`` is a pure function ``(stage_params, x) -> x``; the
    last stage's output feeds ``loss_fn(logits, y) -> (loss, aux)``
    (aux is typically accuracy).  Gradients of the chained stages equal
    monolithic autodiff exactly.
    """

    def __init__(self, stage_fns: Sequence[Callable],
                 loss_fn: Callable):
        self.stage_fns = list(stage_fns)
        self.n = len(self.stage_fns)
        self._fwd = [jax.jit(f) for f in self.stage_fns]
        # bwd recomputes the stage forward (remat) so each segment is a
        # self-contained jit: (params, x_in, g_out) -> (g_params, g_x_in)
        self._bwd = [
            jax.jit(lambda p, x, g, f=f: jax.vjp(f, p, x)[1](g))
            for f in self.stage_fns
        ]
        # d(loss)/d(logits) + (loss, aux) in one segment
        def _loss_grad(logits, y):
            (loss, aux), g = jax.value_and_grad(
                loss_fn, has_aux=True)(logits, y)
            return loss, aux, g

        self._loss_grad = jax.jit(_loss_grad)

    def forward(self, stage_params: Sequence, x,
                pre_stage: Optional[Callable[[int], None]] = None):
        """Run the staged forward; returns (logits, residuals).
        ``pre_stage(i)`` runs before stage i — the overlap hook where the
        worker loop blocks on stage i's pulled params."""
        residuals = []
        for i in range(self.n):
            if pre_stage is not None:
                pre_stage(i)
            residuals.append((stage_params[i], x))
            x = self._fwd[i](stage_params[i], x)
        return x, residuals

    def backward(self, residuals, g_out,
                 on_stage_grad: Callable[[int, object], None]):
        """Walk VJPs deepest-first; ``on_stage_grad(i, g_params)`` fires
        the moment stage i's gradient exists (the push hook)."""
        for i in reversed(range(self.n)):
            p, x_in = residuals[i]
            g_params, g_out = self._bwd[i](p, x_in, g_out)
            on_stage_grad(i, g_params)

    def loss_and_logit_grad(self, logits, y):
        return self._loss_grad(logits, y)


class _StagePullTracker:
    """Round-counted arrival tracking: one pull per stage per round."""

    def __init__(self, n_stages: int):
        self._cv = threading.Condition()
        self._rounds = [0] * n_stages

    def arrived(self, stage: int):
        with self._cv:
            self._rounds[stage] += 1
            self._cv.notify_all()

    def wait(self, stage: int, round_no: int, timeout: float = 120.0):
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._rounds[stage] >= round_no, timeout=timeout)
        if not ok:
            raise TimeoutError(
                f"stage {stage} params for round {round_no} never arrived")


def run_worker_overlapped(
    kv: WorkerKVStore,
    model: StagedModel,
    stage_params: Sequence,
    data_iter: Iterable,
    steps: int,
    barrier_init: bool = True,
    log_fn: Optional[Callable[[int, float, float], None]] = None,
    params_out: Optional[dict] = None,
) -> List[Tuple[float, float]]:
    """The overlapped counterpart of ``training.run_worker``.

    Semantics are identical to the BSP loop (FSA: every worker holds the
    same params each round); only the schedule differs — pushes stream
    during backward, pulls gate the next forward per stage.  What
    crosses the slice edge, and how, is ``training._exchange``, a stage
    at a time.
    """
    n = model.n
    # tid assignment: stage i's leaves get consecutive ids, stage-major,
    # so priority=-tid means shallow stages outrank deep ones (ref:
    # examples/cnn.py:121 priority=-idx)
    flats: List[List[np.ndarray]] = []
    treedefs = []
    stage_tids: List[List[int]] = []
    tid = 0
    for p in stage_params:
        leaves, td = flatten_params(p)
        flats.append(leaves)
        treedefs.append(td)
        stage_tids.append(list(range(tid, tid + len(leaves))))
        tid += len(leaves)
    for i in range(n):
        for t, leaf in zip(stage_tids[i], flats[i]):
            kv.init(t, leaf, barrier=False)
    if barrier_init:
        kv.barrier()
    stage_params = [
        jax.tree_util.tree_unflatten(td, leaves)
        for td, leaves in zip(treedefs, flats)
    ]

    scale = 1.0 / kv.num_workers
    tracker = _StagePullTracker(n)
    pulled: dict = {}  # tid -> np.ndarray

    def _mk_cb(stage: int, want: int):
        got = []

        def cb(t, arr):
            pulled[t] = arr
            got.append(t)
            if len(got) == want:
                tracker.arrived(stage)

        return cb

    def _push_and_pull_stage(i: int, g_params):
        g_leaves = jax.tree_util.tree_leaves(g_params)
        _exchange(kv, stage_tids[i], g_leaves, _mk_cb(i, len(g_leaves)),
                  scale=scale)

    def _adopt_stage(i: int):
        # under the NEXT step's root (a stage's pulls gate that step's
        # forward): ``worker.wait`` until the stage's pulls are in,
        # ``edge.h2d`` for their way back onto the chip
        with kv.trace_span("worker.wait", key=stage_tids[i][0]):
            tracker.wait(i, round_no)
        arrs = [pulled[t] for t in stage_tids[i]]
        with kv.trace_span("edge.h2d", of=lambda: {
                "nbytes": sum(a.nbytes for a in arrs)}):
            stage_params[i] = unflatten_params(treedefs[i], arrs)

    history: List[Tuple[float, float]] = []
    round_no = 0
    for step, (x, y) in enumerate(data_iter):
        if step >= steps:
            break

        def pre_stage(i: int):
            if round_no > 0:
                _adopt_stage(i)

        with kv.trace_round(step):
            logits, residuals = model.forward(stage_params, x,
                                              pre_stage=pre_stage)
            loss, acc, g_logits = model.loss_and_logit_grad(logits, y)
            model.backward(residuals, g_logits, _push_and_pull_stage)
        round_no += 1
        history.append((float(loss), float(acc)))
        if log_fn is not None:
            log_fn(step, float(loss), float(acc))

    # drain the final round so callers observe the synced params
    # (round_no == 0 means the iterator yielded nothing: no pulls exist)
    if round_no > 0:
        for i in range(n):
            _adopt_stage(i)
    kv.wait_all()
    if params_out is not None:
        params_out["params"] = list(stage_params)
    return history
