"""Worker-side training loop gluing JAX compute to the HiPS kvstore.

Reproduces the reference hot loop (ref: examples/cnn.py:112-126 —
autograd → per-layer kv.push(grad, priority=-idx) → kv.pull → next step),
with the device↔host handoff at the slice edge: grads leave jit as numpy,
pulls come back and are re-wrapped as jax arrays.  Per-layer priorities
mean shallow layers jump the send queue under P3 exactly like the
reference's engine priorities.
"""

from __future__ import annotations

from typing import Callable, Iterable, List, Optional, Tuple

import jax
import numpy as np

from geomx_tpu.kvstore.client import WorkerKVStore


def save_params(path: str, params) -> None:
    """Client-side parameter checkpoint (ref: gluon save_parameters /
    Module save_checkpoint — python/mxnet/gluon/block.py,
    module/module.py).  Atomic write; msgpack via flax serialization, so
    the tree structure restores without a template."""
    from flax import serialization

    from geomx_tpu.utils.io import atomic_write

    data = serialization.msgpack_serialize(
        jax.tree_util.tree_map(np.asarray, params))
    with atomic_write(path) as f:
        f.write(data)


def load_params(path: str):
    """Inverse of :func:`save_params`."""
    from flax import serialization

    with open(path, "rb") as f:
        return serialization.msgpack_restore(f.read())


def _preempt_noticed(kv) -> bool:
    """True once a spot-preemption notice landed on this worker
    (Control.PREEMPT_NOTICE / the launch.py SIGTERM mapping): the
    training loops poll it at every step boundary — the noticed worker
    finishes its in-flight step, then stops pushing so the drain can
    flush and leave gracefully.  One attribute load + Event check."""
    ev = getattr(kv, "preempt_noticed", None)
    return ev is not None and ev.is_set()


def flatten_params(params) -> Tuple[List[np.ndarray], object]:
    leaves, treedef = jax.tree_util.tree_flatten(params)
    return [np.asarray(x) for x in leaves], treedef


def unflatten_params(treedef, arrs: List[np.ndarray]):
    return jax.tree_util.tree_unflatten(treedef, [jax.numpy.asarray(a) for a in arrs])


def run_worker_hfa(
    kv: WorkerKVStore,
    params,
    grad_fn: Callable,
    data_iter: Iterable,
    steps: int,
    k1: int = 2,
    optimizer=None,
    barrier_init: bool = True,
    log_fn: Optional[Callable[[int, float, float], None]] = None,
    params_out: Optional[dict] = None,
    measure=None,
) -> List[Tuple[float, float]]:
    """HFA client loop (ref: examples/cnn_hfa.py): each worker runs a LOCAL
    optimizer for k1 steps, then pushes weight/num_workers (the local server
    averages weights; every k2-th sync the milestone delta crosses the WAN).
    """
    import optax

    from geomx_tpu.utils.measure import Measure

    m = measure if measure is not None else Measure()
    if optimizer is None:
        optimizer = optax.adam(1e-2)
    leaves, treedef = flatten_params(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=barrier_init)
    params = unflatten_params(treedef, leaves)
    opt_state = optimizer.init(params)
    history: List[Tuple[float, float]] = []
    buf: List[Optional[np.ndarray]] = [None] * len(leaves)

    for step, (x, y) in enumerate(data_iter):
        if step >= steps or _preempt_noticed(kv):
            break
        m.step_start()
        with m.phase("grad"):
            loss, acc, grads = grad_fn(params, x, y)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            import optax as _optax

            params = _optax.apply_updates(params, updates)
        if (step + 1) % k1 == 0:
            params, _ = _hfa_sync_round(kv, params, treedef, len(leaves),
                                        buf, m)
        m.step_end()
        history.append((float(loss), float(acc)))
        if log_fn is not None:
            log_fn(step, float(loss), float(acc))
    if params_out is not None:
        params_out["params"] = params
    return history


def _hfa_sync_round(kv, params, treedef, n_leaves, buf, m,
                    measure_comm: bool = False):
    """One weight-exchange sync: push party-mean weights, pull the
    merged result (shared by the HFA and ESync loops — one place for
    the push normalization and pull-into-buf pattern).

    Returns ``(params, comm_s)``.  ``comm_s`` (only when
    ``measure_comm``) is the TRANSMISSION time: the server acks each
    push on receipt, so waiting on push acks measures the uplink — the
    pull barrier below it is the straggler wait ESync exists to
    eliminate, and counting it as comm would feed the wait back into
    the plan and pin every fast worker at min_steps."""
    import time as _time

    w_leaves, _ = jax.tree_util.tree_flatten(params)
    comm_s = None
    # re-read the party size EVERY sync: dynamic join/leave moves it
    # mid-training (membership broadcast -> kv.num_workers), and the
    # denominator each push used is announced as ``hfa_n`` so the
    # server can renormalize a transition round's mixed-scale mean
    n = kv.num_workers
    t1 = _time.perf_counter()
    with m.phase("push"):
        push_ts = [kv.push(tid, np.asarray(w) / n, priority=-tid,
                           body={"hfa_n": n})
                   for tid, w in enumerate(w_leaves)]
        if measure_comm:
            for pts in push_ts:
                kv.worker.wait(pts)
            comm_s = _time.perf_counter() - t1
        for tid in range(n_leaves):
            kv.pull(tid, lambda t, arr: buf.__setitem__(t, arr),
                    priority=-tid)
    with m.phase("pull_wait"):
        kv.wait_all()
    return unflatten_params(treedef, buf), comm_s


def build_flagship_lm():
    """One shared builder for the flagship LM workload (>=10 M params)
    so the TCP acceptance run (launch.py --workload lm) and the bench's
    lm child train the IDENTICAL step — a size tweak applied to one
    cannot silently diverge the other.  Size via GEOMX_LM_* env.
    Returns ``(cfg, params, n_params, grad_fn, data)``."""
    import os

    import jax
    import numpy as np

    from geomx_tpu.data import synthetic_lm
    from geomx_tpu.models.transformer import (
        TransformerConfig, init_params, make_lm_grad_fn)

    def _e(name, dflt):
        return int(os.environ.get(name, dflt))

    moe_experts = _e("GEOMX_LM_MOE_EXPERTS", 0)
    cfg = TransformerConfig(
        vocab=_e("GEOMX_LM_VOCAB", 8192),
        d_model=_e("GEOMX_LM_DMODEL", 384),
        n_heads=_e("GEOMX_LM_HEADS", 6),
        n_layers=_e("GEOMX_LM_LAYERS", 4),
        d_ff=_e("GEOMX_LM_DFF", 1536),
        max_seq=_e("GEOMX_LM_SEQ", 128),
        attn_impl="fast",
        # GEOMX_LM_MOE_EXPERTS > 0 makes every 2nd layer a top-k routed
        # MoE (real EP) — the flagship's expert gradients then ride the
        # same PS stack as the dense leaves.  top_k clamps to the expert
        # count (top_k > E would raise an opaque trace-time error from
        # lax.top_k inside every worker)
        moe_every=2 if moe_experts > 0 else 0,
        n_experts=max(moe_experts, 1),
        moe_top_k=(min(_e("GEOMX_LM_MOE_TOP_K", 2), moe_experts)
                   if moe_experts > 0 else 0),
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    grad_fn = make_lm_grad_fn(cfg)
    data = synthetic_lm(n=512, seq=cfg.max_seq, vocab=cfg.vocab, seed=0)
    return cfg, params, n_params, grad_fn, data


def run_worker_esync(
    kv: WorkerKVStore,
    params,
    grad_fn: Callable,
    data_iter: Iterable,
    rounds: int,
    optimizer=None,
    barrier_init: bool = True,
    log_fn: Optional[Callable[[int, float, float], None]] = None,
    params_out: Optional[dict] = None,
    max_local_steps: int = 64,
    measure=None,
    rounds_out: Optional[list] = None,
) -> List[Tuple[float, float]]:
    """ESync client loop (geomx_tpu.sched.esync; ref README.md:45 — the
    reference's planned-but-unintegrated straggler balancer, ESync
    TSC'20).

    Like HFA, each worker runs a LOCAL optimizer and pushes mean weights
    at every sync — but the number of local steps between syncs is
    assigned per worker per round by the party's state server, which
    balances reach-server time across heterogeneous workers: fast
    workers fill the slowest worker's round with extra local progress
    instead of idling at the barrier.

    ``rounds`` counts SYNC rounds, identical on every worker of the
    party (one push per worker per round keeps the HFA merge in
    lockstep; a per-worker local-step budget would deadlock the party
    when fast workers exhausted it in fewer rounds).  Local step counts
    per round vary per worker.  ``data_iter`` should yield enough
    batches (up to rounds × max_local_steps) or be cyclic; if it runs
    dry the worker still pushes each remaining round.  Requires HFA mode
    on the servers (weights, not gradients, cross the tiers;
    Config.use_hfa / SET_HFA).
    """
    import time as _time

    import optax

    from geomx_tpu.utils.measure import Measure

    m = measure if measure is not None else Measure()
    if optimizer is None:
        optimizer = optax.adam(1e-2)
    leaves, treedef = flatten_params(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=barrier_init)
    params = unflatten_params(treedef, leaves)
    opt_state = optimizer.init(params)
    history: List[Tuple[float, float]] = []
    buf: List[Optional[np.ndarray]] = [None] * len(leaves)

    it = iter(data_iter)
    local_steps = 1  # until the state server has a plan
    loss = acc = 0.0
    for _round in range(rounds):
        if _preempt_noticed(kv):
            break
        m.step_start()
        t0 = _time.perf_counter()
        ran = 0
        with m.phase("grad"):
            for _ in range(local_steps):
                try:
                    x, y = next(it)
                except StopIteration:
                    break
                loss, acc, grads = grad_fn(params, x, y)
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
                ran += 1
                history.append((float(loss), float(acc)))
        step_s = (_time.perf_counter() - t0) / max(ran, 1)
        params, comm_s = _hfa_sync_round(kv, params, treedef, len(leaves),
                                         buf, m, measure_comm=True)
        m.step_end()
        if rounds_out is not None:
            # acceptance observable: (assigned local steps, reach-server
            # seconds) per round — heterogeneous workers must receive
            # different assignments and their reach spread must shrink
            rounds_out.append((ran, round(step_s * ran + comm_s, 4)))
        if ran > 0:
            # a dry data iterator (ran == 0) must not report: its
            # near-zero "step time" would make the planner believe this
            # worker is infinitely fast, collapse the reach-time target,
            # and pin every worker that still has data at min_steps
            local_steps = kv.esync_report(step_s, comm_s,
                                          max_steps=max_local_steps)
        if log_fn is not None:
            log_fn(_round, float(loss), float(acc))
    if params_out is not None:
        params_out["params"] = params
    return history


class Trainer:
    """High-level fit/evaluate facade over the worker loop.

    The reference's user surface is ``gluon.Trainer`` + ``Module.fit``
    (ref: python/mxnet/gluon/trainer.py; module/base_module.py:410 fit —
    bind/init/optimizer/metric handled for the user).  This wraps the
    same ceremony: rank-0 control-plane configuration (optimizer to the
    global tier, compression to the party server), init barrier, the
    training loop (plain FSA or HFA), and streaming-metric evaluation.
    """

    def __init__(self, kv: WorkerKVStore, params, grad_fn: Callable,
                 model=None, optimizer: Optional[dict] = None,
                 compression: Optional[dict] = None,
                 hfa_k1: Optional[int] = None):
        self.kv = kv
        self.params = params
        self.grad_fn = grad_fn
        self.model = model  # flax module; needed for evaluate()
        self.hfa_k1 = hfa_k1
        if (hfa_k1 is not None) != bool(kv.config.use_hfa):
            # the HFA client loop pushes WEIGHTS, the plain loop pushes
            # GRADIENTS — a mismatch with the servers' mode silently
            # corrupts training (weights fed to the optimizer as grads)
            raise ValueError(
                "hfa_k1 must be set if and only if the cluster runs with "
                f"use_hfa (got hfa_k1={hfa_k1!r}, "
                f"config.use_hfa={kv.config.use_hfa})")
        if kv.party == 0 and kv.rank == 0 and optimizer is not None:
            kv.set_optimizer(optimizer)
        if kv.rank == 0 and compression is not None:
            kv.set_gradient_compression(compression)
        kv.barrier()

    def fit(self, data_iter: Iterable, steps: int,
            log_fn: Optional[Callable[[int, float, float], None]] = None,
            measure=None,
            ) -> List[Tuple[float, float]]:
        """Train; returns [(loss, acc)] per step.  Updated params stay on
        the trainer for evaluate()/further fits.  Pass a
        ``utils.Measure`` to collect the per-phase timing report
        (ref: examples/utils.py:120-192)."""
        captured: dict = {}
        if self.hfa_k1 is not None:
            hist = run_worker_hfa(self.kv, self.params, self.grad_fn,
                                  data_iter, steps, k1=self.hfa_k1,
                                  log_fn=log_fn, params_out=captured,
                                  measure=measure)
        else:
            hist = run_worker(self.kv, self.params, self.grad_fn,
                              data_iter, steps, log_fn=log_fn,
                              params_out=captured, measure=measure)
        if "params" in captured:
            self.params = captured["params"]
        return hist

    def save(self, path: str) -> None:
        """Persist the current params (ref: Module save_checkpoint)."""
        save_params(path, self.params)

    def load(self, path: str) -> None:
        """Restore params AND propagate them to the servers (overwrite
        init) — on an already-initialized cluster a local-only load
        would be silently discarded at the first sync.

        Call collectively on every worker of every party, between fits
        (fit() completes all its rounds before returning, so nothing is
        in flight then).  The barrier is party-local; across parties the
        overwrites commute because every party restores the same file —
        the worst cross-party race discards one racing round's gradient
        (equivalent to joining that round one step late)."""
        self.params = load_params(path)
        leaves, _ = flatten_params(self.params)
        self.kv.init_all(dict(enumerate(leaves)), overwrite=True)
        self.kv.barrier()

    def evaluate(self, data_iter: Iterable, batches: int, metric=None):
        """Forward `batches` batches through the model, streaming
        (labels, probabilities) into `metric` (default Accuracy);
        returns ``metric.get()`` — the reference's Module.score
        (ref: module/base_module.py score + metric.py).  Logits are
        softmaxed before the metric so probability-contract metrics
        (CrossEntropy) are correct; argmax metrics are unaffected."""
        from geomx_tpu.utils import metrics as _metrics

        if self.model is None:
            raise ValueError("evaluate() needs the model; pass it to "
                             "Trainer(model=...)")
        if metric is None:
            metric = _metrics.Accuracy()
        for i, (x, y) in enumerate(data_iter):
            if i >= batches:
                break
            logits = self.model.apply(self.params, x)
            probs = np.asarray(jax.nn.softmax(logits, axis=-1))
            metric.update(np.asarray(y), probs)
        return metric.get()


def _edge_to_host(kv: WorkerKVStore, tid: int, g,
                  scale: float) -> np.ndarray:
    """One gradient leaf across the slice edge, in ONE pass: the copy
    off the device (``edge.d2h``) and nothing after it.  A ``scale``
    other than 1.0 (more than one worker a party) is applied on the
    device before that copy (``edge.scale``: one elementwise program a
    leaf), never as a second pass over the host copy; with ``scale ==
    1.0`` no program is launched and no ``edge.scale`` span recorded.

    The result is read-only and is the host value jax caches on the
    array it was copied from: nothing writes to it again.  ``kv.push``
    sends a view of it (the aliasing contract of
    :meth:`WorkerKVStore.push`), and that view's ``base`` is what keeps
    the buffer alive: until the ack through the in-flight message the
    worker keeps for replay, and past it through the reference the
    server's staged H2D holds until its transfer completes.  The caller
    may drop ``g`` as soon as it has pushed."""
    if scale != 1.0:
        with kv.trace_span("edge.scale", key=tid,
                           nbytes=getattr(g, "nbytes", None)):
            g = g * scale
    with kv.trace_span("edge.d2h", key=tid,
                       nbytes=getattr(g, "nbytes", None)):
        return np.asarray(g)


def run_worker(
    kv: WorkerKVStore,
    params,
    grad_fn: Callable,
    data_iter: Iterable,
    steps: int,
    normalize: bool = True,
    barrier_init: bool = True,
    log_fn: Optional[Callable[[int, float, float], None]] = None,
    params_out: Optional[dict] = None,
    measure=None,
) -> List[Tuple[float, float]]:
    """Train `steps` steps; returns [(loss, acc), ...] per step.

    Under FSA the returned params after each step are identical on every
    worker (the convergence oracle the acceptance tests assert).

    ``measure`` (utils.Measure) brackets each phase — grad compute /
    push / pull-wait — per step, the reference examples' per-phase
    timing report (ref: examples/utils.py:120-192).
    """
    from geomx_tpu.utils.measure import Measure

    m = measure if measure is not None else Measure()
    leaves, treedef = flatten_params(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=barrier_init)
    params = unflatten_params(treedef, leaves)
    # grads are summed across the party then averaged over parties at the
    # global server; pre-divide by party size so the update is the all-worker
    # mean (the reference examples normalize client-side the same way,
    # ref: examples/cnn_hfa.py pushes param/num_local_workers)
    history: List[Tuple[float, float]] = []
    buf: List[Optional[np.ndarray]] = [None] * len(leaves)

    for step, (x, y) in enumerate(data_iter):
        if step >= steps or _preempt_noticed(kv):
            break
        # re-read per step: dynamic join/leave changes the party size
        # mid-training (the server broadcasts the new count, the client
        # hook updates kv.num_workers) — a scale frozen at start would
        # weight this worker's contribution wrongly after a membership
        # change
        scale = 1.0 / kv.num_workers if normalize else 1.0
        m.step_start()
        # the whole step under one sampled root span (no-op unless
        # Config.trace_sample_every hits this round): every push/pull the
        # step issues joins the round's cross-node trace
        with kv.trace_round(step):
            with m.phase("grad"):
                loss, acc, grads = grad_fn(params, x, y)
                g_leaves = jax.tree_util.tree_leaves(grads)
                # g_leaves alone holds the gradient from here on, so
                # that the plain branch below can let each leaf's device
                # buffer go as soon as its copy is off the chip
                del grads
                # block HERE so the phase split is honest: jax dispatch
                # is async, and without this the whole backward pass
                # would be billed to the push phase's first np.asarray
                # (the plain loop converts leaf-by-leaf right below
                # anyway, so this does not change the schedule; the
                # staged OVERLAP loop — overlap.py — is the path that
                # interleaves, not this one)
                jax.block_until_ready(g_leaves)
            with m.phase("push"):
                if kv.ts_push is not None:
                    # TS push direction: worker-to-worker merge tree; the
                    # elected holder pushes the merged set for the party
                    kv.ts_merge_push({tid: _edge_to_host(kv, tid, g, scale)
                                      for tid, g in enumerate(g_leaves)})
                    for tid in range(len(leaves)):
                        kv.pull(tid,
                                lambda t, arr: buf.__setitem__(t, arr),
                                priority=-tid)
                elif kv.config.enable_p3:
                    # P3: sliced push+pull, values ride the response
                    for tid, g in enumerate(g_leaves):
                        kv.push_pull(tid, _edge_to_host(kv, tid, g, scale),
                                     lambda t, arr: buf.__setitem__(t, arr),
                                     priority=-tid)
                else:
                    # each tensor's pull right behind its push: the
                    # client holds the pull back until that push is
                    # acked (``after_ts``), so the early tensors come
                    # back and are decoded on the response thread while
                    # this thread still copies the later ones off the
                    # device
                    for tid in range(len(leaves)):
                        kv.push(tid, _edge_to_host(kv, tid, g_leaves.pop(0),
                                                   scale), priority=-tid)
                        kv.pull(tid,
                                lambda t, arr: buf.__setitem__(t, arr),
                                priority=-tid)
            with m.phase("pull_wait"):
                kv.wait_all()
        params = unflatten_params(treedef, buf)  # type: ignore[arg-type]
        m.step_end()
        history.append((float(loss), float(acc)))
        if log_fn is not None:
            log_fn(step, float(loss), float(acc))
    if params_out is not None:
        params_out["params"] = params
    return history
