"""Worker-side training loop gluing JAX compute to the HiPS kvstore.

Reproduces the reference hot loop (ref: examples/cnn.py:112-126 —
autograd → per-layer kv.push(grad, priority=-idx) → kv.pull → next step),
with the device↔host handoff at the slice edge: grads leave jit as numpy,
pulls come back and are re-wrapped as jax arrays.  Per-layer priorities
mean shallow layers jump the send queue under P3 exactly like the
reference's engine priorities.

One loop (:func:`run_worker`) and one exchange across the slice edge
(:func:`_exchange`) serve every sync mode: what differs between FSA /
MixedSync, HFA and ESync is the :class:`Schedule` the loop is given,
picked in one place (:meth:`Trainer.schedule_for`).
"""

from __future__ import annotations

import contextlib
import itertools
import time
from typing import (Callable, Iterable, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import numpy as np

from geomx_tpu.kvstore.client import WorkerKVStore
from geomx_tpu.kvstore.keys import DENSE, leaf_groups
from geomx_tpu.trace.recorder import _NULL_SPAN


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


def build_flagship_lm():
    """The builder of the LM workload (>=10 M params) that the TCP
    acceptance run trains (launch.py --workload lm).  Size via
    GEOMX_LM_* env.
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


class ESync(NamedTuple):
    """What a caller hands :class:`Trainer` to train under ESync
    (geomx_tpu.sched.esync; ref README.md:45 — the reference's
    planned-but-unintegrated straggler balancer, ESync TSC'20): the
    party's state server assigns each worker its local steps a round, up
    to ``max_local_steps``, so that fast workers fill the slowest
    worker's round with local progress instead of idling at the barrier.
    ``rounds_out`` collects ``(local steps run, reach-server seconds)`` a
    round: heterogeneous workers must receive different assignments and
    their reach spread must shrink (the acceptance observable)."""

    max_local_steps: int = 64
    rounds_out: Optional[list] = None


class Schedule(NamedTuple):
    """What :func:`run_worker` is told about a sync mode; every other
    statement of the loop is the same for all of them.  The default is
    FSA / MixedSync: the step's gradients cross the slice edge every
    step, times ``1 / party size``, and the global tier runs the
    optimizer.  :meth:`Trainer.schedule_for` picks one."""

    # the worker's own optimizer (optax): when set, every step ends with
    # a local update and WEIGHTS cross, divided by the party size (the
    # local server averages them; HFA and ESync)
    optimizer: Optional[object] = None
    # an exchange after every k1-th step (HFA's local steps between syncs)
    k1: int = 1
    # the steps of a round come from the party's state server
    esync: Optional[ESync] = None


class Trainer:
    """High-level fit/evaluate facade over the worker loop.

    The reference's user surface is ``gluon.Trainer`` + ``Module.fit``
    (ref: python/mxnet/gluon/trainer.py; module/base_module.py:410 fit —
    bind/init/optimizer/metric handled for the user).  This wraps the
    same ceremony: rank-0 control-plane configuration (optimizer to the
    global tier, compression to the party server), init barrier, the
    training loop under the cluster's sync mode, and streaming-metric
    evaluation.
    """

    def __init__(self, kv: WorkerKVStore, params, grad_fn: Callable,
                 model=None, optimizer: Optional[dict] = None,
                 compression: Optional[dict] = None,
                 hfa_k1: Optional[int] = None,
                 esync: Optional[ESync] = None):
        self.kv = kv
        self.params = params
        self.grad_fn = grad_fn
        self.model = model  # flax module; needed for evaluate()
        self.schedule = self.schedule_for(kv, hfa_k1, esync)
        if kv.party == 0 and kv.rank == 0 and optimizer is not None:
            kv.set_optimizer(optimizer)
        if kv.rank == 0 and compression is not None:
            kv.set_gradient_compression(compression)
        kv.barrier()

    @staticmethod
    def schedule_for(kv: WorkerKVStore, hfa_k1: Optional[int] = None,
                     esync: Optional[ESync] = None) -> Schedule:
        """The one place a sync mode becomes the loop's schedule, from
        what the worker can observe: servers in HFA mode
        (``Config.use_hfa``) average WEIGHTS, every ``Config.hfa_k1``
        local steps, or after as many as the state server assigns when
        the caller hands an :class:`ESync`; every other cluster takes
        GRADIENTS every step.  ``hfa_k1`` is a caller's own statement of
        the mode (the benchmark's traffic files carry one) and has to
        agree with the cluster: weights fed to the global optimizer as
        gradients, or gradients averaged as weights, corrupt training in
        silence."""
        cfg = kv.config
        if hfa_k1 is not None and (not cfg.use_hfa or hfa_k1 != cfg.hfa_k1):
            raise ValueError(
                f"hfa_k1={hfa_k1!r} disagrees with the cluster "
                f"(config.use_hfa={cfg.use_hfa}, "
                f"config.hfa_k1={cfg.hfa_k1})")
        if esync is not None and not cfg.use_hfa:
            raise ValueError("ESync exchanges weights: the cluster has to "
                             "run with use_hfa")
        if not cfg.use_hfa:
            return Schedule()
        import optax

        return Schedule(optimizer=optax.adam(1e-2),
                        k1=1 if esync is not None else cfg.hfa_k1,
                        esync=esync)

    def fit(self, data_iter: Iterable, steps: int,
            log_fn: Optional[Callable[[int, float, float], None]] = None,
            measure=None,
            ) -> List[Tuple[float, float]]:
        """Train; returns [(loss, acc)] per step.  Updated params stay on
        the trainer for evaluate()/further fits.  Pass a
        ``utils.Measure`` to collect the per-phase timing report
        (ref: examples/utils.py:120-192)."""
        captured: dict = {}
        hist = run_worker(self.kv, self.params, self.grad_fn, data_iter,
                          steps, log_fn=log_fn, params_out=captured,
                          measure=measure, schedule=self.schedule)
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


def _edge_to_host(kv: WorkerKVStore, tid: int, g, scale: float,
                  divide: bool = False) -> np.ndarray:
    """One leaf across the slice edge, in ONE pass: the copy off the
    device (``edge.d2h``) and nothing after it.  A ``scale`` other than
    1 (more than one worker a party) is applied on the device before
    that copy (``edge.scale``: one elementwise program a leaf), never as
    a second pass over the host copy: a gradient is multiplied by it, a
    weight divided (``divide``; the two differ in the last bit where the
    party size is not a power of two).  With ``scale == 1`` no program
    is launched and no ``edge.scale`` span recorded.

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
            g = g / scale if divide else g * scale
    with kv.trace_span("edge.d2h", key=tid,
                       nbytes=getattr(g, "nbytes", None)) as sp:
        if sp is not _NULL_SPAN:
            # a sampled round tells the wait for the leaf from its copy
            # (``wait_us``: about 0 in ``run_worker``, which blocks on
            # the gradient before the exchange; not in the staged loop)
            sp.await_device(g)
        return np.asarray(g)


def _route_args(route, tokens: int) -> dict:
    """The arguments of a step's ``moe.route`` span from the counts the
    gradient program returned (``parallel/moe.py`` ``routed_ffn``, a row
    a routed layer), read to the host HERE: ``rows`` every row the held
    experts' grouped products were given; ``max_over_mean`` the fullest
    held expert's rows over the mean, in the worst layer;
    ``empty_pct`` the share of the step's tokens none of whose experts
    is held, mean over the layers; ``dropped`` the routed pairs whose
    expert is held that reached no group: 0, or the layer is broken;
    ``buffer_rows`` the rows of the layers' sorted buffers over all the
    chunks their held pairs took (``moe.chunk_rows`` a chunk),
    ``fill_pct`` the share of them that ``rows`` filled, and
    ``chunks_max`` the most chunks a layer took (1: every layer's load
    fitted one buffer)."""
    rows = np.asarray(route["rows"], np.int64)
    dropped = int(np.asarray(route["held_pairs"], np.int64).sum()
                  - rows.sum())
    assert dropped == 0, f"the routed layers dropped {dropped} rows"
    buffer_rows = int(np.asarray(route["buffer_rows"], np.int64).sum())
    return {
        "rows": int(rows.sum()),
        "buffer_rows": buffer_rows,
        "fill_pct": float(100.0 * rows.sum() / max(buffer_rows, 1)),
        "chunks_max": int(np.max(route["chunks"])),
        "max_over_mean": float(
            (rows.max(axis=1) / np.maximum(rows.mean(axis=1), 1e-9)).max()),
        "empty_pct": float(100.0 * np.mean(
            np.asarray(route["empty_tokens"])) / tokens),
        "dropped": dropped}


def _scan_args(scan) -> dict:
    """The arguments of a step's ``kda.scan`` span from the counts the
    gradient program returned (``ops/kda.py`` ``chunk_kda``, a row a
    ``kda`` layer), read to the host HERE: ``layers``; ``chunk`` the
    positions of a chunk and ``chunks`` the chunks of a layer's scan;
    ``state_MB`` the carried states a layer keeps for the backward pass
    and ``kept_MB`` what the layer's checkpoint keeps of the scan (those
    states and its output under ``remat``, 0 without);
    ``log_decay_min`` the most negative log decay cumulated inside any
    chunk of the step (the naive factored form overflows past -88)."""
    return {
        "layers": int(np.size(scan["chunks"])),
        "chunk": int(np.max(scan["chunk"])),
        "chunks": int(np.max(scan["chunks"])),
        "state_MB": float(np.max(scan["state_bytes"]) / 1e6),
        "kept_MB": float(np.max(scan["kept_bytes"]) / 1e6),
        "log_decay_min": float(np.min(scan["log_decay_min"]))}


def _exchange(kv: WorkerKVStore, tids: Sequence[int], leaves: list,
              on_pulled: Callable[[int, np.ndarray], None],
              scale: float = 1.0, divide: bool = False,
              body: Optional[dict] = None) -> List[int]:
    """What crosses the slice edge in a training step, and in what
    order, for every sync mode and loop: each leaf's copy off the device
    (:func:`_edge_to_host`), its push, and its pull right behind it —
    the client holds the pull back until that push is acked
    (``after_ts``), so the early tensors come back and are decoded on
    the response thread while this thread still copies the later ones
    off the device.  ``leaves`` is EMPTIED as it goes: a leaf's device
    buffer is let go as soon as its copy is handed over, so hold no
    other reference to them.  ``body`` rides every plain push
    (``{"hfa_n": n}``: the denominator a weight was divided by).

    The variant follows the cluster: under intra-party TS the leaves go
    through the worker-to-worker merge tree and the elected holder
    pushes for the party; under P3 a sliced push_pull whose response
    carries the values.  Returns the pushes' timestamps (ESync times
    their acks); the caller drains the pulls with ``kv.wait_all()``."""
    if kv.ts_push is not None:
        kv.ts_merge_push({tid: _edge_to_host(kv, tid, leaves.pop(0), scale,
                                             divide) for tid in tids})
        for tid in tids:
            kv.pull(tid, on_pulled, priority=-tid)
        return []
    push_ts: List[int] = []
    for tid in tids:
        host = _edge_to_host(kv, tid, leaves.pop(0), scale, divide)
        if kv.config.enable_p3:
            push_ts += kv.push_pull(tid, host, on_pulled, priority=-tid)
        else:
            push_ts.append(kv.push(tid, host, priority=-tid, body=body))
            kv.pull(tid, on_pulled, priority=-tid)
    return push_ts


def run_worker(
    kv: WorkerKVStore,
    params,
    grad_fn: Callable,
    data_iter: Iterable,
    steps: int,
    barrier_init: bool = True,
    log_fn: Optional[Callable[[int, float, float], None]] = None,
    params_out: Optional[dict] = None,
    measure=None,
    schedule: Schedule = Schedule(),
) -> List[Tuple[float, float]]:
    """The worker loop of every sync mode; returns [(loss, acc), ...]
    per gradient step.

    ``steps`` counts gradient steps, and under ESync sync ROUNDS,
    identical on every worker of the party (one push a worker a round
    keeps the weight merge in lockstep; a budget of local steps would
    deadlock the party when fast workers exhausted it in fewer rounds):
    there ``data_iter`` should be cyclic or yield up to ``steps x
    max_local_steps`` batches, and a worker whose iterator runs dry
    still pushes each remaining round.

    Under FSA the returned params after each step are identical on every
    worker (the convergence oracle the acceptance tests assert).

    ``measure`` (utils.Measure) brackets each phase — grad compute /
    push / pull-wait — per step, the reference examples' per-phase
    timing report (ref: examples/utils.py:120-192).
    """
    from geomx_tpu.utils.measure import Measure

    m = measure if measure is not None else Measure()
    opt, k1, esync = schedule
    if opt is not None:
        import optax
    leaves, treedef = flatten_params(params)
    groups = leaf_groups(params)
    for tid, leaf in enumerate(leaves):
        kv.init(tid, leaf, barrier=barrier_init,
                group=groups[tid] if groups[tid] != DENSE else None)
    params = unflatten_params(treedef, leaves)
    opt_state = opt.init(params) if opt is not None else None
    history: List[Tuple[float, float]] = []
    buf: List[Optional[np.ndarray]] = [None] * len(leaves)

    def on_pulled(tid, arr):
        buf[tid] = arr

    it = iter(data_iter)
    local_steps = 1  # ESync: until the state server has a plan
    loss = acc = 0.0
    for step in itertools.count():
        batch = next(it, None)
        if (step >= steps or _preempt_noticed(kv)
                or (batch is None and esync is None)):
            break
        due = (step + 1) % k1 == 0
        m.step_start()
        t0 = time.perf_counter()
        # the whole step under one sampled root span (no-op unless
        # Config.trace_sample_every hits this round): every push/pull the
        # step issues joins the round's cross-node trace.  Rounds are
        # numbered by exchanges, the same on every worker.
        with kv.trace_round(step // k1) if due else contextlib.nullcontext():
            # ``worker.grad``: the gradient program(s) up to the block
            # below (the ``bench:`` phase of the same name is the
            # benchmark's hook's, this is the program's own span)
            with m.phase("grad"), kv.trace_span("worker.grad"):
                ran, grads = [], None
                while batch is not None:
                    # a model with routed experts hands back a fourth
                    # value, its routers' counts: still on the device
                    loss, acc, grads, *extra = grad_fn(params, *batch)
                    ran.append((loss, acc))
                    fed = batch
                    if opt is not None:
                        updates, opt_state = opt.update(grads, opt_state,
                                                        params)
                        params = optax.apply_updates(params, updates)
                    batch = next(it, None) if len(ran) < local_steps else None
                if due:
                    # out alone holds a gradient from here on, so that
                    # the exchange can let each leaf's device buffer go
                    # as soon as its copy is off the chip
                    out = jax.tree_util.tree_leaves(
                        grads if opt is None else params)
                    del grads
                    # block HERE so the phase split is honest: jax
                    # dispatch is async, and without this the whole
                    # backward pass would be billed to the push phase's
                    # first np.asarray (the exchange converts
                    # leaf-by-leaf right below anyway, so this does not
                    # change the schedule; the staged OVERLAP loop —
                    # overlap.py — is the path that interleaves, not
                    # this one)
                    jax.block_until_ready(out)
                    if extra and extra[0].get("moe_route") is not None:
                        # the counts cross to the host only where the
                        # span is recorded: in a sampled round
                        with kv.trace_span("moe.route", of=lambda: _route_args(
                                extra[0]["moe_route"], np.size(fed[0]))):
                            pass
                    if extra and extra[0].get("kda_scan") is not None:
                        with kv.trace_span("kda.scan", of=lambda: _scan_args(
                                extra[0]["kda_scan"])):
                            pass
            grad_s = time.perf_counter() - t0
            if due:
                # re-read per exchange: dynamic join/leave changes the
                # party size mid-training (the server broadcasts the new
                # count, the client hook updates kv.num_workers).  Grads
                # are summed across the party then averaged over parties
                # at the global server: pre-scaled by 1 / party size the
                # update is the all-worker mean (ref: examples/cnn_hfa.py
                # pushes param / num_local_workers the same way).  The
                # denominator a weight was divided by is announced as
                # ``hfa_n``, so that the server can renormalize a
                # transition round's mixed-scale mean.
                n = kv.num_workers
                crossing = (dict(scale=1.0 / n) if opt is None else
                            dict(scale=n, divide=True, body={"hfa_n": n}))
                t1 = time.perf_counter()
                with m.phase("push"):
                    push_ts = _exchange(kv, range(len(buf)), out, on_pulled,
                                        **crossing)
                    if esync is not None:
                        # ESync's comm time is the TRANSMISSION: the
                        # server acks each push on receipt, so waiting on
                        # push acks measures the uplink — the pull barrier
                        # below is the straggler wait ESync exists to
                        # eliminate, and counting it as comm would feed
                        # the wait back into the plan and pin every fast
                        # worker at min_steps
                        for ts in push_ts:
                            kv.worker.wait(ts)
                        comm_s = time.perf_counter() - t1
                with m.phase("pull_wait"):
                    kv.wait_all()
                # the round ends when this worker holds the parameters
                # it will step on: the pulled weights' way back onto the
                # chip is inside the root
                with kv.trace_span("edge.h2d", of=lambda: {
                        "nbytes": sum(a.nbytes for a in buf)}):
                    params = unflatten_params(
                        treedef, buf)  # type: ignore[arg-type]
        m.step_end()
        history.extend((float(lo), float(ac)) for lo, ac in ran)
        if esync is not None:
            step_s = grad_s / max(len(ran), 1)
            if esync.rounds_out is not None:
                esync.rounds_out.append(
                    (len(ran), round(step_s * len(ran) + comm_s, 4)))
            if ran:
                # a dry data iterator must not report: its near-zero
                # "step time" would make the planner believe this worker
                # is infinitely fast, collapse the reach-time target, and
                # pin every worker that still has data at min_steps
                local_steps = kv.esync_report(
                    step_s, comm_s, max_steps=esync.max_local_steps)
        if log_fn is not None:
            log_fn(step, float(loss), float(acc))
    if params_out is not None:
        params_out["params"] = params
    return history
