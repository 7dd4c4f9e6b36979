"""Per-node span recorder + batched reporter.

One ``Tracer`` per node (keyed like ``utils.get_profiler``).  Spans are
recorded as Chrome-trace events **into the node's existing Profiler
event buffer** (one buffer per node — the remote-profiler dump and the
distributed trace cannot drift apart), with the causal identity
(trace_id / span / parent) in ``args``.  A second reference to each
event dict sits in the tracer's pending batch until it is shipped to the
scheduler-side collector (``Ctrl.TRACE_REPORT``) — the dicts are shared,
never copied.

Timestamps: events carry the profiler-relative ``ts`` (so a per-node
``Profiler.dump`` stays coherent) plus an absolute ``t_mono_us`` in
``args`` — the collector merges on the monotonic clock, corrected by the
per-node offset estimated from heartbeat RTTs.

On the profiler's clock: every recorded span also enters a
``jax.profiler.TraceAnnotation`` named ``geomx:<node>:<span name>`` with
the causal ids and the site's arguments (``key``, ``nbytes``,
``queued_us``) as its keyword arguments, so that under a live profiler
session the spans sit on the host plane of the device's own trace.  With
no session the annotation is one no-op call.

Waits: a span that waited says so itself, in fields the collector's
blocking chain reads (``trace/collector.py``): ``wait_us`` (a device
value was not ready: :meth:`_Span.await_device`) and ``lock_us`` (a
stripe or a leaf lock was held by another thread: :meth:`Tracer.locked`),
each with the wait's place inside the span (``waits``).  A site tests
``span is not _NULL_SPAN`` before it measures one, so an unsampled
message pays that branch and nothing else.

Memory: a worker's ``round`` root says, when it closes, the most the
process has held (``rss_peak_MB``: ``ru_maxrss``, one syscall a sampled
round).

Nothing recorded here keeps anything alive: a span, a context and a
wait record hold ids and numbers only (``of=`` is read once, when the
span opens; a lock is known by ``id()`` and its holder by span id).

Shipping: a batch of ``batch_events`` as before, and a worker also
when its ``round`` root closes, which is what tells the collector that
the round is over.  A flush never raises: what cannot be shipped (the
collector's node is gone) stays pending, under ``_cap``.

Overhead: ``span()`` / ``round()`` return the shared ``_NULL_SPAN``
whenever tracing is inactive or the current thread carries no sampled
context — no allocation, no branch beyond the gate, nothing stamped.
"""

from __future__ import annotations

import logging
import resource
import threading
import time
from typing import Dict, List, Optional

from geomx_tpu.trace import context as _ctx
from geomx_tpu.utils.profiler import Profiler, get_profiler


_log = logging.getLogger(__name__)


class _NullSpan:
    """Shared no-op span: the entire cost of an instrumented site when
    tracing is off (``tracer.span(...) is _NULL_SPAN``)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
# the group of a key no init named one for (kvstore/keys.py KeyPlan)
DEFAULT_GROUP = "dense"

# the span's name on the profiler's timeline: ``geomx:<node>:<name>``
# (NOT the benchmark's ``bench:``, which names the workers' phases)
ANNOTATION_PREFIX = "geomx:"
_annotate = None
_block = None   # jax.block_until_ready, fetched by the first device wait


def _annotation(name: str, args: dict):
    """A ``jax.profiler.TraceAnnotation``: records only while a profiler
    session is live.  jax is imported by the first sampled span, never
    by a process that traces nothing."""
    global _annotate
    if _annotate is None:
        from jax.profiler import TraceAnnotation

        _annotate = TraceAnnotation
    return _annotate(name, **args)


# a wait shorter than this is summed into its field (``wait_us``,
# ``lock_us``) and not kept as an interval of the chain
MIN_WAIT_US = 20.0
# lock -> the span id of the sampled span that holds it (id(lock) keys:
# a few stripes and leaf locks a server), so that a waiter can name the
# holder; written on the sampled path only
_HOLDERS: Dict[int, int] = {}


class _TimedLock:
    """``with`` a lock on behalf of an open span: the time to acquire it
    goes to the span's ``lock_us``, and where it had to wait, to its
    ``waits`` with the holder's span id (0 where no sampled span held
    it)."""

    __slots__ = ("_span", "_lock", "_prev")

    def __init__(self, span: "_Span", lock):
        self._span = span
        self._lock = lock

    def __enter__(self):
        lock, span = self._lock, self._span
        if lock.acquire(False):
            span.add("lock_us", 0.0)
        else:
            holder = _HOLDERS.get(id(lock), 0)
            t0 = time.monotonic()
            lock.acquire()
            span.waited("lock", "lock_us", t0, holder)
        self._prev = _HOLDERS.get(id(lock), 0)
        _HOLDERS[id(lock)] = span.span_id
        return lock

    def __exit__(self, *exc):
        if self._prev:
            _HOLDERS[id(self._lock)] = self._prev   # a re-entrant hold
        else:
            _HOLDERS.pop(id(self._lock), None)
        self._lock.release()
        return False


def _rss_peak_MB() -> float:
    """The most this process has held (``ru_maxrss``, KiB on Linux), in
    MB of 1e6 bytes."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e6


def _carried(of) -> dict:
    """``key`` and ``nbytes`` of the ``Message`` or ``KVPairs`` a site
    works on, read on the sampled path only (a site hands over the
    object, which costs nothing when tracing is off)."""
    keys = of.keys
    out = {}
    if keys is not None and len(keys):
        out["key"] = int(keys[0])
    nbytes = getattr(of, "nbytes", None)  # a Message's wire size
    if nbytes is None and of.vals is not None:
        nbytes = of.vals.nbytes
    if nbytes is not None:
        out["nbytes"] = int(nbytes)
    return out


class _Span:
    __slots__ = ("_tr", "name", "cat", "_prev", "span_id", "parent",
                 "trace_id", "args", "_ann", "_t0", "dur_us", "_late",
                 "waits")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 trace_id: int, parent: int, of=None, args=None):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.parent = parent
        self.span_id = _ctx.new_span_id()
        self.args = {k: v for k, v in args.items() if v is not None} \
            if args else {}
        if callable(of):
            self.args.update(of())
        elif of is not None:
            self.args.update(_carried(of))
        if "key" in self.args:
            # the key's group of the KeyPlan ("expert" for a stacked
            # expert leaf), as this node was told at the key's init
            self.args["group"] = tracer.key_groups.get(
                self.args["key"], DEFAULT_GROUP)
        self.dur_us = 0.0
        self._late = None   # fields known only at the close
        self.waits = None   # [[kind, offset_us, dur_us, holder], ...]

    def add(self, field: str, us: float) -> None:
        """``us`` more of a field that sums (``wait_us``, ``lock_us``)."""
        late = self._late
        if late is None:
            late = self._late = {}
        late[field] = late.get(field, 0.0) + us

    def waited(self, kind: str, field: str, t0: float, holder: int = 0):
        """A wait of this span that began at monotonic ``t0`` and ended
        now: summed into ``field``, and from ``MIN_WAIT_US`` up kept with
        its place inside the span for the blocking chain."""
        us = (time.monotonic() - t0) * 1e6
        self.add(field, us)
        if us >= MIN_WAIT_US:
            if self.waits is None:
                self.waits = []
            self.waits.append([kind, (t0 - self._t0) * 1e6, us, holder])

    def locked(self, lock) -> _TimedLock:
        """``with span.locked(lock):`` the lock, the time to acquire it
        in this span's ``lock_us``."""
        return _TimedLock(self, lock)

    def await_device(self, value):
        """Wait HERE for a device value the site is about to copy off
        the chip, and say how long as ``wait_us``: the copy would block
        there anyway, so the schedule is the same and no device
        operation is added."""
        global _block
        if _block is None:
            from jax import block_until_ready

            _block = block_until_ready
        t0 = time.monotonic()
        _block(value)
        self.waited("device", "wait_us", t0)

    def __enter__(self):
        self._prev = _ctx.swap(
            _ctx.TraceContext(self.trace_id, self.span_id, self))
        self._ann = _annotation(
            self._tr.annotation_name(self.name),
            dict(self.args, trace_id=self.trace_id, span=self.span_id,
                 parent=self.parent))
        self._ann.__enter__()
        # one clock for the start and the duration (CLOCK_MONOTONIC)
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.dur_us = (time.monotonic() - self._t0) * 1e6
        if self.cat == "round":
            self._late = dict(self._late or (), rss_peak_MB=_rss_peak_MB())
        if self._late:
            self.args.update(self._late)
            self._ann.set_metadata(**self._late)
        self._ann.__exit__(*exc)
        _ctx.restore(self._prev)
        if self.waits:
            self.args["waits"] = self.waits
        tr = self._tr
        tr._record(self.name, self.cat, self.dur_us, self.trace_id,
                   self.span_id, self.parent, self._t0, **self.args)
        if self.cat == "round":
            # a worker's round is over, and the collector is told so
            tr.flush()
        return False


class Tracer:
    """Span recorder for one node; ship via :meth:`attach` + flush."""

    def __init__(self, node: str, profiler: Optional[Profiler] = None):
        self.node = node
        self.profiler = profiler or get_profiler(node)
        self._mu = threading.Lock()
        self._pending: List[dict] = []
        self._po = None  # postoffice, once attached
        self._collector = None  # in-proc shortcut (collector on this node)
        self.batch_events = 256
        self.dropped_events = 0
        self._cap = 100_000
        self._names: Dict[str, str] = {}
        # key -> group, for the spans that carry ``key``: filled at a
        # key's init (a worker's tensor ids and ps keys, a server's ps
        # keys); read on the sampled path only
        self.key_groups: Dict[int, str] = {}

    def annotation_name(self, name: str) -> str:
        full = self._names.get(name)
        if full is None:
            full = self._names[name] = (
                f"{ANNOTATION_PREFIX}{self.node}:{name}")
        return full

    # ---- recording ----------------------------------------------------------
    def span(self, name: str, cat: str = "trace", of=None, **args):
        """Timed child span of the thread's current context (no-op when
        tracing is off or the context is unsampled).  ``args`` are what
        the site carries (``key``, ``nbytes``, ``queued_us``): plain
        values already at hand, because the call evaluates them with
        tracing off too; ``of`` is a ``Message`` or ``KVPairs`` whose
        first key and bytes are read only when the span is recorded, or
        a callable that returns the arguments, called only then (what
        costs a device-to-host read, like ``moe.route``'s counts)."""
        if not _ctx.ACTIVE:
            return _NULL_SPAN
        cur = _ctx.current()
        if cur is None:
            return _NULL_SPAN
        return _Span(self, name, cat, cur.trace_id, cur.span_id, of, args)

    def locked(self, lock):
        """``with tracer.locked(lock):`` in place of ``with lock:`` at a
        site that may wait for a key's stripe or a leaf lock: under an
        open sampled span the time to acquire goes to that span's
        ``lock_us``; with none the lock itself comes back.  Sites gate
        on ``context.ACTIVE`` first, so that with tracing off they pay
        one branch."""
        cur = _ctx.current()
        if cur is None or cur.span is None:
            return lock
        return cur.span.locked(lock)

    def round(self, round_idx: int, sample_every: int):
        """Root span of one sampled round: every node derives the same
        ``trace_id`` from the round index, so the collector can merge
        all parties' round-N spans into one tree."""
        if (not _ctx.ACTIVE or sample_every <= 0
                or round_idx % sample_every != 0):
            return _NULL_SPAN
        return _Span(self, "round", "round",
                     _ctx.trace_id_for_round(round_idx), 0)

    def instant(self, name: str, span: int = 0, parent: int = 0,
                trace_id: int = 0, **extra):
        """Zero-duration event.  With ``trace_id`` (the message hooks:
        wan.send / wan.recv) it joins that trace; without one it adopts
        the thread's context when present, else records traceless — how
        failover / eviction control events land on the shared timeline
        even though no sampled round is open around them."""
        if not _ctx.ACTIVE:
            return
        if trace_id == 0:
            cur = _ctx.current()
            if cur is not None:
                trace_id, parent = cur.trace_id, cur.span_id
        span = span or _ctx.new_span_id()
        with _annotation(self.annotation_name(name),
                         dict(extra, trace_id=trace_id, span=span,
                              parent=parent)):
            pass
        self._record(name, "event", 0.0, trace_id, span, parent,
                     time.monotonic(), **extra)

    def _record(self, name: str, cat: str, dur_us: float, trace_id: int,
                span: int, parent: int, t_mono: float, **extra):
        prof = self.profiler
        ev = {
            "name": name, "cat": cat, "ph": "X" if dur_us else "i",
            "ts": (t_mono - prof.t0_mono) * 1e6,
            "dur": dur_us,
            "pid": self.node, "tid": threading.current_thread().name,
            "args": {"trace_id": trace_id, "span": span, "parent": parent,
                     "t_mono_us": t_mono * 1e6, **extra},
        }
        prof.add_event(ev)
        with self._mu:
            if len(self._pending) >= self._cap:
                self.dropped_events += 1
                return
            self._pending.append(ev)
            ship = (self._po is not None
                    and len(self._pending) >= self.batch_events)
        if ship:
            self.flush()

    # ---- shipping -----------------------------------------------------------
    def attach(self, postoffice, collector=None) -> "Tracer":
        """Bind to this node's postoffice; completed spans batch-ship to
        the global scheduler's collector (or straight into ``collector``
        when it lives on this very node)."""
        self._po = postoffice
        self._collector = collector
        return self

    def detach(self) -> None:
        """Let go of the postoffice: this tracer lives in a registry
        for as long as the process does, and through the postoffice it
        would keep a stopped node's servers, and what they hold on the
        device, alive with it."""
        self._po = None
        self._collector = None

    def flush(self) -> int:
        """Ship every pending span to the collector; returns the count.
        Safe to call with nothing attached (spans just keep pending),
        and never raises: it runs where a worker's ``round`` root closes
        and where a node is being shut down, and a collector that is
        down, unreachable or already stopped must cost the round
        nothing.  What could not be shipped is re-queued (bounded by
        ``_cap`` like everything else)."""
        with self._mu:
            po, collector = self._po, self._collector
            if not self._pending or po is None:
                return 0
            batch, self._pending = self._pending, []
        try:
            body = {"node": self.node, "spans": batch,
                    "offsets": po.clock_offsets()}
            if collector is not None:
                collector.ingest(body)
                return len(batch)
            from geomx_tpu.kvstore.common import APP_PS, Ctrl
            from geomx_tpu.transport.message import Domain, Message

            with _ctx.suppressed():  # trace traffic never traces itself
                po.van.send(Message(
                    recipient=po.topology.global_scheduler(),
                    domain=Domain.GLOBAL, app_id=APP_PS, customer_id=0,
                    request=True, cmd=int(Ctrl.TRACE_REPORT), body=body))
        except Exception:
            _log.debug("%s: trace report not shipped", self.node,
                       exc_info=True)
            with self._mu:
                self._pending = batch + self._pending
                del self._pending[self._cap:]
            return 0
        return len(batch)

    def pending(self) -> int:
        with self._mu:
            return len(self._pending)

    def reset(self) -> None:
        """Drop unshipped spans (a fresh deployment reusing this node
        name must not inherit a previous run's leftovers — round-derived
        trace ids would collide across runs)."""
        with self._mu:
            self._pending.clear()


_tracers: Dict[str, Tracer] = {}
_mu = threading.Lock()


def get_tracer(node: str) -> Tracer:
    with _mu:
        t = _tracers.get(node)
        if t is None:
            t = _tracers[node] = Tracer(node)
        return t
