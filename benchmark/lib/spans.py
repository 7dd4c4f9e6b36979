"""The program's own spans, read from the profiler's trace.

With ``Config.trace_sample_every > 0`` every span of ``geomx_tpu.trace``
is also a ``jax.profiler.TraceAnnotation`` named
``geomx:<node>:<span name>`` whose keyword arguments are the span's
causal ids and what its site carries (``key``, ``nbytes``,
``queued_us``...).  Under the benchmark's profiler session they land on
the thread lines of ``/host:CPU``, on the clock the device's operations
are on.  This module is the reader kind ``program_span`` and the two
breakdowns made from the same spans: self time by span
(:func:`host_spans`) and the chips' idle time by the span that was open
(:func:`idle_by_span`).

``lib/trace.py`` keeps only ``bench:`` events of the host planes (the
workers' phases), so what ``idle_gaps`` and the window read cannot move
with anything here; this module reads the ``geomx:`` events itself.

A program without the annotations (a checkout from before PR 26, or a
run with the tracer off) has no such event: :func:`load` returns an
empty list, the reader None, and the line leaves the metric out.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import NamedTuple

import numpy as np

from . import trace as tr

PREFIX = "geomx:"
ROOT_SPAN = "round"     # a worker's whole step: explains nothing


class Span(NamedTuple):
    node: str       # "worker:0:0", "server:1", "global_server:0"
    name: str       # "handle", "be.h2d", ...
    thread: str     # the host thread's line in the trace
    start: float    # seconds on the profiler's clock
    dur: float
    args: dict      # the annotation's keyword arguments

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def label(self) -> str:
        """``<role>:<name>``: the spans of every node of one role go
        under one entry of a breakdown."""
        return f"{self.node.split(':')[0]}:{self.name}"


def load(trace_dir: str) -> list:
    """Every ``geomx:`` event of the newest ``.xplane.pb`` under
    ``trace_dir`` as a :class:`Span`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(tr._newest(trace_dir))
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            # every python thread's line is named "python": the index
            # tells them apart
            thread = f"{plane.name}/{i}"
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                node, _, name = e.name[len(PREFIX):].rpartition(":")
                out.append(Span(node, name, thread, e.start_ns * 1e-9,
                                e.duration_ns * 1e-9, dict(e.stats)))
    return out


def _select(spec: dict, spans, t0: float, t1: float) -> list:
    """The spans a metric reads: name and node by pattern, any argument
    by pattern (``where``), started inside the window."""
    name = re.compile(spec["pattern"])
    node = re.compile(spec.get("node", ""))
    where = {k: re.compile(rx) for k, rx in spec.get("where", {}).items()}
    return [s for s in spans
            if t0 <= s.start <= t1 and name.search(s.name)
            and node.search(s.node)
            and all(k in s.args and rx.search(str(s.args[k]))
                    for k, rx in where.items())]


def _union_seconds(intervals) -> float:
    total, at = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > at:
            total += b - max(a, at)
            at = b
    return total


def program_span(spec: dict, obs: dict):
    """The reader kind ``program_span``: the spans named by ``pattern``
    (and ``node``, ``where``), their ``field`` (``duration`` in seconds,
    or a named argument times ``scale``), reduced by ``reduce``:

    * ``sum_per_step``: summed over every node and thread, a step;
    * ``sum_per_worker_step``: that sum over the number of nodes that
      have such spans (the workers, for a worker's span): a step of one
      worker;
    * ``union_per_step``: for each node and thread the union of the
      spans' intervals cut to the window, so that nesting is counted
      once, summed, a step (``field`` must be ``duration``);
    * ``p<n>``: a percentile over the spans.

    None where the trace holds no such span."""
    if not obs.get("spans") or obs.get("t0") is None:
        return None
    t0, t1 = obs["t0"], obs["t1"]
    found = _select(spec, obs["spans"], t0, t1)
    field, scale = spec["field"], spec.get("scale", 1.0)
    if field != "duration":
        found = [s for s in found if field in s.args]
    if not found:
        return None
    reduce = spec["reduce"]
    if reduce == "union_per_step":
        if field != "duration":
            raise ValueError("union_per_step reads durations")
        by_thread = defaultdict(list)
        for s in found:
            by_thread[s.node, s.thread].append((s.start, min(s.end, t1)))
        return sum(map(_union_seconds, by_thread.values())) / obs["steps"]
    values = [s.dur if field == "duration" else float(s.args[field]) * scale
              for s in found]
    if reduce == "sum_per_step":
        return sum(values) / obs["steps"]
    if reduce == "sum_per_worker_step":
        return sum(values) / obs["steps"] / len({s.node for s in found})
    pct = re.fullmatch(r"p(\d{1,3})", reduce)
    if not pct or int(pct.group(1)) > 100:
        raise ValueError(f"unknown reducer {reduce!r}")
    return float(np.percentile(values, int(pct.group(1))))


# ---------------------------------------------------------------------------
# the two breakdowns
# ---------------------------------------------------------------------------

def innermost(spans) -> list:
    """One thread's spans flattened: ``(a, b, span)`` pieces that do not
    overlap, each instant given to the innermost span open on the
    thread.  A span's pieces sum to its SELF time: its duration less
    what its children on the same thread cover."""
    out, stack, at = [], [], 0.0

    def emit(a, b, s):
        if b > a:
            out.append((a, b, s))

    for s in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            emit(at, top.end, top)
            at = max(at, top.end)
        if stack:
            emit(at, s.start, stack[-1])
        stack.append(s)
        at = s.start
    while stack:
        top = stack.pop()
        emit(at, top.end, top)
        at = max(at, top.end)
    return out


def _pieces(spans) -> list:
    """:func:`innermost` over every thread, the round roots left out."""
    by_thread = defaultdict(list)
    for s in spans:
        if s.name != ROOT_SPAN:
            by_thread[s.thread].append(s)
    return [p for evs in by_thread.values() for p in innermost(evs)]


def _top(total: dict, n: int) -> list:
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def host_spans(spans, t0: float, t1: float, steps: int, n: int = 15) -> list:
    """[[``<role>:<span>``, seconds a step], ...]: the program's spans by
    self time inside the window, summed over nodes and threads."""
    total: dict = defaultdict(float)
    for a, b, s in _pieces(spans):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            total[s.label] += (b - a) / steps
    return _top(total, n)


def _overlap(pieces, gaps):
    """(piece's span, seconds) for every overlap of a sorted list of
    ``(a, b, span)`` with a sorted list of gaps, two pointers."""
    i = 0
    for a, b, s in pieces:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            lo, hi = max(a, gaps[j][0]), min(b, gaps[j][1])
            if hi > lo:
                yield s, lo, hi
            j += 1


NO_SPAN = "no span open"


def idle_by_span(spans, busy, t0: float, t1: float, n: int = 15) -> list:
    """[[``<role>:<span>``, seconds], ...]: the time inside [t0, t1] in
    which NO chip ran an operation (``busy``: the merged intervals in
    which one did), each instant's seconds given to the innermost open
    span of every thread that has one (so the entries can sum to more
    than the idle time: several nodes work at once), plus
    ``no span open``: the idle time in which no thread had any span but
    a round's root open, the share this tracing cannot explain."""
    gaps, at = [], t0
    for a, b in list(busy) + [[t1, t1]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    idle = sum(b - a for a, b in gaps)
    total: dict = defaultdict(float)
    covered = []
    for s, lo, hi in _overlap(sorted(_pieces(spans), key=lambda p: p[0]),
                              gaps):
        total[s.label] += hi - lo
        covered.append((lo, hi))
    out = _top(total, n)
    out.append([NO_SPAN, idle - _union_seconds(covered)])
    return out
