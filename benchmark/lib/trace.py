"""From a profiler trace to numbers: the benchmark's own reduction.

A trace is held as ``{plane: {line: [Event]}}`` with times in seconds on
the profiler's clock, so that the tests can build one by hand;
:func:`load` fills it from the ``.xplane.pb`` that ``jax.profiler``
writes.  What a TPU trace looks like (which planes are chips, how the
programs and kernels are named) was read by hand from a real one and is
recorded in ``PERF.md`` section 3.

* a chip is a plane named ``/device:TPU:<n>``;
* its line ``XLA Ops`` holds one event per executed HLO operation (the
  busy union is taken over these), named by the whole text of the
  instruction (``%sort = (f32[16777216]{...`` ...), which :func:`load`
  cuts to ``sort f32[16777216]``; its line ``XLA Modules`` holds one
  event per executed program, named ``<jit name>(<fingerprint>)``;
* the benchmark's own host spans are events named ``bench:<worker>:
  <phase>`` on the thread lines of ``/host:CPU``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
# two short spans the harness writes at the window's edges, so that the
# window is known on the profiler's clock
WINDOW_OPEN, WINDOW_CLOSE = "window:open", "window:close"


class Event(NamedTuple):
    name: str
    start: float    # seconds on the profiler's clock
    dur: float


def op_name(text: str) -> str:
    """``<instruction> <result type>`` from the HLO text an ``XLA Ops``
    event is named by, so that a pattern cannot match an operand."""
    instr, _, rest = text.partition(" = ")
    result = rest.split("{", 1)[0].lstrip("(")
    return f"{instr.lstrip('%')} {result}".strip()


class PatternMatchedNothing(RuntimeError):
    """A module or kernel pattern found no event in the trace: the name
    changed, and a 0 would hide it."""


def _newest(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(trace_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a trace dict."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_newest(trace_dir))
    trace: dict = {}
    for plane in data.planes:
        host = plane.name.startswith("/host:")
        if not host and not DEVICE_PLANE.match(plane.name):
            continue
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            if not host and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            short = op_name if line.name == OPS_LINE else str
            evs = [Event(short(e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9)
                   for e in line.events
                   if not host or e.name.startswith(SPAN_PREFIX)]
            if evs:
                # two host threads can share a line name
                lines.setdefault(line.name, []).extend(evs)
    return trace


def chips(trace: dict) -> list:
    """Device plane names in chip order."""
    found = [(int(m.group(1)), p) for p in trace
             if (m := DEVICE_PLANE.match(p))]
    return [p for _, p in sorted(found)]


def _clip(events, t0, t1):
    for e in events:
        a, b = max(e.start, t0), min(e.start + e.dur, t1)
        if b > a:
            yield a, b


def busy_intervals(events, t0: float, t1: float) -> list:
    """The union of the events' intervals inside [t0, t1], merged."""
    merged: list = []
    for a, b in sorted(_clip(events, t0, t1)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(events, t0: float, t1: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, t0, t1))


def device_ops(trace: dict, chip: str) -> list:
    lines = trace.get(chip, {})
    return lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []


def _inside(events, t0, t1):
    return [e for e in events if e.start >= t0 and e.start + e.dur <= t1]


def module_seconds(trace: dict, chip: str, pattern: str, t0: float,
                   t1: float):
    """Summed device time of the programs on ``chip`` whose module name
    matches ``pattern`` and that ran wholly inside [t0, t1]; None where
    none did (the readers decide whether that is an error)."""
    rx = re.compile(pattern)
    evs = [e for e in _inside(trace.get(chip, {}).get(MODULES_LINE, []),
                              t0, t1) if rx.search(e.name)]
    return sum(e.dur for e in evs) if evs else None


def kernel_events(trace: dict, chip: str, pattern: str, t0: float,
                  t1: float) -> list:
    """The ``XLA Ops`` events on ``chip`` whose name matches."""
    rx = re.compile(pattern)
    return [e for e in _inside(trace.get(chip, {}).get(OPS_LINE, []),
                               t0, t1) if rx.search(e.name)]


def top_device_ops(trace: dict, t0: float, t1: float, n: int = 10) -> list:
    """[[name, seconds], ...]: the programs that took most device time,
    summed over every chip, and, under them, the operations that did."""
    out = []
    for line, tag in ((MODULES_LINE, "module "), (OPS_LINE, "op ")):
        total: dict = defaultdict(float)
        for e in (e for chip in chips(trace)
                  for e in _inside(trace[chip].get(line, []), t0, t1)):
            # one entry per program or op, whatever its fingerprint or
            # the layer it belongs to (``fusion.12`` -> ``fusion``)
            total[tag + re.sub(r"\(\d+\)$|\.\d+(?= |$)", "", e.name)] += e.dur
        out += sorted(total.items(), key=lambda kv: -kv[1])[:n // 2]
    return [[k, v] for k, v in out]


def _bench_spans(trace: dict) -> list:
    out = []
    for plane, lines in trace.items():
        if not plane.startswith("/host:"):
            continue
        for evs in lines.values():
            out += [Event(e.name[len(SPAN_PREFIX):], e.start, e.dur)
                    for e in evs if e.name.startswith(SPAN_PREFIX)]
    return out


def host_spans(trace: dict) -> list:
    """The workers' phases: Events named ``<worker>:<phase>``."""
    return [s for s in _bench_spans(trace)
            if s.name not in (WINDOW_OPEN, WINDOW_CLOSE)]


def window(trace: dict) -> tuple:
    """(t0, t1) of the measured window on the profiler's clock: from the
    end of the ``window:open`` span to the start of ``window:close``."""
    marks = {s.name: s for s in _bench_spans(trace)}
    try:
        opened, closed = marks[WINDOW_OPEN], marks[WINDOW_CLOSE]
    except KeyError as e:
        raise RuntimeError(f"the trace has no bench:{e.args[0]} span")
    return opened.start + opened.dur, closed.start


def idle_gaps(trace: dict, t0: float, t1: float, n: int = 10) -> list:
    """[[what the workers were doing, seconds], ...]: the time inside
    [t0, t1] in which NO chip ran an operation, split at every change of
    any worker's phase and summed by the set of phases the workers were
    in (``w0:push+w1:pull_wait``; a worker between phases is
    ``w0:between``)."""
    busy = busy_intervals([e for chip in chips(trace)
                           for e in device_ops(trace, chip)], t0, t1)
    gaps, at = [], t0
    for a, b in busy + [[t1, t1]]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    spans = host_spans(trace)
    workers = sorted({s.name.split(":")[0] for s in spans})
    cuts = sorted({t for s in spans for t in (s.start, s.start + s.dur)})
    total: dict = defaultdict(float)
    for g0, g1 in gaps:
        edges = [g0] + [c for c in cuts if g0 < c < g1] + [g1]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            doing = {w: "between" for w in workers}
            for s in spans:
                if s.start <= mid < s.start + s.dur:
                    w, phase = s.name.split(":", 1)
                    doing[w] = phase
            name = "+".join(f"{w}:{p}" for w, p in sorted(doing.items()))
            total[name or "no host span"] += b - a
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
