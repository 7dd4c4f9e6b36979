#!/usr/bin/env python3
"""A traced run of one cell with the program's tracer ON.

    python3 benchmark/run_spans.py --workload <cell> --seed <n> [--seconds <s>] [--rehearse] [--every-span-metric]

``run.py --trace 1`` with three additions, made here at run time because
a PR that is not a benchmark PR may edit no file the benchmark has
(PERF.md section 7 names the three edits; when ``benchmark/lib`` has
them this file and ``proposed_per_layer.json`` go):

1. the reader kind ``program_span`` (``lib/spans.py``) joins
   ``readers.KINDS``;
2. ``reduce_trace`` hands the readers the program's ``geomx:`` spans and
   adds ``breakdown.host_spans`` and ``breakdown.idle_by_span``;
3. the cluster's ``Config`` gets ``trace_sample_every=1``, and the cell
   also reports the metrics of ``proposed_per_layer.json``.

Everything else (the window, the gate, ``correct``, the old metrics and
their readers, the last line's shape) is ``run.py``'s, unchanged.
``--every-span-metric`` reports the proposed ``program_span`` metrics in
this cell whatever their ``workloads`` list says (the host-clock ones
belong to the four-chip cell, where the host is the cell's alone; on one
chip they are a reading, not a metric).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmark import run as bench_run            # noqa: E402
from benchmark.lib import harness, readers, spans, trace as tr, validate  # noqa: E402,E501


def trace_module_or_nothing(spec, obs):
    """``trace_module`` for a proposed metric: on a program from before
    PR 26, whose servers' programs lack the names, the pattern matches
    nothing and the metric is left out instead of failing the run."""
    try:
        return readers.trace_module(spec, obs)
    except tr.PatternMatchedNothing:
        return None


def install(root: Path = ROOT, every_span_metric: bool = False) -> None:
    readers.KINDS["program_span"] = spans.program_span
    readers.KINDS["trace_module_or_nothing"] = trace_module_or_nothing
    load_cell, reduce_trace = harness.load_cell, harness.reduce_trace
    proposed = json.loads(
        (root / "benchmark" / "proposed_per_layer.json").read_text())

    def load_cell_traced(root, name):
        spec = load_cell(root, name)
        spec["traffic"].setdefault("config", {})["trace_sample_every"] = 1
        paths = json.loads((root / "BENCHMARK.json").read_text())["paths"]
        for m in proposed["per_layer"]:
            if name in m["workloads"] or (
                    every_span_metric and m["source"] == "program_span"):
                f = validate.layer_metric_file(root, paths, m["name"])
                body = {**json.loads(f.read_text()), "name": m["name"]}
                if body["kind"] == "trace_module":
                    body["kind"] = "trace_module_or_nothing"
                spec["per_layer"].append(body)
        return spec

    def reduce_trace_with_spans(trace_dir, obs, on_chip, breakdown=True):
        seen = reduce_trace(trace_dir, obs, on_chip, breakdown)
        found = spans.load(trace_dir)
        if obs.get("t0") is None:
            # off the chip nothing was reduced: the window all the same
            obs["t0"], obs["t1"] = tr.window(tr.load(trace_dir))
        obs["spans"] = found
        if breakdown and found:
            t0, t1 = obs["t0"], obs["t1"]
            trace = obs.get("trace") or {}
            busy = tr.busy_intervals(
                [e for chip in tr.chips(trace)
                 for e in tr.device_ops(trace, chip)], t0, t1)
            seen.setdefault("breakdown", {}).update(
                host_spans=spans.host_spans(found, t0, t1, obs["steps"]),
                idle_by_span=spans.idle_by_span(found, busy, t0, t1))
            seen["spans_in_window_per_step"] = sum(
                t0 <= s.start <= t1 for s in found) / obs["steps"]
        return seen

    harness.load_cell = load_cell_traced
    harness.reduce_trace = reduce_trace_with_spans


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--trace" in argv:
        sys.exit("run_spans.py is always a traced run; leave --trace out")
    every = "--every-span-metric" in argv
    if every:
        argv.remove("--every-span-metric")
    install(every_span_metric=every)
    return bench_run.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
