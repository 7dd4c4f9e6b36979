"""The program's spans as the benchmark reads them (ISSUE 26): the
``program_span`` reducers, self time and ``idle_by_span`` on a hand-built
trace with two nodes and a known answer; a recorded profile that holds
both ``bench:`` and ``geomx:`` events; the manifest's entries that read
them (since PR 29); and ``run.py --trace 1`` end to end off the chip."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.lib import readers, spans, trace as tr
from benchmark.lib.spans import Span

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = ROOT / "benchmark" / "layer_metrics"
# ISSUE 26's eight, in the manifest since PR 29
SPAN_METRICS = ("edge_d2h_s_per_step", "edge_enqueue_s_per_step",
                "queue_wait_s_p50", "server_h2d_s_per_step",
                "server_d2h_s_per_step", "server_busy_s_per_step")
SPLIT_METRICS = ("merge_dev_ms_per_step", "opt_dev_ms_per_step")
S, G = "server:0@p0", "global_server:0"
W0, W1 = "worker:0@p0", "worker:0@p1"


def _spec(name):
    return json.loads((METRICS / f"{name}.json").read_text())


def _spans():
    """A window [0, 10] of two steps.  Thread t1 is the local server's,
    t2 the global server's, t3/t4 the workers'."""
    return [
        # the local server: a handler with two children, then another
        Span(S, "handle", "t1", 1.0, 4.0, {"op": "push", "queued_us": 2e5}),
        Span(S, "local.push", "t1", 1.5, 3.0, {"key": 3}),
        Span(S, "be.h2d", "t1", 2.0, 1.0, {"key": 3, "nbytes": 64}),
        Span(S, "handle", "t1", 6.0, 1.0, {"op": "pull", "queued_us": 4e5}),
        # the global server, overlapping the local one in time
        Span(G, "handle", "t2", 3.0, 3.0, {"op": "push", "queued_us": 6e5}),
        Span(G, "be.d2h", "t2", 4.0, 1.0, {}),
        Span(G, "handle", "t2", 8.0, 0.5, {"op": "ctrl", "queued_us": 9e6}),
        # the workers: a round root over everything, leaves inside
        Span(W0, "round", "t3", 0.0, 10.0, {}),
        Span(W0, "edge.d2h", "t3", 0.5, 0.5, {"key": 0}),
        Span(W0, "edge.scale", "t3", 1.0, 0.25, {"key": 0}),
        Span(W0, "worker.push", "t3", 1.25, 0.25, {"key": 0}),
        Span(W1, "edge.d2h", "t4", 0.5, 1.5, {"key": 0}),
        # started before the window: read by nobody
        Span(S, "be.h2d", "t1", -1.0, 0.5, {}),
    ]


def _obs(**kw):
    return dict({"spans": _spans(), "t0": 0.0, "t1": 10.0, "steps": 2}, **kw)


@pytest.mark.parametrize("metric, value", [
    ("edge_d2h_s_per_step", (0.5 + 1.5) / 2 / 2),      # two workers
    ("edge_enqueue_s_per_step", (0.25 + 0.25) / 2 / 1),  # one has such spans
    ("queue_wait_s_p50", 0.4),      # the ctrl message's 9 s is not data
    ("server_h2d_s_per_step", 1.0 / 2),
    ("server_d2h_s_per_step", 1.0 / 2),
    # per thread the union: t1 4 + 1, t2 3 + 0.5; nesting counted once
    ("server_busy_s_per_step", (5.0 + 3.5) / 2),
])
def test_the_committed_span_metrics_on_a_known_trace(metric, value):
    spec = _spec(metric)
    assert spec["kind"] == "program_span"
    assert spans.program_span(spec, _obs()) == pytest.approx(value)


def test_a_program_without_spans_reports_nothing():
    spec = _spec("edge_d2h_s_per_step")
    assert spans.program_span(spec, _obs(spans=[])) is None
    assert spans.program_span(spec, {"steps": 2}) is None
    none_match = dict(spec, pattern="^nothing$")
    assert spans.program_span(none_match, _obs()) is None
    with pytest.raises(ValueError):
        spans.program_span(dict(spec, reduce="mean"), _obs())


def test_self_time_is_duration_less_the_children_on_the_thread():
    pieces = spans.innermost([s for s in _spans() if s.thread == "t1"
                              and s.start >= 0])
    self_s = {}
    for a, b, s in pieces:
        self_s[s.name, s.start] = self_s.get((s.name, s.start), 0) + b - a
    assert self_s == pytest.approx({
        ("handle", 1.0): 4.0 - 3.0, ("local.push", 1.5): 3.0 - 1.0,
        ("be.h2d", 2.0): 1.0, ("handle", 6.0): 1.0})
    top = dict(spans.host_spans(_spans(), 0.0, 10.0, steps=2))
    assert top["server:handle"] == pytest.approx((1.0 + 1.0) / 2)
    assert top["server:local.push"] == pytest.approx(2.0 / 2)
    assert top["global_server:handle"] == pytest.approx((2.0 + 0.5) / 2)
    assert top["worker:edge.d2h"] == pytest.approx(2.0 / 2)
    assert "worker:round" not in top
    assert list(top) == sorted(top, key=lambda k: -top[k])


def test_idle_time_goes_to_the_innermost_open_span_of_every_thread():
    # the chips work in [0, 2] and [5, 5.5]: idle is [2, 5] + [5.5, 10]
    busy = [[0.0, 2.0], [5.0, 5.5]]
    got = dict(spans.idle_by_span(_spans(), busy, 0.0, 10.0))
    # t1: be.h2d 2..3, local.push 3..4.5, handle 4.5..5, handle 6..7
    assert got["server:be.h2d"] == pytest.approx(1.0)
    assert got["server:local.push"] == pytest.approx(1.5)
    assert got["server:handle"] == pytest.approx(0.5 + 1.0)
    # t2 at the same time: handle 3..4, be.d2h 4..5, handle 5.5..6, 8..8.5
    assert got["global_server:be.d2h"] == pytest.approx(1.0)
    assert got["global_server:handle"] == pytest.approx(1.0 + 0.5 + 0.5)
    # the workers' leaves ended before the first idle second; a round's
    # root explains nothing: 7..8 and 8.5..10 had no span open
    assert "worker:round" not in got
    assert got[spans.NO_SPAN] == pytest.approx(1.0 + 1.5)
    assert list(got)[-1] == spans.NO_SPAN


def test_a_recorded_profile_holds_both_kinds_of_span(tmp_path):
    """A real .xplane.pb: ``lib/trace.py`` goes on reading the ``bench:``
    events alone (what ``idle_gaps`` and the window stand on does not
    move), and this module reads the ``geomx:`` ones with their
    arguments, on the same clock."""
    import jax

    from benchmark.lib.harness import SpanMeasure, _profiler_options

    m = SpanMeasure("w0")
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=_profiler_options())
    with jax.profiler.TraceAnnotation("bench:window:open"):
        pass
    with m.phase("push"):
        with jax.profiler.TraceAnnotation(
                "geomx:worker:0@p0:edge.d2h", key=3, nbytes=64,
                trace_id=1, span=7, parent=0):
            pass
    with jax.profiler.TraceAnnotation("bench:window:close"):
        pass
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    (phase,) = tr.host_spans(t)
    assert phase.name == "w0:push"
    assert not any(e.name.startswith("geomx:") for lines in t.values()
                   for evs in lines.values() for e in evs)
    (s,) = spans.load(str(tmp_path))
    assert (s.node, s.name) == ("worker:0@p0", "edge.d2h")
    assert s.args["key"] == 3 and s.args["nbytes"] == 64
    assert phase.start <= s.start and s.end <= phase.start + phase.dur
    t0, t1 = tr.window(t)
    assert t0 <= s.start <= t1


# the servers' programs under the names they have from PR 26 on
NEW_MODULES = {
    "jit_geomx_merge_add(1)": "merge_dev_ms_per_step",
    "jit_geomx_merge_scale(2)": "merge_dev_ms_per_step",
    "jit_geomx_screen(3)": "merge_dev_ms_per_step",
    "jit_geomx_mesh_reduce(4)": "merge_dev_ms_per_step",
    "jit_geomx_adam(5)": "opt_dev_ms_per_step",
    "jit_geomx_sgd(6)": "opt_dev_ms_per_step",
    "jit_geomx_sgd_plain(7)": "opt_dev_ms_per_step",
    "jit_geomx_nag(8)": "opt_dev_ms_per_step",
    # the dense casts and the 2-bit pair: a server's, in neither split
    "jit_geomx_fp16_enc(9)": None,
    "jit_geomx_fp16_dec(10)": None,
    "jit_geomx_2bit_enc(11)": None,
    "jit_geomx_2bit_dec(12)": None,
}


def test_the_new_program_names_fall_into_one_split_each():
    from benchmark.tests.test_bench_trace import SEEN_MODULES

    specs = {p.stem: json.loads(p.read_text())
             for p in METRICS.glob("*.json")}
    by_module = {n: s["pattern"] for n, s in specs.items()
                 if s["kind"] == "trace_module"}
    assert {"merge_dev_ms_per_step", "opt_dev_ms_per_step"} <= set(by_module)
    for name, owner in NEW_MODULES.items():
        hits = {n for n, rx in by_module.items() if re.search(rx, name)}
        # every one is a server's program, by exclusion as before
        assert "server_dev_ms_per_step" in hits, name
        hits.discard("server_dev_ms_per_step")
        assert hits == ({owner} if owner else set()), (name, hits)
    # and no new pattern takes a program that was seen under an old name
    for name in SEEN_MODULES:
        for n in ("merge_dev_ms_per_step", "opt_dev_ms_per_step"):
            assert not re.search(by_module[n], name), (n, name)


def test_the_manifest_has_the_eight_and_the_kind_is_registered():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert readers.KINDS["program_span"] is spans.program_span
    for name in SPAN_METRICS + SPLIT_METRICS:
        spec = _spec(name)
        assert entries[name]["source"] == spec["source"]
        assert (spec["kind"] == "program_span") == (name in SPAN_METRICS)
        assert (spec["source"] == "program_span") == (name in SPAN_METRICS)
    assert not (ROOT / "benchmark" / "run_spans.py").exists()
    assert not (ROOT / "benchmark" / "proposed_per_layer.json").exists()


# long enough for a traced window to reach its 6 steps with the tracer
# on and the machine busy (it ends there): under MPQ at these sizes the
# loss has not always fallen after one or two
SECONDS = "10"


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), *args,
         "--seed", "5", "--seconds", SECONDS, "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_a_traced_run_reads_the_programs_spans(cell):
    line = _run("--workload", cell, "--trace", "1")
    # the tracer is on, and its reports stay off the WAN in a rehearsal
    # (lib/rehearsal.json), so MPQ's ceiling on WAN bytes holds too
    assert line["correct"] is True, line["failures"]
    span_metrics = {"rehearsal_" + m["name"] for m in MANIFEST["per_layer"]
                    if cell in m["workloads"]
                    and m["source"] == "program_span"
                    and m["name"] in SPAN_METRICS}
    assert len(span_metrics) == (6 if cell.endswith("dp2x2.fsa") else 0)
    assert span_metrics <= set(line["metrics"])
    # never a value under a device metric's name off the chip
    assert all(n.startswith("rehearsal_") for n in line["metrics"])
    assert not {"rehearsal_" + n for n in SPLIT_METRICS} & set(
        line["metrics"])
    rows = line["breakdown"]["host_spans"]
    assert rows and len(rows) <= 10 and all(v > 0 for _, v in rows)
    idle = dict(line["breakdown"]["idle_by_span"])
    assert spans.NO_SPAN in idle and 1 < len(idle) <= 10
    assert line["spans_in_window_per_step"] > 100


def test_an_untraced_run_loads_no_spans():
    """The tracer is on in the traced run alone: an untraced run records
    no ``geomx:`` span, reads none, and its set-up gains nothing."""
    line = _run("--workload", "flagship-l4-1chip.fsa", "--trace", "0")
    assert line["correct"] is True, line["failures"]
    assert "spans_in_window_per_step" not in line
    assert "breakdown" not in line


def test_a_split_metric_fails_on_a_program_without_the_names():
    """``trace_module`` keeps raising where a pattern matches nothing:
    every parent from PR 26 on has the servers' program names."""
    from benchmark.tests.test_bench_trace import _obs, _trace

    obs = _obs(_trace())    # the hand-built trace: jit__lambda, jit_f
    spec = _spec("merge_dev_ms_per_step")
    with pytest.raises(tr.PatternMatchedNothing):
        readers.read(spec, obs)
    named = dict(spec, pattern=r"^jit_(_lambda|f)\(")
    assert readers.read(named, obs) > 0
