"""The trace reduction on a hand-built trace: busy union, idle share,
module and kernel sums, the window, gap attribution."""

from pathlib import Path

import pytest

from benchmark.lib import family, readers, trace as tr
from benchmark.lib.trace import Event

FLAGSHIP = family.load(Path(__file__).resolve().parents[2], ["benchmark"],
                       "flagship")

CHIP0, CHIP1 = "/device:TPU:0", "/device:TPU:1"


def _trace():
    ms = 1e-3
    return {
        CHIP0: {
            tr.OPS_LINE: [
                Event("fusion.1", 10 * ms, 20 * ms),          # 10..30
                Event("jvp_jit_flash_attention__.1", 25 * ms, 15 * ms),
                Event("flash_mha_bwd_dkv_block_q_128.1", 40 * ms, 10 * ms),
                Event("flash_mha_bwd_dq_block_q_128.1", 50 * ms, 10 * ms),
                Event("add.3", 80 * ms, 5 * ms),              # 80..85
                Event("fusion.1", 120 * ms, 50 * ms),         # outside
            ],
            tr.MODULES_LINE: [
                Event("jit_grad_fn(123)", 10 * ms, 50 * ms),
                Event("jit__lambda(77)", 80 * ms, 5 * ms),
                Event("jit_f(5)", 90 * ms, 2 * ms),
                Event("jit_grad_fn(123)", 95 * ms, 50 * ms),  # crosses t1
            ],
            "Steps": [Event("0", 0.0, 1.0)],
        },
        CHIP1: {tr.OPS_LINE: [Event("fusion.9", 10 * ms, 10 * ms)]},
        "/host:CPU": {
            "python": [
                Event("bench:window:open", 0.0, 5 * ms),
                Event("bench:w0:grad", 5 * ms, 57 * ms),      # 5..62
                Event("bench:w0:push", 62 * ms, 20 * ms),     # 62..82
                Event("bench:w0:pull_wait", 82 * ms, 18 * ms),
                Event("bench:window:close", 100 * ms, 1 * ms),
                Event("PjitFunction(f)", 1 * ms, 1 * ms),
            ],
            "python 2": [Event("bench:w1:pull_wait", 5 * ms, 95 * ms)],
        },
        "Task Environment": {},
    }


def test_chips_window_and_busy_union():
    t = _trace()
    assert tr.chips(t) == [CHIP0, CHIP1]
    t0, t1 = tr.window(t)
    assert (t0, t1) == pytest.approx((0.005, 0.100))
    # 10..60 merged (overlapping ops counted once) + 80..85; the op at
    # 120 ms lies outside the window
    assert tr.busy_intervals(tr.device_ops(t, CHIP0), t0, t1) == [
        pytest.approx([0.010, 0.060]), pytest.approx([0.080, 0.085])]
    assert tr.busy_seconds(tr.device_ops(t, CHIP0), t0, t1) == \
        pytest.approx(0.055)


def test_busy_is_clipped_to_the_window():
    evs = [Event("a", 0.0, 1.0)]
    assert tr.busy_seconds(evs, 0.25, 0.5) == pytest.approx(0.25)


def test_module_sums_and_a_pattern_that_matches_nothing():
    t = _trace()
    t0, t1 = tr.window(t)
    # the second grad program crosses the window's end and is left out
    assert tr.module_seconds(t, CHIP0, r"^jit_(grad_fn|local)\(", t0, t1) \
        == pytest.approx(0.050)
    assert tr.module_seconds(t, CHIP0, r"^jit_(_lambda_?|f)\(", t0, t1) \
        == pytest.approx(0.007)
    # nothing on this chip: for the readers to judge over all chips
    assert tr.module_seconds(t, CHIP0, r"^jit_enc\(", t0, t1) is None
    assert tr.module_seconds(t, CHIP1, r"^jit_", t0, t1) is None
    assert tr.kernel_events(t, CHIP0, "no_such_kernel", t0, t1) == []


def test_idle_gaps_are_named_by_the_workers_phases():
    t = _trace()
    t0, t1 = tr.window(t)
    gaps = dict(tr.idle_gaps(t, t0, t1))
    # no chip busy: 5..10 (grad), 60..62 (grad), 62..80 (push), 85..100
    # (82..85 is busy) in pull_wait; w1 waits throughout; chip 1's one
    # op, 10..20, lies inside chip 0's
    assert gaps["w0:grad+w1:pull_wait"] == pytest.approx(0.007)
    assert gaps["w0:push+w1:pull_wait"] == pytest.approx(0.018)
    assert gaps["w0:pull_wait+w1:pull_wait"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.095 - 0.055)


def test_top_device_ops_merges_fingerprints():
    t = _trace()
    t0, t1 = tr.window(t)
    top = dict(tr.top_device_ops(t, t0, t1))
    assert top["module jit_grad_fn"] == pytest.approx(0.050)
    # fusion.1 on chip 0 and fusion.9 on chip 1 -> fusion, over the chips
    assert top["op fusion"] == pytest.approx(0.030)
    assert len(top) <= 10


def test_op_names_are_cut_from_the_hlo_text():
    """On the chip an XLA Ops event is named by its whole instruction;
    a pattern must see the instruction and its result, never an operand."""
    text = ("%fusion.5 = f32[16777216]{0:T(1024)} fusion(f32[4]{0} "
            "%jvp_jit_flash_attention__.1), kind=kCustom")
    assert tr.op_name(text) == "fusion.5 f32[16777216]"
    assert tr.op_name("%sort = (f32[8]{0:T(1024)}, s32[8]{0}) sort(f32[8]"
                      "{0} %x)") == "sort f32[8]"
    assert tr.op_name("add.3") == "add.3"


def _obs(trace):
    t0, t1 = tr.window(trace)
    busy = {p: tr.busy_seconds(tr.device_ops(trace, p), t0, t1)
            for p in tr.chips(trace)}
    return {"trace": trace, "t0": t0, "t1": t1, "busy": busy, "steps": 2,
            "batch_per_chip": 4, "peaks": {"bf16_flops": 197e12,
                                           "hbm_bytes_per_s": 819e9},
            "counts": FLAGSHIP.counts,
            "model": {"vocab": 8192, "d_model": 2048, "n_heads": 16,
                      "n_layers": 4, "d_ff": 8192, "max_seq": 2048}}


def test_trace_readers():
    obs = _obs(_trace())
    assert readers.read({"kind": "derived", "fn": "device_idle_pct"}, obs) \
        == pytest.approx(100 * (1 - 0.055 / 0.095))
    assert readers.read({"kind": "trace_module", "chips": "max",
                         "pattern": r"^jit_grad_fn\(", "per_step": True},
                        obs) == pytest.approx(25.0)        # ms a step
    spec = {"kind": "trace_kernel", "kernels": [
        {"pattern": "^(?!.*bwd).*flash_attention", "fn": "flash_fwd"},
        {"pattern": "flash_mha_bwd_dkv", "fn": "flash_bwd_dkv"},
        {"pattern": "flash_mha_bwd_dq", "fn": "flash_bwd_dq"}]}
    # one call each: (2 + 4 + 3) causal matmuls of 2 * 128 FLOPs a pair
    # over 4 * 16 * 2048 * 2049 / 2 pairs, compute-bound, in 35 ms
    pairs = 4 * 16 * 2048 * 2049 / 2
    least = 9 * 2 * 128 * pairs / 197e12
    assert readers.read(spec, obs) == pytest.approx(100 * least / 0.035)
    spec["kernels"][0]["pattern"] = "renamed_kernel"
    with pytest.raises(tr.PatternMatchedNothing):
        readers.read(spec, obs)
    # the whole step against the peak while a chip was busy: 2 steps of
    # 100 tokens at 1,409,335,296 FLOPs a token in 55 + 10 ms of device
    # time on the two chips
    obs["tokens_per_step"] = 100
    assert readers.read({"kind": "derived", "fn": "step_mfu_pct"}, obs) \
        == pytest.approx(100 * 2 * 100 * 1_409_335_296 / (0.065 * 197e12))
    assert readers.read({"kind": "derived", "fn": "step_mfu_pct"},
                        dict(obs, trace=None)) is None


def _four_chip_trace(server_chip_busy: float):
    """The dp2x2 layout as the v5e showed it (PERF.md section 3): every
    chip runs ``jit_local``; Adam (``jit_f``) and the merge run on chip 0
    alone, the mesh reduce (``jit_body``) on chips 0 and 1; the profiler
    lost one of chip 0's two ``jit_local`` executions.  Chip 1 is the
    busiest unless chip 0's ops add up to ``server_chip_busy``."""
    ms = 1e-3
    t = {"/host:CPU": {"python": [
        Event("bench:window:open", 0.0, 1 * ms),
        Event("bench:window:close", 1.0, 1 * ms)]}}
    for n in range(4):
        mods = [Event("jit_local(1)", 0.1, 0.160),
                Event("jit_local(1)", 0.5, 0.170)][n == 0:]
        ops = [Event("fusion.1", e.start, e.dur) for e in mods]
        if n == 0:
            mods += [Event("jit_f(2)", 0.30, 0.010),
                     Event("jit__lambda(3)", 0.32, 0.002),
                     Event("jit_body(4)", 0.35, 0.030)]
            ops += [Event("add.1", 0.30, server_chip_busy - 0.170)]
        if n == 1:
            mods += [Event("jit_body(4)", 0.35, 0.006)]
            ops += [Event("psum.1", 0.35, 0.006)]
        t[f"/device:TPU:{n}"] = {tr.MODULES_LINE: mods, tr.OPS_LINE: ops}
    return t


@pytest.mark.parametrize("server_chip_busy", [0.2, 0.4])
def test_server_and_grad_metrics_do_not_follow_the_busiest_chip(
        server_chip_busy):
    """The committed metric files read the same numbers whether chip 0
    (which holds every server) or chip 1 (which holds none of Adam and
    merge) happens to be the busiest chip."""
    import json
    from pathlib import Path

    t = _four_chip_trace(server_chip_busy)
    obs = dict(_obs(t), steps=2)
    busiest = max(obs["busy"], key=obs["busy"].get)
    assert busiest == (CHIP1 if server_chip_busy < 0.33 else CHIP0)
    files = Path(__file__).resolve().parents[1] / "layer_metrics"
    spec = {n: json.loads((files / f"{n}.json").read_text())
            for n in ("server_dev_ms_per_step", "grad_dev_ms_per_step")}
    # Adam 10 + merge 2 + mesh reduce 30 on chip 0, + 6 on chip 1
    assert readers.read(spec["server_dev_ms_per_step"], obs) == \
        pytest.approx((10 + 2 + 30 + 6) / 2)
    # a chip with every execution of the program, not chip 0's half
    assert readers.read(spec["grad_dev_ms_per_step"], obs) == \
        pytest.approx((160 + 170) / 2)
    # a codec program ran on no chip at all: an error, never a 0
    with pytest.raises(tr.PatternMatchedNothing):
        readers.read({"kind": "trace_module", "chips": "sum",
                      "pattern": r"^jit_enc\("}, obs)


def test_readers_return_nothing_without_a_trace():
    obs = dict(_obs(_trace()), trace=None)
    for spec in ({"kind": "derived", "fn": "device_idle_pct"},
                 {"kind": "trace_module", "pattern": "x"},
                 {"kind": "trace_kernel", "kernels": []}):
        assert readers.read(spec, obs) is None


def test_host_readers():
    obs = {"phases": {"grad": [0.3, 0.1, 0.2, 0.4]}, "steps": 4,
           "counters": {"h2d_bytes": 6_000_000, "d2h_bytes": 2_000_000},
           "compiles": 0}
    assert readers.read({"kind": "measure_phase", "phase": "grad",
                         "reduce": "p50"}, obs) == pytest.approx(0.25)
    assert readers.read({"kind": "measure_phase", "phase": "grad",
                         "reduce": "p100"}, obs) == pytest.approx(0.4)
    assert readers.read({"kind": "measure_phase", "phase": "absent",
                         "reduce": "p50"}, obs) is None
    with pytest.raises(ValueError):
        readers.read({"kind": "measure_phase", "phase": "grad",
                      "reduce": "mean"}, obs)
    assert readers.read({"kind": "stats_counter", "scale": 1e-6,
                         "keys": ["h2d_bytes", "d2h_bytes"],
                         "per_step": True}, obs) == pytest.approx(2.0)
    assert readers.read({"kind": "stats_counter", "keys": ["absent"]},
                        obs) is None
    assert readers.read({"kind": "derived", "fn": "compiles_in_window"},
                        obs) == 0


def test_load_reads_a_recorded_profile(tmp_path):
    """A real .xplane.pb written by jax.profiler on the CPU: the
    benchmark's spans come back on one clock; there is no chip plane."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib.harness import SpanMeasure, _profiler_options

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    m = SpanMeasure("w0")
    jax.profiler.start_trace(str(tmp_path), profiler_options=_profiler_options())
    with jax.profiler.TraceAnnotation("bench:window:open"):
        pass
    with m.phase("grad"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench:window:close"):
        pass
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert tr.chips(t) == []
    t0, t1 = tr.window(t)
    (span,) = tr.host_spans(t)
    assert span.name == "w0:grad" and t0 <= span.start
    assert span.start + span.dur <= t1
    assert span.dur == pytest.approx(m.spans[0][2] - m.spans[0][1], abs=5e-3)


def test_reduce_trace_fills_the_line_and_refuses_an_idle_chip(monkeypatch):
    from benchmark.lib import harness, spans

    monkeypatch.setattr(tr, "load", lambda _dir: _trace())
    # a program without the tracer's annotations: no geomx: event
    monkeypatch.setattr(spans, "load", lambda _dir: [])
    obs = {}
    seen = harness.reduce_trace("unused", obs, on_chip=True)
    assert "host_spans" not in seen["breakdown"] and "spans" not in obs
    assert seen["device"]["window_s"] == pytest.approx(0.095)
    # averaged over the chips used: chip 0 busy 55 ms, chip 1 10 ms
    assert seen["device"]["busy_s"] == pytest.approx((0.055 + 0.010) / 2)
    assert seen["idle_pct_per_chip"][CHIP1] == pytest.approx(
        100 * (1 - 0.010 / 0.095))
    assert len(seen["breakdown"]["device_ops"]) <= 10
    assert len(seen["breakdown"]["idle_gaps"]) <= 10
    assert obs["busy"][CHIP0] == pytest.approx(0.055)
    # the end-to-end chip_ms_per_step of an untraced run: the same busy
    # seconds, averaged over the chips, over the traced steps; no
    # breakdown is worked out there
    assert harness.chip_ms_per_step(obs["busy"], 5) == pytest.approx(
        1e3 * (0.055 + 0.010) / 2 / 5)
    # ... and the program's spans are not even looked for
    monkeypatch.setattr(spans, "load", lambda _dir: 1 / 0)
    assert "breakdown" not in harness.reduce_trace(
        "unused", {}, on_chip=True, traced=False)
    monkeypatch.setattr(spans, "load", lambda _dir: [])
    # a trace with no chip plane: nothing to reduce off the chip, an
    # error on it
    host_only = {k: v for k, v in _trace().items() if k.startswith("/host")}
    monkeypatch.setattr(tr, "load", lambda _dir: host_only)
    assert harness.reduce_trace("unused", {}, on_chip=False) == {}
    with pytest.raises(SystemExit):
        harness.reduce_trace("unused", {}, on_chip=True)


def test_reduce_trace_hands_the_readers_the_programs_spans(monkeypatch):
    """The traced run: the ``geomx:`` spans go into ``obs`` and into two
    more lists of the breakdown, of the contract's ten rows at most; off
    the chip too, with the window from the host's marks."""
    from benchmark.lib import harness, spans

    ms = 1e-3
    found = [spans.Span("server:0@p0", f"s{i}", "t1", (10 + 5 * i) * ms,
                        4 * ms, {}) for i in range(14)]
    found.append(spans.Span("server:0@p0", "before", "t1", 0.0, 2 * ms, {}))
    monkeypatch.setattr(spans, "load", lambda _dir: found)
    for trace, on_chip in ((_trace(), True), (
            {k: v for k, v in _trace().items() if k.startswith("/host")},
            False)):
        monkeypatch.setattr(tr, "load", lambda _dir, t=trace: t)
        obs = {"steps": 2}
        seen = harness.reduce_trace("unused", obs, on_chip=on_chip)
        assert obs["spans"] is found
        assert (obs["t0"], obs["t1"]) == pytest.approx((0.005, 0.100))
        assert seen["spans_in_window_per_step"] == 14 / 2
        rows = seen["breakdown"]["host_spans"]
        assert len(rows) == 10 and rows[0][1] == pytest.approx(0.004 / 2)
        idle = seen["breakdown"]["idle_by_span"]
        # off the chip every second is idle and every span is in it
        assert len(idle) == (5 if on_chip else 10)
        assert idle[-1][0] == spans.NO_SPAN
        assert ("device_ops" in seen["breakdown"]) == on_chip
        assert ("device" in seen) == on_chip


# names read by hand from real traces on the v5e (PERF.md section 3)
SEEN_MODULES = {
    "jit_grad_fn(3222801134538832802)": "grad_dev_ms_per_step",
    "jit_local(4506948524681775281)": "grad_dev_ms_per_step",
    "jit_enc(12624580337269507270)": "codec_dev_ms_per_step",
    "jit__scatter(14027924021195068632)": "codec_dev_ms_per_step",
    "jit_f(6637280702383815194)": "server_dev_ms_per_step",
    "jit__lambda(4199301853649907021)": "server_dev_ms_per_step",
    "jit_body(5172561683473037485)": "server_dev_ms_per_step",
    "jit_gather(9114275001582854465)": "server_dev_ms_per_step",
    "jit_broadcast_in_dim(14229292825365299168)": "server_dev_ms_per_step",
}
SEEN_OPS = {
    "jvp_jit_flash_attention__.6 bf16[4,16,2048,128]": "flash_fwd",
    "flash_mha_bwd_dkv_block_q_major_128_block_q_128_block_k_major_128_"
    "block_k_128.11 bf16[4,16,2048,128]": "flash_bwd_dkv",
    "flash_mha_bwd_dq_block_q_major_128_block_k_major_128_block_k_128.8 "
    "bf16[4,16,2048,128]": "flash_bwd_dq",
    "sort f32[16777216]": None,
    "fusion.98 f32[2048]": None,
    "psum.7 f32[1,16777216]": None,
}


def test_the_committed_patterns_split_the_names_seen_on_the_chip():
    import json
    import re
    from pathlib import Path

    metrics = Path(__file__).resolve().parents[1] / "layer_metrics"
    specs = {p.stem: json.loads(p.read_text()) for p in metrics.glob("*.json")}
    by_module = {n: s["pattern"] for n, s in specs.items()
                 if s["kind"] == "trace_module"}
    for name, owner in SEEN_MODULES.items():
        hits = [n for n, rx in by_module.items() if re.search(rx, name)]
        assert hits == [owner], (name, hits)
    kernels = specs["attn_roofline_pct"]["kernels"]
    for name, fn in SEEN_OPS.items():
        hits = [k["fn"] for k in kernels if re.search(k["pattern"], name)]
        assert hits == ([fn] if fn else []), (name, hits)
