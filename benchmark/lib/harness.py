"""One run of one cell: load, warm up, measure, check, report.

The structure is ``chip_smoke.py``'s ``train_phase`` (2 parties x 1
worker + the global tier in one ``Simulation``, ``Trainer.fit`` on a
thread per worker), driven by the cell's data files: the configuration
gives sizes, topology and layout; the traffic mix gives the cluster's
``Config`` fields (sync mode, codec parameters), the ``Trainer``'s
arguments (optimizer, compression, HFA), an optional ``FaultPolicy``
(modeled WAN), the data stream, the warm-up and the ``correct`` rule;
each per-layer metric is a file read by a reader of its ``kind``; the
model is the configuration's ``family``, a directory of files
(``lib/family.py``): the system's entry, the plain reference, the counts
and the keys it needs.  From the program the harness takes only the
system under test, the ``measure=`` hook of ``Trainer.fit``,
``JaxBackend.stats()``, ``Simulation.wan_bytes()`` and, in a traced run,
the spans of its tracer (``Config.trace_sample_every``).

``--trace 1`` runs the profiler over a window of the mix's
``trace_steps`` and prints the per-layer metrics.  ``--trace 0`` prints
the end-to-end ones over the whole window; where one of them is read
from the device trace (``chip_ms_per_step``), the profiler covers the
window's first ``trace_steps`` and is stopped at the gate's mark.

What is NOT files, and needs an edit here: a new ``layout.kind`` (how
parties map onto chips), a worker loop that ``Trainer.fit`` does not
reach (``run_worker_overlapped``), and a new ``correct.mode``.  No cell
needs another of either, and code that no cell runs is not measured.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from . import data, family, readers, spans, trace as tr, validate
from .gate import StepGate

# a hung barrier is a run that never exits: past this every thread's
# stack is dumped and the process dies (the driver allows a compiling
# run 1200 s)
DEADLINE_S = 1150
# the contract's most for a list of ``breakdown``; idle_by_span's last
# row is "no span open"
BREAKDOWN_ROWS = 10
COUNTER_KEYS = ("h2d_bytes", "d2h_bytes", "codec_d2h_bytes",
                "codec_host_bytes")


class BenchmarkError(SystemExit):
    """The run cannot be made; exits non-zero and prints no result."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------

def load_cell(root: Path, name: str) -> dict:
    """Everything the manifest and the data files say about one cell."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload named {name!r}; the manifest "
                             f"has {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    paths = manifest["paths"]
    tfile = validate.traffic_file(root, paths, cell["traffic"])
    if tfile is None or tfile.suffix != ".json":
        raise BenchmarkError(f"no traffic file {cell['traffic']}.json "
                             f"under {paths}")
    reported = lambda m: name in validate.metric_cells(m, cells)  # noqa: E731
    layer = []
    for m in filter(reported, manifest["per_layer"]):
        f = validate.layer_metric_file(root, paths, m["name"])
        if f is None:
            raise BenchmarkError(f"per-layer metric {m['name']!r} has no "
                                 "reader file")
        layer.append({**json.loads(f.read_text()), "name": m["name"]})
    return {"cell": cell, "paths": paths,
            "config": json.loads((root / entry["file"]).read_text()),
            "traffic": json.loads(tfile.read_text()),
            "end_to_end": list(filter(reported, manifest["end_to_end"])),
            "per_layer": layer}


def load_peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchmarkError(
            f"no peak table entry for device_kind {device_kind!r}; add it "
            "to benchmark/lib/peaks.json with its source")
    return table[device_kind]


# ---------------------------------------------------------------------------
# observation
# ---------------------------------------------------------------------------

class SpanMeasure:
    """What ``Trainer.fit(measure=...)`` is handed: ``utils.Measure``'s
    interface, with every phase also a ``TraceAnnotation`` so that the
    worker's phases sit on the profiler's clock beside the device ops."""

    def __init__(self, worker: str):
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self.worker = worker
        self.spans: list = []          # (phase, start, end), perf_counter
        self.starts: list = []         # when each step began

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            with self._annotate(f"{tr.SPAN_PREFIX}{self.worker}:{name}"):
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def step_start(self):
        self.starts.append(time.perf_counter())

    def step_end(self):
        pass


class CompileCounter:
    """Programs built or loaded (``backend_compile_duration`` fires for a
    persistent-cache hit too) and persistent-cache hits, process-wide."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _snapshot(sim, compiles: CompileCounter) -> dict:
    servers = sim.local_servers + sim.global_servers
    stats = [s.stats() for s in servers]
    return {"wan": sim.wan_bytes()["wan_send_bytes"],
            "compiles": compiles.compiles,
            "counters": {k: sum(st[k] for st in stats)
                         for k in COUNTER_KEYS if all(k in st for st in stats)}}


def _profiler_options():
    import jax

    po = jax.profiler.ProfileOptions()
    # the workers' and servers' Python frames would swamp the trace and
    # slow the host path that is being measured
    po.python_tracer_level = 0
    po.host_tracer_level = 1       # TraceAnnotation spans only
    po.enable_hlo_proto = False
    return po


def compare_losses(losses, ref, rule: dict, warmup: int):
    """The traffic mix's ``correct`` rule on the system's losses (every
    step of the run) against the reference's (the warm-up steps):
    (what is wrong, the steps that are, each number compared beside its
    limit or limits)."""
    failures, bad, compared = [], set(), {}
    tol = rule["loss_tol"]
    if rule["mode"] == "match_reference":
        agree = range(warmup)
    elif rule["mode"] == "band":
        # before any update the two must agree; at step K-1 a codec that
        # sends part of each gradient sits, with band_margin to spare at
        # either end, between the reference there (the whole gradient
        # applied) and the point where it has learned a share of what
        # the reference had by then: no learning at all is outside
        agree = range(1)
        fall = ref[0] - ref[warmup - 1]
        lo = ref[warmup - 1] - rule["band_margin"]
        hi = (ref[0] - rule["band_min_share_of_reference_fall"] * fall
              + rule["band_margin"])
        compared[f"loss_step{warmup - 1}_in_band"] = [
            float(losses[warmup - 1]), lo, hi]
        if not lo <= losses[warmup - 1] <= hi:
            bad.add(warmup - 1)
            failures.append(f"step {warmup - 1}: loss "
                            f"{losses[warmup - 1]:.5f} outside the band "
                            f"[{lo:.5f}, {hi:.5f}]")
    else:
        raise BenchmarkError(f"unknown correct.mode {rule['mode']!r}")
    for k in agree:
        compared[f"loss_gap_step{k}"] = [float(abs(losses[k] - ref[k])), tol]
        if not abs(losses[k] - ref[k]) <= tol:
            bad.add(k)
            failures.append(f"step {k}: loss {losses[k]:.5f} against the "
                            f"reference's {ref[k]:.5f}, over {tol}")
    if rule["require_falling"]:
        # fresh batches every step: over K steps the batch-to-batch noise
        # can hide the fall, over the whole run it cannot
        compared["loss_fall_over_run"] = [float(losses[0] - losses[-1]), 0.0]
        if not losses[-1] < losses[0]:
            failures.append(f"loss did not fall over the run: first "
                            f"{losses[0]:.5f}, last {losses[-1]:.5f}")
    return failures, bad, compared


def chip_ms_per_step(busy: dict, steps: int) -> float:
    """Device time a step costs: the seconds in which an operation ran,
    averaged over the chips used, over the traced steps."""
    return 1e3 * sum(busy.values()) / len(busy) / steps


def reduce_trace(trace_dir: str, obs: dict, on_chip: bool,
                 traced: bool = True) -> dict:
    """Load the run's trace into ``obs`` for the readers; returns what the
    result line carries from it (``device`` additions, ``breakdown``,
    every chip's idle share).  Off the chip there is no device plane and
    nothing of the device is reduced.  ``traced`` is the ``--trace 1``
    run: only there are the breakdowns worked out and the program's
    ``geomx:`` spans loaded (its tracer is on in that run alone), the
    window's edges with them, off the chip too."""
    trace = tr.load(trace_dir)
    planes = tr.chips(trace)
    if not planes and on_chip:
        raise BenchmarkError("the trace has no /device:TPU:<n> plane: "
                             "no operation ran on a chip")
    t0, t1 = obs["t0"], obs["t1"] = tr.window(trace)
    seen: dict = {}
    if planes:
        busy = {p: tr.busy_seconds(tr.device_ops(trace, p), t0, t1)
                for p in planes}
        obs.update(trace=trace, busy=busy)
        seen = {"device": {"busy_s": sum(busy.values()) / len(planes),
                           "window_s": t1 - t0},
                "idle_pct_per_chip": {p: 100.0 * (1 - b / (t1 - t0))
                                      for p, b in busy.items()}}
        if traced:
            # over every chip: no entry depends on which chip was busiest
            seen["breakdown"] = {
                "device_ops": tr.top_device_ops(trace, t0, t1),
                "idle_gaps": tr.idle_gaps(trace, t0, t1)}
    found = spans.load(trace_dir) if traced else []
    if found:
        obs["spans"] = found
        every_op = [e for p in planes for e in tr.device_ops(trace, p)]
        seen.setdefault("breakdown", {}).update(
            host_spans=spans.host_spans(found, t0, t1, obs["steps"],
                                        n=BREAKDOWN_ROWS),
            idle_by_span=spans.idle_by_span(
                found, tr.busy_intervals(every_op, t0, t1), t0, t1,
                n=BREAKDOWN_ROWS - 1))
        seen["spans_in_window_per_step"] = sum(
            t0 <= s.start <= t1 for s in found) / obs["steps"]
    return seen


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             rehearse: bool, t_start: float) -> dict:
    """Run the cell; returns the result object of the last line."""
    spec = load_cell(root, name)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    layout = config["layout"]

    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearse and dev.platform != "tpu":
        raise BenchmarkError(
            f"the benchmark needs a TPU: jax found platform="
            f"{dev.platform!r} ({dev.device_kind}); nothing was measured "
            "(--rehearse runs the control flow on a CPU at a tiny size)")
    if len(devices) < cell["chips"]:
        raise BenchmarkError(
            f"cell {name!r} needs {cell['chips']} chips; jax found "
            f"{len(devices)}")
    devices = devices[:cell["chips"]]
    peaks = None if rehearse else load_peaks(dev.device_kind)

    if layout["kind"] == "party_dp_mesh":
        # a sub-slice program loaded back from the persistent cache halts
        # the chip (parallel/dp.py party_meshes refuses it): cache off
        jax.config.update("jax_enable_compilation_cache", False)
        cache = "off for the sub-slice layout"
    elif rehearse:
        cache = "untouched in a rehearsal"
    else:
        from geomx_tpu.utils.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        # the servers' programs compile in well under a second each; at
        # the default threshold they would compile again in every run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = CompileCounter()
    say(f"cell {name} seed {seed} on {len(devices)} x {dev.device_kind} "
        f"({dev.platform}); compile cache {cache}")

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.parallel.dp import make_party_step, party_meshes
    from geomx_tpu.training import Trainer
    from geomx_tpu.transport.van import FaultPolicy

    # the model is the configuration's family: its files, found by name
    fam = family.load(root, spec["paths"], config["family"])
    model = {k: config[k] for k in (*family.MODEL_KEYS, *fam.needs["keys"])}
    compute_dtype = config["compute_dtype"]
    cluster = dict(traffic.get("config", {}))
    if trace:
        # the program's tracer, whose spans the program_span readers and
        # the host breakdowns read: on in the traced run alone
        cluster["trace_sample_every"] = 1
    if rehearse:
        tiny = json.loads(
            (Path(__file__).parent / "rehearsal.json").read_text())
        model.update(fam.needs["rehearsal"])
        compute_dtype = tiny["compute_dtype"]
        cluster.update(tiny["config"])
        traffic["trainer"]["optimizer"]["lr"] = tiny["lr"]
    init, grad_fn = fam.system.build(model, compute_dtype)
    parties = config["topology"]["parties"]
    per_party = config["topology"]["workers_per_party"]
    # worker i is worker i % per_party of party i // per_party
    workers = [(p, w) for p in range(parties) for w in range(per_party)]
    if layout["kind"] == "party_dp_mesh":
        per = layout["chips_per_party"]
        meshes = party_meshes(parties, devices=devices[:parties * per])
        grad_fns = [make_party_step(grad_fn, m) for m in meshes]
    elif layout["kind"] == "shared_chip":
        per = 1
        grad_fns = [grad_fn] * parties
    else:
        raise BenchmarkError(f"unknown layout kind {layout['kind']!r}")
    batch_per_chip = config["batch_per_chip_per_party"]
    batch = batch_per_chip * per
    seq = model["max_seq"]
    tokens_per_step = len(workers) * batch * seq

    # weights on the device in one jitted call from the seed, handed to
    # the workers as host arrays: that is what Trainer / kv.init take
    params = jax.tree_util.tree_map(
        np.asarray, jax.jit(init)(jax.random.PRNGKey(seed)))
    # jax keeps a jitted function's program loaded for as long as the
    # function lives: the initialiser's would stay on the chip for the
    # whole run (6.6 MB of peak_hbm_GB at the flagship's sizes)
    del init
    pool = data.batch_pool(traffic["data"], seed, len(workers), batch, seq,
                           model["vocab"])
    warmup = int(traffic["warmup_steps"])
    if warmup < 1:
        raise BenchmarkError("warmup_steps must be at least 1: the warm-up "
                             "compiles, and is what the reference checks")

    # the mix's "config" group goes straight into the cluster's Config,
    # its "fault" group (a modeled WAN) into a FaultPolicy
    sim = Simulation(
        Config(topology=Topology(num_parties=parties,
                                 workers_per_party=per_party), **cluster),
        fault=FaultPolicy(**traffic["fault"]) if "fault" in traffic else None)
    edges: dict = {}
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
    # the profiler runs in the traced run, whose window is the mix's
    # trace_steps, and in an untraced run of a cell with an end-to-end
    # metric read from the device trace: there it covers the first
    # trace_steps of the whole window and is stopped at the gate's mark
    trace_steps = int(traffic["trace_steps"])
    profile = trace or any(m["source"] == "device_trace"
                           for m in spec["end_to_end"])
    profiling: dict = {"on": False, "steps": 0}

    def stop_profile(steps: int) -> None:
        if profiling["on"]:
            with jax.profiler.TraceAnnotation(
                    tr.SPAN_PREFIX + tr.WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()
            profiling.update(on=False, steps=steps)

    def on_open():
        edges["open"] = _snapshot(sim, compiles)
        # every worker between steps: weights, the gradients held, the
        # servers' state, no activations
        edges["resident"] = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                             for d in devices]
        if profile:
            with jax.profiler.TraceAnnotation(
                    tr.SPAN_PREFIX + tr.WINDOW_OPEN):
                pass

    def on_close():
        edges["close"] = _snapshot(sim, compiles)
        stop_profile(gate.steps_in_window)

    gate = StepGate(len(workers), warmup, seconds,
                    max_steps=trace_steps if trace else None,
                    on_open=on_open, on_close=on_close,
                    mark_steps=trace_steps if profile and not trace else None,
                    on_mark=lambda: stop_profile(trace_steps),
                    barrier_timeout=DEADLINE_S)
    measures = [SpanMeasure(f"w{i}") for i in range(len(workers))]
    out: dict = {"losses": {}, "params": {}, "errors": []}

    def batches(i: int):
        k = 0
        while gate.admit(k):
            if profile and i == 0 and k == warmup - 1:
                # a whole step before the window opens: the device
                # tracer is then live on every chip when it does
                jax.profiler.start_trace(
                    trace_dir.name, profiler_options=_profiler_options())
                profiling["on"] = True
            x = pool[k % len(pool)][i]
            yield x, x
            k += 1

    def worker_main(i: int) -> None:
        party, rank = workers[i]
        try:
            # the mix's "trainer" group: optimizer, compression, hfa_k1
            trainer = Trainer(sim.worker(party, rank), params,
                              grad_fns[party], **traffic["trainer"])
            hist = trainer.fit(batches(i), 10 ** 9, measure=measures[i])
            out["params"][i] = trainer.params
            out["losses"][i] = [loss for loss, _acc in hist]
        except BaseException as e:   # re-raised on the main thread below
            out["errors"].append(e)
            gate.abort()
            raise

    try:
        threads = [threading.Thread(target=worker_main, args=(i,),
                                    name=f"bench-worker-{i}", daemon=True)
                   for i in range(len(workers))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(DEADLINE_S)
        if out["errors"]:
            raise out["errors"][0]
        if any(t.is_alive() for t in threads):
            raise BenchmarkError("a worker hung")
        stats = {str(s.po.node): s.stats()
                 for s in sim.local_servers + sim.global_servers}
        mem = [d.memory_stats() or {} for d in devices]
    finally:
        sim.shutdown()

    # ---- the window ---------------------------------------------------
    steps, window_s = gate.steps_in_window, gate.window_s
    opened, closed = edges["open"], edges["close"]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    values = {
        "tokens_per_s": steps * tokens_per_step / window_s,
        "wan_MB_per_step": (closed["wan"] - opened["wan"]) / steps / 1e6,
        "peak_hbm_GB": peak / 1e9,
    }
    in_window = [[s for s in m.spans
                  if s[1] >= gate.t_open and s[2] <= gate.t_close]
                 for m in measures]
    # a step lasts from its start to the next one's (the window's last,
    # to the window's end): the iterator and the history are in it
    step_s = [np.diff([t for t in m.starts if t >= gate.t_open]
                      + [gate.t_close]).tolist() for m in measures]
    phases: dict = {}
    for spans in in_window:
        for phase, t0, t1 in spans:
            phases.setdefault(phase, []).append(t1 - t0)
    obs = {"steps": steps, "window_s": window_s,
           "tokens_per_step": tokens_per_step, "phases": phases,
           "counters": {k: closed["counters"][k] - opened["counters"][k]
                        for k in closed["counters"]},
           "compiles": closed["compiles"] - opened["compiles"],
           "model": model, "counts": fam.counts, "chips": len(devices),
           "batch_per_chip": batch_per_chip, "peaks": peaks,
           "trace": None, "t0": None, "t1": None, "busy": None,
           "spans": None}
    say(f"window: {steps} steps in {window_s:.3f}s, "
        f"{values['tokens_per_s']:.1f} tokens/s, WAN "
        f"{values['wan_MB_per_step']:.3f} MB/step, peak "
        f"{values['peak_hbm_GB']:.3f} GB (resident at the window's "
        f"opening, per chip: "
        + " ".join(f"{b / 1e9:.3f}" for b in edges["resident"])
        + f"), compiles in window {obs['compiles']}")

    # ---- correct: the system's checks, then the plain reference --------
    losses = np.mean([out["losses"][i] for i in range(len(workers))], axis=0)
    failures = []
    bad_steps = {int(k) for k in np.flatnonzero(~np.isfinite(losses))}
    platform = devices[0].platform
    for node, st in stats.items():
        if st["merge_backend"] != "jax" or st["merge_device"] != platform:
            failures.append(f"{node} merged on {st['merge_backend']}/"
                            f"{st['merge_device']}, not jax/{platform}")
        if st["h2d_bytes"] <= 0:
            failures.append(f"{node}: no push ever reached the device")
        if st["codec_host_bytes"] != 0:
            failures.append(f"{node}: codec_host_bytes "
                            f"{st['codec_host_bytes']}, not 0")
    compared = {"compiles_in_window": [obs["compiles"], 0]}
    if obs["compiles"]:
        failures.append(f"{obs['compiles']} compilation(s) inside the window")
    rule = traffic["correct"]
    if rule["require_party_parity"]:
        # the repo's own FSA oracle: every worker holds the same weights
        first = jax.tree_util.tree_leaves(out["params"][0])
        if not all(np.array_equal(np.asarray(a), np.asarray(b))
                   for i in range(1, len(workers)) for a, b in zip(
                       first, jax.tree_util.tree_leaves(out["params"][i]))):
            failures.append("the workers' parameters differ after the "
                            "window")
    # every party's model up and down the WAN once a step, uncompressed
    dense_mb = 2 * parties * 4 * fam.counts.n_params(model) / 1e6
    if "wan_dense_share" in rule:
        # a codec's bytes lie between a floor (sending nothing is not a
        # faster codec) and a ceiling (it must compress)
        lo, hi = (share * dense_mb for share in rule["wan_dense_share"])
        compared["wan_MB_per_step"] = [values["wan_MB_per_step"], lo, hi]
        if not lo < values["wan_MB_per_step"] < hi:
            failures.append(f"WAN {values['wan_MB_per_step']:.2f} MB/step "
                            f"is not between {lo:.2f} and {hi:.2f} (shares "
                            f"{rule['wan_dense_share']} of the uncompressed "
                            f"{dense_mb:.1f})")
    # nothing of the system may be alive beside the float32 reference
    del sim, out["params"], grad_fns, grad_fn
    gc.collect()
    if layout["kind"] == "party_dp_mesh" and not rehearse:
        # the sub-slice programs are done with: the reference's one-chip
        # programs on chip 0 may come from the cache, as in a one-chip cell
        from jax.experimental.compilation_cache import compilation_cache
        from geomx_tpu.utils.compile_cache import enable_compile_cache

        jax.config.update("jax_enable_compilation_cache", True)
        enable_compile_cache()
        compilation_cache.reset_cache()
    say("GB in use per chip before the reference: " + " ".join(
        f"{(d.memory_stats() or {}).get('bytes_in_use', 0) / 1e9:.2f}"
        for d in devices))
    t_ref = time.perf_counter()
    ref = fam.reference.train(
        params, [pool[k].reshape(-1, seq) for k in range(warmup)],
        lr=traffic["trainer"]["optimizer"]["lr"], device=devices[0])
    say("loss, system   : " + " ".join(f"{x:.5f}" for x in losses[:warmup])
        + f"   (last of the window {losses[-1]:.5f})")
    say("loss, reference: " + " ".join(f"{x:.5f}" for x in ref)
        + f"   ({time.perf_counter() - t_ref:.1f}s)")
    more, bad, numbers = compare_losses(losses, ref, rule, warmup)
    failures += more
    bad_steps |= bad
    compared.update(numbers)

    # ---- per-layer metrics (the traced run) ----------------------------
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak}
    result = {"correct": not failures, "attempted": len(losses),
              "failed": len(bad_steps)}
    if profile:
        seen = reduce_trace(trace_dir.name, obs, on_chip=not rehearse,
                            traced=trace)
        device.update(seen.pop("device", {}))
        result.update(seen)
        if obs["busy"]:
            values["chip_ms_per_step"] = chip_ms_per_step(
                obs["busy"], profiling["steps"])
    if trace:
        values = {}
        for m in spec["per_layer"]:
            v = readers.read(m, obs)
            if v is not None and math.isfinite(v):
                values[m["name"]] = v
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
    trace_dir.cleanup()
    result["device"] = device
    # everything outside the measured window is set-up: backend start,
    # weights, Simulation, compile and warm-up before it; shutdown and
    # the reference check after it
    if not trace:
        values["setup_s"] = time.perf_counter() - t_start - window_s
    prefix = "rehearsal_" if rehearse else ""
    result["metrics"] = {prefix + k: {"value": float(v), "unit": units[k]}
                         for k, v in values.items() if k in units}
    result.update(
        cell=name, seed=seed, steps=steps, window_s=window_s,
        tokens_per_step=tokens_per_step, traced_steps=profiling["steps"],
        mark_pause_s=gate.paused_s,
        resident_GB_at_open=[b / 1e9 for b in edges["resident"]],
        losses=[float(x) for x in losses[:warmup]] + [float(losses[-1])],
        reference_losses=[float(x) for x in ref],
        step_s=step_s,
        compiles_total=compiles.compiles, cache_hits=compiles.cache_hits,
        failures=failures, compared=compared)
    # the last lines on standard error: what is wrong, and each number
    # compared beside its limit or limits (the result line ends in them)
    for f in failures:
        say("NOT CORRECT: " + f)
    say("compared: " + "; ".join(
        f"{k} {v[0]:.6g} against " + " to ".join(f"{x:.6g}" for x in v[1:])
        for k, v in compared.items()))
    return result
