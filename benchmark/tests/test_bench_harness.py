"""The command end to end off the chip: it refuses to measure without a
TPU, and the rehearsal drives load, warm-up, window, stop, the reference
check and the last line's shape in every cell, printing no value under a
device metric's name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(*args, devices=4, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=root)


def test_check_manifest_passes():
    r = _run("--check-manifest")
    assert r.returncode == 0, r.stdout
    assert "valid against the contract" in r.stdout


def test_without_a_tpu_it_fails_by_name_and_prints_no_result():
    r = _run("--workload", "flagship-l4-1chip.fsa", "--seed", "0",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "platform='cpu'" in r.stderr
    assert r.stdout.strip() == ""


def test_too_few_chips_fail():
    r = _run("--workload", "flagship-l4-dp2x2.fsa", "--rehearse",
             "--seconds", "1", devices=2)
    assert r.returncode != 0 and "needs 4 chips" in r.stderr
    assert r.stdout.strip() == ""


def test_an_unknown_cell_fails():
    r = _run("--workload", "nothing", "--rehearse")
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    # a traced window ends after its 6 steps, and is given the time to
    # reach them: the tracer is on there, and under MPQ at these sizes
    # the loss has not always fallen after one or two steps
    r = _run("--workload", cell, "--seed", "5", "--seconds",
             "10" if trace else "2", "--trace", str(trace), "--rehearse")
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["failures"]
    assert line["failed"] == 0 and line["attempted"] == 3 + line["steps"]
    assert line["device"]["platform"] == "cpu"
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"] for m in MANIFEST[group]
                if cell in m.get("workloads", [cell])}
    # never a value under a device metric's name off the chip
    assert line["metrics"] and not declared & set(line["metrics"])
    for name, m in line["metrics"].items():
        assert name.startswith("rehearsal_")
        assert name[len("rehearsal_"):] in declared
        assert set(m) == {"value", "unit"}
    if not trace:
        # a metric read from the device trace has no value off the chip
        off_chip = {m["name"] for m in MANIFEST["end_to_end"]
                    if m["source"] != "device_trace"}
        assert {"rehearsal_" + n for n in declared & off_chip} == \
            set(line["metrics"])
        assert line["steps"] >= 1
        if declared - off_chip:
            # ... but its trace is taken: the first trace_steps of the
            # window, stopped at the gate's mark, the window going on
            assert line["traced_steps"] == min(6, line["steps"])
            assert (line["mark_pause_s"] > 0) == (line["steps"] > 6)
        else:
            assert line["traced_steps"] == 0 == line["mark_pause_s"]
    else:
        # the traced window is the traffic file's trace_steps at most
        assert 1 <= line["steps"] <= 6
        assert line["metrics"]["rehearsal_compiles_in_window"]["value"] == 0


def test_wan_bytes_repeat_exactly_across_seeds():
    """Under plain FSA the WAN bytes a step are a count that no seed
    moves.  (Under MPQ the pull direction carries the union of the
    parties' top-k supports, which depends on the data.)"""
    got = set()
    for seed in (1, 2):
        r = _run("--workload", "flagship-l4-1chip.fsa", "--seed", str(seed),
                 "--seconds", "1", "--rehearse")
        assert r.returncode == 0, r.stderr[-2000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        got.add(line["metrics"]["rehearsal_wan_MB_per_step"]["value"])
    assert len(got) == 1, got


@pytest.mark.parametrize("trace", [0, 1])
def test_a_later_prs_cell_runs_from_files_alone(tmp_path, trace):
    """What PERF.md's open questions ask of a later PR, added as data and
    run: two workers a party (a local merge that merges), a modeled WAN
    on the in-proc fabric, a 95th-percentile phase metric.  No file that
    is there is edited."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "geomx_tpu").symlink_to(ROOT / "geomx_tpu")
    b = tmp_path / "benchmark"
    before = {f: f.read_bytes() for f in b.rglob("*") if f.is_file()}
    cfg = json.loads((b / "configs" / "flagship-l4-1chip.json").read_text())
    cfg["topology"]["workers_per_party"] = 2
    cfg["source"] = "as flagship-l4-1chip, two workers a party"
    (b / "configs" / "flagship-l4-2w.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "fsa.json").read_text())
    mix["fault"] = {"wan_latency_s": 0.002, "wan_bandwidth_bps": 1e9}
    (b / "traffic" / "fsa-wan.json").write_text(json.dumps(mix))
    (b / "layer_metrics" / "pull_wait_s_p95.json").write_text(json.dumps({
        "layer": "Worker loop", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "tokens_per_s",
        "kind": "measure_phase", "phase": "pull_wait", "reduce": "p95"}))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "flagship-l4-2w", "source": cfg["source"],
        "file": "benchmark/configs/flagship-l4-2w.json",
        "reduced": ["n_layers"], "why": "a local merge that merges"})
    doc["workloads"].append({
        "name": "flagship-l4-2w.fsa-wan", "config": "flagship-l4-2w",
        "traffic": "fsa-wan", "chips": 1, "why": "WAN bytes cost time"})
    # tokens_per_s lists its cells; the new one joins them
    speed = next(m for m in doc["end_to_end"] if m["name"] == "tokens_per_s")
    speed["workloads"].append("flagship-l4-2w.fsa-wan")
    doc["per_layer"].append({
        "name": "pull_wait_s_p95", "unit": "s", "better": "lower",
        "source": "program_span", "layer": "Worker loop",
        "moves": "tokens_per_s", "workloads": ["flagship-l4-2w.fsa-wan"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    r = _run("--check-manifest", root=tmp_path)
    assert r.returncode == 0, r.stdout
    r = _run("--workload", "flagship-l4-2w.fsa-wan", "--seed", "5",
             "--seconds", "2", "--trace", str(trace), "--rehearse",
             root=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    # four workers' gradients, merged two to a party, match the plain
    # reference's mean over all four batches
    assert line["correct"] is True, line["failures"]
    assert len(line["step_s"]) == 4
    if trace:
        assert line["metrics"]["rehearsal_pull_wait_s_p95"]["value"] > 0
    else:
        assert line["metrics"]["rehearsal_tokens_per_s"]["value"] == \
            pytest.approx(line["steps"] * 4 * 4 * 32 / line["window_s"])
    for f, content in before.items():
        assert f.read_bytes() == content, f"{f} was edited"


TOY = {
    "system.py": '''"""A toy family's entry: one embedding, one matrix, a head."""


def build(model, compute_dtype):
    import jax
    import jax.numpy as jnp

    v, d = model["vocab"], model["width"]

    def init(key):
        ke, km, kh = jax.random.split(key, 3)
        return {"embed": 0.5 * jax.random.normal(ke, (v, d), jnp.float32),
                "mix": jax.random.normal(km, (d, d), jnp.float32) / d ** 0.5,
                "head": jax.random.normal(kh, (d, v), jnp.float32) / d ** 0.5}

    @jax.jit
    def grad_fn(p, x, _y):
        def loss_fn(p):
            h = jnp.tanh(p["embed"][x].astype(compute_dtype)
                         @ p["mix"].astype(compute_dtype))
            logits = (h @ p["head"].astype(compute_dtype)).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits[:, :-1])
            loss = -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], -1))
            return loss, jnp.mean(jnp.argmax(logits[:, :-1], -1) == x[:, 1:])

        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, acc, g

    return init, grad_fn
''',
    "reference.py": '''"""The toy's own plain reference."""

import jax
import jax.numpy as jnp

from benchmark.lib import plain


def loss_fn(p, t):
    logits = jnp.tanh(p["embed"][t] @ p["mix"]) @ p["head"]
    logp = jax.nn.log_softmax(logits[:, :-1])
    return -jnp.mean(jnp.take_along_axis(logp, t[:, 1:, None], -1))


_sums = plain.summer(loss_fn)


def grads(params, tokens):
    return plain.mean_grads(_sums, params, tokens)


def train(params, batches, lr, device=None):
    return plain.adam_train(_sums, params, batches, lr, device)
''',
    "counts.py": '''def n_params(cfg):
    return cfg["width"] * (2 * cfg["vocab"] + cfg["width"])


def train_flops_per_token(cfg):
    return 6.0 * cfg["width"] * (cfg["width"] + cfg["vocab"])
''',
    "needs.json": json.dumps({
        "keys": ["width"],
        "rehearsal": {"vocab": 64, "width": 16, "max_seq": 32}}),
}


def _add_the_toy(tmp_path, system=TOY["system.py"]):
    """A copy of the benchmark's tree with the toy family, its
    configuration, mix and cell added as new files and entries; returns
    the copy's ``benchmark`` and the files that were there before."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  ".*"))
    (tmp_path / "geomx_tpu").symlink_to(ROOT / "geomx_tpu")
    b = tmp_path / "benchmark"
    before = {f.relative_to(b) for f in b.rglob("*") if f.is_file()}
    (b / "families" / "toy").mkdir()
    for name, text in dict(TOY, **{"system.py": system}).items():
        (b / "families" / "toy" / name).write_text(text)
    (b / "configs" / "toy.json").write_text(json.dumps({
        "source": "a toy, to show that a family is files", "reduced": [],
        "family": "toy", "vocab": 512, "width": 256, "max_seq": 128,
        "compute_dtype": "bfloat16", "batch_per_chip_per_party": 4,
        "topology": {"parties": 2, "workers_per_party": 1},
        "layout": {"kind": "shared_chip", "chips": 1}}))
    mix = json.loads((b / "traffic" / "fsa.json").read_text())
    mix["correct"]["loss_tol"] = 1e-4
    (b / "traffic" / "toy-fsa.json").write_text(json.dumps(mix))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "toy", "source": "a toy, to show that a family is files",
        "file": "benchmark/configs/toy.json", "reduced": [],
        "why": "no attention, no layers: nothing of the flagship's"})
    doc["workloads"].append({
        "name": "toy.toy-fsa", "config": "toy", "traffic": "toy-fsa",
        "chips": 1, "why": "a second family through both tiers"})
    # the one per-layer metric every cell reports lists its cells
    next(m for m in doc["per_layer"] if m["name"] == "compiles_in_window")[
        "workloads"].append("toy.toy-fsa")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    return b, before


@pytest.mark.parametrize("trace", [0, 1])
def test_a_second_family_runs_from_new_files_alone(tmp_path, trace):
    """The room a ``model_config`` PR needs: a family (system entry,
    plain reference, counts, needs), a configuration, a traffic mix with
    its own tolerance and a cell, all NEW files and manifest entries; the
    manifest checks out and a rehearsal is ``correct`` against the toy's
    own reference, with every file that was there byte for byte the
    repo's."""
    b, before = _add_the_toy(tmp_path)
    r = _run("--check-manifest", root=tmp_path)
    assert r.returncode == 0, r.stdout
    r = _run("--workload", "toy.toy-fsa", "--seed", "5", "--seconds", "2",
             "--trace", str(trace), "--rehearse", root=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["failures"]
    # 2 workers x 4 sequences of the toy's rehearsal length, not the
    # flagship's model: its three leaves, its reference's losses
    assert line["tokens_per_step"] == 2 * 4 * 32
    assert len(line["reference_losses"]) == 3
    assert line["losses"][:3] == pytest.approx(line["reference_losses"],
                                               abs=1e-4)
    if trace:
        assert line["metrics"]["rehearsal_compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"rehearsal_wan_MB_per_step",
                                        "rehearsal_setup_s"}
        # FSA's bytes are the toy's counts (both ways, two parties) and,
        # on tensors this small, 6% of message headers
        assert line["metrics"]["rehearsal_wan_MB_per_step"]["value"] == \
            pytest.approx(2 * 2 * 4 * 16 * (2 * 64 + 16) / 1e6, rel=0.1)
    for rel in before:
        assert (b / rel).read_bytes() == (ROOT / "benchmark" / rel) \
            .read_bytes(), f"{rel} differs from the repo's"


# the timed path broken underneath, where the family enters the system:
# what each fault does to the toy's grad_fn
FAULTS = {
    "a step that returns its state unchanged": (
        "return loss, acc, g",
        "return loss, acc, jax.tree_util.tree_map(jnp.zeros_like, g)"),
    "half of the batch left out, the mean taken over the rest": (
        "def grad_fn(p, x, _y):",
        "def grad_fn(p, x, _y):\n        x = x[: x.shape[0] // 2]"),
}
# NOT caught, and not planted here: a gradient scaled by a constant.
# Adam's step does not depend on the gradient's scale and the rules
# compare losses alone (PERF.md section 7).


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    """The rest of a run driven with the timed path broken underneath:
    the same toy cell, its family's ``grad_fn`` with one fault planted,
    against the same plain reference; ``correct`` has to come out false,
    by a number that ``compared`` names."""
    old, new = FAULTS[fault]
    assert TOY["system.py"].count(old) == 1
    _add_the_toy(tmp_path, TOY["system.py"].replace(old, new))
    r = _run("--workload", "toy.toy-fsa", "--seed", "5", "--seconds", "2",
             "--trace", "0", "--rehearse", root=tmp_path)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failures"]
    over = [k for k, (v, *limits) in line["compared"].items()
            if k.startswith("loss_gap") and v > limits[0]]
    assert over, line["compared"]
    assert list(line)[-1] == "compared"
    assert "compared: " in r.stderr.strip().splitlines()[-1]


def test_an_untraced_run_reads_chip_ms_per_step_from_its_first_steps(
        monkeypatch):
    """The one path no CPU has a device for, so a chip plane is put into
    the run's real trace: the untraced run of a cell whose end-to-end
    metrics include one from the device trace profiles the window's
    first trace_steps, stops at the mark, goes on, and prints device
    busy time over THOSE steps; no breakdown is worked out."""
    import time

    from benchmark.lib import harness, trace as tr

    real_load = tr.load
    seen = {}

    def load_with_a_chip(trace_dir):
        trace = real_load(trace_dir)
        t0, t1 = tr.window(trace)
        seen.update(t0=t0, t1=t1)
        # 3 ms before the window (not counted), 12 ms inside it
        trace["/device:TPU:0"] = {tr.OPS_LINE: [
            tr.Event("fusion.1", t0 - 0.004, 0.003),
            tr.Event("fusion.1", t0, 0.004),
            tr.Event("sort f32[16]", t0 + (t1 - t0) / 2, 0.008)]}
        return trace

    monkeypatch.setattr(tr, "load", load_with_a_chip)
    line = harness.run_cell(ROOT, "flagship-l4-1chip.fsa", seed=3,
                            seconds=2.0, trace=False, rehearse=True,
                            t_start=time.perf_counter())
    assert line["correct"] is True, line["failures"]
    assert line["steps"] > 6 and line["traced_steps"] == 6
    assert line["mark_pause_s"] > 0
    assert line["metrics"]["rehearsal_chip_ms_per_step"] == {
        "value": pytest.approx(1e3 * 0.012 / 6), "unit": "ms/step"}
    assert set(line["metrics"]) == {
        "rehearsal_" + m["name"] for m in MANIFEST["end_to_end"]
        if "flagship-l4-1chip.fsa" in m.get("workloads",
                                            ["flagship-l4-1chip.fsa"])}
    # the traced part is shorter than the window, which went on
    assert line["device"]["window_s"] == pytest.approx(
        seen["t1"] - seen["t0"])
    assert line["device"]["window_s"] < line["window_s"]
    assert line["device"]["busy_s"] == pytest.approx(0.012)
    assert "breakdown" not in line
