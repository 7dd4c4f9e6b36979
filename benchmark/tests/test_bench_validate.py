"""The manifest validator: the real manifest passes, and each limit of
the contract that a manifest can break is reported."""

import json
import shutil
from pathlib import Path

import pytest

from benchmark.lib import validate

ROOT = Path(__file__).resolve().parents[2]


def test_the_real_manifest_is_valid():
    assert validate.check(ROOT) == []


@pytest.fixture
def checkout(tmp_path):
    """A copy of the manifest and the benchmark's data files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "bench.py").write_text("")   # a file of the repo outside paths
    return tmp_path


def _edit(checkout, fn):
    doc = json.loads((checkout / "BENCHMARK.json").read_text())
    fn(doc)
    (checkout / "BENCHMARK.json").write_text(json.dumps(doc))
    return validate.check(checkout)


def _set(path, value):
    def fn(doc):
        at = doc
        for k in path[:-1]:
            at = at[k]
        at[path[-1]] = value
    return fn


BREAKS = {
    "source of 201 characters": (_set(["configs", 0, "source"], "x" * 201),
                                 "source must be 1 to 200"),
    "source that is not ASCII": (
        _set(["configs", 0, "source"], "d_model 2048, 16 heads × 128"),
        "source must be 1 to 200"),
    "source with a newline": (_set(["configs", 0, "source"], "a\nb"),
                              "source must be 1 to 200"),
    "empty source": (_set(["configs", 0, "source"], ""),
                     "source must be 1 to 200"),
    "why with a tab": (_set(["workloads", 0, "why"], "a\tb"), "why must be"),
    "why of 201 characters": (_set(["workloads", 1, "why"], "y" * 201),
                              "why must be"),
    "name with a space": (_set(["end_to_end", 0, "name"], "tokens per s"),
                          "is not a name"),
    "name starting with a dot": (_set(["workloads", 0, "name"], ".fsa"),
                                 "is not a name"),
    "name of 65 characters": (_set(["configs", 0, "name"], "n" * 65),
                              "is not a name"),
    "unit with a space": (_set(["end_to_end", 0, "unit"], "tokens per s"),
                          "unit"),
    "unit with a Greek letter": (_set(["per_layer", 0, "unit"], "μs"),
                                 "unit"),
    "unit of 17 characters": (_set(["per_layer", 0, "unit"], "u" * 17),
                              "unit"),
    "better that is neither": (_set(["per_layer", 0, "better"], "faster"),
                               "better must be"),
    "bound over a tenth": (_set(["end_to_end", 0, "bound"], 0.2), "bound"),
    "bound of zero": (_set(["end_to_end", 0, "bound"], 0), "bound"),
    "end-to-end source from the program": (
        _set(["end_to_end", 0, "source"], "program_counter"),
        "host_clock or device_trace"),
    "unknown source": (_set(["per_layer", 0, "source"], "guess"),
                       "is not one of"),
    "run_seconds of 52": (_set(["run_seconds"], 52), "run_seconds"),
    "run_seconds that is a float": (_set(["run_seconds"], 10.5),
                                    "run_seconds"),
    "moves that names no end-to-end metric": (
        _set(["per_layer", 0, "moves"], "grad_s_p50"), "moves"),
    "a key the contract does not have": (
        _set(["end_to_end", 0, "why"], "because"), "not in the contract"),
    "a missing key": (lambda d: d["workloads"][0].pop("why"), "missing key"),
    "a top-level key too many": (_set(["notes"], "x"), "top-level keys"),
    "chips of 2": (_set(["workloads", 0, "chips"], 2), "chips must be"),
    "too many four-chip cells": (_set(["workloads", 0, "chips"], 4),
                                 "ask for 4 chips"),
    "a cell of an unknown configuration": (
        _set(["workloads", 0, "config"], "nothing"), "no configuration"),
    "a cell whose traffic has no file": (
        _set(["workloads", 0, "traffic"], "nothing"), "no traffic file"),
    "a configuration no cell uses": (
        lambda d: d["workloads"].__setitem__(
            2, dict(d["workloads"][2], config="flagship-l4-1chip",
                    traffic="other")),
        "no cell uses it"),
    "the same pair twice": (
        lambda d: d["workloads"].append(
            dict(d["workloads"][0], name="again")), "appears twice"),
    "two metrics of one name": (
        lambda d: d["per_layer"].append(dict(d["per_layer"][0])),
        "appears twice"),
    "no setup_s": (lambda d: d.__setitem__("end_to_end", [
        m for m in d["end_to_end"] if m["name"] != "setup_s"]), "setup_s"),
    "a per-layer metric where the metric it moves is not reported": (
        _set(["per_layer", 0, "workloads"], ["flagship-l4-1chip.fsa"]),
        "where 'tokens_per_s' is not"),
    "a config file outside paths": (
        _set(["configs", 0, "file"], "bench.py"), "must lie under paths"),
    "a reduced width": (_set(["configs", 0, "reduced"], ["d_model"]),
                        "no width may change"),
    "a reduced key ending in _dim": (
        _set(["configs", 0, "reduced"], ["head_dim"]), "no width may"),
    "a per-layer metric in no cell's list once every metric has one": (
        lambda d: [m.__setitem__("workloads", m["workloads"][1:])
                   for m in d["per_layer"]
                   if "flagship-l4-1chip.fsa" in m["workloads"]],
        "reports no per-layer metric"),
    "a reduced key the file lacks": (
        _set(["configs", 0, "reduced"], ["depth"]), "is not in"),
    "a command outside paths": (_set(["command"], ["python3", "bench.py"]),
                                "outside paths"),
    "an absolute command path": (
        _set(["command"], ["python3", "/root/repo/benchmark/run.py"]),
        "starts with /"),
    "a path that leads out": (_set(["paths"], ["../benchmark"]), "paths"),
    "a per-layer metric without a reader": (
        _set(["per_layer", 0, "name"], "unknown_metric"), "no reader file"),
    "a metric file that disagrees": (
        _set(["per_layer", 0, "unit"], "ms"), "the manifest"),
    "a metric in a cell that does not exist": (
        _set(["per_layer", 0, "workloads"], ["nothing"]), "no cell named"),
    "one workload only": (lambda d: d.__setitem__("workloads",
                                                  d["workloads"][:1]),
                          "2 to 24"),
}


@pytest.mark.parametrize("what", sorted(BREAKS))
def test_a_broken_manifest_is_reported(checkout, what):
    fn, expect = BREAKS[what]
    errors = _edit(checkout, fn)
    assert any(expect in e for e in errors), (what, errors)


FILE_BREAKS = {
    "a reduced key with no source value": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.__setitem__("source_values", {}), "under source_values"),
    "a reduced key that was not reduced": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.__setitem__("source_values", {"n_layers": 4}),
        "under source_values"),
    "a source value of a key that is not reduced": (
        "configs/flagship-l4-dp2x2.json",
        lambda d: d["source_values"].__setitem__("d_ff", 16384),
        "which reduced does not list"),
    "a trace metric that follows the busiest chip": (
        "layer_metrics/server_dev_ms_per_step.json",
        lambda d: d.__setitem__("chips", "busiest"), "how the chips combine"),
    "a trace metric that names no chips": (
        "layer_metrics/grad_dev_ms_per_step.json",
        lambda d: d.pop("chips"), "how the chips combine"),
    "a configuration without its batch": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.pop("batch_per_chip_per_party"), "lacks"),
    "a configuration that names no family": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.pop("family"), "lacks 'family'"),
    "a family that names nothing": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.__setitem__("family", "nothing"),
        "no directory <path>/families/nothing"),
    "a family that is not a name": (
        "configs/flagship-l4-dp2x2.json",
        lambda d: d.__setitem__("family", "../lib"), "is not a name"),
    "a configuration that lacks a key its family needs": (
        "configs/flagship-l4-dp2x2.json",
        lambda d: d.pop("d_ff"), "lacks 'd_ff', which its family"),
    "a configuration without its attention": (
        "configs/flagship-l4-1chip.json",
        lambda d: d.pop("attn_impl"), "lacks 'attn_impl', which its family"),
    "a family's needs without the rehearsal sizes": (
        "families/flagship/needs.json",
        lambda d: d.pop("rehearsal"), "needs.json: must hold"),
    "a kernel function the family's counts do not define": (
        "layer_metrics/attn_roofline_pct.json",
        lambda d: d["kernels"][0].__setitem__("fn", "paged_fwd"),
        "counts.py of the family 'flagship' does not define"),
    "a layout the harness does not know": (
        "configs/flagship-l4-dp2x2.json",
        lambda d: d["layout"].__setitem__("kind", "ring"), "layout kind"),
    "a mix without a learning rate": (
        "traffic/fsa.json",
        lambda d: d["trainer"]["optimizer"].pop("lr"),
        "lacks 'trainer.optimizer.lr'"),
    "a band without its share": (
        "traffic/mpq.json",
        lambda d: d["correct"].pop("band_min_share_of_reference_fall"),
        "lacks 'correct.band_min_share_of_reference_fall'"),
    "a correct mode that does not exist": (
        "traffic/mpq.json",
        lambda d: d["correct"].__setitem__("mode", "trust"), "correct.mode"),
    "a reader kind that does not exist": (
        "layer_metrics/grad_s_p50.json",
        lambda d: d.__setitem__("kind", "guess"), "reader kind"),
}


@pytest.mark.parametrize("what", sorted(FILE_BREAKS))
def test_a_broken_data_file_is_reported(checkout, what):
    name, fn, expect = FILE_BREAKS[what]
    f = checkout / "benchmark" / name
    body = json.loads(f.read_text())
    fn(body)
    f.write_text(json.dumps(body))
    errors = validate.check(checkout)
    assert any(expect in e for e in errors), (what, errors)


FAMILY_BREAKS = {
    "a family directory that lacks a part": (
        lambda d: (d / "counts.py").unlink(), "lacks the part counts.py"),
    "a family without its needs": (
        lambda d: (d / "needs.json").unlink(), "lacks the part needs.json"),
    "a reference that imports the program": (
        lambda d: (d / "reference.py").write_text(
            (d / "reference.py").read_text()
            + "\n\ndef _borrowed():\n"
              "    from geomx_tpu.models.transformer import make_apply\n"
              "    return make_apply\n"),
        "reference.py imports geomx_tpu"),
    "a reference that imports the program by its name in a string": (
        lambda d: (d / "reference.py").write_text(
            (d / "reference.py").read_text()
            + "\nimport importlib\n"
              "_m = importlib.import_module('geomx_tpu.models')\n"),
        "reference.py imports geomx_tpu"),
    "counts that import the program": (
        lambda d: (d / "counts.py").write_text(
            "import geomx_tpu\n" + (d / "counts.py").read_text()),
        "counts.py imports geomx_tpu"),
    "a reference without train": (
        lambda d: (d / "reference.py").write_text(
            (d / "reference.py").read_text().replace("def train(",
                                                     "def fit(")),
        "reference.py does not define ['train']"),
    "a system entry without build": (
        lambda d: (d / "system.py").write_text("SIZE_KEYS = ()\n"),
        "system.py does not define ['build']"),
    "a part that does not parse": (
        lambda d: (d / "counts.py").write_text("def n_params(:\n"),
        "counts.py:"),
}


@pytest.mark.parametrize("what", sorted(FAMILY_BREAKS))
def test_a_broken_family_is_reported(checkout, what):
    fn, expect = FAMILY_BREAKS[what]
    fn(checkout / "benchmark" / "families" / "flagship")
    errors = validate.check(checkout)
    assert any(expect in e for e in errors), (what, errors)
    # once for each configuration that names the family
    assert len([e for e in errors if expect in e]) == 2


# what ``reduced`` may list (a count: depth, experts, vocabulary) and
# what it may never (a width); num_hidden_layers is the key under which
# every catalog config gives its depth
COUNTS = ("num_hidden_layers", "n_layers", "n_routed_experts", "num_experts",
          "vocab_size", "vocab", "num_layers", "num_nextn_predict_layers")
WIDTHS = ("hidden_size", "moe_intermediate_size", "intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
          "num_experts_per_tok", "d_model", "d_ff", "head_dim",
          "ffn_hidden_size", "v_head_dim", "ssm_state_size", "expand")


@pytest.mark.parametrize("key", COUNTS + WIDTHS)
def test_reduced_tells_a_count_from_a_width(checkout, key):
    # d_model and d_ff are widths by their family's word (needs.json)
    assert validate.names_width(key, ("d_model", "d_ff", "n_heads")) == (
        key in WIDTHS)
    assert not validate.names_width("d_ff") and not validate.names_width(
        "n_heads")
    # ... and through the whole check, with the key in the file
    f = checkout / "benchmark" / "configs" / "flagship-l4-1chip.json"
    body = json.loads(f.read_text())
    body.update({key: 4, "reduced": [key], "source_values": {key: 8}})
    f.write_text(json.dumps(body))
    errors = _edit(checkout, _set(["configs", 0, "reduced"], [key]))
    refused = [e for e in errors if "no width may change" in e]
    assert bool(refused) == (key in WIDTHS), errors
    assert [e for e in errors if e not in refused] == []


def test_every_error_is_reported_not_the_first_alone(checkout):
    def fn(doc):
        doc["configs"][0]["source"] = "x" * 201
        doc["configs"][1]["source"] = "café"
        doc["end_to_end"][0]["unit"] = "tokens per second"
    errors = " | ".join(_edit(checkout, fn))
    for part in ("'flagship-l4-1chip': source must", "'flagship-l4-dp2x2': "
                 "source must", "'tokens_per_s': unit"):
        assert part in errors, errors


def test_an_oversized_manifest_is_reported(checkout):
    p = checkout / "BENCHMARK.json"
    p.write_text(p.read_text() + " " * (64 * 1024))
    assert any("bytes" in e for e in validate.check(checkout))


def test_a_file_with_a_bad_name_under_paths_is_reported(checkout):
    (checkout / "benchmark" / "configs" / "a file.json").write_text("{}")
    assert any("a file.json" in e for e in validate.check(checkout))


def test_cell_config_traffic_and_metric_are_added_as_files(checkout):
    """A later PR adds a cell, a configuration, a traffic mix and a
    per-layer metric of an existing reader kind as new files plus one
    manifest entry each, and edits no file that is there."""
    from benchmark.lib import harness

    before = {p: p.read_bytes() for p in (checkout / "benchmark").rglob("*")
              if p.is_file()}
    b = checkout / "benchmark"
    cfg = json.loads((b / "configs" / "flagship-l4-1chip.json").read_text())
    cfg.update(n_layers=2, source="a paper, 2026")
    (b / "configs" / "flagship-l2.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "mpq.json").read_text())
    mix["trainer"]["compression"] = {"type": "fp16"}
    (b / "traffic" / "fp16.json").write_text(json.dumps(mix))
    (b / "layer_metrics" / "grad_s_max.json").write_text(json.dumps({
        "layer": "Worker loop", "unit": "s", "better": "lower",
        "source": "program_span", "moves": "tokens_per_s",
        "kind": "measure_phase", "phase": "grad", "reduce": "p100"}))

    def fn(doc):
        doc["configs"].append({
            "name": "flagship-l2", "source": "a paper, 2026",
            "file": "benchmark/configs/flagship-l2.json",
            "reduced": ["n_layers"], "why": "two workers a party fit"})
        for i in range(2):      # room under the 25% rule is not the point
            doc["workloads"].append({
                "name": f"flagship-l2.{'fp16' if i == 0 else 'fsa'}",
                "config": "flagship-l2",
                "traffic": "fp16" if i == 0 else "fsa", "chips": 1,
                "why": "fp16 on every tensor"})
        # tokens_per_s lists its cells; the new ones join them
        next(m for m in doc["end_to_end"] if m["name"] == "tokens_per_s")[
            "workloads"] += ["flagship-l2.fp16", "flagship-l2.fsa"]
        # ... and so does the one per-layer metric every cell reports
        next(m for m in doc["per_layer"]
             if m["name"] == "compiles_in_window")[
            "workloads"] += ["flagship-l2.fp16", "flagship-l2.fsa"]
        doc["per_layer"].append({
            "name": "grad_s_max", "unit": "s", "better": "lower",
            "source": "program_span", "layer": "Worker loop",
            "moves": "tokens_per_s", "workloads": ["flagship-l2.fp16"]})
    assert _edit(checkout, fn) == []
    spec = harness.load_cell(checkout, "flagship-l2.fp16")
    assert spec["config"]["n_layers"] == 2
    assert spec["traffic"]["trainer"]["compression"] == {"type": "fp16"}
    names = [m["name"] for m in spec["per_layer"]]
    assert "grad_s_max" in names and "codec_dev_ms_per_step" not in names
    assert "grad_s_max" not in [
        m["name"] for m in
        harness.load_cell(checkout, "flagship-l2.fsa")["per_layer"]]
    # and the new reader needs no code
    from benchmark.lib import readers
    new = next(m for m in spec["per_layer"] if m["name"] == "grad_s_max")
    assert readers.read(new, {"phases": {"grad": [0.1, 0.3, 0.2]}}) == 0.3
    for p, content in before.items():
        assert p.read_bytes() == content, f"{p} was edited"
