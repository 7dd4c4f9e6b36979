"""The benchmark's own check of ``BENCHMARK.json`` against the contract.

PR 24's manifest was thrown out by the driver before any run over one
string.  Every limit the contract states is checked here, for every
field of every entry, before a second of chip time is spent:
``python benchmark/run.py --check-manifest``.  :func:`check` returns the
list of everything wrong (empty = valid); it never stops at the first.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

from . import family, readers

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_BYTES = 64 * 1024
MAX_RUN_SECONDS = 51
MAX_BOUND = 0.1
# ``reduced`` may never name a width: a hidden, intermediate, latent,
# state or projection size, a key ending in _dim or _rank, a head size,
# an expansion factor, or the number of experts per token
WIDTH_RE = re.compile(
    r"(_dim|_rank)$|hidden|intermediate|latent|state_size|proj|expand|"
    r"expansion|head_size|experts_per_tok|^head_dim$|^moe_top_k$|^top_k$",
    re.IGNORECASE)
# ... but a number of layers is a count whatever the layers are called:
# num_hidden_layers is the key under which every catalog config gives
# its depth, the one cut every configuration makes
COUNT_RE = re.compile(r"^(num|n)_(\w+_)?(layers|blocks)$", re.IGNORECASE)


def names_width(key: str, family_widths=()) -> bool:
    """Whether ``reduced`` may not list ``key``: it names a width by the
    contract's pattern and is not a count of layers, or it is one of the
    keys its family calls a width (``needs.json``, ``widths``: names no
    pattern could know)."""
    return key in family_widths or (bool(WIDTH_RE.search(key))
                                    and not COUNT_RE.match(key))


# what the harness reads from a configuration's and a mix's file: a file
# that lacks one fails here, not minutes into a run on the chip.  The
# model's own keys are its family's (needs.json), beside these.
CONFIG_NEEDS = ("family", *family.MODEL_KEYS, "compute_dtype",
                "batch_per_chip_per_party", "topology.parties",
                "topology.workers_per_party", "layout.kind")
TRAFFIC_NEEDS = ("trainer.optimizer.lr", "data.order", "data.pool_steps",
                 "warmup_steps", "trace_steps", "correct.mode",
                 "correct.loss_tol", "correct.require_falling",
                 "correct.require_party_parity")
CORRECT_MODES = {"match_reference": (),
                 "band": ("correct.band_margin",
                          "correct.band_min_share_of_reference_fall")}
LAYOUT_KINDS = ("shared_chip", "party_dp_mesh")


def _missing(body, dotted) -> list:
    """The dotted keys of ``dotted`` that ``body`` does not have."""
    out = []
    for key in dotted:
        at = body
        for part in key.split("."):
            at = at.get(part) if isinstance(at, dict) else None
        if at is None:
            out.append(key)
    return out


def _line(s, lo: int = 1, hi: int = 200) -> bool:
    """1 to 200 printable ASCII characters on one line with no tab."""
    return (isinstance(s, str) and lo <= len(s) <= hi and s.isascii()
            and s.isprintable())


def _inside(path: str, roots) -> bool:
    return any(path == r or path.startswith(r.rstrip("/") + "/")
               for r in roots)


def _relative(path: str) -> bool:
    return not path.startswith("/") and ".." not in path.split("/")


def _entries(errs, doc, key, lo, hi, required, optional=()):
    """The list under ``key`` if it is a list of lo..hi objects with just
    the required keys (plus any of ``optional``); reports the rest."""
    items = doc.get(key)
    if not isinstance(items, list) or not lo <= len(items) <= hi:
        errs.append(f"{key}: must be a list of {lo} to {hi} entries")
        return []
    good = []
    for i, e in enumerate(items):
        if not isinstance(e, dict):
            errs.append(f"{key}[{i}]: must be an object")
            continue
        missing = required - set(e)
        extra = set(e) - required - set(optional)
        if missing:
            errs.append(f"{key}[{i}]: missing key(s) {sorted(missing)}")
        if extra:
            errs.append(f"{key}[{i}]: key(s) {sorted(extra)} are not in "
                        "the contract")
        if not missing:
            good.append(e)
    return good


def _name(errs, where, value) -> bool:
    if not isinstance(value, str) or not NAME_RE.match(value):
        errs.append(f"{where}: {value!r} is not a name (1 to 64 of "
                    "a-z A-Z 0-9 _ . -, not starting with . or -)")
        return False
    return True


def _unique(errs, what, names):
    seen = set()
    for n in names:
        if n in seen:
            errs.append(f"{what}: the name {n!r} appears twice")
        seen.add(n)


def traffic_file(root: Path, paths, traffic: str):
    """The data file of a traffic mix: ``<path>/traffic/<mix>.<suffix>``
    under one of the manifest's ``paths``; None if there is none."""
    for p in paths:
        for suffix in TRAFFIC_SUFFIXES:
            f = root / p / "traffic" / (traffic + suffix)
            if f.is_file():
                return f
    return None


def layer_metric_file(root: Path, paths, name: str):
    for p in paths:
        f = root / p / "layer_metrics" / (name + ".json")
        if f.is_file():
            return f
    return None


def _traffic(f: Path) -> list:
    """What a mix's file lacks of what the harness reads from it."""
    try:
        body = json.loads(f.read_text())
    except ValueError as e:
        return [str(e)]
    mode = (body.get("correct") or {}).get("mode")
    if mode is not None and mode not in CORRECT_MODES:
        return [f"correct.mode {mode!r} is not one of "
                f"{sorted(CORRECT_MODES)}"]
    lacks = _missing(body, TRAFFIC_NEEDS + CORRECT_MODES.get(mode, ()))
    return [f"lacks {k!r}" for k in lacks]


def _family(errs, root, paths, where, f, body):
    """Hold a configuration's family to its shape; returns its needs
    (``needs.json``) where the family is sound, else None."""
    fam = body.get("family") if isinstance(body, dict) else None
    if fam is None or not _name(errs, where + " family", fam):
        return None             # a file without the key is reported there
    wrong = family.check(root, paths, fam)
    errs += [f"{where}: {e}" for e in wrong]
    if wrong:
        return None
    needs = family.needs(family.directory(root, paths, fam))
    errs += [f"{where}: {f} lacks {k!r}, which its family {fam!r} needs"
             for k in _missing(body, needs["keys"])]
    return needs


def metric_cells(metric: dict, cells) -> list:
    """The cells a metric is reported in: its ``workloads`` or all."""
    return list(metric.get("workloads", cells))


def check(root, manifest_name: str = "BENCHMARK.json") -> list:
    """Everything in ``<root>/BENCHMARK.json`` that breaks the contract."""
    root = Path(root)
    path = root / manifest_name
    errs: list = []
    try:
        raw = path.read_bytes()
    except OSError as e:
        return [f"{path}: cannot be read: {e}"]
    if len(raw) > MAX_BYTES:
        errs.append(f"the file has {len(raw)} bytes, over {MAX_BYTES}")
    try:
        doc = json.loads(raw)
    except ValueError as e:
        return errs + [f"not JSON: {e}"]
    if not isinstance(doc, dict):
        return errs + ["the top level must be an object"]
    if set(doc) != TOP_KEYS:
        errs.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}; "
                    f"missing {sorted(TOP_KEYS - set(doc))}, "
                    f"extra {sorted(set(doc) - TOP_KEYS)}")

    # ---- paths, command, run_seconds ----------------------------------
    paths = doc.get("paths")
    if (not isinstance(paths, list) or not 1 <= len(paths) <= 16
            or not all(isinstance(p, str) for p in paths)):
        errs.append("paths: must be a list of 1 to 16 strings")
        paths = []
    for p in paths:
        if not PATH_RE.match(p) or not _relative(p):
            errs.append(f"paths: {p!r} must be a relative path of at most "
                        "200 letters, digits, _ . - /")
        elif not (root / p).is_dir():
            errs.append(f"paths: {p!r} is not a directory")
    cmd = doc.get("command")
    if (not isinstance(cmd, list) or not 1 <= len(cmd) <= 32
            or not all(_line(w) for w in cmd)):
        errs.append("command: must be a list of 1 to 32 one-line ASCII "
                    "strings of 1 to 200 characters")
    else:
        for w in cmd:
            if not _relative(w):
                errs.append(f"command: {w!r} starts with / or leads out "
                            "through ..")
            elif (root / w).exists() and not _inside(w, paths):
                errs.append(f"command: {w!r} is a file of the repo "
                            "outside paths")
    rs = doc.get("run_seconds")
    if type(rs) is not int or not 1 <= rs <= MAX_RUN_SECONDS:
        errs.append(f"run_seconds: must be a whole number from 1 to "
                    f"{MAX_RUN_SECONDS}, not {rs!r}")

    # ---- configs -------------------------------------------------------
    configs = _entries(errs, doc, "configs", 1, 24, CONFIG_KEYS)
    files = []
    families: dict = {}          # configuration -> the family it names
    for c in configs:
        where = f"config {c['name']!r}"
        _name(errs, where + " name", c["name"])
        if not _line(c["source"]):
            errs.append(f"{where}: source must be 1 to 200 printable ASCII "
                        f"characters on one line (it has "
                        f"{len(str(c['source']))})")
        if not _line(c["why"]):
            errs.append(f"{where}: why must be 1 to 200 printable ASCII "
                        "characters on one line")
        f = c["file"]
        body = None
        if not isinstance(f, str) or not PATH_RE.match(f) \
                or not _relative(f) or not _inside(f, paths):
            errs.append(f"{where}: file {f!r} must lie under paths")
        else:
            files.append(f)
            try:
                body = json.loads((root / f).read_text())
            except (OSError, ValueError) as e:
                errs.append(f"{where}: file {f!r}: {e}")
        red = c["reduced"]
        if not isinstance(red, list) or len(red) > 16:
            errs.append(f"{where}: reduced must be a list of at most 16")
            red = []
        needs = _family(errs, root, paths, where, f, body)
        if needs is not None:
            families[c["name"]] = body["family"]
        for k in red:
            if not _name(errs, where + " reduced", k):
                continue
            if names_width(k, (needs or {}).get("widths", ())):
                errs.append(f"{where}: reduced names the width {k!r}; no "
                            "width may change")
            if isinstance(body, dict) and k not in body:
                errs.append(f"{where}: reduced key {k!r} is not in {f}")
            elif isinstance(body, dict) and body.get(
                    "source_values", {}).get(k) in (None, body[k]):
                # what was it cut from?  a "reduced" key whose source
                # value is missing or equal was not reduced
                errs.append(f"{where}: {f} must give the source's {k!r} "
                            "under source_values, other than the value run")
        if isinstance(body, dict):
            for k in _missing(body, CONFIG_NEEDS):
                errs.append(f"{where}: {f} lacks {k!r}")
            kind = body.get("layout", {}).get("kind")
            if kind is not None and kind not in LAYOUT_KINDS:
                errs.append(f"{where}: {f} names the layout kind {kind!r}, "
                            f"not one of {list(LAYOUT_KINDS)}")
            for k in set(body.get("source_values", {})) - set(red):
                errs.append(f"{where}: {f} gives a source value for {k!r}, "
                            "which reduced does not list")
            if body.get("source") not in (None, c["source"]):
                errs.append(f"{where}: {f} gives another source than the "
                            "manifest")
            if sorted(body.get("reduced", red)) != sorted(red):
                errs.append(f"{where}: {f} lists another 'reduced' than "
                            "the manifest")
    _unique(errs, "configs", [c["name"] for c in configs])
    _unique(errs, "configs' files", files)

    # ---- workloads -----------------------------------------------------
    cells = _entries(errs, doc, "workloads", 2, 24, WORKLOAD_KEYS)
    config_names = {c["name"] for c in configs}
    for w in cells:
        where = f"workload {w['name']!r}"
        _name(errs, where + " name", w["name"])
        _name(errs, where + " config", w["config"])
        if w["config"] not in config_names:
            errs.append(f"{where}: no configuration named {w['config']!r}")
        if _name(errs, where + " traffic", w["traffic"]):
            tf = traffic_file(root, paths, w["traffic"])
            if tf is None:
                errs.append(f"{where}: no traffic file "
                            f"<path>/traffic/{w['traffic']}.json (or "
                            ".jsonl .toml .txt .csv) under paths")
            elif tf.suffix == ".json":
                errs += [f"{where}: {tf.name}: {e}" for e in _traffic(tf)]
        if w["chips"] not in (1, 4) or type(w["chips"]) is not int:
            errs.append(f"{where}: chips must be 1 or 4")
        if not _line(w["why"]):
            errs.append(f"{where}: why must be 1 to 200 printable ASCII "
                        "characters on one line")
    cell_names = [w["name"] for w in cells]
    _unique(errs, "workloads", cell_names)
    _unique(errs, "workloads' (config, traffic) pairs",
            [(w["config"], w["traffic"]) for w in cells])
    for c in sorted(config_names - {w["config"] for w in cells}):
        errs.append(f"config {c!r}: no cell uses it")
    four = sum(1 for w in cells if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        errs.append(f"{four} cells ask for 4 chips; at most "
                    f"{max(1, len(cells) // 4)} of {len(cells)} may")

    # ---- metrics -------------------------------------------------------
    e2e = _entries(errs, doc, "end_to_end", 1, 16, E2E_KEYS, ("workloads",))
    layer = _entries(errs, doc, "per_layer", 1, 128, LAYER_KEYS,
                     ("workloads",))
    for m in e2e + layer:
        where = f"metric {m['name']!r}"
        _name(errs, where + " name", m["name"])
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            errs.append(f"{where}: unit {m['unit']!r} must be 1 to 16 of "
                        "a-z A-Z 0-9 _ / % . -")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"{where}: better must be 'lower' or 'higher'")
        if "workloads" in m:
            ws = m["workloads"]
            if not isinstance(ws, list) or not ws:
                errs.append(f"{where}: workloads must be a non-empty list")
                m["workloads"] = []
            for w in m["workloads"]:
                if w not in cell_names:
                    errs.append(f"{where}: no cell named {w!r}")
    _unique(errs, "metrics", [m["name"] for m in e2e + layer])
    for m in e2e:
        where = f"metric {m['name']!r}"
        if m["source"] not in E2E_SOURCES:
            errs.append(f"{where}: an end-to-end metric's source is "
                        f"host_clock or device_trace, not {m['source']!r}")
        b = m["bound"]
        if isinstance(b, bool) or not isinstance(b, (int, float)) \
                or not 0 < b <= MAX_BOUND:
            errs.append(f"{where}: bound must be above 0 and at most "
                        f"{MAX_BOUND}, not {b!r}")
    if not any(m["name"] == "setup_s" for m in e2e):
        errs.append("end_to_end: one metric must be setup_s")
    e2e_cells = {m["name"]: metric_cells(m, cell_names) for m in e2e}
    for m in layer:
        where = f"metric {m['name']!r}"
        if m["source"] not in SOURCES:
            errs.append(f"{where}: source {m['source']!r} is not one of "
                        f"{sorted(SOURCES)}")
        if not _line(m["layer"]):
            errs.append(f"{where}: layer must be 1 to 200 printable ASCII "
                        "characters on one line")
        if m["moves"] not in e2e_cells:
            errs.append(f"{where}: moves {m['moves']!r} is not an "
                        "end-to-end metric")
            continue
        for w in metric_cells(m, cell_names):
            if w not in e2e_cells[m["moves"]]:
                errs.append(f"{where}: reported in cell {w!r}, where "
                            f"{m['moves']!r} is not")
        f = layer_metric_file(root, paths, m["name"])
        if f is None:
            errs.append(f"{where}: no reader file "
                        f"<path>/layer_metrics/{m['name']}.json")
            continue
        try:
            body = json.loads(f.read_text())
        except ValueError as e:
            errs.append(f"{where}: {f}: {e}")
            continue
        for k in ("unit", "better", "source", "layer", "moves"):
            if body.get(k) != m[k]:
                errs.append(f"{where}: {f.name} says {k}="
                            f"{body.get(k)!r}, the manifest {m[k]!r}")
        if body.get("kind") not in readers.KINDS:
            errs.append(f"{where}: {f.name} names the reader kind "
                        f"{body.get('kind')!r}, not one of "
                        f"{sorted(readers.KINDS)}")
        elif body["kind"] == "trace_module" and \
                body.get("chips") not in readers.COMBINE:
            errs.append(f"{where}: {f.name} must say how the chips "
                        f"combine: chips = one of {sorted(readers.COMBINE)}")
        elif body["kind"] == "trace_kernel":
            # the operations and bytes are the family's of each cell
            # that reports the metric
            fams = {families[w["config"]] for w in cells
                    if w["name"] in metric_cells(m, cell_names)
                    and w["config"] in families}
            for fam in sorted(fams):
                have = family.kernel_fns(root, paths, fam)
                for k in body.get("kernels", []):
                    if k.get("fn") not in have:
                        errs.append(
                            f"{where}: {f.name} names the kernel function "
                            f"{k.get('fn')!r}, which counts.py of the "
                            f"family {fam!r} does not define")
    for w in cell_names:
        mine = [m["name"] for m in e2e if w in e2e_cells[m["name"]]]
        if "setup_s" not in mine:
            errs.append(f"cell {w!r}: does not report setup_s")
        if len([n for n in mine if n != "setup_s"]) < 1:
            errs.append(f"cell {w!r}: reports no end-to-end metric "
                        "besides setup_s")
        if not any(w in metric_cells(m, cell_names) for m in layer):
            errs.append(f"cell {w!r}: reports no per-layer metric")

    # ---- files under paths ---------------------------------------------
    for p in paths:
        base = root / p
        if not base.is_dir():
            continue
        for f in base.rglob("*"):
            rel = f.relative_to(root).as_posix()
            if "__pycache__" in rel or rel.endswith(".pyc"):
                continue
            if not PATH_RE.match(rel):
                errs.append(f"file {rel!r}: named with other characters "
                            "than a name's and /")
    return errs
