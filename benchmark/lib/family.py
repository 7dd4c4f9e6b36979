"""A model family: a directory of files found by the name a
configuration's file gives as ``family``.

``<path>/families/<family>/`` under one of the manifest's ``paths``
holds everything the benchmark knows about a model:

* ``system.py``, the one file of the family that imports ``geomx_tpu``:
  ``build(model, compute_dtype) -> (init, grad_fn)``, the seeded
  parameter initialiser ``init(key)`` and the jitted ``grad_fn(params,
  x, y) -> (loss, acc, grads)`` that ``Trainer`` takes;
* ``reference.py``, the plain reference, which imports nothing of the
  program: ``train(params, batches, lr, device)`` (the loss before each
  step) and ``grads(params, tokens)``;
* ``counts.py``: ``n_params(model)``, ``train_flops_per_token(model)``
  and the kernel functions ``fn(model, batch) -> (FLOPs, bytes)`` that
  ``trace_kernel`` reader files name by ``fn``;
* ``needs.json``: ``keys``, the configuration keys the family reads
  beside the generic ones (:data:`MODEL_KEYS`, ``compute_dtype``);
  ``widths``, those of them that ``reduced`` may never list (a width
  under a name no pattern could know); and ``rehearsal``, the tiny sizes
  ``--rehearse`` swaps in.

A later PR adds a family as new files; nothing here names one.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

# what each part must define at its top level
PARTS = {"system": ("build",), "reference": ("train", "grads"),
         "counts": ("n_params", "train_flops_per_token")}
NEEDS = "needs.json"
# the model keys every configuration has whatever its family: the ids
# the data draws from and the sequence length
MODEL_KEYS = ("vocab", "max_seq")
PROGRAM = "geomx_tpu"


def directory(root: Path, paths, name):
    """``<path>/families/<name>`` under one of ``paths``, or None."""
    for p in paths:
        d = Path(root) / p / "families" / str(name)
        if d.is_dir():
            return d
    return None


def defined(source: str) -> set:
    """The names a module's source defines at its top level."""
    names = set()
    for node in ast.parse(source).body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            names |= {t.id for t in targets if isinstance(t, ast.Name)}
    return names


def imports_program(source: str) -> bool:
    """Whether a module's source imports the program, anywhere in it."""
    for node in ast.walk(ast.parse(source)):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            # importlib.import_module("geomx_tpu...") and the like; a
            # docstring's prose is not a module's dotted name
            mods = [node.value] if " " not in node.value.strip() else []
        if any(m == PROGRAM or m.startswith(PROGRAM + ".") for m in mods):
            return True
    return False


def needs(d: Path) -> dict:
    """``needs.json`` of the family directory ``d``; raises ValueError
    where it is not the two groups it has to be."""
    body = json.loads((d / NEEDS).read_text())
    names = lambda v: (isinstance(v, list)            # noqa: E731
                       and all(isinstance(k, str) for k in v))
    if not (isinstance(body, dict) and names(body.get("keys"))
            and names(body.get("widths", []))
            and isinstance(body.get("rehearsal"), dict)):
        raise ValueError("must hold 'keys', a list of configuration keys, "
                         "and 'rehearsal', the tiny sizes ('widths', a "
                         "list of keys, is optional)")
    return body


def check(root: Path, paths, name) -> list:
    """What is wrong with the family ``name``, read from its files
    without importing any: the directory, its parts and what they
    define, the reference's imports, ``needs.json``."""
    d = directory(root, paths, name)
    if d is None:
        return [f"family {name!r}: no directory <path>/families/{name} "
                "under paths"]
    errs = []
    for part, names in PARTS.items():
        f = d / f"{part}.py"
        if not f.is_file():
            errs.append(f"family {name!r}: lacks the part {f.name}")
            continue
        try:
            source = f.read_text()
            lacks = sorted(set(names) - defined(source))
        except (OSError, SyntaxError, ValueError) as e:
            errs.append(f"family {name!r}: {f.name}: {e}")
            continue
        if lacks:
            errs.append(f"family {name!r}: {f.name} does not define "
                        f"{lacks}")
        if part != "system" and imports_program(source):
            errs.append(f"family {name!r}: {f.name} imports {PROGRAM}; "
                        "only system.py may")
    if not (d / NEEDS).is_file():
        errs.append(f"family {name!r}: lacks the part {NEEDS}")
    else:
        try:
            needs(d)
        except (OSError, ValueError) as e:
            errs.append(f"family {name!r}: {NEEDS}: {e}")
    return errs


def kernel_fns(root: Path, paths, name) -> set:
    """The names ``counts.py`` of the (checked) family defines: what a
    ``trace_kernel`` reader file's ``fn`` may be."""
    return defined((directory(root, paths, name) / "counts.py").read_text())


def _module(d: Path, part: str):
    name = f"benchmark_family.{d.name}.{part}"
    spec = importlib.util.spec_from_file_location(name, d / f"{part}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load(root: Path, paths, name):
    """The family ``name``: ``.needs`` (``needs.json``) and its three
    parts as modules, ``.system``, ``.reference`` and ``.counts``."""
    d = directory(root, paths, name)
    if d is None:
        raise FileNotFoundError(f"no family directory families/{name} "
                                f"under {list(paths)}")
    return SimpleNamespace(
        name=name, needs=needs(d),
        **{part: _module(d, part) for part in PARTS})
