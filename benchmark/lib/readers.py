"""Per-layer metric readers, one per ``kind`` in ``layer_metrics/*.json``.

A reader takes the metric's own file (``spec``) and the run's
observations (``obs``, below) and returns a number, or None when there
is nothing to read, in which case the harness leaves the metric out of
the line.  A new metric of an existing kind is a data file only.

A trace reader looks at EVERY chip the cell used, and its file says how
the chips combine (``chips``: ``sum`` or ``max``).  Never "the busiest
chip": which chip that is changes between runs of one code (PERF.md,
PR 25), and then one metric is read from different chips.

``obs`` keys: ``steps`` (whole steps in the window), ``window_s``,
``tokens_per_step``, ``phases`` {name: [seconds, ...]} over workers and
window steps, ``counters`` {key: delta over the window, summed over
servers}, ``compiles`` (in the window), ``model`` (the configuration's
own keys, as its family reads them) and ``counts`` (the family's
``counts.py``: ``n_params``, ``train_flops_per_token`` and the kernel
functions a ``trace_kernel`` file names by ``fn``, each taking
``model``), ``chips``, ``batch_per_chip``, ``peaks`` (this device
kind's row), ``trace`` (a trace dict or None), ``t0``/``t1`` (the window
on the profiler's clock), ``busy`` {chip: seconds an operation ran in
the window}, ``spans`` (the program's ``geomx:`` spans of a traced run,
``lib/spans.py``, or None).
"""

from __future__ import annotations

import re

import numpy as np

from . import roofline, spans, trace as tr

COMBINE = {"sum": sum, "max": max}


def measure_phase(spec, obs):
    """A percentile (``reduce``: ``p50``, ``p95``, ``p100``...) of a
    worker phase's durations over workers and window steps."""
    values = obs["phases"].get(spec["phase"], [])
    if not values:
        return None
    pct = re.fullmatch(r"p(\d{1,3})", spec["reduce"])
    if not pct or int(pct.group(1)) > 100:
        raise ValueError(f"unknown reducer {spec['reduce']!r}")
    return float(np.percentile(values, int(pct.group(1))))


def stats_counter(spec, obs):
    if any(k not in obs["counters"] for k in spec["keys"]):
        return None
    total = sum(obs["counters"][k] for k in spec["keys"])
    total *= spec.get("scale", 1.0)
    return total / obs["steps"] if spec.get("per_step") else total


def trace_module(spec, obs):
    """Device time of the programs whose XLA module name matches
    ``pattern``: summed on each chip, then over the chips as ``chips``
    says.  A program that ran on one chip only (a server's, on chip 0)
    is found wherever it ran; a pattern that matches on no chip at all
    is an error, not a 0."""
    if obs["trace"] is None:
        return None
    per_chip = [tr.module_seconds(obs["trace"], chip, spec["pattern"],
                                  obs["t0"], obs["t1"])
                for chip in obs["busy"]]
    if all(s is None for s in per_chip):
        raise tr.PatternMatchedNothing(
            f"no XLA module on any chip matches {spec['pattern']!r}")
    ms = 1e3 * COMBINE[spec["chips"]](s or 0.0 for s in per_chip)
    return ms / obs["steps"] if spec.get("per_step") else ms


def trace_kernel(spec, obs):
    """Roofline share of a group of kernels: the least time a chip could
    take for the calls seen, over the time they took, over every chip's
    calls together."""
    if obs["trace"] is None:
        return None
    least = took = 0.0
    for k in spec["kernels"]:
        evs = [e for chip in obs["busy"] for e in tr.kernel_events(
            obs["trace"], chip, k["pattern"], obs["t0"], obs["t1"])]
        if not evs:
            raise tr.PatternMatchedNothing(
                f"no XLA op on any chip matches {k['pattern']!r}")
        fl, by = getattr(obs["counts"], k["fn"])(obs["model"],
                                                 obs["batch_per_chip"])
        least += len(evs) * roofline.least_seconds(fl, by, obs["peaks"])[0]
        took += sum(e.dur for e in evs)
    return 100.0 * least / took


def _device_idle_pct(spec, obs):
    if obs["trace"] is None:
        return None
    return 100.0 * (1.0 - max(obs["busy"].values())
                    / (obs["t1"] - obs["t0"]))


def _mfu_pct(spec, obs):
    if obs["peaks"] is None:
        return None
    tokens_per_s = obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
    return (100.0 * tokens_per_s
            * obs["counts"].train_flops_per_token(obs["model"])
            / (obs["chips"] * obs["peaks"]["bf16_flops"]))


def _step_mfu_pct(spec, obs):
    """The FLOPs the window's steps require over what the chips could
    have done in the seconds an operation ran on them: the whole step's
    share of the peak in device time, which bounds every kernel's."""
    if obs["trace"] is None or obs["peaks"] is None:
        return None
    required = (obs["steps"] * obs["tokens_per_step"]
                * obs["counts"].train_flops_per_token(obs["model"]))
    return 100.0 * required / (sum(obs["busy"].values())
                               * obs["peaks"]["bf16_flops"])


def _compiles_in_window(spec, obs):
    return obs["compiles"]


DERIVED = {"device_idle_pct": _device_idle_pct, "mfu_pct": _mfu_pct,
           "step_mfu_pct": _step_mfu_pct,
           "compiles_in_window": _compiles_in_window}


def derived(spec, obs):
    return DERIVED[spec["fn"]](spec, obs)


KINDS = {"measure_phase": measure_phase, "stats_counter": stats_counter,
         "trace_module": trace_module, "trace_kernel": trace_kernel,
         "derived": derived, "program_span": spans.program_span}


def read(spec: dict, obs: dict):
    return KINDS[spec["kind"]](spec, obs)
