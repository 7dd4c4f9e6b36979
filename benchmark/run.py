#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, every chip the cell asks for: weights and data from
``--seed``, warm-up (which doubles as the check against the plain
reference), a window of whole steps of about ``--seconds`` seconds, and
as the last line of standard output one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``).  Without a TPU it exits non-zero and prints no
result.  ``--check-manifest`` validates ``BENCHMARK.json`` against the
contract and runs nothing; ``--rehearse`` runs the control flow at the
configuration's tiny rehearsal widths wherever jax runs, and prints its
numbers under ``rehearsal_`` names only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse          # noqa: E402
import faulthandler      # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check-manifest", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.lib import validate

    errors = validate.check(ROOT)
    if args.check_manifest:
        for e in errors:
            print("BENCHMARK.json: " + e)
        print("BENCHMARK.json: " + (f"{len(errors)} error(s)" if errors
                                    else "valid against the contract"))
        return 1 if errors else 0
    if errors:
        sys.exit("BENCHMARK.json is not valid (run --check-manifest): "
                 + errors[0])
    if not args.workload:
        ap.error("--workload is required")
    if not (ROOT / "geomx_tpu").is_dir():
        sys.exit(f"there is no system to measure: {ROOT / 'geomx_tpu'} "
                 "is missing; nothing was run")
    seconds = args.seconds
    if seconds is None:
        seconds = json.loads((ROOT / "BENCHMARK.json").read_text())[
            "run_seconds"]

    from benchmark.lib import harness

    faulthandler.dump_traceback_later(harness.DEADLINE_S, exit=True,
                                      file=sys.__stderr__)
    try:
        result = harness.run_cell(
            ROOT, args.workload, args.seed, seconds, bool(args.trace),
            args.rehearse, T_START)
    finally:
        faulthandler.cancel_dump_traceback_later()
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
