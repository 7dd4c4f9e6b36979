"""The Bi-Sparse encoder's two compactions on a TPU, bit for bit and
timed.

    python scripts/bsc_compact_check.py --sizes 16777216 4194304 --seed 7

For each size this draws the encoder's state and gradients from the seed
(normal values with NaN, +-inf, -0.0, denormals and runs of ties mixed
in), then encodes ``--steps`` times in a row with the encoder the codec
uses (on a TPU its compaction is the ``bsc_compact`` kernel) and with the
plain-XLA encode on the same inputs, and asserts that the frames and the
velocity and accumulator states agree bit for bit.  Then it times
``--reps`` back-to-back encodes of each (the state fed back, as the codec
does) and prints one JSON line a size: the median milliseconds an encode
of each path, from the host's clock around work that ends in
``block_until_ready``.  With ``--trace DIR`` it also traces ten encodes
of each path and adds the device milliseconds an encode of the module and
of its largest operations, from the profiler's trace.  It exits non-zero
where the device is not a TPU or a frame differs.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def inputs(n: int, seed: int):
    rng = np.random.default_rng(seed)
    arrays = []
    for _ in range(3):
        x = rng.standard_normal(n).astype(np.float32)
        at = rng.choice(n, size=min(n, 4096), replace=False)
        x[at[:16]] = np.nan
        x[at[16:32]] = np.inf
        x[at[32:48]] = -np.inf
        x[at[48:64]] = -0.0
        x[at[64:128]] = np.float32(1e-40) * rng.standard_normal(64)
        # a run of ties at a magnitude near the top 1%
        x[at[128:]] = np.float32(2.5758)
        arrays.append(x)
    return arrays


def traced(enc, v, u, g, m, k, where: str, calls: int = 10) -> dict:
    """Device ms an encode: the module and its eight largest operations,
    from a profiler trace of ``calls`` encodes."""
    import re
    from collections import defaultdict

    import jax

    from benchmark.lib import trace as tr

    jax.profiler.start_trace(where)
    for _ in range(calls):
        wire, v, u = enc(v, u, g, m, k)
    jax.block_until_ready((wire, v, u))
    jax.profiler.stop_trace()
    t = tr.load(where)
    total: dict = defaultdict(float)
    for chip in tr.chips(t):
        for line in (tr.MODULES_LINE, tr.OPS_LINE):
            for e in t[chip].get(line, []):
                name = re.sub(r"\(\d+\)$|\.\d+(?= |$)", "", e.name)
                total[(line == tr.OPS_LINE, name)] += e.dur * 1e3 / calls
    ops = sorted(((n, s) for (op, n), s in total.items() if op),
                 key=lambda kv: -kv[1])[:8]
    return {"modules": {n: s for (op, n), s in total.items() if not op},
            "ops": dict(ops)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", type=int, nargs="+",
                    default=[16777216, 4194304])
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trace", help="trace the encodes under this directory")
    a = ap.parse_args(argv)

    import jax

    from geomx_tpu.kvstore import jax_backend as jb

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"not a TPU: {dev.platform}", file=sys.stderr)
        return 2
    kernel = jb._bsc_encoder()
    plain = jax.jit(jb._bsc_step(jb._topk_frame), static_argnums=(4,),
                    donate_argnums=(0, 1))
    m = np.float32(0.9)
    bad = 0
    for n in a.sizes:
        k = max(1, int(a.ratio * n))
        v0, u0, g = (jax.device_put(x, dev) for x in inputs(n, a.seed + n))
        state = {"kernel": (v0 + 0, u0 + 0), "plain": (v0 + 0, u0 + 0)}
        same = True
        for _ in range(a.steps):
            out = {}
            for name, enc in (("kernel", kernel), ("plain", plain)):
                wire, v, u = enc(*state[name], g, m, k)
                state[name] = (v, u)
                out[name] = [np.asarray(x).view(np.int32)
                             for x in (wire, v, u)]
            same &= all(np.array_equal(x, y)
                        for x, y in zip(out["kernel"], out["plain"]))
        ms = {}
        for name, enc in (("kernel", kernel), ("plain", plain)):
            v, u = state[name]
            times = []
            for _ in range(a.reps):
                t0 = time.perf_counter()
                wire, v, u = enc(v, u, g, m, k)
                jax.block_until_ready((wire, v, u))
                times.append((time.perf_counter() - t0) * 1e3)
            ms[name] = statistics.median(times)
            if a.trace:
                ms[name + "_device"] = traced(
                    enc, v, u, g, m, k, f"{a.trace}/{n}-{name}")
        bad += not same
        print(json.dumps({"n": n, "k": k, "bitwise_equal": bool(same),
                          "encode_ms_kernel": ms["kernel"],
                          "encode_ms_plain": ms["plain"],
                          "device": dev.device_kind,
                          **{key: val for key, val in ms.items()
                             if key.endswith("_device")}}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
