"""The static bundle profile of a program's Pallas kernels on the TPU v5e,
without a chip.

    python scripts/kernel_bundles.py dsa_fwd --seq 4096
    python scripts/kernel_bundles.py dsa --seq 4096 --dump /tmp/llo
    python scripts/kernel_bundles.py bsc_compact --seq 16777216
    python scripts/kernel_bundles.py some.module:build

A child process compiles the target ahead of time for one chip of a
described ``v5e:2x2`` topology, with libtpu's LLO dump on
(``--xla_jf_dump_to``, ``--xla_jf_dump_llo_text``).  For each Pallas
kernel of the program this prints the bundles of its body (one grid
step, both sides of every ``pl.when``) and the share of them in which
each unit is busy: MXU, XLU, VALU, EUP, VLOAD, VSTORE, and the spill
stores and fill loads among them.  Nothing runs, so nothing here is a
time; ``docs/perf.md`` gives the calibration against the chip.

libtpu aborts (SIGABRT, exit 134 from a shell) after it has written the
dump, when it looks for a template of its VMEM report that is not
installed: that exit is read as success where the dump is there.  One
process at a time may hold libtpu: run one profile at a time.

Targets: ``dsa_fwd`` (the sparse attention's forward kernel) and ``dsa``
(its gradient: all four kernels) at Keye-VL's widths, ``bsc_compact``
(the Bi-Sparse encoder's compaction, ``--seq`` the tensor's length), or
``module:function``, a callable ``(sharding, seq) -> (fn, args)`` whose
``args`` are ``jax.ShapeDtypeStruct`` on that sharding.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
FINAL = "-final_hlo-static-per-bundle-utilization.txt"
UNITS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VSTORE", "VSTORE:SPILL",
         "VLOAD:FILL")


def dsa_args(sharding, seq):
    """Keye-VL's sparse attention at its published widths: 32 q and 4 kv
    heads of 128, an indexer of 16 x 64, tiles of 512, ``topk`` 2048."""
    import jax
    import jax.numpy as jnp

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (arg((1, seq, 32, 128)), arg((1, seq, 4, 128)),
            arg((1, seq, 4, 128)), arg((1, seq, 16, 64)), arg((1, seq, 64)),
            arg((1, seq, 16), jnp.float32))


def dsa_fwd(sharding, seq):
    from geomx_tpu.ops import dsa

    def fn(q, k, v, qi, ki, w):
        return dsa.dsa_attention(q, k, v, qi, ki, w, topk=min(2048, seq))

    return fn, dsa_args(sharding, seq)


def dsa_grad(sharding, seq):
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import dsa

    def loss(q, k, v, qi, ki, w):
        o, ce, _ = dsa.dsa_attention(q, k, v, qi, ki, w, topk=min(2048, seq))
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(ce)

    return jax.grad(loss, argnums=tuple(range(6))), dsa_args(sharding, seq)


def bsc_compact(sharding, seq):
    """The Bi-Sparse encoder's compaction kernel for a tensor of ``seq``
    elements at the codec's 1%."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops import compact

    rows = compact.padded_rows(seq)

    def fn(words, p, tc):
        return compact.compact(words, p, tc[0], tc[1], max(1, seq // 100))

    return fn, (jax.ShapeDtypeStruct((rows, compact.LANES), jnp.int32,
                                     sharding=sharding),
                jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=sharding),
                jax.ShapeDtypeStruct((2,), jnp.int32, sharding=sharding))


TARGETS = {"dsa_fwd": dsa_fwd, "dsa": dsa_grad, "bsc_compact": bsc_compact}


def _child(target: str, seq: int) -> None:
    """Lower and compile ``target`` for the v5e; print the kernels'
    names first, since libtpu may abort before the compile returns."""
    sys.path.insert(0, str(ROOT))
    import importlib

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    if ":" in target:
        module, name = target.split(":")
        build = getattr(importlib.import_module(module), name)
    else:
        build = TARGETS[target]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    fn, args = build(SingleDeviceSharding(topo.devices[0]), seq)
    lowered = jax.jit(fn).lower(*args)
    names = sorted(set(re.findall(r'kernel_name = "([^"]+)"',
                                  lowered.as_text())))
    print("KERNELS " + json.dumps(names), flush=True)
    lowered.compile()


def utilization(path) -> dict:
    """One ``*-final_hlo-static-per-bundle-utilization.txt``: the bundle
    count and, for each unit, the share of bundles (%) that use it."""
    lines = Path(path).read_text().splitlines()
    at = lines.index("== UTILIZATION:")
    names = [n.strip() for n in lines[at - 2].split(",")]
    rows = [line.split() for line in lines[at + 1:] if line.strip()]
    out = {"bundles": len(rows)}
    for c, name in enumerate(names):
        busy = sum(int(r[c]) > 0 for r in rows)
        out[name] = 100.0 * busy / max(len(rows), 1)
    return out


def kernel_files(dump: Path, kernels) -> dict:
    """``{program: file}`` for the dumped programs that are the named
    kernels (HLO names them after the call: ``dsa_attn_fwd.1``, and
    under a gradient ``jvp_dsa_attn_fwd_.1`` or
    ``transpose_jvp_dsa_attn_bwd_dq__.1``)."""
    pattern = re.compile(r"^\d+-(?P<prog>.+)-\d+" + re.escape(FINAL) + "$")
    found = {}
    for f in sorted(dump.iterdir()):
        m = pattern.match(f.name)
        if m and any(k in m.group("prog") for k in kernels):
            found[m.group("prog")] = f
    return found


def profile(target: str, seq: int, dump: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR=str(dump))
    env["LIBTPU_INIT_ARGS"] = " ".join(filter(None, [
        env.get("LIBTPU_INIT_ARGS"), f"--xla_jf_dump_to={dump}",
        "--xla_jf_dump_llo_text=true"]))
    done = subprocess.run(
        [sys.executable, __file__, target, "--seq", str(seq), "--child"],
        env=env, capture_output=True, text=True)
    said = re.search(r"^KERNELS (.*)$", done.stdout, re.M)
    files = kernel_files(dump, json.loads(said.group(1))) if said else {}
    if done.returncode not in (0, 134, -signal.SIGABRT) or not files:
        sys.stderr.write(done.stderr[-4000:])
        raise SystemExit(f"the compile gave no kernel profile "
                         f"(exit {done.returncode})")
    return {prog: utilization(f) for prog, f in files.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("target", help="dsa_fwd, dsa or module:function")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--dump", help="keep the LLO dump here")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.child:
        return _child(a.target, a.seq)
    with tempfile.TemporaryDirectory() as tmp:
        dump = Path(a.dump or tmp)
        dump.mkdir(parents=True, exist_ok=True)
        stats = profile(a.target, a.seq, dump)
    wide = max(map(len, stats))
    print(f"{'kernel':{wide}s} {'bundles':>8s} " +
          " ".join(f"{u.split(':')[-1]:>6s}" for u in UNITS))
    for prog, s in stats.items():
        print(f"{prog:{wide}s} {s['bundles']:8d} " +
              " ".join(f"{s.get(u, 0.0):6.1f}" for u in UNITS))


if __name__ == "__main__":
    main()
