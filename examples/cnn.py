#!/usr/bin/env python
"""Geo-distributed CNN training demo — parity with the reference examples
(ref: examples/cnn.py, cnn_fp16.py, cnn_bsc.py, cnn_mpq.py, cnn_hfa.py —
one flag here per reference script; ref prints wall time + accuracy per
iteration, examples/cnn.py:128-131).

Runs the full HiPS topology (parties × workers + global tier) in one
process over the in-proc fabric (the reference's pseudo-distributed mode,
ref: docs/source/pseudo-distributed-deployment.rst), one thread per
worker, JAX/XLA for compute.

Examples:
    python examples/cnn.py --parties 2 --workers 2 --steps 20
    python examples/cnn.py --compression bsc --bsc-ratio 0.01
    python examples/cnn.py --sync mixed --optimizer dcasgd
    python examples/cnn.py --hfa --hfa-k2 4
"""

import argparse
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.data import ShardedIterator, synthetic_classification
from geomx_tpu.kvstore import Simulation
from geomx_tpu.models import MODEL_REGISTRY, create_model_state
from geomx_tpu.training import ESync, Trainer, run_worker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parties", type=int, default=2)
    ap.add_argument("--workers", type=int, default=2, help="workers per party")
    ap.add_argument("--global-servers", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--optimizer", default="adam",
                    choices=["sgd", "adam", "dcasgd"])
    ap.add_argument("--model", default="cnn",
                    choices=sorted(MODEL_REGISTRY))
    ap.add_argument("--sync", default="fsa", choices=["fsa", "mixed"],
                    help="fsa = both tiers sync; mixed = async global tier")
    ap.add_argument("--compression", default="none",
                    choices=["none", "fp16", "2bit", "bsc", "mpq"])
    ap.add_argument("--bsc-ratio", type=float, default=0.01)
    ap.add_argument("--p3", action="store_true",
                    help="priority-based parameter propagation (sliced "
                         "sends + piggybacked pulls)")
    ap.add_argument("--tsengine", action="store_true",
                    help="TSEngine overlay dissemination (intra-party)")
    ap.add_argument("--tsengine-inter", action="store_true",
                    help="TSEngine WAN overlay (global servers -> local "
                         "servers replaces the FSA pull-down)")
    ap.add_argument("--tsengine-inter-push", action="store_true",
                    help="TSEngine WAN push overlay: local servers "
                         "pair-merge before one elected server pushes up "
                         "(implies --tsengine-inter)")
    ap.add_argument("--dgt", type=int, default=0, choices=[0, 1, 2, 3],
                    help="DGT transport mode (1=lossy channels, 2=reliable, 3=reliable+4bit requant)")
    ap.add_argument("--hfa", action="store_true")
    ap.add_argument("--hfa-k1", type=int, default=2,
                    help="local steps between party syncs")
    ap.add_argument("--hfa-k2", type=int, default=2,
                    help="party syncs between WAN syncs")
    ap.add_argument("--esync", action="store_true",
                    help="ESync straggler balancing: the party's state "
                         "server assigns per-worker local step counts "
                         "(implies HFA-style weight exchange; --steps "
                         "counts sync rounds)")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="train from a record-IO dataset file instead of "
                         "in-memory synthetic data (written on first use); "
                         "exercises the IO subsystem: record reader + "
                         "augmentation + threaded prefetch")
    ap.add_argument("--mnist", metavar="DIR", default=None,
                    help="train on REAL MNIST idx files from DIR "
                         "(train-images-idx3-ubyte[.gz] etc. — the "
                         "reference's exact demo dataset, examples/"
                         "cnn.py:54-63); falls back to synthetic when "
                         "unset.  Prints held-out t10k accuracy at the "
                         "end when the test files are present.")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from geomx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = Config(
        topology=Topology(num_parties=args.parties,
                          workers_per_party=args.workers,
                          num_global_servers=args.global_servers),
        sync_global_mode=(args.sync == "fsa"),
        compression=args.compression,
        bsc_ratio=args.bsc_ratio,
        use_hfa=args.hfa or args.esync,
        hfa_k1=args.hfa_k1,
        hfa_k2=args.hfa_k2,
        enable_p3=args.p3,
        p3_slice_elems=50_000,
        enable_intra_ts=args.tsengine,
        enable_inter_ts=args.tsengine_inter or args.tsengine_inter_push,
        enable_inter_ts_push=args.tsengine_inter_push,
        enable_dgt=args.dgt,
    )
    sim = Simulation(cfg)

    def _mnist_file(stem):
        from pathlib import Path as _P

        for name in (stem, stem + ".gz", stem.replace("-idx", ".idx"),
                     stem.replace("-idx", ".idx") + ".gz"):
            p = _P(args.mnist) / name
            if p.exists():
                return str(p)
        return None

    if args.mnist and args.record:
        ap.error("--mnist and --record are mutually exclusive")
    if args.mnist:
        # decode ONCE in main and share the arrays across every worker
        # thread (ShardedIterator indexes a shared array, like the
        # synthetic path) — per-worker re-reads would hold
        # num_workers copies of the decoded train set
        from geomx_tpu.data import MNISTIter

        ti = _mnist_file("train-images-idx3-ubyte")
        tl = _mnist_file("train-labels-idx1-ubyte")
        if ti is None or tl is None:
            ap.error(f"--mnist {args.mnist}: train idx files not found")
        x = MNISTIter._read_idx(ti).astype(np.float32) / 255.0
        if x.ndim == 3:
            x = x[..., None]
        y = MNISTIter._read_idx(tl).astype(np.int32)
    else:
        x, y = synthetic_classification(n=4096, seed=args.seed)
    if args.record:
        from pathlib import Path as _P

        from geomx_tpu.data import write_array_dataset

        if not _P(args.record).exists():
            write_array_dataset(args.record, x, y)
            print(f"wrote record dataset: {args.record}", flush=True)
    num_all = cfg.topology.num_workers_total

    model, params, grad_fn = create_model_state(
        args.model, jax.random.PRNGKey(args.seed),
        input_shape=(1, 28, 28, 1))

    histories = {}
    final_params: dict = {}
    lock = threading.Lock()

    def worker_main(party, rank, widx):
        kv = sim.worker(party, rank)
        if rank == 0:
            # rank-0 of each party configures its party's server; only one
            # worker needs to ship the optimizer to the global tier
            if party == 0:
                kv.set_optimizer({"type": args.optimizer, "lr": args.lr})
            if args.compression != "none":
                kv.set_gradient_compression(
                    {"type": args.compression, "ratio": args.bsc_ratio})
        kv.barrier()
        prefetch = None
        if args.record:
            from geomx_tpu.data import (AugmentIter, PrefetchIter,
                                        RecordDatasetIter)

            it = prefetch = PrefetchIter(AugmentIter(
                RecordDatasetIter(args.record, args.batch, widx, num_all,
                                  seed=args.seed),
                flip=True, seed=args.seed + widx))
        else:
            it = ShardedIterator(x, y, args.batch, widx, num_all,
                                 seed=args.seed)
        t0 = time.time()

        def log(step, loss, acc):
            if rank == 0 and party == 0:
                print(f"step {step:4d}  loss {loss:.4f}  acc {acc:.3f}  "
                      f"({time.time() - t0:.2f}s)", flush=True)

        outp: dict = {}
        hist = run_worker(
            kv, params, grad_fn, it, args.steps, log_fn=log,
            params_out=outp, schedule=Trainer.schedule_for(
                kv, esync=ESync() if args.esync else None))
        if prefetch is not None:
            prefetch.close()
        with lock:
            histories[(party, rank)] = hist
            if widx == 0:
                final_params["p"] = outp.get("params")

    threads = []
    widx = 0
    for p in range(args.parties):
        for r in range(args.workers):
            t = threading.Thread(target=worker_main, args=(p, r, widx))
            t.start()
            threads.append(t)
            widx += 1
    for t in threads:
        t.join()

    wan = sim.wan_bytes()
    final_acc = np.mean([histories[k][-1][1] for k in histories])
    print(f"final mean acc {final_acc:.3f}; "
          f"WAN bytes/step {wan['wan_send_bytes'] / max(args.steps, 1):.0f}")
    if args.mnist and final_params.get("p") is not None:
        # the reference's oracle: held-out test accuracy
        # (examples/cnn.py:128-131 prints test accuracy per iteration)
        ti = _mnist_file("t10k-images-idx3-ubyte")
        tl = _mnist_file("t10k-labels-idx1-ubyte")
        if ti and tl:
            from geomx_tpu.data import MNISTIter

            tx = MNISTIter._read_idx(ti).astype(np.float32) / 255.0
            if tx.ndim == 3:
                tx = tx[..., None]
            ty = MNISTIter._read_idx(tl).astype(np.int32)
            logits = model.apply(final_params["p"], tx[:2048])
            acc = float(np.mean(
                np.argmax(np.asarray(logits), -1) == ty[:2048]))
            print(f"MNIST t10k accuracy (2048 held-out): {acc:.4f}")
    sim.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
