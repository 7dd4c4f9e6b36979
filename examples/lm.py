#!/usr/bin/env python
"""Geo-distributed language-model training demo: the flagship transformer
through the full HiPS topology.

The reference's example matrix trains CNNs only (ref: examples/cnn.py et
al.); this demo is the TPU-native flagship equivalent — a GPT-style LM
(``models/transformer.py``, optionally top-k MoE) whose gradients ride
the same two-tier kvstore, WAN compression, and sync algorithms as the
CNN demos.  Runs pseudo-distributed in one process over the in-proc
fabric (one thread per worker), like examples/cnn.py.

Examples:
    python examples/lm.py --parties 2 --workers 2 --steps 20
    python examples/lm.py --compression bsc --layers 4 --d-model 128
    python examples/lm.py --moe-top-k 2 --experts 4
"""

import argparse
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.data import TokenIterator, synthetic_lm
from geomx_tpu.kvstore import Simulation
from geomx_tpu.models.transformer import (
    AUX_COEF, TransformerConfig, init_params, make_apply,
    token_cross_entropy,
)
from geomx_tpu.training import run_worker


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parties", type=int, default=2)
    ap.add_argument("--workers", type=int, default=1, help="workers per party")
    ap.add_argument("--global-servers", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=256)
    ap.add_argument("--moe-top-k", type=int, default=0,
                    help=">0 turns every 2nd layer into a top-k routed "
                         "MoE (real EP, parallel/moe.py)")
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adam",
                    choices=["sgd", "adam", "dcasgd"])
    ap.add_argument("--sync", default="fsa", choices=["fsa", "mixed"])
    ap.add_argument("--compression", default="none",
                    choices=["none", "fp16", "2bit", "bsc", "mpq"])
    ap.add_argument("--bsc-ratio", type=float, default=0.01)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from geomx_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    topo_cfg = Config(
        topology=Topology(num_parties=args.parties,
                          workers_per_party=args.workers,
                          num_global_servers=args.global_servers),
        sync_global_mode=(args.sync == "fsa"),
        compression=args.compression,
        bsc_ratio=args.bsc_ratio,
    )
    sim = Simulation(topo_cfg)
    tokens = synthetic_lm(n=2048, seq=args.seq, vocab=args.vocab,
                          seed=args.seed)
    num_all = topo_cfg.topology.num_workers_total

    use_aux = args.moe_top_k > 0
    mcfg = TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_layers=args.layers, d_ff=args.d_ff, max_seq=args.seq,
        moe_every=2 if use_aux else 0, n_experts=args.experts,
        moe_top_k=args.moe_top_k, compute_dtype=jnp.float32,
    )
    params = init_params(mcfg, jax.random.PRNGKey(args.seed))
    apply_fn = make_apply(mcfg, return_aux=use_aux)

    @jax.jit
    def grad_fn(p, x, _y):
        def loss_fn(p):
            out = apply_fn(p, x)
            logits, aux = out if use_aux else (out, 0.0)
            loss = token_cross_entropy(logits, x) + AUX_COEF * aux
            acc = jnp.mean(
                jnp.argmax(logits[:, :-1], axis=-1) == x[:, 1:])
            return loss, acc

        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, acc, g

    histories = {}
    lock = threading.Lock()

    def worker_main(party, rank, widx):
        kv = sim.worker(party, rank)
        if rank == 0:
            if party == 0:
                kv.set_optimizer({"type": args.optimizer, "lr": args.lr})
            if args.compression != "none":
                kv.set_gradient_compression(
                    {"type": args.compression, "ratio": args.bsc_ratio})
        kv.barrier()
        it = TokenIterator(tokens, args.batch, widx, num_all,
                           seed=args.seed)
        t0 = time.time()

        def log(step, loss, acc):
            if rank == 0 and party == 0:
                print(f"step {step:4d}  loss {loss:.4f}  "
                      f"next-tok acc {acc:.3f}  ({time.time() - t0:.2f}s)",
                      flush=True)

        hist = run_worker(kv, params, grad_fn, it, args.steps, log_fn=log)
        with lock:
            histories[(party, rank)] = hist

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
    first = np.mean([histories[k][0][0] for k in histories])
    last = np.mean([histories[k][-1][0] for k in histories])
    print(f"loss {first:.4f} -> {last:.4f} "
          f"(uniform = {np.log(args.vocab):.2f}); "
          f"WAN bytes/step {wan['wan_send_bytes'] / max(args.steps, 1):.0f}")
    sim.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
