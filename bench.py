#!/usr/bin/env python
"""Benchmark harness. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", ...extras}.

The orchestrator (this process) never imports jax: a chip belongs to one
process at a time, so every benchmark runs in a child process, and the
device children run strictly one after another while the CPU children
run beside them on a second thread under ``JAX_PLATFORMS=cpu``.  Rules:

- **global wall-clock deadline** (``BENCH_DEADLINE_S``, default 480 s):
  every child's timeout is clipped to the remaining budget and children
  are skipped outright once it is exhausted;
- **incremental emission**: the record is re-printed as one JSON line
  after *every* child completes — last line wins — so a kill at any
  point still leaves the freshest complete record on stdout;
- **SIGTERM/SIGINT flush**: the handler kills running children, prints
  the current record, and exits non-zero;
- **a device number needs a device**: each device child refuses to run
  unless jax finds a TPU, and a kind missing from ``CHIP_PEAKS`` is an
  error.  Without ``--skip-tpu`` the harness exits non-zero when no chip
  is found or any device child fails.

Benchmarks (TPU: cnn/mfu/quant/overlap_tpu/flash_autotune; CPU:
wan/lm/scaling/stress/overlap):
- **cnn**   CIFAR-10-shape CNN images/sec/chip (BASELINE.md metric #1).
  The step loop runs on-device via lax.scan — one dispatch per
  measurement.
- **mfu**   flagship transformer (models/transformer.py) fwd+bwd+adam,
  bf16: achieved TFLOP/s vs the chip's peak (VERDICT r1 item 1).
- **quant** on-chip pallas 2-bit quantization throughput vs the host
  C++/numpy codec (VERDICT r1 item 2).
- **flash_autotune** on-chip Q-tile sweep for the pallas ring-flash
  kernel at the real hop geometry (feeds GEOMX_FLASH_BLOCK_Q).
- **wan**   WAN bytes/step per codec config on the full two-tier stack
  (CPU, in-proc sim) + the 50M-element MultiGPS×BSC flagship ledger.
- **lm**    the 10.3M-param flagship LM through 2 parties with MPQ:
  steady tokens/s + WAN bytes/step (BASELINE.md metric #2 at scale).
- **scaling** weak-scaling points on virtual meshes + the modeled
  8->256-chip ICI/DCN roofline (BASELINE.md metric #3).
- **stress** 200 MB x 4-worker server merge throughput.
- **overlap** P3 staged overlap vs BSP under a serialized WAN.

vs_baseline: BASELINE.md's north star is >=0.9x the per-chip throughput
of an A100 running the reference CUDA build on the same CNN.  No A100
is reachable (zero egress), so the A100 reference is **derived**, not
measured: images/sec = EFF_A100 * A100_PEAK_BF16 / CNN_FLOPS_PER_IMAGE,
with the assumed efficiency stated in the output.  For the tiny
2-conv/3-dense CNN the honest statement is that both chips are
launch/input-bound; the FLOP-derived bound with a generous efficiency
is an upper estimate of the reference, making vs_baseline conservative.
"""

import argparse
import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

# run strictly one at a time, in this order, each with its timeout (s)
DEVICE_CHILDREN = (("cnn", 300), ("mfu", 300), ("quant", 180),
                   ("overlap_tpu", 240), ("flash_autotune", 240))

BATCH = 4096        # measured: throughput saturates at 4096 (584k img/s
#                     vs 302k at 1024 — the tiny CNN is HBM-bound and
#                     needs the batch to amortize per-step overheads)
STEPS = 32          # per on-device scan segment
A100_PEAK_BF16 = 312e12
A100_SXM_BW = 2039e9   # A100-SXM 80GB HBM2e
A100_PCIE_BW = 1555e9  # A100 40GB HBM2
# Published per-chip peaks, keyed by jax's ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  A kind
# that is not in the table is an error, never a default.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
V5E = CHIP_PEAKS["TPU v5 lite"]  # the modeled scaling roofline's chip


def _device():
    """The chip a device child measures on; a host without one fails the
    child instead of printing a CPU number under a device metric."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"device child needs a TPU; jax found platform={dev.platform!r}")
    return dev


def _chip_peaks(dev) -> dict:
    try:
        return CHIP_PEAKS[dev.device_kind]
    except KeyError:
        raise SystemExit(
            f"no peak table entry for device_kind {dev.device_kind!r}; "
            "add it to CHIP_PEAKS with its source") from None


# --------------------------------------------------------------------------
# children (each runs in its own subprocess; prints one JSON line)
# --------------------------------------------------------------------------

def _cnn_flops_per_image():
    """Analytic fwd FLOPs/image of models/cnn.py's CNN at 32x32x3; the
    train step is ~3x fwd (fwd + 2x in bwd)."""
    f = 0.0
    # conv1: 32x32x3 -> 32x32x32, 3x3;  conv2: pool-> 16x16x64, 3x3
    f += 2 * 32 * 32 * 32 * (3 * 3 * 3)
    f += 2 * 16 * 16 * 64 * (3 * 3 * 32)
    # dense: flatten 8*8*64=4096 -> 128 -> 64 -> 10 (models/cnn.py)
    f += 2 * (8 * 8 * 64) * 128 + 2 * 128 * 64 + 2 * 64 * 10
    return 3.0 * f


# per-image activation tensor sizes (elements) of the demo CNN
_CNN_T = dict(x=32 * 32 * 3, y1=32 * 32 * 32, p1=16 * 16 * 32,
              y2=16 * 16 * 64, p2=8 * 8 * 64, d1=128, d2=64, lg=10)
_CNN_PARAMS = (27 * 32 + 32) + (288 * 64 + 64) + \
    (4096 * 128 + 128) + (128 * 64 + 64) + (64 * 10 + 10)


def _cnn_bytes_per_image(act_b: float, fused: bool, batch: int) -> float:
    """HBM traffic per image of one train step, from a per-op table.

    ``act_b``: activation dtype bytes (2=bf16, 4=fp32).  ``fused``:
    True models an XLA-style executor (pointwise ops — relu, cast, bias
    — fused into the adjacent conv/pool/dense kernel, so they cost no
    extra HBM round-trip); False models the reference's MXNet 1.x
    executor, where each relu fwd/bwd is its own CUDA kernel that
    re-reads and re-writes the activation (MXNet's pointwise fuser only
    merges chains of pointwise ops; a lone relu between conv and pool
    stays a kernel).  Conv/pool/dense boundaries are never fused on
    either stack.  Input x stays fp32 (4B) in all scenarios.
    """
    T = _CNN_T
    b = 0.0
    # conv1: read x fp32, write y1
    b += T["x"] * 4 + T["y1"] * act_b
    if not fused:                       # relu1 kernel: r+w y1
        b += 2 * T["y1"] * act_b
    b += (T["y1"] + T["p1"]) * act_b    # pool1
    b += (T["p1"] + T["y2"]) * act_b    # conv2
    if not fused:
        b += 2 * T["y2"] * act_b        # relu2
    b += (T["y2"] + T["p2"]) * act_b    # pool2
    b += (T["p2"] + T["d1"]) * act_b    # dense1
    if not fused:
        b += 2 * T["d1"] * act_b
    b += (T["d1"] + T["d2"]) * act_b    # dense2
    if not fused:
        b += 2 * T["d2"] * act_b
    b += (T["d2"] + T["lg"]) * act_b    # dense3
    b += 2 * T["lg"] * act_b            # softmax+loss
    # bwd
    b += 2 * T["lg"] * act_b                                # dloss
    b += (T["lg"] + T["d2"] + T["d2"]) * act_b              # dense3 bwd
    if not fused:
        b += 3 * T["d2"] * act_b
    b += (T["d2"] + T["d1"] + T["d1"]) * act_b              # dense2 bwd
    if not fused:
        b += 3 * T["d1"] * act_b
    b += (T["d1"] + T["p2"] + T["p2"]) * act_b              # dense1 bwd
    b += (T["p2"] + T["y2"] + T["y2"]) * act_b              # pool2 bwd (mask)
    if not fused:
        b += 3 * T["y2"] * act_b                            # relu2 bwd
    b += (T["y2"] + T["p1"]) * act_b                        # conv2 dx
    b += (T["p1"] + T["y2"]) * act_b                        # conv2 dw
    b += (T["p1"] + T["y1"] + T["y1"]) * act_b              # pool1 bwd
    if not fused:
        b += 3 * T["y1"] * act_b                            # relu1 bwd
    b += T["x"] * 4 + T["y1"] * act_b                       # conv1 dw
    # adam: read g,p,m,v; write p,m,v — fp32, amortized over the batch
    b += _CNN_PARAMS * 4 * 7 / batch
    return b


def child_cnn():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from geomx_tpu.models import create_cnn_state

    dev = _device()
    peaks = _chip_peaks(dev)
    rng = jax.random.PRNGKey(0)
    model, params, _ = create_cnn_state(
        rng, input_shape=(BATCH, 32, 32, 3), num_classes=10)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    def step(carry, _):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        updates, s = tx.update(grads, s, p)
        return (optax.apply_updates(p, updates), s), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_steps(p, s):
        (p, s), losses = jax.lax.scan(step, (p, s), None, length=STEPS)
        return p, s, losses[-1]

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (BATCH, 32, 32, 3), dtype=np.float32))
    y = jnp.asarray(np.random.default_rng(1).integers(
        0, 10, BATCH, dtype=np.int32))

    # compile + warmup; the scalar readback is the sync point
    params, opt_state, loss = run_steps(params, opt_state)
    _ = float(loss)

    best_dt = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        params, opt_state, loss = run_steps(params, opt_state)
        _ = float(loss)
        best_dt = min(best_dt, time.perf_counter() - t0)

    ips = BATCH * STEPS / best_dt

    # ---- A100 reference derivation (no A100 is reachable; BASELINE.md:
    # the reference repo publishes no throughput numbers either).  The
    # tiny CNN is HBM-bound on any modern chip (arithmetic intensity
    # ~50 FLOP/byte << both chips' ridge points), so the roofline is the
    # bandwidth one.  Method: compute per-op HBM traffic tables for (a)
    # our XLA execution and (b) the reference's MXNet-1.x execution
    # (unfused pointwise kernels; fp32 activations as its examples run,
    # plus a bf16-granted variant), calibrate the achievable bandwidth
    # fraction from OUR measured throughput, and grant the reference the
    # same fraction on A100 — i.e. the reference is modeled with
    # XLA-grade kernel efficiency and only pays for its own executor's
    # memory traffic.  Every input is a spec sheet number, a measured
    # number, or an auditable per-op count (_cnn_bytes_per_image).
    flops_img = _cnn_flops_per_image()
    xla_bytes = _cnn_bytes_per_image(2, fused=True, batch=BATCH)
    f_bw = ips * xla_bytes / peaks["hbm_bytes_per_s"]  # achieved HBM fraction

    # The reference is granted a FIXED 0.70 HBM fraction per kernel (the
    # practical ceiling of well-tuned bandwidth-bound CUDA kernels; its
    # executor's inefficiency is the extra traffic, already counted in
    # the per-op tables) — NOT our measured fraction.  Granting the
    # measured fraction would cancel ips out of the ratio entirely,
    # making vs_baseline blind to real regressions on our side.
    EFF_REF_BW = 0.70
    EFF_REF_FLOPS = 0.25

    def a100_ips(act_b, fused, bw, flop_peak):
        byt = _cnn_bytes_per_image(act_b, fused, BATCH)
        t_bytes = byt / (EFF_REF_BW * bw)
        t_flops = flops_img / (EFF_REF_FLOPS * flop_peak)
        return 1.0 / max(t_bytes, t_flops), byt

    # per-scenario matmul peak: fp32 convs on A100 run TF32 tensor cores
    # at best (156 TF; generous — the as-published cu80/cu101 builds
    # predate A100 and TF32 entirely); bf16 scenarios get the 312 TF
    # bf16 peak
    A100_TF32 = 156e12
    scen = {}
    for name, (act_b, fused, fpk) in {
        "reference_as_published_fp32": (4, False, A100_TF32),
        "reference_granted_bf16": (2, False, A100_PEAK_BF16),
        "hypothetical_xla_grade_peer": (2, True, A100_PEAK_BF16),
    }.items():
        sxm, byt = a100_ips(act_b, fused, A100_SXM_BW, fpk)
        pcie, _ = a100_ips(act_b, fused, A100_PCIE_BW, fpk)
        scen[name] = {
            "bytes_per_image": round(byt, 1),
            "a100_sxm80_ips": round(sxm, 1),
            "a100_pcie40_ips": round(pcie, 1),
            "vs_0.9x_sxm80": round(ips / (0.9 * sxm), 3),
            "vs_0.9x_pcie40": round(ips / (0.9 * pcie), 3),
        }
    primary = scen["reference_as_published_fp32"]["vs_0.9x_sxm80"]
    print(json.dumps({
        "images_per_sec": round(ips, 1),
        "vs_baseline": primary,
        "a100_ref_derivation": {
            "method": ("bandwidth roofline, per-op traffic tables; "
                       "reference granted a fixed 0.70 HBM fraction per "
                       "kernel + 0.25 matmul-peak fraction (see bench.py)"),
            "primary": "reference_as_published_fp32 on A100-SXM 80GB",
            "granted_ref_hbm_fraction": EFF_REF_BW,
            "measured_tpu_hbm_fraction": round(f_bw, 3),
            "tpu_xla_bytes_per_image": round(xla_bytes, 1),
            "cnn_train_flops_per_image": flops_img,
            "scenarios": scen,
        },
        "timing": "best_of_3_min, 32-step on-device scan",
        "batch": BATCH,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device": str(dev),
    }))


# flagship MFU config: MXU-friendly shapes, fits v5e 16 GB with adam.
# attn_impl='flash' (pallas fused attention, no materialized probs) at
# batch 4 measured best on-chip: 84.5 TFLOP/s vs 82.8 for bf16-dense
# at batch 2 and 76.8 for the fp32-dense r1 config; batch 8/16(+remat)
# and seq 4096 all measured lower (see PROGRESS notes).
MFU_CFG = dict(vocab=8192, d_model=2048, n_heads=16, n_layers=8,
               d_ff=8192, max_seq=2048, attn_impl="flash")
MFU_BATCH = 4
MFU_STEPS = 8

# On-chip batch/remat/seq sweep evidence for the config above (VERDICT
# r2 weak #4) — measured interactively via `bench.py --child mfu_sweep`
# on the real chip and baked in here so the driver-run child times only
# the winner but the record carries the full justification.  None =
# sweep not yet captured on hardware this round.
MFU_SWEEP_MEASURED = None


def _transformer_train_flops_per_step(cfg, batch, seq):
    """Standard 6*N*T + attention-matmul term (12*L*T*seq*d_model*3 for
    fwd+bwd), counting the train step (fwd + 2x bwd)."""
    n_params = (cfg["vocab"] * cfg["d_model"]          # embed (tied head)
                + cfg["max_seq"] * cfg["d_model"]      # pos
                + cfg["n_layers"] * 12 * cfg["d_model"] ** 2)
    tokens = batch * seq
    dense = 6.0 * n_params * tokens
    attn = 12.0 * cfg["n_layers"] * tokens * seq * cfg["d_model"]
    return dense + attn, n_params


def _flash_exactness_check(attn_impl: str) -> str:
    """flash vs the fast bf16-dense reference at the geometry the MFU
    child times — the headline number never times an unvalidated kernel.
    Returns a status line; a kernel that does not lower or does not
    match RAISES (no fallback: a "flash" number is flash or nothing)."""
    import jax
    import jax.numpy as jnp

    if attn_impl != "flash":
        return f"skipped (attn_impl={attn_impl!r})"
    from geomx_tpu.models.transformer import (
        TransformerConfig, _single_device_attention)
    from geomx_tpu.parallel.ring_attention import fast_dense_attention

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    shp = (1, MFU_CFG["max_seq"], MFU_CFG["n_heads"],
           MFU_CFG["d_model"] // MFU_CFG["n_heads"])  # [B, T, H, Dh]
    q = jax.random.normal(kq, shp, jnp.bfloat16)
    k = jax.random.normal(kk, shp, jnp.bfloat16)
    v = jax.random.normal(kv, shp, jnp.bfloat16)
    chk = TransformerConfig(attn_impl="flash")
    o = _single_device_attention(chk, q, k, v).astype(jnp.float32)
    r = fast_dense_attention(q, k, v, causal=True).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(o - r)))
    if not (err < 5e-2):  # bf16 attention tolerance (unit inputs)
        raise AssertionError(f"flash vs dense max abs diff {err}")
    return f"ok (max abs diff {err:.2e})"


def child_mfu():
    dev = _device()
    peak = _chip_peaks(dev)["bf16_flops"]
    flash_check = _flash_exactness_check(MFU_CFG["attn_impl"])
    tflops, tokens_per_sec = _time_mfu_config(
        MFU_CFG, MFU_BATCH, steps=MFU_STEPS, reps=3)
    _flops, n_params = _transformer_train_flops_per_step(
        MFU_CFG, MFU_BATCH, MFU_CFG["max_seq"])
    print(json.dumps({
        "achieved_tflops": round(tflops, 2),
        "peak_tflops": peak / 1e12,
        "mfu": round(tflops * 1e12 / peak, 4),
        "model": (f"transformer d{MFU_CFG['d_model']} L{MFU_CFG['n_layers']} "
                  f"ff{MFU_CFG['d_ff']} seq{MFU_CFG['max_seq']} "
                  f"batch{MFU_BATCH} bf16 ({n_params/1e6:.0f}M params)"),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "attn_impl": MFU_CFG["attn_impl"],
        "flash_check": flash_check,
        "config_sweep": MFU_SWEEP_MEASURED,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


def _time_mfu_config(cfg_dict, batch, steps=4, reps=2):
    """Compile + time one MFU config; returns (tflops, tokens/s)."""
    import jax
    import jax.numpy as jnp
    import optax

    from geomx_tpu.models.transformer import (
        TransformerConfig, init_params, lm_loss, make_apply)

    cfg = TransformerConfig(**cfg_dict)
    params = init_params(cfg, jax.random.PRNGKey(0))
    apply_fn = make_apply(cfg)
    tx = optax.adam(1e-4)
    opt_state = tx.init(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, cfg_dict["max_seq"]), 0,
        cfg_dict["vocab"], dtype=jnp.int32)

    def step(carry, _):
        p, s = carry
        loss, grads = jax.value_and_grad(
            lambda p_: lm_loss(apply_fn, p_, tokens))(p)
        updates, s = tx.update(grads, s, p)
        return (optax.apply_updates(p, updates), s), loss

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_steps(p, s):
        (p, s), losses = jax.lax.scan(step, (p, s), None, length=steps)
        return p, s, losses[-1]

    params, opt_state, loss = run_steps(params, opt_state)
    _ = float(loss)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        params, opt_state, loss = run_steps(params, opt_state)
        _ = float(loss)
        best = min(best, time.perf_counter() - t0)
    flops, _n = _transformer_train_flops_per_step(
        cfg_dict, batch, cfg_dict["max_seq"])
    return flops * steps / best / 1e12, batch * cfg_dict["max_seq"] * steps / best


def child_mfu_sweep():
    """Interactive-only: sweep batch/remat/seq/attn around MFU_CFG on the
    real chip; the winning row gets baked into MFU_CFG/MFU_SWEEP_MEASURED.
    Not scheduled by the orchestrator (too slow for the driver budget)."""
    rows = []
    for name, cfg_d, batch in [
        ("flash_b4", dict(MFU_CFG, attn_impl="flash"), 4),
        ("flash_b8", dict(MFU_CFG, attn_impl="flash"), 8),
        ("flash_b16_remat", dict(MFU_CFG, attn_impl="flash", remat=True), 16),
        ("flash_b8_seq4k", dict(MFU_CFG, attn_impl="flash", max_seq=4096), 8),
        ("fast_b4", dict(MFU_CFG, attn_impl="fast"), 4),
        ("fast_b8", dict(MFU_CFG, attn_impl="fast"), 8),
    ]:
        try:
            tf, tps = _time_mfu_config(cfg_d, batch)
            rows.append({"config": name, "tflops": round(tf, 1),
                         "tokens_per_sec": round(tps, 1)})
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rows.append({"config": name,
                         "error": f"{type(e).__name__}: {e}"[:200]})
        print(json.dumps({"sweep": rows}), flush=True)


def child_flash_autotune():
    """On-chip tile autotune for the pallas ring-flash kernel
    (ops/block_attention): time bq candidates at the kernel's REAL
    production geometry — ring hops of max_seq/sp tokens (the kernel's
    only caller is ring_attention fast="flash"; the single-device MFU
    path uses jax's library kernel) — validate each hop's winner against
    the einsum reference, and report the best ``GEOMX_FLASH_BLOCK_Q``
    per hop size."""
    import jax
    import jax.numpy as jnp

    dev = _device()

    from geomx_tpu.ops.block_attention import (
        _block_attn_ref, flash_block_attention)

    B, H = 2, MFU_CFG["n_heads"]
    D = MFU_CFG["d_model"] // MFU_CFG["n_heads"]
    reps = 16
    hops = {}
    for sp in (4, 8):  # flagship sp mesh sizes; hop block = max_seq/sp
        T = MFU_CFG["max_seq"] // sp
        ks = jax.random.split(jax.random.PRNGKey(sp), 3)
        q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, T, H, D), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, T, H, D), jnp.bfloat16)
        offs = jnp.array([T, 0], jnp.int32)  # below-diagonal hop (no mask)
        rows = []
        for bq in (512, 256, 128, 64):
            if bq > T or T % bq:
                continue
            os.environ["GEOMX_FLASH_BLOCK_Q"] = str(bq)

            @jax.jit
            def run(q, k, v):
                # feed the kernel's output back into its own input so
                # every iteration is genuinely data-dependent — a mere
                # scalar carry would leave the kernel loop-invariant
                # and free for XLA to hoist out of the scan
                def body(qc, _):
                    _m, _l, o = flash_block_attention(qc, k, v, offs, True)
                    return qc + (1e-6 * o).astype(qc.dtype), None
                qf, _ = jax.lax.scan(body, q, None, length=reps)
                return qf[0, 0, 0, 0]

            try:
                _ = float(run(q, k, v))  # compile + warmup
                best = float("inf")
                for _i in range(3):
                    t0 = time.perf_counter()
                    _ = float(run(q, k, v))
                    best = min(best, time.perf_counter() - t0)
                rows.append({"block_q": bq,
                             "ms_per_call": round(best / reps * 1e3, 3)})
            except Exception as e:  # noqa: BLE001 — keep sweeping
                rows.append({"block_q": bq,
                             "error": f"{type(e).__name__}: {e}"[:160]})
        timed = [r for r in rows if "ms_per_call" in r]
        if not timed:
            hops[f"hop_{T}"] = {"rows": rows, "error": "none compiled"}
            continue
        winner = min(timed, key=lambda r: r["ms_per_call"])
        os.environ["GEOMX_FLASH_BLOCK_Q"] = str(winner["block_q"])
        _m, _l, o = flash_block_attention(q, k, v, offs, True)
        _rm, _rl, ro = _block_attn_ref(q, k, v, offs, True)
        err = float(jnp.max(jnp.abs(o - ro)))
        if not err < 5e-2:  # bf16 tolerance, unit inputs
            raise AssertionError(
                f"hop {T} winner bq={winner['block_q']} exactness failed: "
                f"max abs diff {err}")
        hops[f"hop_{T}"] = {
            "best_block_q": winner["block_q"],
            "rows": rows,
            "winner_max_abs_err_vs_ref": round(err, 5),
        }
    if not any("best_block_q" in h for h in hops.values()):
        raise RuntimeError(f"no hop produced a winner: {hops}")
    print(json.dumps({
        "hops": hops,
        "geometry": (f"B{B} H{H} D{D} bf16, ring hops of "
                     f"max_seq/sp for sp in (4, 8)"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


QUANT_MB = 64


def child_quant():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from geomx_tpu.ops.quantize import dequantize_2bit_tpu, quantize_2bit_tpu

    dev = _device()
    n = QUANT_MB * (1 << 20) // 4
    g = jnp.asarray(np.random.default_rng(0).standard_normal(n).astype(np.float32))
    r = jnp.zeros_like(g)

    packed, newr = quantize_2bit_tpu(g, r)          # compile + correctness
    out = dequantize_2bit_tpu(packed, n)
    _ = float(out[0]); _ = float(newr[0])
    # spot-check round-trip semantics on-device
    gi = np.asarray(g[:4096]); oi = np.asarray(out[:4096])
    expect = np.where(gi > 0.5, 0.5, np.where(gi < -0.5, -0.5, 0.0))
    if not np.allclose(oi, expect):
        raise AssertionError("on-chip 2bit round-trip mismatch")

    # time the kernel with an ON-DEVICE scan loop: one Python dispatch
    # per measurement
    reps = 32

    @jax.jit
    def run_reps(g, r):
        def body(r, _):
            packed, r = quantize_2bit_tpu(g, r)
            return r, packed[0]
        r, lasts = jax.lax.scan(body, r, None, length=reps)
        return r, lasts[-1]

    rr, last = run_reps(g, r)      # compile + warmup
    _ = float(last)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        rr, last = run_reps(g, r)
        _ = float(last)
        best = min(best, time.perf_counter() - t0)
    dev_dt = best / reps

    # host codec throughput for comparison
    from geomx_tpu.compression.codecs import TwoBitCodec
    codec = TwoBitCodec(threshold=0.5)
    gh = np.asarray(g)
    codec.compress(0, gh)                            # residual warmup
    t0 = time.perf_counter()
    for _ in range(reps):
        codec.compress(0, gh)
    host_dt = (time.perf_counter() - t0) / reps

    print(json.dumps({
        "tpu_quant_mbps": round(QUANT_MB / dev_dt, 1),
        "host_quant_mbps": round(QUANT_MB / host_dt, 1),
        "payload_mb": QUANT_MB,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "roundtrip": "ok",
    }))


def child_overlap():
    """P3 staged-overlap vs BSP step time under a serialized WAN uplink
    (in-proc sim; VERDICT r1 item 3).  Thin wrapper over the shared
    harness in geomx_tpu.overlap — the regression test runs the same
    code, so benchmark and test cannot drift apart."""
    from geomx_tpu.overlap import overlap_vs_bsp_benchmark

    res = overlap_vs_bsp_benchmark()
    res["bsp_s_per_step"] = round(res["bsp_s_per_step"], 4)
    res["overlap_s_per_step"] = round(res["overlap_s_per_step"], 4)
    res["speedup"] = round(res["speedup"], 3)
    print(json.dumps(res))


def child_serde():
    """Wire-format + sharded-merge microbench (CPU, in-proc).

    Measures BOTH wire formats in one run — v2 (raw header +
    np.frombuffer views, scatter-gather frames) vs the legacy v1
    np.save path — and the aggregate push throughput of the key-sharded
    server merge at 8 concurrent pushers, sharded vs single-lock, with
    a bit-identical-sum check (integer-valued gradients make float
    accumulation exact, so any order is the same sum)."""
    import threading as _th

    import numpy as np

    from geomx_tpu.core.config import Config, NodeId, Role, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.kvstore.common import Cmd
    from geomx_tpu.ps.kv_app import KVPairs
    from geomx_tpu.transport.message import Message

    # ---- serde: encode/decode MB/s, v1 vs v2 ----------------------------
    n = int(os.environ.get("BENCH_SERDE_ELEMS", str(8 << 20)))  # 32 MB f32
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(n).astype(np.float32)
    msg = Message(sender=NodeId(Role.SERVER, 0, 0),
                  recipient=NodeId(Role.GLOBAL_SERVER, 0),
                  keys=np.array([0], np.int64), vals=vals,
                  lens=np.array([n], np.int64), push=True, request=True)
    mb = vals.nbytes / 1e6
    reps = 5

    def best(fn):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    raw1 = msg.to_bytes_v1()
    raw2 = bytearray(b"".join(bytes(f) for f in msg.to_frames()))
    t_enc1 = best(msg.to_bytes_v1)
    t_enc2 = best(msg.to_bytes)        # includes the one join copy
    t_frames = best(msg.to_frames)     # the TCP scatter-gather path
    t_dec1 = best(lambda: Message.from_bytes(raw1))
    t_dec2 = best(lambda: Message.from_bytes(raw2))
    decoded = Message.from_bytes(raw2)
    zero_copy_ok = bool(
        np.shares_memory(decoded.vals, np.frombuffer(raw2, np.uint8))
        and decoded.vals.flags.writeable)

    # ---- sharded merge: 8 pushers, disjoint + shared keys ---------------
    def push_throughput(shards: int, pushers: int = 8, pushes: int = 16,
                        elems: int = 1 << 18):
        cfg = Config(topology=Topology(num_parties=1,
                                       workers_per_party=pushers),
                     server_shards=shards)
        sim = Simulation(cfg)
        try:
            ls = sim.local_servers[0]
            # rounds must never complete (pure merge throughput, no WAN
            # round side effects): raise the aggregation target out of
            # reach for the bench's push count, and drop the acks on
            # the floor — we measure the merge, not reply routing
            ls._workers_target = 1 << 30
            ls.server.response = lambda *a, **k: None
            grads = [np.full(elems, float(i + 1), np.float32)
                     for i in range(pushers)]
            workers = sim.topology.workers(0)

            def pusher(i):
                for t in range(pushes):
                    k = i  # disjoint: one key per pusher
                    m = Message(sender=workers[i], recipient=ls.po.node,
                                push=True, request=True, timestamp=t,
                                cmd=Cmd.DEFAULT,
                                keys=np.array([k], np.int64),
                                vals=grads[i],
                                lens=np.array([elems], np.int64))
                    ls._handle_push(m, KVPairs(m.keys, m.vals, m.lens))

            threads = [_th.Thread(target=pusher, args=(i,))
                       for i in range(pushers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            ls._shards.drain()
            wall = time.perf_counter() - t0
            sums = {int(k): float(st.accum.sum())
                    for k, st in ls._keys.items() if st.accum is not None}
            return wall, sums
        finally:
            sim.shutdown()

    t_single, sums_single = push_throughput(shards=1)
    t_sharded, sums_sharded = push_throughput(shards=8)
    print(json.dumps({
        "elems": n,
        "encode_MBps": {"v1_npsave": round(mb / t_enc1, 1),
                        "v2": round(mb / t_enc2, 1),
                        "v2_frames": round(mb / t_frames, 1)},
        "decode_MBps": {"v1_npsave": round(mb / t_dec1, 1),
                        "v2": round(mb / t_dec2, 1)},
        "speedup_encode": round(t_enc1 / t_enc2, 2),
        "speedup_decode": round(t_dec1 / t_dec2, 2),
        # one full hop, old vs new: v1 encode+decode vs v2 frames+decode
        # (the actual TCP path — scatter-gather out, frombuffer in)
        "speedup_roundtrip": round((t_enc1 + t_dec1)
                                   / (t_frames + t_dec2), 2),
        "zero_copy_ok": zero_copy_ok,
        "merge_scaling": {
            "pushers": 8,
            "single_lock_s": round(t_single, 3),
            "sharded_s": round(t_sharded, 3),
            "scaling": round(t_single / t_sharded, 2),
            "sums_bit_identical": sums_single == sums_sharded,
            # scaling > 1 needs real cores: stripes beyond cpu_count
            # only remove lock contention, not compute serialization
            "cpus": os.cpu_count(),
        },
    }))


def child_merge():
    """numpy vs jax merge-backend round wall (ISSUE 10): 8 concurrent
    pushers of one 20M-element (80 MB f32) gradient into one key — the
    pure merge lane, rounds never complete — swept over
    ``Config.merge_backend``, with a bit-parity sum check
    (integer-valued gradients make f32 accumulation exact in any
    order, so numpy and jax must agree to the bit).  Runs in the cpu
    chain under JAX_PLATFORMS=cpu, where it exercises the staged H2D +
    jitted donated-accumulate machinery on the CPU backend: its walls
    are host numbers, never a device metric."""
    import threading as _th

    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.kvstore.common import Cmd
    from geomx_tpu.ps.kv_app import KVPairs
    from geomx_tpu.transport.message import Message

    elems = int(os.environ.get("BENCH_MERGE_ELEMS", "20000000"))
    pushers, pushes = 8, 2

    def run(backend: str):
        cfg = Config(topology=Topology(num_parties=1,
                                       workers_per_party=pushers),
                     merge_backend=backend)
        sim = Simulation(cfg)
        try:
            ls = sim.local_servers[0]
            # pure merge throughput: the round must never complete and
            # acks go on the floor (same harness as serde's
            # merge_scaling — we measure the backend, not reply routing)
            ls._workers_target = 1 << 30
            ls.server.response = lambda *a, **k: None
            grads = [np.full(elems, float(i + 1), np.float32)
                     for i in range(pushers)]
            workers = sim.topology.workers(0)

            def pusher(i):
                for t in range(pushes):
                    m = Message(sender=workers[i], recipient=ls.po.node,
                                push=True, request=True, timestamp=t,
                                cmd=Cmd.DEFAULT,
                                keys=np.array([0], np.int64),
                                vals=grads[i],
                                lens=np.array([elems], np.int64))
                    ls._handle_push(m, KVPairs(m.keys, m.vals, m.lens))

            threads = [_th.Thread(target=pusher, args=(i,))
                       for i in range(pushers)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            ls._shards.drain()
            wall = time.perf_counter() - t0
            acc = ls._backend.materialize(ls._keys[0].accum)
            return wall, float(acc.sum()), ls._backend.stats()
        finally:
            sim.shutdown()

    w_np, s_np, _ = run("numpy")
    w_jx, s_jx, bs = run("jax")

    # ---- full round close: merge -> optimize -> serve-snapshot ------------
    # The pure-merge phase above never completes a round, so it measures
    # accumulate-only machinery.  This phase drives the GLOBAL server
    # through complete rounds — optimizer update included — then pays
    # one serve materialization, the event-driven D2H the device
    # optimizer stage defers everything to (docs/merge-backends.md).
    close_elems = int(os.environ.get("BENCH_MERGE_CLOSE_ELEMS",
                                     str(min(elems, 5_000_000))))
    close_parties, close_rounds = 4, 3

    def run_close(backend: str):
        import hashlib

        from geomx_tpu.optim import make_optimizer

        cfg = Config(topology=Topology(num_parties=close_parties,
                                       workers_per_party=1),
                     merge_backend=backend)
        sim = Simulation(cfg)
        try:
            gs = sim.global_servers[0]
            gs.server.response = lambda *a, **k: None
            with gs._mu:
                gs.optimizer = make_optimizer({"type": "sgd", "lr": 0.1})
                gs._optimizer_configured = True
                gs._activate_dev_opt_locked()
                gs.store[0] = np.zeros(close_elems, np.float32)
            senders = [sim.topology.server(p)
                       for p in range(close_parties)]
            ts = [0]

            def one_round():
                for i, s in enumerate(senders):
                    ts[0] += 1
                    m = Message(sender=s, recipient=gs.po.node,
                                push=True, request=True,
                                timestamp=ts[0], cmd=Cmd.DEFAULT,
                                keys=np.array([0], np.int64),
                                vals=np.full(close_elems, float(i + 1),
                                             np.float32),
                                lens=np.array([close_elems], np.int64))
                    gs._handle(m, KVPairs(m.keys, m.vals, m.lens),
                               gs.server)
                gs._shards.drain()

            one_round()  # warmup (jit compile, device adoption)
            t0 = time.perf_counter()
            for _ in range(close_rounds):
                one_round()
            wall = time.perf_counter() - t0
            st_pre = gs._backend.stats()
            t1 = time.perf_counter()
            w = gs.store[0]  # THE serve-snapshot materialization
            serve_ms = (time.perf_counter() - t1) * 1e3
            st = gs._backend.stats()
            return {
                "wall_s": round(wall, 3),
                "rounds": close_rounds,
                "serve_snapshot_ms": round(serve_ms, 3),
                "opt_device": gs.stats().get("opt_device", ""),
                "round_close_d2h_bytes": st_pre.get("d2h_bytes", 0),
                "d2h_bytes_after_serve": st.get("d2h_bytes", 0),
                "weights_md5": hashlib.md5(
                    np.ascontiguousarray(w).tobytes()).hexdigest(),
            }
        finally:
            sim.shutdown()

    close_np = run_close("numpy")
    close_jx = run_close("jax")

    gb = elems * 4 * pushers * pushes / 1e9
    print(json.dumps({
        "elems": elems, "pushers": pushers, "pushes_per": pushes,
        "numpy_wall_s": round(w_np, 3),
        "jax_wall_s": round(w_jx, 3),
        "numpy_GBps": round(gb / max(w_np, 1e-9), 2),
        "jax_GBps": round(gb / max(w_jx, 1e-9), 2),
        "speedup": round(w_np / max(w_jx, 1e-9), 2),
        "sums_bit_identical": s_np == s_jx,
        "jax_backend": bs,  # names the platform that actually ran
        # full round close (merge->optimize->serve-snapshot): the
        # number the device optimizer stage is judged by.  On a no-TPU
        # host this measures the CPU-jax MACHINERY (the staging memcpy
        # with no collective win) — read device: "cpu" as "not a TPU
        # number"; parity of the trajectories is the real assertion
        "round_close": {
            "elems": close_elems, "parties": close_parties,
            "numpy": close_np, "jax": close_jx,
            "speedup": round(close_np["wall_s"]
                             / max(close_jx["wall_s"], 1e-9), 2),
            "weights_bit_identical":
                close_np["weights_md5"] == close_jx["weights_md5"],
        },
        "cpus": os.cpu_count(),
    }))


# staged-overlap-on-chip config: big enough that per-stage compute is
# real MXU work, small enough that 10 stage jits compile fast.  The sim
# kvstore runs in-proc on the host (no WAN throttle): the child isolates
# the *schedule cost* of staging — per-stage dispatch overhead vs one
# monolithic jit — the open risk against the sim-only overlap claim.
OVL_TPU_CFG = dict(vocab=8192, d_model=1024, n_heads=8, n_layers=8,
                   d_ff=4096, max_seq=1024, attn_impl="fast")
OVL_TPU_BATCH = 8
OVL_TPU_STEPS = 3


def child_overlap_tpu():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.models.transformer import (
        TransformerConfig, make_staged, token_cross_entropy)
    from geomx_tpu.overlap import StagedModel, run_worker_overlapped
    from geomx_tpu.training import run_worker

    dev = _device()
    cfg_d = dict(OVL_TPU_CFG)
    batch = OVL_TPU_BATCH
    cfg = TransformerConfig(**cfg_d)
    fns, stage_params = make_staged(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (batch, cfg.max_seq)), jnp.int32)

    def ce(logits, tokens):
        return token_cross_entropy(logits, tokens), jnp.mean(logits)

    data = [(tokens, tokens)] * (OVL_TPU_STEPS + 1)

    def timed(staged: bool) -> float:
        sim = Simulation(Config(
            topology=Topology(num_parties=1, workers_per_party=1),
            enable_p3=True))
        try:
            kv = sim.all_workers()[0]
            kv.set_optimizer({"type": "sgd", "lr": 1e-4})
            if staged:
                model = StagedModel(fns, ce)
                run_worker_overlapped(kv, model, stage_params, data[:1], 1,
                                      barrier_init=False)  # compile
                t0 = time.perf_counter()
                run_worker_overlapped(kv, model, stage_params,
                                      data[:OVL_TPU_STEPS], OVL_TPU_STEPS,
                                      barrier_init=False)
                return time.perf_counter() - t0

            def grad_fn(ps, x, y):
                def composed(ps):
                    h = x
                    for f, p in zip(fns, ps):
                        h = f(p, h)
                    return ce(h, y)
                (loss, aux), grads = jax.value_and_grad(
                    composed, has_aux=True)(ps)
                return loss, aux, grads

            grad_fn = jax.jit(grad_fn)
            run_worker(kv, stage_params, grad_fn, data[:1], 1,
                       barrier_init=False)  # compile
            t0 = time.perf_counter()
            run_worker(kv, stage_params, grad_fn, data[:OVL_TPU_STEPS],
                       OVL_TPU_STEPS, barrier_init=False)
            return time.perf_counter() - t0
        finally:
            sim.shutdown()

    mono = timed(False) / OVL_TPU_STEPS
    stag = timed(True) / OVL_TPU_STEPS
    n_stages = len(fns)
    print(json.dumps({
        "monolithic_s_per_step": round(mono, 3),
        "staged_s_per_step": round(stag, 3),
        "staged_overhead_s_per_step": round(stag - mono, 3),
        "staged_overhead_per_stage_ms": round(
            (stag - mono) / n_stages * 1000, 1),
        "n_stages": n_stages,
        "model": (f"transformer d{cfg_d['d_model']} "
                  f"L{cfg_d['n_layers']} seq{cfg_d['max_seq']} "
                  f"batch{batch}"),
        "note": ("in-proc kvstore, no WAN throttle: measures the pure "
                 "schedule/dispatch cost of staging on this backend; the "
                 "overlap *win* under WAN contention is the cpu overlap "
                 "child"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }))


def child_lm():
    """Flagship LM through the two-tier stack (VERDICT r3 item 5): the
    same >=10 M-param transformer + MPQ the TCP acceptance test trains
    (tests/test_acceptance_matrix.py::test_lm_flagship_tcp_topology),
    in-proc for bench stability; reports tokens/s (steady: compile step
    excluded) and WAN bytes/step."""
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.data import TokenIterator
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.training import build_flagship_lm, run_worker

    cfg, params, n_params, grad_fn, data = build_flagship_lm()
    batch, steps = 4, 3
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        compression="mpq"))
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "adam", "lr": 1e-3})
        for p in range(2):
            # size bound tuned to the flagship's leaf-size distribution
            # (the reference tunes the same knob,
            # MXNET_KVSTORE_SIZE_LOWER_BOUND): the 147k-element qkv/wo
            # matrices carry most of the bytes and belong on BSC; at the
            # 200k default they ride fp16 and dominate the WAN ledger
            sim.worker(p, 0).set_gradient_compression(
                {"type": "mpq", "size_bound": 100_000})
        hists = {}
        measures = {}
        cur_params = {i: params for i in range(len(ws))}

        def phase(n_steps):
            errs = []

            def one(widx):
                try:
                    from geomx_tpu.utils.measure import Measure

                    kv = ws[widx]
                    it = TokenIterator(data, batch, widx, len(ws))
                    out = {}
                    m = measures[widx] = Measure()
                    hists[widx] = run_worker(kv, cur_params[widx], grad_fn,
                                             it, n_steps,
                                             barrier_init=False,
                                             params_out=out, measure=m)
                    # phase 2 must CONTINUE from phase 1's params — a
                    # restart from the initial point would push a stale
                    # gradient against the servers' trained state and
                    # re-INIT the full model inside the timed window
                    cur_params[widx] = out["params"]
                except Exception as e:  # noqa: BLE001 — re-raised below
                    errs.append((widx, e))

            ths = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(len(ws))]
            t0 = time.perf_counter()
            for t in ths:
                t.start()
            # bounded join: one dead worker must not hang the other
            # party's FSA merge for the child's whole timeout budget
            deadline = time.monotonic() + 150
            for t in ths:
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            if errs:
                raise RuntimeError(f"lm worker(s) failed: {errs!r}")
            if any(t.is_alive() for t in ths):
                raise RuntimeError("lm phase deadlocked (150s)")
            return time.perf_counter() - t0

        # phase 1 pays the one-offs: INIT broadcast of the full model
        # (~n_params*4 bytes on the WAN), jit compile, MPQ tracked-view
        # setup.  Phase 2 is the steady state — its WAN delta and wall
        # are what every subsequent training step sees.
        warm_wall = phase(1)
        base = sim.wan_bytes()["wan_send_bytes"]
        steady_wall = phase(steps)
        sent = sim.wan_bytes()["wan_send_bytes"] - base
        print(json.dumps({
            "n_params": n_params,
            "model": (f"transformer d{cfg.d_model} L{cfg.n_layers} "
                      f"ff{cfg.d_ff} seq{cfg.max_seq} batch{batch}"),
            "topology": "2 parties x 1 worker, MPQ",
            "tokens_per_sec_steady": round(
                batch * cfg.max_seq * steps * len(ws) / steady_wall, 1),
            "warmup_step_wall_s": round(warm_wall, 3),
            "wan_bytes_per_step": round(sent / steps, 1),
            "dense_wan_bytes_would_be": 2 * 2 * n_params * 4,
            "last_loss": round(float(hists[0][-1][0]), 4),
            # per-phase split of the steady steps (worker 0): on this
            # CPU host grad compute dominates and tokens/s is NOT a PS
            # overhead statement (VERDICT r4 weak 5) — the split makes
            # that checkable instead of asserted
            "step_phase_means_s": (
                {name: row["mean_s"]
                 for name, row in measures[0].report().items()}
                if 0 in measures else None),
        }))
    finally:
        sim.shutdown()


# inner script for the measured weak-scaling points: one process per
# device count (xla_force_host_platform_device_count is fixed at backend
# init).  Fixed PER-DEVICE work (batch 1/device), real XLA collectives.
_SCALING_INNER = r"""
import json, time
import jax, jax.numpy as jnp, numpy as np, optax, functools
from geomx_tpu.models.transformer import (
    TransformerConfig, init_params, make_apply, lm_loss)
from geomx_tpu.parallel import make_mesh

n = len(jax.devices())
mesh = make_mesh({"dp": n, "sp": 1, "tp": 1})
cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4, n_layers=2,
                        d_ff=128, max_seq=32, attn_impl="fast")
params = init_params(cfg, jax.random.PRNGKey(0))
apply_fn = make_apply(cfg, mesh=mesh)
tx = optax.sgd(1e-3)
opt = tx.init(params)
tokens = jax.random.randint(jax.random.PRNGKey(1), (n, cfg.max_seq), 0,
                            cfg.vocab, jnp.int32)  # batch 1 per device
from jax.sharding import NamedSharding, PartitionSpec as P
tokens = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))

# tokens MUST be a jit argument, not a closure: a closed-over array is
# baked into the module as a (replicated) constant, which silently
# un-shards the batch — every device then computes the full batch with
# ZERO collectives and the scaling points measure nothing (r5 bug:
# the audit's all-reduce count of 0 exposed it)
@functools.partial(jax.jit, donate_argnums=(0, 1))
def run(p, s, tok):
    def step(carry, _):
        p_, s_ = carry
        loss, g = jax.value_and_grad(
            lambda pp: lm_loss(apply_fn, pp, tok))(p_)
        u, s_ = tx.update(g, s_, p_)
        return (optax.apply_updates(p_, u), s_), loss
    (p, s), losses = jax.lax.scan(step, (p, s), None, length=4)
    return p, s, losses[-1]

# per-point collective audit on the OPTIMIZED HLO (VERDICT r4 item 7):
# the collective mix must scale as expected as the mesh grows — the
# all-reduce count per step stays constant under pure dp weak scaling
# (one grad reduction per pytree fusion group, independent of n), and
# no sharded-size all-gather may exceed the regression bound
from geomx_tpu.utils.hlo import collective_counts, large_gathers
t0 = time.perf_counter()
lowered = run.lower(params, opt, tokens)
compiled = lowered.compile()
compile_s = time.perf_counter() - t0
hlo = compiled.as_text()
audit = {"collectives": collective_counts(hlo),
         "large_gathers": large_gathers(hlo, threshold_bytes=16 * 1024)}

params, opt, loss = compiled(params, opt, tokens)  # warmup execute
_ = float(loss)
best = float("inf")
for _ in range(3):                          # >= 3 timed reps per point
    t0 = time.perf_counter()
    params, opt, loss = compiled(params, opt, tokens)
    _ = float(loss)
    best = min(best, time.perf_counter() - t0)
print(json.dumps({"devices": n, "compile_s": round(compile_s, 2),
                  "step_wall_s": round(best / 4, 4),
                  "loss_finite": bool(jnp.isfinite(loss)),
                  "audit": audit}))
"""


def child_scaling():
    """Scaling-efficiency artifact (BASELINE.md metric #3; VERDICT r3
    item 3).  Two explicitly-labeled halves:

    - **measured**: weak-scaling points on 8/16/32 *virtual CPU*
      devices — real GSPMD partitioning + XLA collectives, fixed
      per-device work.  On this single-core host all virtual devices
      share one core, so wall times prove the sharded program compiles
      and stays numerically sane as the mesh grows; they are NOT chip
      throughput.
    - **modeled**: an ICI/DCN roofline for the HiPS topology (8-chip
      v5e slice per party, parties over WAN), calibrated by measured
      inputs where they exist: the lm child's WAN ledger
      (BENCH_LM_WAN_BYTES_PER_STEP, passed by the orchestrator).  Every
      other constant, the MFU included, is a stated assumption in the
      output.
    """
    from geomx_tpu.training import build_flagship_lm

    measured = []
    t_start = time.monotonic()
    points_budget = float(os.environ.get("BENCH_SCALING_POINTS_S", "200"))
    for n in (8, 16, 32, 64):
        if time.monotonic() - t_start > points_budget - 30:
            # the modeled half (instant) must always land — drop the
            # remaining points, visibly, instead of timing out the child
            measured.append({"devices": n,
                             "error": "skipped: scaling points budget"})
            continue
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_PLATFORM_NAME"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}"
                            ).strip()
        try:
            # 70 s per point: 4 points must fit the orchestrator's child
            # budget WITH the modeled half — one slow compile must cost
            # its point, not the whole scaling artifact
            out = subprocess.run(
                [sys.executable, "-c", _SCALING_INNER], env=env,
                capture_output=True, text=True, timeout=70, cwd=ROOT)
            row = json.loads(out.stdout.strip().splitlines()[-1])
        except (subprocess.SubprocessError, ValueError, IndexError) as e:
            row = {"devices": n, "error": f"{type(e).__name__}: {e}"[:160]}
        measured.append(row)
    # cross-point collective-mix invariant (VERDICT r4 item 7): under
    # pure-dp weak scaling the per-step all-reduce count must NOT grow
    # with the mesh — growth would mean GSPMD re-partitioned the step
    # into per-device reductions (a scaling bug the wall clocks of a
    # shared-core host can't see)
    ar_counts = {r["devices"]: r["audit"]["collectives"].get(
        "all-reduce", 0) for r in measured if "audit" in r}
    # constant AND non-zero: zero all-reduces would mean the batch was
    # silently un-sharded (exactly the baked-in-constant bug this audit
    # caught in r5) — not a healthy scaling point
    audit_ok = (len(set(ar_counts.values())) <= 1
                and all(c > 0 for c in ar_counts.values())
                ) if ar_counts else None
    # None (not a vacuous True) when no point produced an audit
    gather_free = (all(not r["audit"]["large_gathers"]
                       for r in measured if "audit" in r)
                   if ar_counts else None)

    # ---- modeled 8 -> 256-chip curve -----------------------------------
    cfg, _params, n_params, _g, _d = build_flagship_lm()
    batch_per_chip = 32
    cfg_d = dict(vocab=cfg.vocab, d_model=cfg.d_model, n_heads=cfg.n_heads,
                 n_layers=cfg.n_layers, d_ff=cfg.d_ff, max_seq=cfg.max_seq)
    flops_chip, _n = _transformer_train_flops_per_step(
        cfg_d, batch_per_chip, cfg.max_seq)

    mfu, mfu_src = 0.30, "assumed"
    wan_env = os.environ.get("BENCH_LM_WAN_BYTES_PER_STEP")
    if wan_env:
        # lm child ledger: total WAN send bytes/step for 2 parties,
        # push+pull -> per-party per-direction
        wan_party_dir = float(wan_env) / 4.0
        wan_src = "measured (lm child WAN ledger, MPQ)"
    else:
        # analytic MPQ: big tensors BSC (2 * ratio * (4B val + 4B idx))
        # + small fp16; approximate all-big at ratio 0.01 with 2x cap
        wan_party_dir = n_params * 0.02 * 8
        wan_src = "analytic (BSC ratio 0.01, 2x cap)"

    CHIPS_PER_PARTY = 8          # one v5e-8 slice per data center
    V5E_ICI_BW = 100e9           # B/s effective allreduce BW per chip
    M_GLOBAL = 4                 # MultiGPS global servers (tier-2 shards)
    # staged-loop speedup vs serial: taken from THIS round's overlap
    # child when the orchestrator ran it first (sim-measured — NOT
    # on-chip), else the r4/r5 sim-measured ~1.5x
    OVERLAP_MEASURED = float(os.environ.get("BENCH_OVERLAP_MEASURED",
                                            "1.51"))
    grad_bytes = n_params * 2    # bf16 grads on ICI

    def t_step(chips, compressed, overlap, k2, mfu_v, dcn):
        """Per-round wall under one (mfu, dcn, overlap-model) scenario.

        ``k2``: HFA gate — the WAN hop fires every k2-th round (ref
        MXNET_KVSTORE_USE_HFA/K2), amortizing t_dcn.  The WAN term takes
        the max of the per-party uplink and the GLOBAL-TIER INGRESS:
        all parties' push-ups land on M_GLOBAL MultiGPS shards, so once
        parties > M_GLOBAL x (uplink/ingress ratio) the central party's
        aggregate bandwidth is the bottleneck — modeled, not assumed
        away (VERDICT r4 weak 2).  ``overlap``: "sum" = no hiding,
        "max" = perfect P3 hiding, "measured" = the sim-measured 1.53x
        staged-loop speedup applied to the serial sum (clamped at the
        perfect-hiding floor)."""
        parties = max(1, chips // CHIPS_PER_PARTY)
        s = min(chips, CHIPS_PER_PARTY)
        t_comp = flops_chip / (mfu_v * V5E["bf16_flops"])
        t_ici = 2 * grad_bytes * (s - 1) / s / V5E_ICI_BW
        b_dir = wan_party_dir if compressed else n_params * 4
        if parties > 1:
            per_dir = max(b_dir / dcn,                    # party uplink
                          parties * b_dir / (M_GLOBAL * dcn))  # ingress
            t_dcn = 2 * per_dir / k2
        else:
            t_dcn = 0.0
        t_comm = t_ici + t_dcn
        if overlap == "max":
            return max(t_comp, t_comm)
        if overlap == "measured":
            return max(max(t_comp, t_comm),
                       (t_comp + t_comm) / OVERLAP_MEASURED)
        return t_comp + t_comm

    # sensitivity grid (VERDICT r4 item 2): mfu x DCN x overlap-model.
    # 0.43 is the r2 builder-reported on-chip MFU (unverified), 0.30 the
    # roofline's standing assumption, 0.20 a pessimistic floor.
    MFU_GRID = (0.20, 0.30, 0.43)
    DCN_GRID = (0.5e9, 1.25e9, 5e9)
    OVERLAP_GRID = ("sum", "max", "measured")

    # four cumulative feature tiers — the framework's WAN features are
    # exactly what keeps weak-scaling efficiency up once parties > 1.
    # Non-overlap tiers pin overlap="sum"; overlap tiers sweep it.
    tiers = {
        "dense_bsp": dict(compressed=False, k2=1, overlaps=("sum",)),
        "mpq": dict(compressed=True, k2=1, overlaps=("sum",)),
        "mpq_p3_overlap": dict(compressed=True, k2=1,
                               overlaps=OVERLAP_GRID),
        "mpq_p3_hfa_k2_8": dict(compressed=True, k2=8,
                                overlaps=OVERLAP_GRID),
    }

    def eff_band(chips, tier):
        effs = [t_step(8, tier["compressed"], ov, tier["k2"], m, d)
                / t_step(chips, tier["compressed"], ov, tier["k2"], m, d)
                for m in MFU_GRID for d in DCN_GRID
                for ov in tier["overlaps"]]
        effs.sort()
        return {"min": round(effs[0], 4),
                "median": round(effs[len(effs) // 2], 4),
                "max": round(effs[-1], 4)}

    curve = []
    for chips in (8, 16, 32, 64, 128, 256):
        row = {"chips": chips, "parties": max(1, chips // CHIPS_PER_PARTY)}
        for name, tier in tiers.items():
            row[f"efficiency_{name}"] = eff_band(chips, tier)
        curve.append(row)
    # the reference's headline comparison (README.md:12 "up to 20x vs
    # vanilla MXNet PS"): full WAN feature stack vs dense BSP at scale,
    # quoted as a BAND across the sensitivity grid with the worst case
    # first (honest counterpart of the reference's "up to")
    ratios = sorted(
        t_step(256, False, "sum", 1, m, d)
        / t_step(256, True, ov, 8, m, d)
        for m in MFU_GRID for d in DCN_GRID for ov in OVERLAP_GRID)
    full_vs_vanilla = {
        "worst": round(ratios[0], 2),
        "median": round(ratios[len(ratios) // 2], 2),
        "best": round(ratios[-1], 2),
    }

    print(json.dumps({
        "measured_virtual_mesh": {
            "points": measured,
            "allreduce_count_constant_across_mesh": audit_ok,
            "allreduce_counts": ar_counts,
            "no_large_gathers": gather_free,
            "semantics": ("real GSPMD sharding + XLA collectives on "
                          "virtual CPU devices sharing ONE core: proves "
                          "the sharded step compiles/runs at each mesh "
                          "size with the expected collective mix, NOT "
                          "chip throughput"),
        },
        "modeled_roofline": {
            "workload": (f"flagship LM {n_params / 1e6:.1f}M params, "
                         f"batch {batch_per_chip}/chip seq {cfg.max_seq}, "
                         "weak scaling"),
            "topology": f"{CHIPS_PER_PARTY}-chip v5e slice per party "
                        "(ICI psum) + HiPS WAN tier (MPQ) per party; "
                        f"global tier = {M_GLOBAL} MultiGPS shards with "
                        "an explicit ingress term",
            "curve": curve,
            "curve_semantics": ("each efficiency is a min/median/max "
                                "BAND over the sensitivity grid "
                                "mfu x dcn x overlap-model"),
            "full_stack_vs_dense_bsp_speedup_at_256": full_vs_vanilla,
            "reference_claim": "up to 20x vs vanilla PS "
                               "(reference README.md:12)",
            "sensitivity_grid": {
                "mfu": list(MFU_GRID),
                "dcn_Bps": list(DCN_GRID),
                "overlap_models": list(OVERLAP_GRID),
                "note": ("0.43 = r2 builder-reported on-chip MFU "
                         "(unverified), 0.30 = standing assumption, "
                         "0.20 = pessimistic floor; overlap 'measured' "
                         f"= sim-measured {OVERLAP_MEASURED}x staged-"
                         "loop speedup (this round's overlap child "
                         "when available)"),
            },
            "hfa_staleness_cost": {
                "note": ("k2=8 divides WAN rounds by 8 at a CONVERGENCE "
                         "cost, not for free: the long-horizon parity "
                         "child trains hfa_k2_8 vs vanilla for 200 "
                         "steps — see the parity block's "
                         "accuracy_delta_vs_vanilla for the measured "
                         "cost at the demo scale"),
            },
            "calibration": {
                "mfu": {"value": mfu, "source": mfu_src,
                        "role": "center of the sensitivity grid only"},
                "wan_bytes_party_per_dir": {
                    "value": round(wan_party_dir, 1), "source": wan_src},
            },
            "assumptions": {
                "ici_allreduce_bw_per_chip_Bps": V5E_ICI_BW,
                "v5e_peak_bf16_flops": V5E["bf16_flops"],
                "multigps_global_servers": M_GLOBAL,
            },
            "semantics": "MODELED, not measured — roofline with the "
                         "stated assumptions; measured inputs only where "
                         "labeled; efficiencies carry sensitivity bands",
        },
    }))


def child_parity():
    """Long-horizon convergence parity (VERDICT r4 item 3; ref:
    examples/cnn.py:128-131 accuracy-as-oracle, SURVEY §4.3): 200-step
    runs of every WAN feature vs vanilla on the identical model/data/
    seed; reports per-config FINAL held-out accuracy and the delta.
    The same harness gates the test suite
    (tests/test_parity_horizon.py) — one code path, two consumers."""
    from geomx_tpu.utils.parity import run_parity_matrix

    results = run_parity_matrix(steps=200)
    worst = None
    for name, r in results.items():
        d = r.get("accuracy_delta_vs_vanilla")
        if d is not None and (worst is None or d < worst[1]):
            worst = (name, d)
    print(json.dumps({
        "configs": results,
        "steps": 200,
        "worst_delta": {"config": worst[0], "delta": worst[1]}
        if worst else None,
        "semantics": ("final held-out accuracy after 200 steps through "
                      "the 2-party HiPS stack, per WAN feature, vs the "
                      "vanilla run (same model/data/seed); negative "
                      "delta = the feature costs accuracy at horizon"),
    }))


def child_shards():
    """``flagship_50m_round_wall_s`` vs global shard count (1/2/4): the
    horizontally-sharded global tier's scaling axis — near-linear
    round-wall scaling with shard count at high party counts is the win
    condition every subsequent scale claim is measured against.  Same
    50M-element (200 MB fp32) BSC workload as the wan child's flagship
    ledger, swept over ``global_shards``, plus the per-shard
    replication-lag/promotion registry counters next to the wall
    times."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.utils.metrics import system_snapshot

    N_FLAG = int(os.environ.get("BENCH_SHARDS_ELEMS", "50000000"))
    sweep = {}
    for shards in (1, 2, 4):
        sim = Simulation(Config(
            topology=Topology(num_parties=2, workers_per_party=1),
            global_shards=shards))
        try:
            ws = sim.all_workers()
            for w in ws:
                w.init(0, np.zeros(N_FLAG, np.float32))
            ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
            for p in range(2):
                sim.worker(p, 0).set_gradient_compression(
                    {"type": "bsc", "ratio": 0.01})
            g = np.abs(np.random.default_rng(1)
                       .standard_normal(N_FLAG)).astype(np.float32)

            def one_round() -> float:
                t0 = time.perf_counter()
                for w in ws:
                    w.push(0, g)
                for w in ws:
                    w.pull_sync(0)
                    w.wait_all()
                return time.perf_counter() - t0

            # round 1 pays one-time costs + a dense pull resync (see the
            # wan child's flagship ledger); steady = best of two
            cold = one_round()
            dt = min(one_round(), one_round())
            sweep[str(shards)] = {"round_wall_s": round(dt, 3),
                                  "round_wall_s_cold": round(cold, 3)}
        finally:
            sim.shutdown()
    base = sweep["1"]["round_wall_s"]
    print(json.dumps({
        "tensor_elems": N_FLAG,
        "flagship_50m_round_wall_s": {k: v["round_wall_s"]
                                      for k, v in sweep.items()},
        "speedup_vs_1shard": {
            k: round(base / max(v["round_wall_s"], 1e-9), 2)
            for k, v in sweep.items()},
        "sweep": sweep,
        "per_shard_registry": system_snapshot("global_shard"),
    }))


def child_parties():
    """Party-count scaling sweep (ISSUE 12 tentpole): round wall time
    and per-process THREAD COUNT at {4, 16, 64, 128} parties x 4
    workers on the event-driven lightweight simulation — the
    measurement substrate every other scale claim (device-resident
    round close, serving plane, ESync elasticity, shard-count scaling)
    is judged against.  The thread curve is the refactor's win
    condition: O(1) in party count (reactor loops + handler pool)
    where the thread-per-endpoint harness runs O(nodes).  The smallest
    points also run under the legacy threads transport for the
    contrast curve (128 legacy parties would mean thousands of OS
    threads fighting the GIL — exactly what the sweep exists to
    retire, so legacy stops at 16)."""
    import threading

    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    points = [int(x) for x in os.environ.get(
        "BENCH_PARTY_POINTS", "4,16,64,128").split(",") if x]
    legacy_points = [int(x) for x in os.environ.get(
        "BENCH_PARTY_LEGACY_POINTS", "4,16").split(",") if x]
    wpp = int(os.environ.get("BENCH_PARTY_WORKERS", "4"))
    N = int(os.environ.get("BENCH_PARTY_ELEMS", "65536"))

    def run_point(parties: int, lightweight: bool) -> dict:
        # flight off: 770 preallocated event rings are pure construction
        # ballast at 128 parties and record nothing the sweep reads
        cfg = Config(topology=Topology(num_parties=parties,
                                       workers_per_party=wpp),
                     enable_flight=False)
        t0 = time.perf_counter()
        sim = Simulation(cfg, lightweight=lightweight)
        build_s = time.perf_counter() - t0
        try:
            ws = sim.all_workers()
            for w in ws:
                w.init(0, np.zeros(N, np.float32))
            ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
            g = np.ones(N, np.float32)

            def one_round() -> float:
                t0 = time.perf_counter()
                for w in ws:
                    w.push(0, g)
                for w in ws:
                    w.pull_sync(0)
                    w.wait_all()
                return time.perf_counter() - t0

            cold = one_round()
            dt = min(one_round(), one_round())
            return {"round_wall_s": round(dt, 3),
                    "round_wall_s_cold": round(cold, 3),
                    "build_s": round(build_s, 2),
                    "workers": parties * wpp,
                    "process_threads": threading.active_count()}
        finally:
            sim.shutdown()

    sweep, legacy = {}, {}
    for p in points:
        sweep[str(p)] = run_point(p, lightweight=True)
    for p in legacy_points:
        legacy[str(p)] = run_point(p, lightweight=False)
    print(json.dumps({
        "tensor_elems": N,
        "workers_per_party": wpp,
        "party_scaling": {k: v["round_wall_s"] for k, v in sweep.items()},
        "round_wall_s": {k: v["round_wall_s"] for k, v in sweep.items()},
        "process_threads": {k: v["process_threads"]
                            for k, v in sweep.items()},
        "threads_at_128p": sweep.get("128", {}).get("process_threads"),
        "legacy_threads": {k: v["process_threads"]
                           for k, v in legacy.items()},
        "legacy_round_wall_s": {k: v["round_wall_s"]
                                for k, v in legacy.items()},
        "sweep": sweep,
        "legacy_sweep": legacy,
    }))


def child_obs():
    """Metrics-pump overhead guard (ISSUE 7 satellite): enabled-vs-
    disabled round wall on the flagship-shaped 2-party push/pull
    workload, mirroring the trace overhead guard — the telemetry plane
    must ride along at ~zero cost to the round pipeline.  Also reports
    the collected-report count so a 'cheap because dead' pump is
    distinguishable from a cheap live one."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    N = int(os.environ.get("BENCH_OBS_ELEMS", "5000000"))

    def run(obs: bool):
        cfg = Config(topology=Topology(num_parties=2, workers_per_party=1),
                     enable_obs=obs,
                     obs_interval_s=(0.05 if obs else 0.0))
        sim = Simulation(cfg)
        try:
            ws = sim.all_workers()
            for w in ws:
                w.init(0, np.zeros(N, np.float32))
            ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
            g = np.ones(N, np.float32)

            def one_round() -> float:
                t0 = time.perf_counter()
                for w in ws:
                    w.push(0, g)
                for w in ws:
                    w.pull_sync(0)
                    w.wait_all()
                return time.perf_counter() - t0

            one_round()  # cold: one-time costs
            dt = min(one_round(), one_round())
            reports = (sim.metrics_collector.reports_received
                       if obs else 0)
            return dt, reports
        finally:
            sim.shutdown()

    base, _ = run(False)
    obs_dt, reports = run(True)
    print(json.dumps({
        "tensor_elems": N,
        "round_wall_s_disabled": round(base, 4),
        "round_wall_s_enabled": round(obs_dt, 4),
        "overhead_pct": round(100.0 * (obs_dt - base) / max(base, 1e-9), 2),
        "reports_received": reports,
    }))


def child_flight():
    """Flight-recorder overhead guard (ISSUE 9 satellite): round wall
    with the DEFAULT-ON recorder vs GEOMX_FLIGHT=0 on the
    flagship-shaped 2-party push/pull workload (the obs child's
    harness).  The recorder taps every message head, so this is the
    direct measurement of the <2% acceptance bound; the event count
    proves the cheap run actually recorded."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    # big enough that the round is compute/copy bound (~0.1 s) and the
    # per-message tap cost shows as a stable percentage, not host noise
    N = int(os.environ.get("BENCH_FLIGHT_ELEMS", "20000000"))

    def run(flight: bool):
        cfg = Config(topology=Topology(num_parties=2, workers_per_party=1),
                     enable_flight=flight)
        sim = Simulation(cfg)
        try:
            ws = sim.all_workers()
            for w in ws:
                w.init(0, np.zeros(N, np.float32))
            ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
            g = np.ones(N, np.float32)

            def one_round() -> float:
                t0 = time.perf_counter()
                for w in ws:
                    w.push(0, g)
                for w in ws:
                    w.pull_sync(0)
                    w.wait_all()
                return time.perf_counter() - t0

            one_round()  # cold: one-time costs
            dt = min(one_round() for _ in range(4))
            events = sum(po.flight._n for po in sim.offices.values()
                         if po.flight is not None)
            return dt, events
        finally:
            sim.shutdown()

    base, base_events = run(False)
    on_dt, events = run(True)
    print(json.dumps({
        "tensor_elems": N,
        "round_wall_s_disabled": round(base, 4),
        "round_wall_s_enabled": round(on_dt, 4),
        "overhead_pct": round(100.0 * (on_dt - base) / max(base, 1e-9), 2),
        "events_recorded": events,
        "events_disabled": base_events,
    }))


def child_churn():
    """Elastic-membership churn cost (ISSUE 13): round wall and
    stall-round count under a fixed seeded ChurnPlan at {8, 16, 24}
    parties (lightweight reactor substrate) vs a stable control, plus
    the drain-latency acceptance reading — the median
    notice→member-folded latency must be a small fraction of the
    eviction timeout (the whole point of the graceful path: membership
    changes cost a drain, not a heartbeat-expiry window)."""
    import numpy as np

    from geomx_tpu.chaos import ChurnPhase, ChurnPlan
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    points = [int(x) for x in os.environ.get(
        "BENCH_CHURN_POINTS", "8,16,24").split(",") if x]
    N = int(os.environ.get("BENCH_CHURN_ELEMS", "65536"))
    rounds = int(os.environ.get("BENCH_CHURN_ROUNDS", "24"))
    seed = int(os.environ.get("GEOMX_CHURN_SEED", "7"))
    hb_timeout = float(os.environ.get("GEOMX_HEARTBEAT_TIMEOUT", "1.0"))

    def run_point(parties: int, churn: bool) -> dict:
        cfg = Config(topology=Topology(num_parties=parties,
                                       workers_per_party=2),
                     enable_flight=False, lightweight=True,
                     heartbeat_interval_s=0.05,
                     heartbeat_timeout_s=hb_timeout,
                     request_retry_s=0.5, enable_preempt=True)
        sim = Simulation(cfg, lightweight=True)
        try:
            alive = {(w.party, w.rank): w for w in sim.all_workers()}
            for w in alive.values():
                w.init(0, np.zeros(N, np.float32))
            next(iter(alive.values())).set_optimizer(
                {"type": "sgd", "lr": 0.1})
            g = np.ones(N, np.float32)
            # a fixed seeded tape, spread evenly across the measured
            # rounds (one event kind sequence for every point — the
            # plan IS the workload contract)
            plan = ChurnPlan(phases=(ChurnPhase(
                float(rounds), departure_rate=6.0 / rounds,
                join_rate=4.0 / rounds, notice_fraction=0.5),),
                seed=seed, min_workers_per_party=1)
            tape = plan.schedule() if churn else []
            import random as _random

            rng = _random.Random(seed + 1)
            drains: list = []

            def inject(kind: str):
                if kind == "depart":
                    cands = {}
                    for (p, r) in alive:
                        cands.setdefault(p, []).append(r)
                    cands = {p: rs for p, rs in cands.items()
                             if len(rs) > plan.min_workers_per_party}
                    if not cands:
                        return
                    p = rng.choice(sorted(cands))
                    r = rng.choice(sorted(cands[p]))
                    if rng.random() < 0.5:
                        reply = sim.notice_worker(p, r, timeout=10)
                        if reply and reply.get("ok"):
                            drains.append(float(reply["latency_s"]))
                    sim.kill_worker(p, r)
                    del alive[(p, r)]
                else:  # join
                    p = rng.choice(range(parties))
                    kv = sim.add_worker(p)
                    kv.init(0, np.zeros(N, np.float32))
                    alive[(p, kv.po.node.rank)] = kv

            walls = []
            for i in range(rounds):
                while tape and tape[0][0] <= i:
                    _, kind, _ph = tape.pop(0)
                    inject(kind)
                t0 = time.perf_counter()
                for w in list(alive.values()):
                    w.push(0, g)
                for w in list(alive.values()):
                    w.pull_sync(0)
                    w.wait_all()
                walls.append(time.perf_counter() - t0)
            med = sorted(walls)[len(walls) // 2]
            stall = sum(1 for w in walls if w > max(4 * med, 0.05))
            return {"round_wall_s": round(med, 4),
                    "total_wall_s": round(sum(walls), 3),
                    "stall_rounds": stall,
                    "drain_latencies_s": [round(d, 4) for d in drains],
                    "final_workers": len(alive)}
        finally:
            sim.shutdown()

    sweep = {}
    all_drains = []
    for p in points:
        control = run_point(p, churn=False)
        churned = run_point(p, churn=True)
        all_drains.extend(churned["drain_latencies_s"])
        sweep[str(p)] = {
            "control": control, "churn": churned,
            "churn_overhead_pct": round(
                100.0 * (churned["total_wall_s"]
                         - control["total_wall_s"])
                / max(control["total_wall_s"], 1e-9), 2),
        }
    drain_med = (sorted(all_drains)[len(all_drains) // 2]
                 if all_drains else None)
    biggest = str(max(points))
    print(json.dumps({
        "tensor_elems": N, "rounds": rounds, "seed": seed,
        "sweep": sweep,
        "churn_overhead_pct": sweep[biggest]["churn_overhead_pct"],
        "stall_rounds": sweep[biggest]["churn"]["stall_rounds"],
        "drain_latency_s": drain_med,
        "eviction_timeout_s": hb_timeout,
        # the acceptance ratio: the graceful fold must cost a small
        # fraction of what heartbeat expiry would have
        "drain_vs_eviction_timeout": (
            round(drain_med / hb_timeout, 4)
            if drain_med is not None else None),
    }))


def child_partition():
    """Partition tolerance cost (ISSUE 16): what a region-sized WAN
    outage costs the party behind it and the deployment healing it.
    Three readings on a 2-party deployment with a blackholed party-0
    uplink: degraded-round wall vs the healthy baseline (the party
    keeps closing LOCAL rounds against frozen weights — the round
    itself should cost the same or less, there is no WAN leg),
    heal→catch-up-merged latency, and the catch-up bytes shipped on
    heal vs a dense resync of the model (2bit delta — the acceptance
    bound is < 25%)."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    N = int(os.environ.get("BENCH_PARTITION_ELEMS", "262144"))
    rounds = int(os.environ.get("BENCH_PARTITION_ROUNDS", "20"))

    cfg = Config(topology=Topology(num_parties=2, workers_per_party=1),
                 enable_flight=False, lightweight=True,
                 heartbeat_interval_s=0.05, heartbeat_timeout_s=0.4,
                 enable_partition_mode=True, probe_timeout_s=0.4,
                 sync_global_mode=False, partition_degrade_s=0.5,
                 partition_catchup_bound=100000)
    sim = Simulation(cfg, lightweight=True)
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(N, np.float32))
        w0.set_optimizer({"type": "sgd", "lr": 0.1})
        for p in range(2):
            sim.worker(p, 0).set_gradient_compression({"type": "2bit"})
        g = np.ones(N, np.float32)

        def timed_rounds(w, n):
            walls = []
            for _ in range(n):
                t0 = time.perf_counter()
                w.push(0, g)
                w.pull_sync(0)
                w.wait_all()
                walls.append(time.perf_counter() - t0)
            return sorted(walls)[len(walls) // 2]

        healthy = timed_rounds(w0, rounds)

        rm = sim.recovery_monitor
        ls0 = sim.local_servers[0]
        sim.partition_party(0)
        w0.push(0, g)  # the in-flight round the watchdog abandons
        w0.wait_all()
        t0 = time.monotonic()
        while not (ls0._degraded and 0 in rm._quarantined):
            if time.monotonic() - t0 > 30:
                raise RuntimeError("degrade/quarantine never fired")
            time.sleep(0.05)
        detect_s = time.monotonic() - t0
        degraded = timed_rounds(w0, rounds)

        dense_bytes = sum(v.nbytes for v in ls0.store.values())
        before = sim.wan_bytes()["wan_send_bytes"]
        t0 = time.monotonic()
        sim.heal_party(0)
        while ls0.catchup_pushes == 0 or 0 in rm._quarantined:
            if time.monotonic() - t0 > 60:
                raise RuntimeError("catch-up rejoin never completed")
            time.sleep(0.05)
        heal_s = time.monotonic() - t0
        shipped = sim.wan_bytes()["wan_send_bytes"] - before

        evictions = sum(m.evictions for m in sim.eviction_monitors)
        print(json.dumps({
            "tensor_elems": N, "rounds": rounds,
            "healthy_round_wall_s": round(healthy, 4),
            "degraded_round_wall_s": round(degraded, 4),
            "degraded_overhead_pct": round(
                100.0 * (degraded - healthy) / max(healthy, 1e-9), 2),
            "outage_detect_s": round(detect_s, 3),
            "heal_to_merged_s": round(heal_s, 3),
            "catchup_bytes": int(shipped),
            "dense_resync_bytes": int(dense_bytes),
            "catchup_vs_dense": round(shipped / max(dense_bytes, 1), 4),
            "degraded_rounds_absorbed": ls0.degraded_rounds,
            "catchup_fallbacks": ls0.catchup_fallbacks,
            "quarantines": rm.party_quarantines,
            "party_folds": rm.party_folds,
            "worker_evictions": evictions,
        }))
    finally:
        sim.shutdown()


def child_integrity():
    """Data-integrity plane cost & coverage (ISSUE 17).  Three readings:

    1. wire-checksum overhead — median encode+decode wall for a
       representative gradient frame with ``GEOMX_INTEGRITY_WIRE`` off
       vs on.  The serde leg alone is CRC-dominated (zlib.crc32 runs
       ~1 GB/s, the v2 encode is near-zero-copy), so the honest
       acceptance number is ``wan_path_overhead_pct``: the CRC's added
       wall against the frame's WAN transfer time at the deployment's
       link speed (``BENCH_INTEGRITY_WAN_MBPS``, default 100 — the
       cross-region WAN class GeoMX targets; the bound is < 5 %);
    2. detection coverage — a seeded single-bit-flip sweep over a
       stamped frame: every flip must surface as a typed decode error,
       never a silently different message (``silent_deliveries`` is
       the number that must be 0);
    3. corruption soak — a 2-party in-proc deployment trains while a
       seeded bit-flip tap corrupts 20 % of one party's WAN uplink
       frames; the fabric ledger must show every injected corruption
       detected + dropped (the NACK resend path re-delivers), the model
       must stay finite, and zero corrupted payloads may reach a merge.
    """
    import numpy as np

    from geomx_tpu.transport import message as M

    N = int(os.environ.get("BENCH_INTEGRITY_ELEMS", "1048576"))
    reps = int(os.environ.get("BENCH_INTEGRITY_REPS", "30"))
    flips = int(os.environ.get("BENCH_INTEGRITY_FLIPS", "1500"))

    rng = np.random.default_rng(7)

    def mk_msg(elems):
        return M.Message(
            sender=M.NodeId.parse("server:0@p0"),
            recipient=M.NodeId.parse("global_server:0"),
            request=True, push=True, timestamp=7, msg_sig=1234,
            keys=np.array([0], np.int64),
            vals=rng.standard_normal(elems).astype(np.float32),
            lens=np.array([elems], np.int64))

    def median_roundtrip(msg, n):
        walls = []
        for _ in range(n):
            t0 = time.perf_counter()
            M.Message.from_bytes(msg.to_bytes())
            walls.append(time.perf_counter() - t0)
        return sorted(walls)[len(walls) // 2]

    wan_mbps = float(os.environ.get("BENCH_INTEGRITY_WAN_MBPS", "100"))

    saved = M.WIRE_INTEGRITY
    try:
        msg = mk_msg(N)
        M.WIRE_INTEGRITY = False
        legacy = median_roundtrip(msg, reps)
        M.WIRE_INTEGRITY = True
        stamped = median_roundtrip(msg, reps)
        frame_bytes = len(msg.to_bytes())
        # One CRC pass on encode + one on verify; the extra wall is what
        # the stamps cost on top of the near-zero-copy legacy serde.
        crc_extra = max(stamped - legacy, 0.0)
        wire_s = frame_bytes * 8.0 / (wan_mbps * 1e6)
        wan_path_overhead = 100.0 * crc_extra / max(legacy + wire_s, 1e-9)

        # 2. seeded bit-flip sweep over a small stamped frame
        small = mk_msg(4096)
        raw = bytearray(small.to_bytes())
        ref = small.vals.tobytes()
        detected = silent = benign = 0
        for pos in rng.choice(len(raw) * 8, size=min(flips, len(raw) * 8),
                              replace=False):
            byte, bit = int(pos) // 8, int(pos) % 8
            raw[byte] ^= 1 << bit
            try:
                out = M.Message.from_bytes(bytes(raw))
                if (out.vals is not None
                        and out.vals.tobytes() == ref
                        and out.msg_sig == small.msg_sig):
                    benign += 1  # flip landed outside any decoded field
                else:
                    silent += 1
            except Exception:
                detected += 1
            finally:
                raw[byte] ^= 1 << bit
    finally:
        M.WIRE_INTEGRITY = saved

    # 3. corruption soak on the in-proc fabric (wire stamps forced on)
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    soak_rounds = int(os.environ.get("BENCH_INTEGRITY_ROUNDS", "25"))
    M.WIRE_INTEGRITY = True
    cfg = Config(topology=Topology(num_parties=2, workers_per_party=1),
                 enable_flight=False, lightweight=True,
                 sync_global_mode=False, resend_timeout_ms=200)
    sim = Simulation(cfg, lightweight=True)
    try:
        w0, w1 = sim.all_workers()
        for w in (w0, w1):
            w.init(0, np.zeros(8192, np.float32))
        w0.set_optimizer({"type": "sgd", "lr": 0.1})
        src = str(sim.local_servers[0].po.node)
        dst = str(sim.global_servers[0].po.node)
        sim.corrupt_link(src, dst, rate=0.2, mode="bitflip", seed=17)
        g = np.ones(8192, np.float32)
        for _ in range(soak_rounds):
            for w in (w0, w1):
                w.push(0, g)
            for w in (w0, w1):
                w.wait_all()
        sim.heal_corrupt(src, dst)
        final = w0.pull_sync(0)
        fab = sim.fabric
        print(json.dumps({
            "tensor_elems": N, "reps": reps,
            "frame_bytes": frame_bytes,
            "legacy_roundtrip_s": round(legacy, 6),
            "stamped_roundtrip_s": round(stamped, 6),
            "crc_throughput_mb_s": round(
                2.0 * frame_bytes / max(crc_extra, 1e-9) / 1e6, 1),
            "serde_overhead_pct": round(
                100.0 * crc_extra / max(legacy, 1e-9), 2),
            "wan_mbps": wan_mbps,
            "wan_frame_transfer_s": round(wire_s, 6),
            "wan_path_overhead_pct": round(wan_path_overhead, 2),
            "bitflips_tried": detected + silent + benign,
            "bitflips_detected": detected,
            "bitflips_benign": benign,
            "silent_deliveries": silent,
            "soak_rounds": soak_rounds,
            "soak_corrupt_injected": fab.corrupt_injected,
            "soak_corrupt_detected": fab.corrupt_detected,
            "soak_corrupt_dropped": fab.corrupt_dropped,
            "soak_corrupt_delivered": fab.corrupt_delivered,
            "soak_model_finite": bool(np.isfinite(final).all()),
        }))
    finally:
        sim.shutdown()
        M.WIRE_INTEGRITY = saved


def child_serve():
    """Read-serving replica tier (ISSUE 8): ``pulls_per_sec`` at 1/2/4
    replicas under CONCURRENT training — the serving tier's brand-new
    bench axis.  A 2-party deployment trains in a background thread
    while client threads hammer the replicas with SERVE_PULL reads;
    reports aggregate QPS, client-side p50/p99 read latency, a
    staleness histogram over the read metas (every read must sit under
    the configured bound — violations are counted, not averaged away),
    and the training rounds that completed during the measurement
    window (proof the reads rode beside live training, not an idle
    store)."""
    import threading as _threading

    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    N_TENSORS = int(os.environ.get("BENCH_SERVE_TENSORS", "8"))
    ELEMS = int(os.environ.get("BENCH_SERVE_ELEMS", "25000"))
    SECONDS = float(os.environ.get("BENCH_SERVE_SECONDS", "3.0"))
    CLIENTS_PER_REPLICA = 2
    BOUND = 1.0

    def pct(vs, q):
        if not vs:
            return None
        vs = sorted(vs)
        return vs[min(len(vs) - 1, max(0, int(round(q * (len(vs) - 1)))))]

    sweep = {}
    for n_rep in (1, 2, 4):
        cfg = Config(
            topology=Topology(num_parties=2, workers_per_party=1,
                              num_replicas=n_rep),
            serve_staleness_s=BOUND, serve_refresh_interval_s=0.1)
        sim = Simulation(cfg)
        try:
            ws = sim.all_workers()
            for w in ws:
                for tid in range(N_TENSORS):
                    w.init(tid, np.zeros(ELEMS, np.float32))
            ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
            g = np.ones(ELEMS, np.float32)
            stop = _threading.Event()
            rounds = [0]

            def train():
                while not stop.is_set():
                    for w in ws:
                        for tid in range(N_TENSORS):
                            w.push(tid, g)
                    for w in ws:
                        for tid in range(N_TENSORS):
                            w.pull_sync(tid)
                        w.wait_all()
                    rounds[0] += 1

            trainer = _threading.Thread(target=train, daemon=True)
            trainer.start()
            # replicas must hold the keys before the clock starts
            deadline = time.monotonic() + 20
            while (time.monotonic() < deadline
                   and any(r.refresh_rounds == 0 or len(r.store) == 0
                           for r in sim.replicas)):
                time.sleep(0.05)
            pulls = [0]
            errors = [0]
            lats: list = []
            stals: list = []
            mu = _threading.Lock()
            # clients up-front: construction cost stays out of the window
            clients = [sim.serve_client(r) for r in range(n_rep)
                       for _ in range(CLIENTS_PER_REPLICA)]
            t_end = time.monotonic() + SECONDS

            def reader(c):
                i = 0
                while time.monotonic() < t_end:
                    tid = i % N_TENSORS
                    i += 1
                    t0 = time.perf_counter()
                    try:
                        _, meta = c.pull_tensor(tid, ELEMS, timeout=5.0)
                    except (TimeoutError, RuntimeError):
                        with mu:
                            errors[0] += 1
                        continue
                    dt = time.perf_counter() - t0
                    with mu:
                        pulls[0] += 1
                        lats.append(dt * 1e3)
                        s = meta.get("staleness_s")
                        if isinstance(s, (int, float)):
                            stals.append(float(s))

            readers = [
                _threading.Thread(target=reader, args=(c,), daemon=True)
                for c in clients]
            r0 = rounds[0]
            for t in readers:
                t.start()
            for t in readers:
                t.join(timeout=SECONDS + 30)
            trained = rounds[0] - r0
            stop.set()
            trainer.join(timeout=30)
            sweep[str(n_rep)] = {
                "pulls_per_sec": round(pulls[0] / SECONDS, 1),
                "pulls": pulls[0],
                "read_errors": errors[0],
                "serve_p50_ms": round(pct(lats, 0.5) or 0, 2),
                "serve_p99_ms": round(pct(lats, 0.99) or 0, 2),
                "staleness_p50_s": round(pct(stals, 0.5) or 0, 3),
                "staleness_p99_s": round(pct(stals, 0.99) or 0, 3),
                "staleness_max_s": round(max(stals), 3) if stals else None,
                "bound_violations": sum(1 for s in stals if s > BOUND),
                "train_rounds_during_window": trained,
            }
        finally:
            sim.shutdown()
    # ---- serving plane (ISSUE 15): balancer vs single-target, then a
    # mixed read+train soak under seeded replica churn with admission
    # control, batched predict, and the autoscaler all on ------------------
    def _reader_pool(read_fn, n_threads, seconds, recs, mu):
        t_end = time.monotonic() + seconds

        def loop(i):
            j = 0
            while time.monotonic() < t_end:
                tid = (i + j) % N_TENSORS
                j += 1
                t0 = time.perf_counter()
                try:
                    _, meta = read_fn(tid)
                except (TimeoutError, RuntimeError):
                    with mu:
                        recs["errors"] += 1
                    continue
                dt = (time.perf_counter() - t0) * 1e3
                with mu:
                    recs["pulls"] += 1
                    recs["lats"].append((time.monotonic(), dt))
                    s = meta.get("staleness_s")
                    if isinstance(s, (int, float)):
                        recs["stals"].append(float(s))

        ths = [_threading.Thread(target=loop, args=(i,), daemon=True)
               for i in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=seconds + 30)

    def _pct_vals(vals, q):
        return pct(vals, q) or 0.0

    # (a) balanced reads at 2 replicas, same shape as the sweep's
    # single-target measurement: the LB must not cost throughput
    lb_phase = {}
    cfg = Config(
        topology=Topology(num_parties=2, workers_per_party=1,
                          num_replicas=2),
        serve_staleness_s=BOUND, serve_refresh_interval_s=0.1,
        serve_attempt_timeout_s=0.5)
    sim = Simulation(cfg)
    try:
        ws = sim.all_workers()
        for w in ws:
            for tid in range(N_TENSORS):
                w.init(tid, np.zeros(ELEMS, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        g = np.ones(ELEMS, np.float32)
        stop = _threading.Event()

        def train():
            while not stop.is_set():
                for w in ws:
                    for tid in range(N_TENSORS):
                        w.push(tid, g)
                for w in ws:
                    for tid in range(N_TENSORS):
                        w.pull_sync(tid)
                    w.wait_all()

        trainer = _threading.Thread(target=train, daemon=True)
        trainer.start()
        deadline = time.monotonic() + 20
        while (time.monotonic() < deadline
               and any(r.refresh_rounds == 0 or len(r.store) == 0
                       for r in sim.replicas)):
            time.sleep(0.05)
        # one balancer per reader, like the sweep's one client per
        # reader — the comparison measures the LB policy, not lock
        # contention on a shared customer
        n_readers = 2 * CLIENTS_PER_REPLICA
        lbs = [sim.serve_balancer(seed=i) for i in range(n_readers)]
        idx = _threading.local()
        counter = [0]
        mu = _threading.Lock()

        def balanced_read(tid):
            if not hasattr(idx, "lb"):
                with mu:
                    idx.lb = lbs[counter[0] % n_readers]
                    counter[0] += 1
            return idx.lb.pull_tensor(tid, ELEMS, timeout=5.0)

        recs = {"pulls": 0, "errors": 0, "lats": [], "stals": []}
        _reader_pool(balanced_read, n_readers, SECONDS, recs, mu)
        stop.set()
        trainer.join(timeout=30)
        single = sweep["2"]["pulls_per_sec"]
        lb_qps = round(recs["pulls"] / SECONDS, 1)
        lats = [v for _, v in recs["lats"]]
        agg = [lb.stats() for lb in lbs]
        lb_phase = {
            "pulls_per_sec": lb_qps,
            "vs_single_target_2rep": round(lb_qps / max(single, 1e-9),
                                           2),
            "p50_ms": round(_pct_vals(lats, 0.5), 2),
            "p99_ms": round(_pct_vals(lats, 0.99), 2),
            "read_errors": recs["errors"],
            "bound_violations": sum(1 for s in recs["stals"]
                                    if s > BOUND),
            "lb": {k: sum(st[k] for st in agg)
                   for k in ("picks", "failovers", "sheds",
                             "ejections", "probes", "recoveries")},
        }
    finally:
        sim.shutdown()

    # (b) the churn soak: 3 replicas, seeded replica kills mid-load,
    # admission + batching + autoscaler on.  Judged on: zero staleness
    # violations SERVED, sheds explicit and bounded, p99 recovered
    # after the kills, autoscaler stable (no reversal inside cooldown)
    from geomx_tpu.chaos.churn import (ChurnOrchestrator, ChurnPhase,
                                       ChurnPlan)

    SOAK_S = float(os.environ.get("BENCH_SERVE_SOAK_S", "7.0"))
    plane = {}
    cfg = Config(
        topology=Topology(num_parties=2, workers_per_party=1,
                          num_replicas=3),
        serve_staleness_s=BOUND, serve_refresh_interval_s=0.1,
        heartbeat_interval_s=0.2, heartbeat_timeout_s=1.0,
        request_retry_s=1.0,
        serve_max_inflight=64, serve_batch_max=8,
        serve_attempt_timeout_s=0.5, serve_eject_errors=2,
        serve_probe_s=0.5, serve_lb_refresh_s=0.5,
        enable_obs=True, obs_interval_s=0.25,
        serve_autoscale=True, serve_scale_interval_s=0.5,
        serve_scale_cooldown_s=2.0, serve_min_replicas=2)
    sim = Simulation(cfg)
    try:
        ws = sim.all_workers()
        for w in ws:
            for tid in range(N_TENSORS):
                w.init(tid, np.zeros(ELEMS, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        g = np.ones(ELEMS, np.float32)
        stop = _threading.Event()
        rounds = [0]

        def train2():
            while not stop.is_set():
                for w in ws:
                    for tid in range(N_TENSORS):
                        w.push(tid, g)
                for w in ws:
                    for tid in range(N_TENSORS):
                        w.pull_sync(tid)
                    w.wait_all()
                rounds[0] += 1

        trainer = _threading.Thread(target=train2, daemon=True)
        trainer.start()
        deadline = time.monotonic() + 20
        while (time.monotonic() < deadline
               and any(r.refresh_rounds == 0 or len(r.store) == 0
                       for r in sim.replicas)):
            time.sleep(0.05)
        lb = sim.serve_balancer(seed=1)
        plan = ChurnPlan(
            phases=(ChurnPhase(duration_s=SOAK_S * 0.7,
                               notice_fraction=0.0,
                               replica_kill_rate=0.45,
                               replica_restart_s=1.2),),
            seed=int(os.environ.get("BENCH_SERVE_SOAK_SEED", "5")),
            min_replicas_live=2)
        orch = ChurnOrchestrator(sim, plan)
        recs = {"pulls": 0, "errors": 0, "lats": [], "stals": []}
        mu = _threading.Lock()
        t_soak0 = time.monotonic()
        orch.start()
        _reader_pool(lambda tid: lb.pull_tensor(tid, ELEMS,
                                                timeout=5.0),
                     6, SOAK_S, recs, mu)
        orch.stop()
        orch.join(timeout=10)
        stop.set()
        trainer.join(timeout=30)
        # p99 recovery: bucket latencies per second; after the LAST
        # kill the tail bucket must sit back near the pre-kill median
        kills = [e["t"] for e in orch.events
                 if e["kind"] == "churn_replica_kill"]
        buckets = {}
        for t, ms in recs["lats"]:
            buckets.setdefault(int(t - t_soak0), []).append(ms)
        per_bucket_p99 = {b: _pct_vals(v, 0.99)
                          for b, v in sorted(buckets.items())}
        pre = ([per_bucket_p99[b] for b in per_bucket_p99
                if not kills or t_soak0 + b < min(kills)]
               or list(per_bucket_p99.values()))
        baseline_p99 = sorted(pre)[len(pre) // 2]
        tail = [per_bucket_p99[b] for b in sorted(per_bucket_p99)[-2:]]
        p99_recovered = (not kills or not tail or
                         min(tail) <= max(3.0 * baseline_p99, 50.0))
        asc = sim.replica_autoscaler
        stable = True
        ds = asc.decisions
        for i in range(1, len(ds)):
            if (ds[i]["action"] != ds[i - 1]["action"]
                    and ds[i]["t_mono"] - ds[i - 1]["t_mono"]
                    < asc.cooldown_s):
                stable = False
        lb_st = lb.stats()
        shed_total = lb_st["sheds"] + sum(
            r.serve_sheds for r in sim.replicas)
        plane = {
            "soak_s": SOAK_S,
            "pulls_per_sec": round(recs["pulls"] / SOAK_S, 1),
            "read_errors": recs["errors"],
            "replica_kills": orch.stats()["replica_kills"],
            "violations_served": sum(1 for s in recs["stals"]
                                     if s > BOUND),
            "sheds": shed_total,
            "sheds_all_carried_retry_after": True,  # shed errors are
            # constructed with retry_after_s unconditionally
            # (serve/replica.py _shed); the balancer counts them as
            # honored sheds, not failures
            "shed_frac": round(shed_total
                               / max(recs["pulls"] + shed_total, 1), 4),
            "lb": lb_st,
            "p99_ms_prekill": round(baseline_p99, 2),
            "p99_ms_tail": [round(v, 2) for v in tail],
            "p99_recovered": bool(p99_recovered),
            "autoscale": asc.stats(),
            "autoscale_stable": bool(stable),
            "train_rounds": rounds[0],
        }
    finally:
        sim.shutdown()

    base = sweep["1"]["pulls_per_sec"]
    print(json.dumps({
        "tensors": N_TENSORS,
        "tensor_elems": ELEMS,
        "staleness_bound_s": BOUND,
        "window_s": SECONDS,
        "pulls_per_sec": {k: v["pulls_per_sec"] for k, v in sweep.items()},
        "speedup_vs_1replica": {
            k: round(v["pulls_per_sec"] / max(base, 1e-9), 2)
            for k, v in sweep.items()},
        "sweep": sweep,
        "balanced": lb_phase,
        "plane_soak": plane,
    }))


def child_stress():
    """Server merge throughput at scale (VERDICT r1 item 5): one party of
    4 workers pushing a 50M-element tensor (200 MB) through the two-tier
    stack; reports merged GB/s per local server and the native threaded
    axpy's raw rate."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.native import bindings

    N = 50_000_000
    rounds = 2
    sim = Simulation(Config(topology=Topology(num_parties=1,
                                              workers_per_party=4)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(N, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        g = np.ones(N, np.float32)
        t0 = time.perf_counter()
        for _ in range(rounds):
            for w in ws:
                w.push(0, g)
            ws[0].pull_sync(0)
            for w in ws:
                w.wait_all()
        dt = time.perf_counter() - t0

        # native threaded axpy microbenchmark (the merge hot loop)
        acc = np.zeros(N, np.float32)
        t1 = time.perf_counter()
        bindings.accumulate(acc, g)
        axpy_dt = time.perf_counter() - t1
        print(json.dumps({
            "tensor_elems": N,
            "rounds": rounds,
            "round_s": round(dt / rounds, 3),
            "server_merged_gb_per_s": round(
                len(ws) * (N * 4 / 1e9) * rounds / dt, 3),
            "native_axpy_gb_per_s": round((N * 4 / 1e9) / axpy_dt, 2),
            "native_available": bindings.available(),
            # auto-calibrated merge backend: "numpy" means the native
            # threaded path measured slower on this host (e.g. a 1-core
            # cpuset) and disabled itself — never a pessimization
            # (VERDICT r4 weak 7)
            "axpy_backend": bindings.axpy_backend(),
        }))
    finally:
        sim.shutdown()


def child_wan():
    """WAN bytes/step per codec config (in-proc sim, 2 parties x 1 worker —
    topology doesn't change the per-party WAN payload, codecs do)."""
    import numpy as np

    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation

    N_BIG, N_SMALL = 400_000, 50_000
    STEPS_W = 4
    configs = {
        "vanilla": None,
        "fp16": {"type": "fp16"},
        "2bit": {"type": "2bit", "threshold": 0.5},
        "bsc": {"type": "bsc", "ratio": 0.01},
        "mpq": {"type": "mpq", "ratio": 0.01, "size_bound": 200_000},
    }
    from geomx_tpu.utils.metrics import system_snapshot

    def _wan_registry():
        return {k: v for k, v in system_snapshot().items()
                if ".wan_bytes_" in k}

    out = {}
    registry = {}
    table = {}   # per-config {wan_bytes_per_step, round_wall_s}: the
    #              static baseline the adaptive controller's win is
    #              measured against (plus an "adaptive" row below)

    def _run_steps(sim, extra_cfg=None, warm=0, after_warm=None):
        """Steady-state (bytes/step, wall s/step) over STEPS_W rounds.
        ``warm`` rounds run (and are discarded) before the clock starts —
        the device-codec rows exclude jit compilation from the wall —
        and ``after_warm`` (counter snapshots) runs between the two."""
        ws = sim.all_workers()
        rng = np.random.default_rng(0)
        for w in ws:
            w.init(0, np.zeros(N_BIG, np.float32))
            w.init(1, np.zeros(N_SMALL, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        if extra_cfg is not None:
            for p in range(2):
                sim.worker(p, 0).set_gradient_compression(extra_cfg)

        def one_step():
            for tid, nel in ((0, N_BIG), (1, N_SMALL)):
                g = rng.standard_normal(nel).astype(np.float32)
                for w in ws:
                    w.push(tid, g)
            for w in ws:
                w.pull_sync(0)
                w.pull_sync(1)

        for _ in range(warm):
            one_step()
        if after_warm is not None:
            after_warm()
        base = sim.wan_bytes()["wan_send_bytes"]
        t0 = time.perf_counter()
        for _ in range(STEPS_W):
            one_step()
        wall = (time.perf_counter() - t0) / STEPS_W
        sent = (sim.wan_bytes()["wan_send_bytes"] - base) / STEPS_W
        return sent, wall

    for name, comp in configs.items():
        sim = Simulation(Config(
            topology=Topology(num_parties=2, workers_per_party=1)))
        try:
            base_reg = _wan_registry()
            sent, wall = _run_steps(sim, comp)
            out[name] = sent
            table[name] = {"wan_bytes_per_step": round(sent, 1),
                           "round_wall_s": round(wall, 4)}
            # per-codec split from the system-metrics registry (the vans
            # count every GLOBAL-domain data send under its wire compr
            # tag) — the same ledger the trace subsystem reports against,
            # so bench and tracer can never disagree on WAN bytes.  mpq
            # shows as the bsc/fp16 mix it actually chose.
            per_tag = {}
            for k, v in _wan_registry().items():
                d = v - base_reg.get(k, 0)
                if d > 0:
                    tag = k.rsplit(".wan_bytes_", 1)[1]
                    per_tag[tag] = per_tag.get(tag, 0) + d
            registry[name] = {t: round(v / STEPS_W, 1)
                              for t, v in sorted(per_tag.items())}
        finally:
            sim.shutdown()

    # adaptive row: same workload under the closed-loop controller with
    # a round budget the vanilla config cannot meet, driven by manual
    # ticks (adapt_interval_s=0) so the run is deterministic.  The
    # controller's decisions move the run down the codec ladder; the row
    # records where it landed and what that cost per step.
    sim = Simulation(Config(
        topology=Topology(num_parties=2, workers_per_party=1),
        adaptive_wan=True, adapt_interval_s=0.0,
        adapt_round_budget_s=1e-4, adapt_cooldown_s=0.0))
    try:
        ws = sim.all_workers()
        rng = np.random.default_rng(0)
        for w in ws:
            w.init(0, np.zeros(N_BIG, np.float32))
            w.init(1, np.zeros(N_SMALL, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        base = sim.wan_bytes()["wan_send_bytes"]
        t0 = time.perf_counter()
        for _ in range(STEPS_W):
            for tid, nel in ((0, N_BIG), (1, N_SMALL)):
                g = rng.standard_normal(nel).astype(np.float32)
                for w in ws:
                    w.push(tid, g)
            for w in ws:
                w.pull_sync(0)
                w.pull_sync(1)
            sim.wan_controller.tick()
        wall = (time.perf_counter() - t0) / STEPS_W
        sent = (sim.wan_bytes()["wan_send_bytes"] - base) / STEPS_W
        st = sim.wan_controller.status()
        table["adaptive"] = {
            "wan_bytes_per_step": round(sent, 1),
            "round_wall_s": round(wall, 4),
            "final_codec": st["compression"].get("type"),
            "epoch": st["epoch"],
            "decisions": st["decisions"],
        }
    finally:
        sim.shutdown()

    # device-codec rows (ISSUE 20): the same rungs with the jitted
    # device codecs on the jax merge backend — encode reads the device
    # accumulator, decode lands device merge buffers, and the only D2H
    # is the wire-ready compressed payload (codec_d2h_bytes).
    # host_copy_bytes counts FULL-TENSOR host crossings inside the
    # codec stage and must be 0 in steady state.  On a CPU-only host
    # jax runs on cpu (pinned below when unset), so round_wall compares
    # XLA-jit kernels against the numpy reference on the same silicon —
    # the win being measured is residency (zero host copies), not
    # device speed (the CPU caveat the record carries).
    device_codec = {}
    saved_env = {k: os.environ.get(k)
                 for k in ("JAX_PLATFORMS", "GEOMX_MERGE_BACKEND",
                           "GEOMX_CODEC_DEVICE")}
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["GEOMX_MERGE_BACKEND"] = "jax"
    os.environ["GEOMX_CODEC_DEVICE"] = "1"
    try:
        for name in ("fp16", "2bit", "bsc", "mpq"):
            sim = Simulation(Config(topology=Topology(
                num_parties=2, workers_per_party=1)))
            snap = {}

            def _counters():
                enc = dec = host = d2h = 0.0
                for s in sim.local_servers:
                    be = s._backend
                    enc += getattr(be, "codec_device_ms", 0.0)
                    host += getattr(be, "codec_host_bytes", 0)
                    d2h += getattr(be, "codec_d2h_bytes", 0)
                for s in sim.global_servers:
                    be = s._backend
                    dec += getattr(be, "codec_device_ms", 0.0)
                    host += getattr(be, "codec_host_bytes", 0)
                return enc, dec, host, d2h

            try:
                # warm round compiles the jit kernels and pays the
                # first-touch residency copies; counters snapshot after
                # it so the row is pure steady state
                sent, wall = _run_steps(
                    sim, configs[name], warm=1,
                    after_warm=lambda: snap.update(zip(
                        ("enc", "dec", "host", "d2h"), _counters())))
                enc, dec, host, d2h = _counters()
                device_codec[name] = {
                    "wan_bytes_per_step": round(sent, 1),
                    "round_wall_s": round(wall, 4),
                    "encode_ms": round((enc - snap["enc"]) / STEPS_W, 3),
                    "decode_ms": round((dec - snap["dec"]) / STEPS_W, 3),
                    "host_copy_bytes": round(
                        (host - snap["host"]) / STEPS_W, 1),
                    "codec_d2h_bytes": round(
                        (d2h - snap["d2h"]) / STEPS_W, 1),
                }
            finally:
                sim.shutdown()
        import jax

        device_codec["platform"] = jax.default_backend()
        device_codec["note"] = (
            "host_copy_bytes counts full-tensor host crossings in the "
            "codec stage (0 = the geo-round never touches host numpy); "
            "on cpu-jax the wall compares jit kernels vs numpy on the "
            "same silicon — residency, not device speed")
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    # flagship-scale ledger (VERDICT r2 #7): one 50M-element tensor (200
    # MB fp32) through MultiGPS shards (3 global servers) x BSC — the
    # regime where per-message overheads amortize and the shard split
    # matters.  Reference payload math: kvstore_dist_server.h:1190-1206.
    N_FLAG = 50_000_000
    flagship = {}
    sim = Simulation(Config(topology=Topology(
        num_parties=2, workers_per_party=1, num_global_servers=3)))
    try:
        ws = sim.all_workers()
        for w in ws:
            w.init(0, np.zeros(N_FLAG, np.float32))
        ws[0].set_optimizer({"type": "sgd", "lr": 0.1})
        for p in range(2):
            sim.worker(p, 0).set_gradient_compression(
                {"type": "bsc", "ratio": 0.01})
        g = np.abs(np.random.default_rng(1)
                   .standard_normal(N_FLAG)).astype(np.float32)
        base = sim.wan_bytes()["wan_send_bytes"]

        def one_round() -> float:
            t0 = time.perf_counter()
            for w in ws:
                w.push(0, g)
            for w in ws:
                w.pull_sync(0)
                w.wait_all()
            return time.perf_counter() - t0

        # round 1 is a different regime on both axes: it pays one-time
        # costs (compressor tracked views, DGC velocity/accum
        # allocation, first-touch store copies) and its pull is a DENSE
        # resync (~1/ratio more WAN bytes than a steady top-k delta) —
        # so it is excluded from BOTH the steady wall time and the
        # steady bytes/step.  Steady state = best of two subsequent
        # rounds (this single-core host is noisy under background load).
        dt_cold = one_round()
        steady_base = sim.wan_bytes()["wan_send_bytes"]
        cold_sent = steady_base - base
        dt = min(one_round(), one_round())
        sent = (sim.wan_bytes()["wan_send_bytes"] - steady_base) / 2
        flagship = {
            "tensor_elems": N_FLAG,
            "global_servers": 3,
            "bsc_ratio": 0.01,
            "wan_bytes_per_step": sent,
            "dense_bytes_would_be": 2 * 2 * N_FLAG * 4,  # 2 parties x p+p
            "reduction": round(2 * 2 * N_FLAG * 4 / max(sent, 1), 2),
            "cold_round_bytes": cold_sent,  # incl. dense pull resync
            "round_wall_s": round(dt, 3),
            "round_wall_s_cold": round(dt_cold, 3),
        }
    finally:
        sim.shutdown()

    print(json.dumps({
        "bytes_per_step": {k: round(v, 1) for k, v in out.items()},
        "reduction": {k: round(out["vanilla"] / v, 2)
                      for k, v in out.items() if v > 0},
        "table": table,
        "device_codec": device_codec,
        "registry_bytes_per_step": registry,
        "flagship_50m_multigps_bsc": flagship,
    }))


# --------------------------------------------------------------------------
# orchestrator
# --------------------------------------------------------------------------

DEADLINE_S = float(os.environ.get("BENCH_DEADLINE_S", "480"))
RESERVE_S = 8.0          # kept back for the final emission
MIN_CHILD_S = 20.0       # don't bother launching a child with less
_T0 = time.monotonic()

_lock = threading.Lock()
_results: dict = {}      # child name -> parsed JSON
_errors: dict = {}       # child name -> error string
_procs: set = set()      # running child Popen handles (for SIGTERM)


def _remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


def _build_record() -> dict:
    """Assemble the full output record from whatever has finished.
    Pure function of _results/_errors — called after every child and
    from the signal handler, so it must never block or throw."""
    cnn = _results.get("cnn")
    mfu = _results.get("mfu")
    wan = _results.get("wan")
    if cnn is not None:
        deriv = cnn.get("a100_ref_derivation", {})
        scen = deriv.get("scenarios", {})
        record = {
            "metric": "cifar10_cnn_images_per_sec_per_chip",
            "value": cnn.get("images_per_sec"),
            "unit": "images/sec/chip",
            "vs_baseline": cnn.get("vs_baseline"),
            # vs_baseline divides measured TPU throughput by a MODELED
            # A100 reference (no A100 reachable; BASELINE.md) — the
            # duplicate key name says so outright, and the least-favorable
            # modeled scenario sits next to it so no consumer mistakes
            # the model for a measurement (VERDICT r3 item 8)
            "vs_modeled_a100": cnn.get("vs_baseline"),
            "vs_baseline_semantics": (
                "modeled, not measured: TPU ips / modeled A100 reference "
                "(reference_as_published_fp32; see a100_ref_derivation)"),
            "vs_modeled_xla_grade_peer": scen.get(
                "hypothetical_xla_grade_peer", {}).get("vs_0.9x_sxm80"),
            "a100_ref_derivation": deriv,
            "device": cnn.get("device"),
        }
    elif mfu is not None:
        record = {
            "metric": "transformer_achieved_tflops",
            "value": mfu.get("achieved_tflops"),
            "unit": "TFLOP/s",
            "vs_baseline": None,
        }
    elif wan is not None:
        record = {
            "metric": "wan_bytes_per_step",
            "value": wan.get("bytes_per_step", {}).get("vanilla"),
            "unit": "bytes/step (vanilla; see configs)",
            "vs_baseline": None,
            "error": "TPU benchmarks unavailable (see errors)",
        }
    else:
        record = {
            "metric": "none_completed_yet",
            "value": None,
            "unit": None,
            "vs_baseline": None,
            "error": "no child benchmark has completed (see errors)",
        }
    for key, name in (("mfu", "mfu"), ("quantize", "quant"),
                      ("wan", "wan"), ("overlap", "overlap"),
                      ("overlap_tpu", "overlap_tpu"),
                      ("flash_autotune", "flash_autotune"),
                      ("stress", "stress"), ("lm", "lm"),
                      ("scaling", "scaling"), ("parity", "parity"),
                      ("serde", "serde"), ("shards", "shards"),
                      ("parties", "parties"),
                      ("merge", "merge"), ("obs", "obs"),
                      ("flight", "flight"), ("churn", "churn"),
                      ("partition", "partition"),
                      ("serve", "serve")):
        if name in _results:
            record[key] = _results[name]
    if _errors:
        record["errors"] = dict(_errors)
    record["elapsed_s"] = round(time.monotonic() - _T0, 1)
    record["deadline_s"] = DEADLINE_S
    return record


DETAIL_PATH = ROOT / "BENCH_DETAIL.json"  # run-time output, git-ignored


def _compact(record: dict) -> dict:
    """A caller may keep only the TAIL of stdout, so the LAST line must
    be a compact, self-contained headline; the full record is written
    to BENCH_DETAIL.json beside this script."""
    out = {k: record.get(k) for k in (
        "metric", "value", "unit", "vs_baseline", "vs_modeled_a100")
        if record.get(k) is not None}
    wan = record.get("wan") or {}
    if wan.get("reduction"):
        out["wan_reduction"] = wan["reduction"]
    lm = record.get("lm") or {}
    if lm.get("tokens_per_sec_steady"):
        out["lm_tokens_per_sec"] = lm["tokens_per_sec_steady"]
    f50 = (record.get("wan") or {}).get("flagship_50m_multigps_bsc") or {}
    if f50.get("round_wall_s") is not None:
        out["flagship_50m_round_wall_s"] = f50["round_wall_s"]
    sc = ((record.get("scaling") or {}).get("modeled_roofline") or {})
    if sc.get("full_stack_vs_dense_bsp_speedup_at_256"):
        out["full_stack_vs_dense_bsp_at_256_band"] = sc[
            "full_stack_vs_dense_bsp_speedup_at_256"]
    mesh = ((record.get("scaling") or {}).get("measured_virtual_mesh")
            or {})
    if mesh.get("allreduce_count_constant_across_mesh") is not None:
        out["mesh_audit_ok"] = (
            mesh["allreduce_count_constant_across_mesh"]
            and mesh.get("no_large_gathers"))
    par = record.get("parity") or {}
    if par.get("worst_delta"):
        out["parity_worst_accuracy_delta"] = par["worst_delta"]
    sh = record.get("shards") or {}
    if sh.get("flagship_50m_round_wall_s"):
        out["shards_round_wall_s"] = sh["flagship_50m_round_wall_s"]
    pt = record.get("parties") or {}
    if pt.get("party_scaling"):
        out["party_scaling"] = pt["party_scaling"]
        out["party_threads"] = pt.get("process_threads")
        if pt.get("threads_at_128p") is not None:
            out["threads_at_128p"] = pt["threads_at_128p"]
    ob = record.get("obs") or {}
    if ob.get("overhead_pct") is not None:
        out["obs_overhead_pct"] = ob["overhead_pct"]
    flt = record.get("flight") or {}
    if flt.get("overhead_pct") is not None:
        out["flight_overhead_pct"] = flt["overhead_pct"]
    sv = record.get("serve") or {}
    if sv.get("pulls_per_sec"):
        out["serve_pulls_per_sec"] = sv["pulls_per_sec"]
    bal = sv.get("balanced") or {}
    if bal.get("pulls_per_sec") is not None:
        out["serve_lb_vs_single"] = bal.get("vs_single_target_2rep")
    pl = sv.get("plane_soak") or {}
    if pl.get("pulls_per_sec") is not None:
        out["serve_plane"] = {
            "qps": pl["pulls_per_sec"],
            "kills": pl.get("replica_kills"),
            "violations_served": pl.get("violations_served"),
            "shed_frac": pl.get("shed_frac"),
            "p99_recovered": pl.get("p99_recovered"),
            "autoscale_stable": pl.get("autoscale_stable"),
        }
    ch = record.get("churn") or {}
    if ch.get("churn_overhead_pct") is not None:
        out["churn_overhead_pct"] = ch["churn_overhead_pct"]
        out["drain_latency_s"] = ch.get("drain_latency_s")
        out["churn_stall_rounds"] = ch.get("stall_rounds")
    pn = record.get("partition") or {}
    if pn.get("catchup_vs_dense") is not None:
        out["partition"] = {
            "catchup_vs_dense": pn["catchup_vs_dense"],
            "heal_to_merged_s": pn.get("heal_to_merged_s"),
            "degraded_overhead_pct": pn.get("degraded_overhead_pct"),
            "quarantines": pn.get("quarantines"),
            "evictions": pn.get("worker_evictions"),
        }
    mg = record.get("merge") or {}
    if mg.get("speedup") is not None:
        out["merge_backend_speedup"] = {
            "speedup": mg["speedup"],
            "parity": mg.get("sums_bit_identical"),
            "device": (mg.get("jax_backend") or {}).get("merge_device")}
        rc = mg.get("round_close") or {}
        if rc.get("speedup") is not None:
            # full round close (merge->optimize->serve-snapshot) under
            # the device optimizer stage; d2h is what the serve events
            # paid — the hot path itself pays none
            out["merge_backend_speedup"]["round_close"] = rc["speedup"]
            out["merge_backend_speedup"]["round_close_parity"] = rc.get(
                "weights_bit_identical")
            out["round_close_d2h_bytes"] = (rc.get("jax") or {}).get(
                "round_close_d2h_bytes")
    sd = record.get("serde") or {}
    if sd.get("speedup_encode"):
        out["serde_speedup"] = {"encode": sd["speedup_encode"],
                                "decode": sd["speedup_decode"],
                                "zero_copy": sd.get("zero_copy_ok"),
                                "merge_scaling": (sd.get("merge_scaling")
                                                  or {}).get("scaling")}
    if record.get("errors"):
        out["errors"] = {k: str(v)[:80] for k, v in
                         record["errors"].items()}
    out["elapsed_s"] = record.get("elapsed_s")
    out["detail_file"] = DETAIL_PATH.name
    return out


def _emit():
    """Persist the full record to BENCH_DETAIL.json and print the
    compact headline as one JSON line (last line wins)."""
    with _lock:
        # write+replace INSIDE the lock: _emit runs concurrently from
        # the cpu_chain thread and the TPU/main thread, and two threads
        # sharing one PID-keyed temp path would tear the detail file
        record = _build_record()
        try:
            tmp = DETAIL_PATH.with_suffix(
                f".json.{os.getpid()}.{threading.get_ident()}.tmp")
            tmp.write_text(json.dumps(record, indent=1))
            tmp.replace(DETAIL_PATH)
        except OSError:
            pass  # detail is best-effort; the stdout line goes out
    sys.stdout.write(json.dumps(_compact(record)) + "\n")
    sys.stdout.flush()


def _kill_children():
    for p in list(_procs):
        try:
            p.kill()
        except Exception:
            pass


def _on_term(signum, frame):
    """Emergency flush.  Runs in the main thread while the CPU worker
    thread may be mid-mutation of _results/_errors and the interrupted
    main-thread _emit may have written half a line — so: try the lock
    briefly (the worker only holds it for dict inserts), serialize
    defensively, and prefix a newline so the LAST stdout line is intact
    whatever was interrupted.  Must never raise.  Exits 128+signum: a
    flushed partial record is still an interrupted run."""
    _kill_children()
    _errors["harness"] = (f"signal {signum} at "
                          f"{time.monotonic() - _T0:.0f}s; partial "
                          "record flushed")
    locked = _lock.acquire(timeout=1.0)
    try:
        try:
            line = json.dumps(_build_record())
        except Exception as e:  # torn concurrent state: minimal record
            line = json.dumps({
                "metric": "none_completed_yet", "value": None,
                "unit": None, "vs_baseline": None,
                "error": f"signal-path serialization failed: {e!r}"})
    finally:
        if locked:
            _lock.release()
    try:
        os.write(1, ("\n" + line + "\n").encode())
    except OSError:
        pass
    os._exit(128 + signum)


def _run_child(name: str, timeout: float, env_extra=None):
    budget = _remaining() - RESERVE_S
    if budget < MIN_CHILD_S:
        return None, "skipped: global deadline exhausted"
    timeout = min(timeout, budget)
    env = dict(os.environ)
    if env_extra:
        env.update(env_extra)
    p = subprocess.Popen(
        [sys.executable, str(ROOT / "bench.py"), "--child", name],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    _procs.add(p)
    try:
        out, err_txt = p.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        p.kill()
        p.communicate()
        return None, f"timeout after {timeout:.0f}s"
    finally:
        _procs.discard(p)
    if p.returncode != 0:
        tail = (err_txt or out or "").strip().splitlines()[-6:]
        return None, f"rc={p.returncode}: " + " | ".join(tail)
    for line in reversed(out.strip().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    return None, "no JSON in child output"


def _do(name: str, timeout: float, env_extra=None) -> bool:
    """Run one child, record its result or error, re-emit the record."""
    res, err = _run_child(name, timeout, env_extra)
    with _lock:
        if res is not None:
            _results[name] = res
        if err:
            _errors[name] = err
    _emit()
    return res is not None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child",
                    choices=["cnn", "mfu", "mfu_sweep", "quant", "wan",
                             "overlap", "overlap_tpu", "stress",
                             "flash_autotune", "lm", "scaling", "parity",
                             "serde", "shards", "parties", "obs",
                             "flight", "serve", "merge", "churn",
                             "partition", "integrity"])
    ap.add_argument("--wan", action="store_true",
                    help="legacy: run only the WAN codec benchmark")
    ap.add_argument("--skip-tpu", action="store_true",
                    help="run the CPU children only (no device metric "
                         "is reported)")
    args = ap.parse_args()

    if args.child:
        from geomx_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
        {"cnn": child_cnn, "mfu": child_mfu, "mfu_sweep": child_mfu_sweep,
         "quant": child_quant, "wan": child_wan, "overlap": child_overlap,
         "overlap_tpu": child_overlap_tpu, "stress": child_stress,
         "lm": child_lm, "scaling": child_scaling,
         "parity": child_parity, "serde": child_serde,
         "shards": child_shards, "parties": child_parties,
         "obs": child_obs,
         "flight": child_flight, "serve": child_serve,
         "merge": child_merge, "churn": child_churn,
         "partition": child_partition, "integrity": child_integrity,
         "flash_autotune": child_flash_autotune}[args.child]()
        return

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    cpu_env = {"JAX_PLATFORMS": "cpu", "JAX_PLATFORM_NAME": "cpu"}

    if args.wan:  # legacy single-benchmark mode: WAN codec numbers only
        wan, wan_err = _run_child("wan", timeout=300, env_extra=cpu_env)
        print(json.dumps({
            "metric": "wan_bytes_per_step",
            "value": wan and wan["bytes_per_step"]["vanilla"],
            "unit": "bytes/step (vanilla; see configs)",
            "vs_baseline": None,
            "configs": wan and wan["bytes_per_step"],
            "reduction": wan and wan["reduction"],
            "error": wan_err,
        }))
        return

    _emit()  # a valid line exists from second zero, whatever happens

    # CPU children on their own thread, beside the device children
    def cpu_chain():
        # flagship metrics first: under a tight driver deadline the tail
        # children are the ones clipped
        _do("wan", 180, cpu_env)
        _do("serde", 120, cpu_env)
        _do("lm", 210, cpu_env)
        _do("overlap", 150, cpu_env)
        # scaling's roofline is calibrated by the lm child's measured
        # WAN ledger and the overlap child's measured staged-loop
        # speedup when available
        scaling_env = dict(cpu_env)
        lm_wan = _results.get("lm", {}).get("wan_bytes_per_step")
        if lm_wan:
            scaling_env["BENCH_LM_WAN_BYTES_PER_STEP"] = str(lm_wan)
        ov = _results.get("overlap", {}).get("speedup")
        if ov:
            scaling_env["BENCH_OVERLAP_MEASURED"] = str(ov)
        _do("scaling", 260, scaling_env)
        _do("parity", 280, cpu_env)
        _do("stress", 180, cpu_env)
        _do("shards", 240, cpu_env)
        _do("parties", 240, cpu_env)
        _do("merge", 180, cpu_env)
        _do("obs", 180, cpu_env)
        _do("flight", 180, cpu_env)
        _do("serve", 210, cpu_env)
        _do("churn", 240, cpu_env)
        _do("partition", 240, cpu_env)

    cpu_thread = threading.Thread(target=cpu_chain, daemon=True)
    cpu_thread.start()

    if not args.skip_tpu:
        # strictly one device child at a time: a chip belongs to one
        # process.  Each child refuses to run without a TPU, so a host
        # with no chip fails here by name instead of reporting CPU
        # numbers under device metrics.
        for name, timeout in DEVICE_CHILDREN:
            _do(name, timeout)

    cpu_thread.join(timeout=max(0.0, _remaining() - RESERVE_S / 2))
    # deadline expiry must not orphan a still-running child (the daemon
    # thread dies with us, its subprocess would not)
    _kill_children()
    _emit()
    failed = [name for name, _t in DEVICE_CHILDREN if name in _errors]
    if failed and not args.skip_tpu:
        sys.exit(f"device children failed: {', '.join(failed)} "
                 "(see errors in the record)")


if __name__ == "__main__":
    main()
