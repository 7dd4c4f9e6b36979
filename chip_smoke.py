#!/usr/bin/env python
"""Quickest proof that the system still starts on the chip.

One process, every chip jax finds.  In order, stopping at the first
failure (nothing here downgrades a failure to a note):

1. print the jax/jaxlib/libtpu versions, the device and the compile
   cache in use; refuse to run unless jax found a TPU;
2. compile every pallas kernel the package ships UNINTERPRETED and check
   it against its reference;
3. train the flagship transformer at its full width (the widths of
   ``benchmark/configs/flagship-l4-1chip.json``; only depth is cut, to what the chip's memory holds)
   through the normal path — ``Simulation`` → ``Trainer.fit`` /
   ``run_worker`` → ``WorkerKVStore`` → ``LocalServer`` →
   ``GlobalServer`` on the jax merge backend — 2 parties x 1 worker,
   global Adam, one compile step then ``steps`` more, once under
   vanilla FSA and once under MPQ (the device codecs);
4. with four chips or more: the parties become 2-chip ``dp`` meshes
   (``party_meshes`` + ``make_party_step``), and ring attention
   ``fast="flash"`` over ``sp=4`` is checked, grads included, against
   dense attention.

Walls and byte counts printed on the way are smoke observations, not
metrics.  The last line of stdout is the result JSON.
"""

from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import gc
import itertools
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax
import jax.numpy as jnp
import numpy as np

# a hung barrier is a script that never exits: past this, dump every
# thread's stack and die (the driver allows 1200 s, compilation included)
DEADLINE_S = 1100


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What one smoke run does.  The defaults are the flagship; the CPU
    test hook (``tests/test_chip_smoke.py``) shrinks every size and sets
    ``interpret_kernels``."""

    # the benchmark's flagship widths: d2048, 16 heads (head dim 128), ff8192,
    # seq 2048, vocab 8192, bf16 compute, batch 4
    vocab: int = 8192
    d_model: int = 2048
    n_heads: int = 16
    d_ff: int = 8192
    max_seq: int = 2048
    batch: int = 4
    attn_impl: str = "flash"
    n_layers: int = 0          # 0 = the deepest the chip's memory holds
    steps: int = 3             # per phase, after the compile step
    lr: float = 3e-4
    mpq_size_bound: int = 200_000   # Config's default small/large split
    merge_backend: str = "auto"     # what a user gets; must resolve to jax
    flash_shape: tuple = (1, 2048, 16, 128)   # flagship attention geometry
    hop_shape: tuple = (2, 512, 16, 128)      # one ring hop at sp=4
    codec_elems: int = (1 << 20) + 5000       # forces the kernels' padding
    interpret_kernels: bool = False  # CPU-test hook: no Mosaic off-chip


def _say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def _rel_err(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _fwd_and_grads(attn, q, k, v):
    """``attn``'s output and the q/k/v grads of ``sum(out**2)``, jitted as
    a train step is."""
    def f(a, b, c):
        o = attn(a, b, c)
        return jnp.sum(o.astype(jnp.float32) ** 2), o

    grads, out = jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(
        q, k, v)
    return out.astype(jnp.float32), grads


def _assert_grads_close(got, want, what: str, tol: float = 5e-2) -> None:
    for a, b, name in zip(got, want, "qkv"):
        e = _rel_err(a, b)
        assert e < tol, f"{what} grad wrt {name}: rel err {e}"


# ---------------------------------------------------------------------------
# depth
# ---------------------------------------------------------------------------

def _n_params(cfg: SmokeConfig, n_layers: int) -> int:
    d, f = cfg.d_model, cfg.d_ff
    return (cfg.vocab * d + cfg.max_seq * d + d
            + n_layers * (4 * d * d + 2 * d * f + 2 * d))


def pick_depth(cfg: SmokeConfig, bytes_limit: int) -> tuple:
    """Deepest model (at most MFU_CFG's 8 layers) whose training fits one
    chip, and the reason as a printable line.

    Float32 copies of the model alive on the busiest chip, both parties
    sharing it, in the MPQ phase: 2 workers x (params + grads) = 4, the
    two local servers' BSC velocity + accumulator = 4, the global
    server's weights + Adam m, v = 3, and a round's transients (two
    local accumulators, two decoded pushes at the global tier) = 4: 15.
    Measured on a v5e at L3 (PR 23's chip run): ``peak_bytes_in_use``
    9.02 GB = 13.1 copies of 0.69 GB, the workers' activations (XLA
    reports 0.54 GB + 0.34 GB a layer of temporaries for
    ``make_lm_grad_fn`` at batch 4 x seq 2048) included — so 15 leaves
    two copies of headroom."""
    copies = 15
    budget = 0.9 * bytes_limit
    for n_layers in range(8, 0, -1):
        need = copies * 4 * _n_params(cfg, n_layers)
        if need <= budget:
            break
    return n_layers, (
        f"depth L{n_layers} of 8: {copies} f32 copies of the model = "
        f"{need / 1e9:.1f} GB against 90% of the chip's "
        f"{bytes_limit / 1e9:.1f} GB")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def check_kernels(cfg: SmokeConfig) -> None:
    """Every pallas kernel in the package, compiled for the device it
    runs on, against its reference."""
    from geomx_tpu.models.transformer import (TransformerConfig,
                                              _flash_block_sizes,
                                              _single_device_attention)
    from geomx_tpu.ops.block_attention import (_block_attn_ref,
                                               flash_block_attention)
    from geomx_tpu.ops.quantize import (dequantize_2bit_tpu, dgc_update_tpu,
                                        quantize_2bit_tpu)
    from geomx_tpu.parallel.ring_attention import fast_dense_attention

    # jax's bundled flash kernel behind attn_impl="flash", fwd and bwd
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, cfg.flash_shape, jnp.bfloat16)
               for kk in ks)
    flash_cfg = TransformerConfig(attn_impl="flash")
    of, gf = _fwd_and_grads(
        lambda a, b, c: _single_device_attention(flash_cfg, a, b, c), q, k, v)
    orf, gr = _fwd_and_grads(
        lambda a, b, c: fast_dense_attention(a, b, c, causal=True), q, k, v)
    err = float(jnp.max(jnp.abs(of - orf)))
    assert err < 5e-2, f"flash fwd vs fast_dense: max abs diff {err}"
    _assert_grads_close(gf, gr, "flash")
    _say(f"kernel flash_attention {cfg.flash_shape} bf16: fwd max abs diff "
         f"{err:.2e}, grads within 5e-2 of fast_dense_attention; tiles "
         f"{_flash_block_sizes(cfg.flash_shape[1], cfg.flash_shape[3])}")

    # the on-chip codec kernels against numpy
    n, thr, mom = cfg.codec_elems, 0.5, 0.9
    rng = np.random.default_rng(0)
    g, r0, u0 = (rng.standard_normal(n).astype(np.float32) for _ in range(3))
    packed, r1 = quantize_2bit_tpu(jnp.asarray(g), jnp.asarray(r0), thr)
    dec = dequantize_2bit_tpu(packed, n, thr)
    s = r0 + g
    want = np.where(s > thr, thr, np.where(s < -thr, -thr, 0)).astype(
        np.float32)
    np.testing.assert_array_equal(np.asarray(dec), want)
    np.testing.assert_allclose(np.asarray(r1), s - want, rtol=1e-6,
                               atol=1e-6)
    vo, uo = dgc_update_tpu(jnp.asarray(r0), jnp.asarray(u0),
                            jnp.asarray(g), mom)
    v_ref = np.float32(mom) * r0 + g
    np.testing.assert_allclose(np.asarray(vo), v_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(uo), u0 + v_ref, rtol=1e-5,
                               atol=1e-6)
    _say(f"kernels quantize_2bit_tpu / dequantize_2bit_tpu / dgc_update_tpu "
         f"at {n} elements: match numpy")

    # our ring-hop block kernel: diagonal (causal triangle),
    # below-diagonal (fully visible), above-diagonal (fully masked)
    B, T, H, D = cfg.hop_shape
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, cfg.hop_shape, jnp.bfloat16)
               for kk in ks)
    for q_off, k_off in ((0, 0), (T, 0), (0, T)):
        offs = jnp.array([q_off, k_off], jnp.int32)
        m, l, o = flash_block_attention(q, k, v, offs, True)
        rm, rl, ro = _block_attn_ref(q, k, v, offs, True)
        if q_off < k_off:
            # every row masked: m is the mask value, l/o are junk the
            # ring's merge wipes
            assert bool(jnp.all(m <= -1e29)), "masked hop: m not the mask"
            continue
        for a, b, name in ((m, rm, "m"), (l, rl, "l"), (o, ro, "o")):
            e = _rel_err(a, b)
            assert e < 2e-2, (f"flash_block_attention offs=({q_off},{k_off}) "
                              f"{name}: rel err {e}")
    _say(f"kernel flash_block_attention {cfg.hop_shape} bf16: three hop "
         f"geometries match _block_attn_ref")


def check_ring_attention(cfg: SmokeConfig, devices) -> None:
    """Ring attention with the flash block kernel over sp=4, forward and
    grads, against dense attention — the on-chip twin of
    tests/test_block_attention.py's interpreted check."""
    from jax.sharding import PartitionSpec as P

    from geomx_tpu.parallel import make_mesh, ring_attention
    from geomx_tpu.parallel.ring_attention import dense_attention

    sp = 4
    mesh = make_mesh({"sp": sp}, devices=devices[:sp])
    _, T, H, D = cfg.hop_shape
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = (jax.random.normal(kk, (1, sp * T, H, D), jnp.bfloat16)
               for kk in ks)
    spec = P(None, "sp", None, None)
    ring = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", axis_size=sp,
                                       causal=True, fast="flash"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    of, gf = _fwd_and_grads(ring, q, k, v)
    orf, gr = _fwd_and_grads(
        lambda a, b, c: dense_attention(a, b, c, causal=True), q, k, v)
    e = _rel_err(of, orf)
    assert e < 2e-2, f"ring flash fwd vs dense: rel err {e}"
    _assert_grads_close(gf, gr, "ring flash")
    _say(f"ring attention fast='flash' over sp={sp}, seq {sp * T}: forward "
         f"and grads match dense_attention")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_phase(cfg: SmokeConfig, n_layers: int, devices,
                compression: str) -> float:
    """One ``Simulation``: 2 parties x 1 worker + the global tier, global
    Adam, a compile step and ``cfg.steps`` more.  Returns the WAN bytes
    a step; raises on anything wrong."""
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.data import synthetic_lm
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.models.transformer import (TransformerConfig, init_params,
                                              make_lm_grad_fn)
    from geomx_tpu.parallel.dp import make_party_step, party_meshes
    from geomx_tpu.training import Trainer

    parties = 2
    mcfg = TransformerConfig(
        vocab=cfg.vocab, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_layers=n_layers, d_ff=cfg.d_ff, max_seq=cfg.max_seq,
        attn_impl=cfg.attn_impl)
    grad_fn = make_lm_grad_fn(mcfg)
    if len(devices) >= 2 * parties:
        # each party is its own slice: batch over dp, grads psum'd by XLA
        per = len(devices) // parties
        meshes = party_meshes(parties, devices=devices[:parties * per])
        grad_fns = [make_party_step(grad_fn, m) for m in meshes]
        layout = f"{parties} parties x {per}-chip dp mesh"
    else:
        grad_fns = [grad_fn] * parties
        layout = f"{parties} parties sharing {devices[0]}"
    # weights: random from a seed, handed over as host arrays so the only
    # device copies are the workers' own
    params = jax.tree_util.tree_map(
        np.asarray, init_params(mcfg, jax.random.PRNGKey(0)))
    # each worker trains on one fixed batch, so the loss on it must fall
    # if gradients and updates flow the right way through both tiers
    tokens = synthetic_lm(n=parties * cfg.batch, seq=cfg.max_seq,
                          vocab=cfg.vocab, seed=0)

    sim = Simulation(Config(
        topology=Topology(num_parties=parties, workers_per_party=1),
        merge_backend=cfg.merge_backend,
        mpq_size_bound=cfg.mpq_size_bound))
    out: dict = {"losses": {}, "ends": {}, "params": {}, "errors": []}
    t0 = time.perf_counter()

    def worker_main(party: int) -> None:
        try:
            trainer = Trainer(
                sim.worker(party, 0), params, grad_fns[party],
                optimizer={"type": "adam", "lr": cfg.lr},
                compression=(None if compression == "none"
                             else {"type": compression}))
            ends = out["ends"].setdefault(party, [])
            batch = tokens[party::parties]
            hist = trainer.fit(
                itertools.repeat((batch, batch)), 1 + cfg.steps,
                log_fn=lambda *_: ends.append(time.perf_counter()))
            out["params"][party] = jax.block_until_ready(trainer.params)
            ends.append(time.perf_counter())
            out["losses"][party] = [loss for loss, _acc in hist]
        except BaseException as e:  # re-raised on the main thread below
            out["errors"].append(e)
            raise

    try:
        threads = [threading.Thread(target=worker_main, args=(p,),
                                    name=f"smoke-worker-{p}", daemon=True)
                   for p in range(parties)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(DEADLINE_S)
        if out["errors"]:
            raise out["errors"][0]
        assert not any(t.is_alive() for t in threads), "a worker hung"

        stats = {str(s.po.node): s.stats()
                 for s in sim.local_servers + sim.global_servers}
        wan = sim.wan_bytes()["wan_send_bytes"] / (1 + cfg.steps)
        # where the tiers' state sits: workers' params, the servers'
        # weights, optimizer and codec state are all still alive here
        held = [(d.memory_stats() or {}).get("bytes_in_use")
                for d in devices]
    finally:
        sim.shutdown()

    # ---- what came out ----------------------------------------------------
    platform = devices[0].platform
    for p in range(parties):
        losses = out["losses"][p]
        assert len(losses) == 1 + cfg.steps, losses
        assert np.isfinite(losses).all(), f"party {p} losses {losses}"
        assert losses[-1] < losses[0], f"party {p} loss did not fall: {losses}"
    if compression == "none":
        # the repo's own FSA oracle: every party holds the same weights
        for a, b in zip(jax.tree_util.tree_leaves(out["params"][0]),
                        jax.tree_util.tree_leaves(out["params"][1])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for node, st in stats.items():
        assert st["merge_backend"] == "jax", (node, st["merge_backend"])
        assert st["merge_device"] == platform, (node, st["merge_device"])
        assert st["h2d_bytes"] > 0, (node, "no push ever reached the device")
        assert st["codec_host_bytes"] == 0, (node, st["codec_host_bytes"])
    if compression != "none":
        assert sum(st["codec_device_ms"] for st in stats.values()) > 0, (
            "the device codecs never ran")
    assert wan > 0, "nothing crossed the WAN tier"

    ends = out["ends"][0]
    walls = np.diff([t0] + ends[:-1])
    _say(f"{compression}: {layout}; loss "
         + " ".join(f"{x:.4f}" for x in out["losses"][0])
         + f"; compile-step wall {walls[0]:.1f}s, then "
         + " ".join(f"{w:.2f}s" for w in walls[1:])
         + f" a step; WAN {wan / 1e6:.1f} MB/step; servers on jax/{platform}"
         + (", parties identical" if compression == "none" else "")
         + "; GB held per chip before shutdown: "
         + " ".join("n/a" if b is None else f"{b / 1e9:.2f}" for b in held))
    return wan


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(cfg: SmokeConfig, devices) -> None:
    """Every phase on ``devices``; raises at the first failure."""
    from jax.experimental.pallas import tpu as pltpu

    from geomx_tpu.native import bindings

    # built from what git holds: the .so is compiled here, from source
    _say("native codecs: " + (
        "libgeocodecs.so built from codecs.cc/recordio.cc and loaded"
        if bindings.available() else
        "no toolchain, the host-side numpy codecs are in use"))

    with (pltpu.force_tpu_interpret_mode() if cfg.interpret_kernels
          else contextlib.nullcontext()):
        check_kernels(cfg)
        if len(devices) >= 4:
            check_ring_attention(cfg, devices)

    n_layers = cfg.n_layers
    if not n_layers:
        n_layers, why = pick_depth(
            cfg, devices[0].memory_stats()["bytes_limit"])
        _say(why)
    _say(f"model: transformer d{cfg.d_model} h{cfg.n_heads} ff{cfg.d_ff} "
         f"seq{cfg.max_seq} vocab{cfg.vocab} bf16 batch{cfg.batch} "
         f"L{n_layers} ({_n_params(cfg, n_layers) / 1e6:.0f}M params), "
         f"attn_impl={cfg.attn_impl}")
    vanilla = train_phase(cfg, n_layers, devices, "none")
    say_memory(devices, "after the vanilla phase")
    mpq = train_phase(cfg, n_layers, devices, "mpq")
    assert mpq < vanilla, ("MPQ did not shrink the WAN bytes", mpq, vanilla)
    say_memory(devices, "after the MPQ phase")


def say_memory(devices, when: str) -> None:
    """Per-chip bytes, once what the last phase dropped is collected."""
    gc.collect()
    for d in devices:
        ms = d.memory_stats()
        if ms is None:
            _say(f"{d} {when}: memory_stats not reported on this platform")
            continue
        _say(f"{d} {when}: in use {ms['bytes_in_use'] / 1e9:.2f} GB, "
             f"peak_bytes_in_use {ms['peak_bytes_in_use'] / 1e9:.2f} GB")


def main() -> int:
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True,
                                      file=sys.__stderr__)
    try:
        return _main()
    finally:
        faulthandler.cancel_dump_traceback_later()


def _main() -> int:
    from importlib import metadata

    import jaxlib

    from geomx_tpu.utils.compile_cache import enable_compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    devices = jax.devices()
    dev = devices[0]
    if len(devices) >= 4:
        # the four-chip layout splits the host into 2-chip parties, and a
        # sub-slice program loaded back from the persistent cache halts
        # the chip (see parallel/dp.py::party_meshes, which refuses it)
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = "off for the sub-slice layout"
    else:
        cache_dir = enable_compile_cache()
    _say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}; "
         f"platform={dev.platform} device_kind={dev.device_kind} "
         f"count={len(devices)}; compile cache {cache_dir}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU: jax found platform="
                 f"{dev.platform!r} ({dev.device_kind}); nothing was run")
    t0 = time.perf_counter()
    run(SmokeConfig(), devices)
    _say(f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
