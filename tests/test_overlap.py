"""Compute/comm overlap (staged P3 loop): correctness + the perf claim.

The claim under test is the reference's defining mechanism: per-layer
communication overlapping compute must beat the BSP loop measurably
when WAN transmissions contend — and be bit-faithful to monolithic
autodiff while doing it.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.overlap import StagedModel, run_worker_overlapped
from geomx_tpu.training import run_worker
from geomx_tpu.transport.van import FaultPolicy


def _mlp_stages(widths, key):
    """Build a stage per dense layer: params [{'w','b'}], fns."""
    params = []
    fns = []
    keys = jax.random.split(key, len(widths) - 1)
    for i, (din, dout) in enumerate(zip(widths, widths[1:])):
        params.append({
            "w": jax.random.normal(keys[i], (din, dout)) / np.sqrt(din),
            "b": jnp.zeros((dout,)),
        })
        last = i == len(widths) - 2

        def fn(p, x, last=last):
            h = x @ p["w"] + p["b"]
            return h if last else jax.nn.relu(h)

        fns.append(fn)
    return fns, params


def _ce_loss(logits, y):
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
    acc = jnp.mean(jnp.argmax(logits, -1) == y)
    return loss, acc


def test_staged_grads_match_monolithic():
    """Chained stage VJPs are the chain rule: gradients must equal
    jax.grad of the composed function (same float ops, same order)."""
    fns, params = _mlp_stages([8, 16, 12, 4], jax.random.PRNGKey(0))
    model = StagedModel(fns, _ce_loss)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    y = jax.random.randint(jax.random.PRNGKey(2), (32,), 0, 4)

    def composed(ps, x, y):
        for f, p in zip(fns, ps):
            x = f(p, x)
        return _ce_loss(x, y)

    (ref_loss, _), ref_grads = jax.value_and_grad(
        composed, has_aux=True)(params, x, y)

    logits, residuals = model.forward(params, x)
    loss, acc, g_logits = model.loss_and_logit_grad(logits, y)
    got = {}
    model.backward(residuals, g_logits, lambda i, g: got.__setitem__(i, g))

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for i, rg in enumerate(ref_grads):
        np.testing.assert_allclose(np.asarray(got[i]["w"]),
                                   np.asarray(rg["w"]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got[i]["b"]),
                                   np.asarray(rg["b"]), rtol=1e-5)


def _drive_workers(sim, loop_fn):
    """Run loop_fn(worker_kv) concurrently on every worker (the staged
    loop blocks per-stage, so workers must progress in parallel)."""
    ws = sim.all_workers()
    outs = [None] * len(ws)
    errs = []

    def run(i, kv):
        try:
            outs[i] = loop_fn(kv)
        except Exception as e:  # surfaced below — don't hang the join
            errs.append(e)

    ts = [threading.Thread(target=run, args=(i, kv))
          for i, kv in enumerate(ws)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs
    return outs


def _data(steps, batch=16, din=8, classes=4, seed=3):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.standard_normal((batch, din), dtype=np.float32)),
             jnp.asarray(rng.integers(0, classes, batch).astype(np.int32)))
            for _ in range(steps)]


def test_overlapped_matches_bsp_convergence():
    """FSA oracle: the overlapped loop must land on exactly the same
    params as the BSP loop — schedule changes, semantics don't."""
    steps = 4
    data = _data(steps)
    widths = [8, 16, 12, 4]

    def final_params_bsp():
        sim = Simulation(Config(topology=Topology(
            num_parties=2, workers_per_party=1)))
        try:
            fns, params = _mlp_stages(widths, jax.random.PRNGKey(0))
            flat = [{"p": params}]  # one pytree for run_worker

            def loop(kv):
                cap = {}
                kv.set_optimizer({"type": "sgd", "lr": 0.1})

                def grad_fn(ps, x, y):
                    def composed(ps):
                        h = x
                        for f, p in zip(fns, ps):
                            h = f(p, h)
                        return _ce_loss(h, y)
                    (loss, acc), grads = jax.value_and_grad(
                        composed, has_aux=True)(ps)
                    return loss, acc, grads

                run_worker(kv, params, grad_fn, data, steps,
                           barrier_init=False, params_out=cap)
                return cap["params"]

            return _drive_workers(sim, loop)
        finally:
            sim.shutdown()

    def final_params_overlap():
        sim = Simulation(Config(topology=Topology(
            num_parties=2, workers_per_party=1)))
        try:
            def loop(kv):
                fns, params = _mlp_stages(widths, jax.random.PRNGKey(0))
                kv.set_optimizer({"type": "sgd", "lr": 0.1})
                model = StagedModel(fns, _ce_loss)
                cap = {}
                run_worker_overlapped(kv, model, params, data, steps,
                                      barrier_init=False, params_out=cap)
                return cap["params"]

            return _drive_workers(sim, loop)
        finally:
            sim.shutdown()

    bsp = final_params_bsp()
    ovl = final_params_overlap()
    # compare worker 0's final stage params leaf-by-leaf
    bsp_leaves = jax.tree_util.tree_leaves(bsp[0])
    ovl_leaves = jax.tree_util.tree_leaves(ovl[0])
    assert len(bsp_leaves) == len(ovl_leaves)
    for a, b in zip(bsp_leaves, ovl_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    # and both workers of the overlapped run agree (FSA invariant)
    for a, b in zip(jax.tree_util.tree_leaves(ovl[0]),
                    jax.tree_util.tree_leaves(ovl[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def _overlap_vs_bsp(stages: int = 6, n: int = 192_000, steps: int = 3,
                    fwd_s: float = 0.012, bwd_s: float = 0.024,
                    wan_bandwidth_bps: float = 20e6,
                    wan_latency_s: float = 0.005) -> dict:
    """Seconds a step of the staged loop and of BSP under a serialized
    WAN uplink, on this CPU: a schedule's shape, not a device metric.

    Per-stage device compute is modeled with deterministic host sleeps
    (machine-dependent matmul times would be noise); both loops carry
    identical total compute — only the schedule differs.
    """
    def build():
        fns, params = [], []
        key = jax.random.PRNGKey(0)
        for i in range(stages):
            k1, key = jax.random.split(key)
            params.append({"w": jax.random.normal(k1, (192, 192)) / 14.0,
                           "big": jnp.zeros((n,), jnp.float32)})
            last = i == stages - 1

            def fn(p, x, last=last):
                h = x @ p["w"] + 1e-9 * jnp.sum(p["big"])
                return h if last else jax.nn.relu(h)

            fns.append(fn)
        return fns, params

    def ce(logits, y):
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        return loss, jnp.mean(logits)

    data = [(jnp.zeros((16, 192)), jnp.zeros(16, jnp.int32))] * steps
    fault = dict(wan_bandwidth_bps=wan_bandwidth_bps,
                 wan_latency_s=wan_latency_s)

    def timed(overlapped: bool) -> float:
        sim = Simulation(Config(
            topology=Topology(num_parties=1, workers_per_party=1),
            enable_p3=True), fault=FaultPolicy(**fault))
        try:
            kv = sim.all_workers()[0]
            kv.set_optimizer({"type": "sgd", "lr": 0.01})
            fns, params = build()
            if overlapped:
                model = StagedModel(fns, ce)
                for i in range(model.n):
                    f0, b0 = model._fwd[i], model._bwd[i]
                    model._fwd[i] = (lambda p, x, f0=f0:
                                     (time.sleep(fwd_s), f0(p, x))[1])
                    model._bwd[i] = (lambda p, x, g, b0=b0:
                                     (time.sleep(bwd_s), b0(p, x, g))[1])
                run_worker_overlapped(kv, model, params, data[:1], 1,
                                      barrier_init=False)
                t0 = time.perf_counter()
                run_worker_overlapped(kv, model, params, data, steps,
                                      barrier_init=False)
                return time.perf_counter() - t0

            def grad_fn(ps, x, y):
                time.sleep(stages * (fwd_s + bwd_s))

                def composed(ps):
                    h = x
                    for f, p in zip(fns, ps):
                        h = f(p, h)
                    return ce(h, y)
                (loss, aux), grads = jax.value_and_grad(
                    composed, has_aux=True)(ps)
                return loss, aux, grads

            run_worker(kv, params, grad_fn, data[:1], 1, barrier_init=False)
            t0 = time.perf_counter()
            run_worker(kv, params, grad_fn, data, steps, barrier_init=False)
            return time.perf_counter() - t0
        finally:
            sim.shutdown()

    bsp = timed(False)
    ovl = timed(True)
    # modeled constants, exported so that the test derives its bound from
    # the SAME source as the schedule: assert against the model, not a
    # wall-clock magic number
    compute_s = (fwd_s + bwd_s) * stages
    wan_dir_s = stages * (n * 4) / wan_bandwidth_bps
    return {
        "bsp_s_per_step": bsp / steps,
        "overlap_s_per_step": ovl / steps,
        "speedup": bsp / ovl,
        "modeled": {
            "compute_s_per_step": compute_s,
            "wan_s_per_direction_per_step": wan_dir_s,
            # the overlap schedule can hide at most min(compute, one
            # direction's WAN) behind the other; this is the structural
            # quantity the staged loop exists to claw back
            "hideable_s_per_step": min(compute_s, wan_dir_s),
        },
        "setting": (f"{stages} stages x {n * 4 // 1024}KB, WAN "
                    f"{wan_bandwidth_bps / 1e6:.0f}MB/s uplink, "
                    f"{wan_latency_s * 1000:.0f}ms latency, modeled "
                    f"compute {compute_s * 1000:.0f}ms/step"),
    }


def test_overlap_beats_bsp_under_bandwidth():
    """With a serialized WAN uplink (the P3 paper's regime), the staged
    loop must beat BSP by a measurable margin: stage rounds pipeline
    against forward/backward compute while BSP pays compute THEN the full
    serialized communication every step (ref: engine-scheduled per-layer
    push, include/mxnet/engine.h:153-263).

    The bar is STRUCTURAL, not a wall-clock magic number: the
    schedule's whole claim is that it hides compute behind
    the serialized WAN, so the overlapped step must run at least half
    the modeled hideable window (min(compute, one direction's WAN))
    faster than the measured BSP step.  Both sides are measured in the
    same process on the same box, and the hideable window is built from
    deterministic sleeps — a loaded CI box inflates both measurements
    additively and leaves the *difference* intact.  One retry absorbs a
    descheduled-thread outlier."""
    last = None
    for _ in range(2):
        last = _overlap_vs_bsp()
        bound = (last["bsp_s_per_step"]
                 - 0.5 * last["modeled"]["hideable_s_per_step"])
        if last["overlap_s_per_step"] < bound:
            return
    assert last["overlap_s_per_step"] < bound, last


def test_flagship_transformer_through_overlap_loop():
    """The flagship model trains through the staged P3-overlap loop:
    stage 0 = embedding, one stage per layer, untied head — loss drops
    and both parties stay in FSA sync."""
    from geomx_tpu.models.transformer import TransformerConfig, make_staged

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=16)

    def ce(logits, y):
        logp = jax.nn.log_softmax(logits[:, :-1])
        tgt = y[:, 1:]
        ll = jnp.take_along_axis(logp, tgt[..., None], axis=-1)
        return -jnp.mean(ll), jnp.float32(0.0)

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, (4, 16)).astype(np.int32))
    data = [(toks, toks)] * 5

    sim = Simulation(Config(topology=Topology(num_parties=2,
                                              workers_per_party=1)))
    try:
        def loop(kv):
            fns, ps = make_staged(cfg, jax.random.PRNGKey(0))
            kv.set_optimizer({"type": "adam", "lr": 0.01})
            model = StagedModel(fns, ce)
            cap = {}
            hist = run_worker_overlapped(kv, model, ps, data, 5,
                                         barrier_init=False,
                                         params_out=cap)
            return hist, cap["params"]

        outs = _drive_workers(sim, loop)
        hist0, params0 = outs[0]
        _, params1 = outs[1]
        losses = [h[0] for h in hist0]
        assert losses[-1] < losses[0], losses
        for a, b in zip(jax.tree_util.tree_leaves(params0),
                        jax.tree_util.tree_leaves(params1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    finally:
        sim.shutdown()
