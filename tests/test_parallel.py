"""Parallelism tests on the 8-device virtual CPU mesh: ring attention
matches dense attention exactly; the flagship transformer's full train
step compiles and runs under dp/sp/tp(+ep) shardings."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from geomx_tpu.models.transformer import (
    TransformerConfig, init_params, lm_loss, make_apply, param_specs,
)
from geomx_tpu.parallel import make_mesh, ring_attention
from geomx_tpu.parallel.ring_attention import dense_attention


def test_has_8_devices():
    assert len(jax.devices()) == 8


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"sp": 4})
    B, T, H, D = 2, 32, 2, 16  # global T = 32, 8 per device
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    ref = dense_attention(q, k, v, causal=causal)

    spec = P(None, "sp", None, None)
    f = shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", axis_size=4,
                                       causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_fast_mode_tracks_dense():
    """fast=True (bf16 MXU matmuls inside each ring block, fp32 online
    softmax) stays within bf16 tolerance of the fp32 reference."""
    mesh = make_mesh({"sp": 4})
    B, T, H, D = 2, 32, 2, 16
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.bfloat16)
               for _ in range(3))

    ref = dense_attention(q, k, v, causal=True)
    spec = P(None, "sp", None, None)
    f = shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sp", axis_size=4,
                                       causal=True, fast=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_attention_matches_dense(causal):
    from geomx_tpu.parallel import ulysses_attention

    mesh = make_mesh({"sp": 4})
    B, T, H, D = 2, 32, 4, 16  # H=4 divisible by sp=4
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

    ref = dense_attention(q, k, v, causal=causal)

    spec = P(None, "sp", None, None)
    f = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp",
                                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = jax.jit(f)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ulysses_rejects_indivisible_heads():
    from geomx_tpu.parallel import ulysses_attention

    mesh = make_mesh({"sp": 4})
    spec = P(None, "sp", None, None)
    x = jnp.zeros((1, 8, 3, 4), jnp.float32)  # 3 heads, sp=4
    f = shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(f)(x, x, x)


def test_transformer_sharded_train_step_ulysses_sp():
    """The flagship with sp_attn='ulysses': sharded train step compiles,
    runs, and the forward matches the dense path."""
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=64, sp_attn="ulysses")
    params = init_params(cfg, jax.random.PRNGKey(0))
    apply_fn = make_apply(cfg, mesh)
    specs = param_specs(cfg)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, pshard)
    tokens = jax.device_put(
        jnp.asarray(np.random.default_rng(2).integers(0, 64, (4, 32)),
                    jnp.int32), NamedSharding(mesh, P("dp", "sp")))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(apply_fn, p, tokens)))(params)
    assert np.isfinite(float(loss))
    dense_apply = make_apply(cfg)
    dense_logits = dense_apply(jax.device_get(params), np.asarray(tokens))
    shard_logits = jax.jit(apply_fn)(params, tokens)
    np.testing.assert_allclose(np.asarray(shard_logits),
                               np.asarray(dense_logits), rtol=3e-2,
                               atol=3e-2)


def test_transformer_dense_forward_and_loss():
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    apply_fn = make_apply(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 16)), jnp.int32)
    logits = jax.jit(apply_fn)(params, tokens)
    assert logits.shape == (2, 16, 64)
    loss = lm_loss(apply_fn, params, tokens)
    assert np.isfinite(float(loss)) and float(loss) < 10


def test_fast_attention_matches_dense():
    """fast_dense_attention (bf16 MXU matmuls, fp32 accum) tracks the
    fp32 reference within bf16 tolerance, including the causal mask."""
    from geomx_tpu.parallel.ring_attention import (
        dense_attention, fast_dense_attention)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 32, 4, 16)),
                           jnp.bfloat16) for _ in range(3))
    ref = dense_attention(q, k, v, causal=True)
    fast = fast_dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(fast, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2)


def test_transformer_attn_impl_and_remat():
    """attn_impl='fast' (default) and 'dense' agree; remat=True changes
    memory strategy, not the math; unknown impl raises."""
    base = dict(vocab=64, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, max_seq=64)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (2, 16)), jnp.int32)
    params = init_params(TransformerConfig(**base), jax.random.PRNGKey(0))
    out = {}
    for impl, remat in (("fast", False), ("dense", False), ("fast", True)):
        cfg = TransformerConfig(**base, attn_impl=impl, remat=remat)
        out[(impl, remat)] = np.asarray(
            jax.jit(make_apply(cfg))(params, tokens))
    np.testing.assert_allclose(out[("fast", False)], out[("dense", False)],
                               rtol=5e-2, atol=5e-2)
    # remat must be bit-identical to non-remat (same ops, same order)
    np.testing.assert_array_equal(out[("fast", False)], out[("fast", True)])
    with pytest.raises(ValueError):
        make_apply(TransformerConfig(**base, attn_impl="nope"))(
            params, tokens)


def test_two_parties_each_a_slice_through_hips():
    """The headline mapping: 2 'data centers', each a 4-device mesh whose
    gradient aggregation is XLA psum over the slice; only the host edge
    pushes the merged gradient into the HiPS tier (workers_per_party=1)."""
    from geomx_tpu.core.config import Config, Topology
    from geomx_tpu.kvstore import Simulation
    from geomx_tpu.parallel.dp import make_party_step, party_meshes
    from geomx_tpu.training import flatten_params, unflatten_params

    meshes = party_meshes(2)  # 4 CPU devices each
    assert all(m.shape["dp"] == 4 for m in meshes)

    # tiny MLP classifier
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((8, 4)) * 0.1, jnp.float32)
    b = jnp.zeros(4, jnp.float32)
    params = {"W": W, "b": b}

    def grad_fn(p, x, y):
        def loss_fn(p):
            logits = x @ p["W"] + p["b"]
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
            acc = jnp.mean(jnp.argmax(logits, -1) == y)
            return loss, acc

        (loss, acc), g = jax.value_and_grad(loss_fn, has_aux=True)(p)
        return loss, acc, g

    steps = [make_party_step(grad_fn, m) for m in meshes]

    sim = Simulation(Config(topology=Topology(num_parties=2,
                                              workers_per_party=1)))
    try:
        kvs = [sim.worker(p, 0) for p in range(2)]
        leaves, treedef = flatten_params(params)
        for kv in kvs:
            for tid, leaf in enumerate(leaves):
                kv.init(tid, leaf)
        kvs[0].set_optimizer({"type": "sgd", "lr": 0.5})

        x = rng.standard_normal((2, 16, 8)).astype(np.float32)
        y = rng.integers(0, 4, (2, 16)).astype(np.int32)
        losses = []
        cur = [params, params]
        for it in range(6):
            for p in range(2):
                loss, acc, grads = steps[p](cur[p], x[p], y[p])
                g_leaves, _ = jax.tree_util.tree_flatten(grads)
                for tid, g in enumerate(g_leaves):
                    kvs[p].push(tid, np.asarray(g))
            buf = {p: [None] * len(leaves) for p in range(2)}
            for p in range(2):
                for tid in range(len(leaves)):
                    kvs[p].pull(tid, lambda t, a, p=p: buf[p].__setitem__(t, a))
                kvs[p].wait_all()
            for p in range(2):
                cur[p] = unflatten_params(treedef, buf[p])
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        # both parties hold identical weights (FSA invariant)
        for l0, l1 in zip(jax.tree_util.tree_leaves(cur[0]),
                          jax.tree_util.tree_leaves(cur[1])):
            np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                       rtol=1e-5)
    finally:
        sim.shutdown()


def test_pipeline_matches_sequential_and_trains():
    """GPipe schedule over pp=4: outputs match the sequential stack, and a
    jitted pipelined train step learns."""
    from geomx_tpu.parallel.pipeline import (
        init_mlp_stack, mlp_block, pipeline_apply, sequential_apply,
    )

    mesh = make_mesh({"pp": 4})
    d, f, L, M, mb = 16, 32, 8, 8, 4
    params = init_mlp_stack(jax.random.PRNGKey(0), L, d, f)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (M, mb, d)), jnp.float32)

    ref = sequential_apply(params, x)
    out = jax.jit(lambda p, x: pipeline_apply(mesh, mlp_block, p, x))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # differentiable: one pipelined SGD step reduces an MSE loss
    y = ref + 0.1

    def loss_fn(p):
        o = pipeline_apply(mesh, mlp_block, p, x)
        return jnp.mean((o - y) ** 2)

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g), l

    p1, l0 = step(params)
    _, l1 = step(p1)
    assert float(l1) < float(l0)


def test_transformer_sharded_train_step_dp_sp_tp_ep():
    """The dryrun_multichip path: full train step (fwd+bwd+adam) jitted
    over a dp×sp×tp mesh with a MoE (ep) layer, on 8 virtual devices."""
    mesh = make_mesh({"dp": 2, "sp": 2, "tp": 2})
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                            d_ff=64, max_seq=64, moe_every=2, n_experts=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    apply_fn = make_apply(cfg, mesh)
    tx = optax.adam(1e-3)

    specs = param_specs(cfg)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, pshard)
    opt_state = tx.init(params)
    tok_shard = NamedSharding(mesh, P("dp", "sp"))

    @jax.jit
    def train_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(apply_fn, p, tokens))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    tokens = jax.device_put(
        jnp.asarray(np.random.default_rng(1).integers(0, 64, (4, 32)),
                    jnp.int32), tok_shard)
    p1, opt_state, loss1 = train_step(params, opt_state, tokens)
    p2, _, loss2 = train_step(p1, opt_state, tokens)
    assert np.isfinite(float(loss1))
    assert float(loss2) < float(loss1)  # learns on the repeated batch

    # sharded-vs-dense numerical agreement of the forward pass
    dense_apply = make_apply(cfg)
    dense_logits = dense_apply(jax.device_get(params), np.asarray(tokens))
    shard_logits = jax.jit(apply_fn)(params, tokens)
    np.testing.assert_allclose(np.asarray(shard_logits),
                               np.asarray(dense_logits), rtol=3e-2, atol=3e-2)
