"""Real expert parallelism (top-k routed MoE, parallel/moe.py).

The reference has no MoE/EP anywhere (SURVEY.md §2.3) — this is the
TPU-design addition VERDICT r2 item 4 demanded: top-k routing with
capacity + dispatch/combine over the expert axis, exact against dense
routing at full capacity, and per-token FLOPs independent of the expert
count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from geomx_tpu.models.transformer import (
    TransformerConfig, init_params, lm_loss_with_aux, make_apply,
    param_specs,
)
from geomx_tpu.parallel import make_mesh
from geomx_tpu.parallel.moe import (
    expert_capacity, moe_ffn_topk, topk_dispatch_combine,
)


def _mats(G, S, D, F, E, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (G, S, D), jnp.float32)
    router = jax.random.normal(ks[1], (D, E)) * 0.1
    we1 = jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)
    we2 = jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)
    return x, router, we1, we2


def _dense_routing_ref(x, router, we1, we2):
    """The exact dense-routing MoE (transformer.py's moe_top_k=0 path)."""
    gates = jax.nn.softmax(jnp.einsum("gsd,de->gse", x, router), axis=-1)
    up = jax.nn.gelu(jnp.einsum("gsd,edf->gsef", x, we1))
    down = jnp.einsum("gsef,efd->gsed", up, we2)
    return jnp.einsum("gsed,gse->gsd", down, gates)


def test_topk_equals_dense_at_full_capacity():
    """k = E with capacity = S is a total dispatch: bit-for-bit the dense
    routing math (the exactness anchor for the whole formulation)."""
    G, S, D, F, E = 2, 16, 8, 32, 4
    x, router, we1, we2 = _mats(G, S, D, F, E)
    ref = _dense_routing_ref(x, router, we1, we2)
    out, _aux = moe_ffn_topk(x, router, we1, we2, k=E, capacity=S,
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_transformer_topk_equals_dense_routing():
    """Flagship-level: moe_top_k=E with capacity >= S reproduces the
    moe_top_k=0 forward exactly (fp32 compute)."""
    base = dict(vocab=64, d_model=16, n_heads=2, n_layers=2, d_ff=32,
                max_seq=32, moe_every=1, n_experts=4,
                compute_dtype=jnp.float32)
    cfg_dense = TransformerConfig(**base)
    # k=E and cf=1.0 gives capacity = S·E·1/E = S — room for every token
    cfg_topk = TransformerConfig(**base, moe_top_k=4,
                                 moe_capacity_factor=1.0)
    params = init_params(cfg_dense, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 32)), jnp.int32)
    ref = make_apply(cfg_dense)(params, tokens)
    out = make_apply(cfg_topk)(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_flops_independent_of_expert_count():
    """The point of top-k dispatch: doubling E at fixed k leaves the
    jitted layer's FLOPs ~unchanged (dense routing would double them)."""
    G, S, D, F = 2, 64, 16, 64

    def flops(E):
        x, router, we1, we2 = _mats(G, S, D, F, E, seed=1)
        f = jax.jit(lambda x: moe_ffn_topk(
            x, router, we1, we2, k=2, capacity_factor=1.0)[0])
        return f.lower(x).compile().cost_analysis()["flops"]

    f4, f16 = flops(4), flops(16)
    assert f16 / f4 < 1.3, (f4, f16)


def test_capacity_bounds_dispatch():
    """capacity=1: each expert accepts at most one token; overflow
    tokens are dropped (their combine weight is zero)."""
    logits = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 4))
    dispatch, combine, _aux = topk_dispatch_combine(logits, k=1, capacity=1)
    # per-expert occupancy <= capacity
    per_expert = np.asarray(jnp.sum(dispatch, axis=(0, 1, 3)))
    assert (per_expert <= 1.0 + 1e-6).all()
    # dropped tokens contribute nothing to combine
    token_weight = np.asarray(jnp.sum(combine, axis=(2, 3)))  # [1, 8]
    assert ((token_weight < 1e-6) | (token_weight > 0.4)).all()


def test_first_choices_claim_slots_before_second():
    """Choice-major priority (GShard): token 7's FIRST choice of expert
    0 outranks token 0's SECOND choice of expert 0."""
    E, S = 2, 4
    # all tokens: first choice expert 1 except token 3 -> expert 0;
    # everyone's second choice is the other expert
    logits = jnp.asarray(
        [[[0.0, 1.0], [0.0, 1.0], [0.0, 1.0], [1.0, 0.0]]], jnp.float32)
    dispatch, _combine, _aux = topk_dispatch_combine(logits, k=2, capacity=1)
    d = np.asarray(dispatch)[0]          # [S, E, C=1]
    assert d[3, 0, 0] == 1.0             # token 3's first choice wins e0
    assert d[0, 1, 0] == 1.0             # token 0's first choice wins e1
    # nobody's second choice got a slot (both experts full after firsts)
    assert d.sum() == 2.0


def test_aux_loss_prefers_balance():
    """Switch aux: uniform routing scores ~1, collapsed routing scores
    ~E (so minimizing it pushes toward balance)."""
    G, S, E = 1, 64, 4
    uniform = jnp.zeros((G, S, E))
    _d, _c, aux_u = topk_dispatch_combine(uniform, k=1, capacity=S)
    collapsed = jnp.zeros((G, S, E)).at[..., 0].set(10.0)
    _d, _c, aux_c = topk_dispatch_combine(collapsed, k=1, capacity=S)
    assert abs(float(aux_u) - 1.0) < 0.1
    assert float(aux_c) > 2.0


def test_moe_sharded_ep_matches_single_device():
    """Top-k MoE under the dp×tp mesh (experts sharded over tp — the ep
    mapping) matches the single-device forward; fp32 so exactly."""
    mesh = make_mesh({"dp": 2, "tp": 4})
    cfg = TransformerConfig(vocab=64, d_model=16, n_heads=4, n_layers=2,
                            d_ff=32, max_seq=32, moe_every=1, n_experts=4,
                            moe_top_k=2, compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    specs = param_specs(cfg)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))
    sharded_params = jax.device_put(params, pshard)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (4, 32)), jnp.int32)
    tokens_sh = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    apply_fn = make_apply(cfg)
    ref = apply_fn(params, tokens)
    out = jax.jit(apply_fn)(sharded_params, tokens_sh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_train_step_with_aux_converges():
    """A few Adam steps through lm_loss_with_aux reduce the loss; the
    aux term backpropagates (router grads are nonzero)."""
    cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2, n_layers=2,
                            d_ff=32, max_seq=16, moe_every=2, n_experts=4,
                            moe_top_k=2, compute_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    apply_fn = make_apply(cfg, return_aux=True)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 32, (4, 16)), jnp.int32)

    @jax.jit
    def step(p, s):
        loss, grads = jax.value_and_grad(
            lambda p_: lm_loss_with_aux(apply_fn, p_, tokens))(p)
        updates, s = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss, grads

    losses = []
    for _ in range(10):
        params, opt_state, loss, grads = step(params, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    router_g = np.abs(np.asarray(grads["layers"][1]["router"]))
    assert router_g.max() > 0


def test_expert_capacity_formula():
    assert expert_capacity(64, 4, 2, 1.0) == 32
    assert expert_capacity(64, 4, 2, 1.25) == 40
    assert expert_capacity(2, 64, 1, 1.0) == 1  # floor at 1
