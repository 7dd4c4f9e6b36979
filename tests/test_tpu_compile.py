"""Ahead-of-time compiles for the v5e, without a chip.

libtpu is installed where the tests run, so the TPU's compiler can be
asked what it makes of a device program at its real size: what the
compiler refuses, what a program is called in a trace, and how much
memory it takes beside its arguments.  Nothing runs, so nothing here is
a time.  The topology is described inside a fixture (one process may
hold libtpu; every xdist worker imports this file), and every such test
lives in this one file."""

import re

import pytest


@pytest.fixture(scope="module")
def one_chip():
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it is held by another process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# instructions that run inside a neighbour or not at all: no device event
_NO_EVENT = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
_SCALAR_CORE = {"or", "and", "add", "subtract", "multiply", "shift-left",
                "compare", "select", "convert", "maximum", "minimum"}


def _executed_operations(hlo: str, trips: int) -> int:
    """How many device operations one call of a compiled module executes
    (each is an event in a profiler's trace): the entry computation's
    instructions, a ``while`` body's ``trips`` times; unfused scalar
    arithmetic runs on the scalar core and leaves none."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name and " = " in line:
            bodies[name].append(line.strip())

    def count(lines) -> int:
        total = 0
        for line in lines:
            rest = line.split(" = ", 1)[1]
            op = re.findall(r"(?:^|[\s)])([a-z][a-z\-]*[a-z])\(", rest)[0]
            if op == "while":
                body = re.search(r"body=%?([\w.\-]+)", rest).group(1)
                total += 1 + trips * count(bodies[body])
            elif op not in _NO_EVENT and not (
                    op in _SCALAR_CORE and re.match(r"\w+\[\]", rest)):
                total += 1
        return total

    return count(bodies["ENTRY"])


def test_bsc_encoder_for_the_v5e_has_no_sort_and_keeps_its_name(one_chip):
    """The Bi-Sparse encoder at the flagship's largest tensor: the
    benchmark's ``codec_dev_ms_per_step`` finds it by the module name
    ``jit_enc``; a sort over n (what ``lax.top_k`` became, 38 ms a
    tensor) must not come back; its temporaries stay under the 8 bytes
    an element that sort's (value, index) pairs took, which is what
    keeps ``peak_hbm_GB`` where it was; and a call executes few enough
    operations that stopping a profiler's trace of six steps stays
    cheap (52 encodes a step: at 147 a call the benchmark's set-up grew
    by 7 s, at 98 by nothing; PR 32)."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.kvstore.jax_backend import _bsc_encoder

    n = 2048 * 8192
    vec = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = _bsc_encoder().lower(vec, vec, vec, m, int(0.01 * n)).compile()
    text = compiled.as_text()
    assert re.match(r"HloModule jit_enc\b", text), text[:80]
    assert not re.search(r"\bsort\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes <= 8 * n
    assert _executed_operations(text, trips=8) <= 115


@pytest.mark.parametrize("shape", [(4, 2048, 16, 128), (4, 2048, 2, 1024)],
                         ids=["flagship", "wide-head"])
def test_flash_kernels_for_the_v5e_keep_their_names_and_carry_their_tiles(
        one_chip, shape):
    """``jax.grad`` through ``attn_impl="flash"`` at the flagship's
    ``[B, T, H, Dh]`` and at a head wider than 512: the tiles
    ``_flash_block_sizes`` picks fit the v5e's default scoped VMEM (the
    compile would refuse them), the program still calls one kernel for
    each pattern the benchmark's ``attn_roofline_pct`` reads the trace by
    (a trace names an event by the instruction), and the backward
    kernels' names carry the chosen sizes: the sign in a trace that the
    chooser engaged."""
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from benchmark.lib.trace import op_name
    from geomx_tpu.models.transformer import (
        TransformerConfig, _flash_block_sizes, _single_device_attention)

    cfg = TransformerConfig(attn_impl="flash")
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(
            _single_device_attention(cfg, q, k, v).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = [op_name(line.strip()) for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reader = json.loads((Path(__file__).parent.parent / "benchmark"
                         / "layer_metrics" / "attn_roofline_pct.json"
                         ).read_text())
    assert len(reader["kernels"]) == 3 == len(calls), calls
    for kernel in reader["kernels"]:
        hits = [c for c in calls if re.search(kernel["pattern"], c)]
        assert len(hits) == 1, (kernel["pattern"], calls)

    bs = _flash_block_sizes(shape[1], shape[3])
    (dkv,) = [c for c in calls if "bwd_dkv" in c]
    (dq,) = [c for c in calls if "bwd_dq" in c]
    assert (f"block_q_major_{bs.block_q_major_dkv}_block_q_{bs.block_q_dkv}"
            f"_block_k_major_{bs.block_k_major_dkv}_block_k_{bs.block_k_dkv}"
            ) in dkv, dkv
    assert (f"block_q_major_{bs.block_q_dq}_block_k_major_"
            f"{bs.block_k_major_dq}_block_k_{bs.block_k_dq}") in dq, dq
    assert bs.block_q_major_dkv > 128 and bs.block_q_dq > 128


def test_the_routed_layer_for_the_v5e_keeps_its_kernels_names(one_chip):
    """One chip's share of a routed layer at the LFM2 cut's shapes
    (8,192 tokens of 2048, 8 of 64 experts 1536 wide, top 4) with the
    megablox grouped products: the tiling of ``parallel/moe.py`` fits
    the v5e's scoped VMEM (the compile would refuse it).  The sorted
    buffer is 8,192 rows, twice an even router's 4,096, and the held
    pairs go through it in a loop of as many turns as they fill: no
    array of the worst case's 32,768 rows is left anywhere in the
    program (a later edit that returns to one buffer for every load
    fails here, not on a ledger line), and the loop's body holds the
    layer once: three products forward (the forward pass's own loop has
    nothing to give the gradient of a sum and is gone here; a whole
    model keeps both), three with the stacks transposed and three
    ``tgmm`` for the stacks' gradients, every one found by the pattern
    the benchmark's ``expert_gmm_roofline_pct`` reads the trace by.  No
    buffer is kept for the backward pass, so the layer's temporaries
    stay under 1 GB (0.75 here), which a model pays once, in the layer
    whose backward pass is running, not once a layer."""
    import json
    from pathlib import Path

    import jax
    import jax.numpy as jnp

    from benchmark.lib.trace import op_name
    from geomx_tpu.parallel.moe import chunk_rows, routed_ffn

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(x, router, bias, experts):
        y, route = routed_ffn(x, router, bias, experts, first=0, k=4,
                              impl="gmm", compute_dtype=jnp.bfloat16)
        return jnp.sum(y.astype(jnp.float32)), route

    experts = {"w1": arg(8, 2048, 1536), "w3": arg(8, 2048, 1536),
               "w2": arg(8, 1536, 2048)}
    compiled = jax.jit(jax.grad(loss, argnums=(0, 3), has_aux=True)).lower(
        jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip),
        arg(2048, 64), arg(64), experts).compile()
    hlo = compiled.as_text()
    calls = [op_name(line.strip()) for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    reader = json.loads((Path(__file__).parent.parent / "benchmark"
                         / "layer_metrics" / "expert_gmm_roofline_pct.json"
                         ).read_text())
    (kernel,) = reader["kernels"]
    assert len(calls) == 9, calls
    assert all(re.search(kernel["pattern"], c) for c in calls), calls
    assert sum(c.startswith("tgmm") for c in calls) == 3
    assert chunk_rows(8192 * 4, 8, 64) == 8192
    # the products are over the buffer's rows, and nothing as wide as the
    # model or an expert is as long as the pairs
    assert sum(c.startswith("gmm") and "[8192," in c for c in calls) == 6
    assert not re.search(r"\[32768,(1536|2048)\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def _kernel_calls(hlo: str) -> list:
    from benchmark.lib.trace import op_name

    return [op_name(line.strip()) for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _reader(name: str) -> dict:
    import json
    from pathlib import Path

    return json.loads((Path(__file__).parent.parent / "benchmark"
                       / "layer_metrics" / f"{name}.json").read_text())


def test_flash_at_the_latent_layers_widths_for_the_v5e(one_chip):
    """Kimi-Linear's latent attention: q and k heads of 192 channels, v
    heads of 128, padded to 256 for jax's kernels
    (``_single_device_attention``).  The tiles ``_flash_block_sizes(8192,
    256)`` picks fit the scoped VMEM at that width, the three kernels
    are found by the patterns ``attn_roofline_pct`` reads, and the
    gradients come back at the widths that went in."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models.transformer import (
        TransformerConfig, _single_device_attention)

    cfg = TransformerConfig(attn_impl="flash")
    arg = lambda width: jax.ShapeDtypeStruct(            # noqa: E731
        (1, 8192, 4, width), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(
            _single_device_attention(cfg, q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arg(192), arg(192), arg(128)).compile()
    calls = _kernel_calls(compiled.as_text())
    assert len(calls) == 3, calls
    for kernel in _reader("attn_roofline_pct")["kernels"]:
        assert len([c for c in calls if re.search(kernel["pattern"], c)]) == 1
    assert all("256]" in c for c in calls), calls
    assert [g.shape[-1] for g in compiled.out_info] == [192, 192, 128]


def test_kimi_linears_routed_layer_for_the_v5e(one_chip):
    """One chip's share of Kimi-Linear's routed layer (8,192 tokens of
    2304, 8 of 256 experts 1024 wide, top 8, a shared expert): the
    tiling ``gmm_tiling`` picks from the shape, (256, 1152, 1024), fits
    the scoped VMEM; the sorted buffer is 4,096 rows, twice an even
    router's 2,048 (``chunk_rows``); nine grouped products found by
    ``expert_gmm_roofline_pct``'s pattern; the shared expert is plain
    matmuls."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.parallel.moe import routed_ffn

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def loss(x, router, bias, experts, shared):
        y, route = routed_ffn(x, router, bias, experts, first=0, k=8,
                              scale=2.446, impl="gmm",
                              compute_dtype=jnp.bfloat16, shared=shared)
        return jnp.sum(y.astype(jnp.float32)), route

    experts = {"w1": arg(8, 2304, 1024), "w3": arg(8, 2304, 1024),
               "w2": arg(8, 1024, 2304)}
    shared = {"w1": arg(2304, 1024), "w3": arg(2304, 1024),
              "w2": arg(1024, 2304)}
    compiled = jax.jit(jax.grad(loss, argnums=(0, 3, 4), has_aux=True)).lower(
        jax.ShapeDtypeStruct((1, 8192, 2304), jnp.bfloat16,
                             sharding=one_chip),
        arg(2304, 256), arg(256), experts, shared).compile()
    hlo = compiled.as_text()
    calls = _kernel_calls(hlo)
    (kernel,) = _reader("expert_gmm_roofline_pct")["kernels"]
    assert len(calls) == 9, calls
    assert all(re.search(kernel["pattern"], c) for c in calls), calls
    assert sum(c.startswith("gmm") and "[4096," in c for c in calls) == 6
    assert not re.search(r"\[65536,(1024|2304)\]", hlo)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_the_chunked_scan_for_the_v5e(one_chip):
    """``chunk_kda`` and its gradient at the cell's shape (8,192
    positions, 32 heads of 128, chunks of 64): the compiler takes it,
    and because a block of 4 chunks is alive at a time and is computed
    again in the backward pass, its temporaries stay a fraction of the
    4 GB they were with every chunk's products kept (PERF.md section 6,
    PR 37)."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.ops.kda import chunk_kda

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = arg(jnp.bfloat16, 1, 8192, 32, 128)

    seen = {}

    def loss(q, k, v, g, beta):
        o, stats = chunk_kda(q, k, v, g, beta, chunk=64)
        seen.update(stats)                  # its counts are from shapes
        return jnp.sum(o.astype(jnp.float32))

    args = (x, x, x, arg(jnp.float32, 1, 8192, 32, 128),
            arg(jnp.float32, 1, 8192, 32))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    # 128 chunks in 32 blocks: 32 float32 states of 32 x 128 x 128 kept
    assert seen["chunks"] == 128
    assert seen["state_bytes"] == 32 * 32 * 128 * 128 * 4


def test_a_kda_layer_under_the_layers_checkpoint_scans_twice(one_chip):
    """One KDA layer (``_layer_forward`` under ``make_apply``'s
    checkpoint, ``remat`` true) at the cell's shape: 8,192 positions,
    2,304 wide, 32 heads of 128, chunks of 64; a narrow FFN and a small
    vocabulary, which are not the point.  The layer's checkpoint keeps
    the scan's 32 states and its output (``ops/kda.py`` ``KEPT_NAMES``),
    so the compiled gradient has two loops over the 32 blocks, forward
    and backward, and three over a block's chunks (forward; again and
    backward inside the backward loop); under a plain ``jax.checkpoint``
    the layer's recompute ran a third and a fourth (PERF.md section 6,
    PR 38).  Kept: 0.13 GB of 2.03 GB of temporaries."""
    import jax
    import jax.numpy as jnp

    from geomx_tpu.models import transformer as tf

    cfg = tf.TransformerConfig(
        vocab=256, d_model=2304, n_heads=32, n_layers=1, d_ff=256,
        max_seq=8192, layer_types=("kda",), kda_heads=32, kda_head_dim=128,
        kda_chunk=64, no_positions=True, gated_ffn=True, remat=True)
    apply = tf.make_apply(cfg)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda k: tf.init_params(cfg, k),
                       jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda p, x: tf.lm_loss(apply, p, x))).lower(params, x).compile()
    loops = [line for line in compiled.as_text().splitlines()
             if " while(" in line]
    # a loop over the blocks carries arrays stacked over the 32 of them
    over_blocks = [line for line in loops
                   if "[32,1,32," in line.split(" while(")[0]]
    assert (len(loops), len(over_blocks)) == (5, 2), loops
    assert not any("rematted_computation/kda" in line for line in loops)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.15e9
