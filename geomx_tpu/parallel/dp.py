"""Party-level data parallelism: one party = one TPU slice.

This is the build plan's core mapping (SURVEY.md §7): the reference's
intra-DC tier — workers pushing to a local server over the LAN, with the
`Comm`/NCCL device-aggregation layer underneath (ref: src/kvstore/comm.h,
kvstore_nccl.h) — lowers to a single jitted train step over the party's
device mesh with the gradient AllReduce over ICI inside it; the host edge
then pushes ONE already-aggregated gradient per tensor into the HiPS
tier (so ``workers_per_party=1`` in the PS topology: the slice is the
worker).

``make_party_step`` builds that step: batch sharded over ``dp``, params
replicated, each chip differentiating its own shard inside ``shard_map``
and the gradients mean-reduced over the slice by one collective.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_party_step(grad_fn: Callable, mesh: Mesh,
                    reduce_grads: Optional[Callable] = None) -> Callable:
    """Wrap ``grad_fn(params, x, y) -> (loss, acc, grads)`` into a
    slice-wide DP step on ``mesh`` (its first axis, ``dp``).

    Returns ``step(params, x, y)`` taking host numpy batches; loss, acc
    and gradients come back replicated, mean-reduced across the slice —
    gradients by ``jax.lax.pmean`` over ICI, or by
    ``reduce_grads(grads, axis_name, axis_size)`` when another
    collective carries them (``quantized_allreduce``'s int8 wire).

    ``shard_map`` rather than GSPMD partitioning of a global-batch step:
    a Mosaic (pallas) kernel cannot be partitioned automatically, so a
    ``grad_fn`` that contains one (``attn_impl="flash"``) lowers only
    when each chip runs its own copy on its own shard.  As in any plain
    data parallelism, a batch-dependent layer sees its local shard.
    """
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(axis))

    def local(params, x, y):
        loss, acc, grads = grad_fn(params, x, y)
        loss = jax.lax.pmean(loss, axis)
        acc = jax.lax.pmean(acc, axis)
        if reduce_grads is None:
            grads = jax.lax.pmean(grads, axis)
        else:
            grads = reduce_grads(grads, axis, n_dev)
        return loss, acc, grads

    _step = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(), P()), check_vma=False))

    def step(params, x, y):
        params = jax.device_put(params, repl)
        x = jax.device_put(jnp.asarray(x), batch_sh)
        y = jax.device_put(jnp.asarray(y), batch_sh)
        return _step(params, x, y)

    return step


def party_meshes(num_parties: int, devices=None, axis: str = "dp"):
    """Split the available devices into one mesh per party — the
    simulation analog of 'each party is its own pod slice'."""
    if devices is None:
        devices = jax.devices()
    per = len(devices) // num_parties
    assert per >= 1, f"{len(devices)} devices cannot host {num_parties} parties"
    if len(devices) % num_parties:
        raise ValueError(
            f"{len(devices)} devices do not divide into {num_parties} "
            f"parties — {len(devices) % num_parties} chips would be "
            "silently stranded; pass an explicit device subset")
    if (per > 1 and num_parties > 1 and devices[0].platform == "tpu"
            and jax.config.jax_enable_compilation_cache
            and jax.config.jax_compilation_cache_dir):
        # seen on a v5e 2x2 (jax 0.9.0, libtpu 0.0.34; PR 23): a 2-chip
        # program compiled for chips {2,3} runs, but the same program
        # loaded back from the persistent cache halts the cores
        # ("Invalid logical z: enhanced-barrier-parent-phase-1"); on
        # chips {0,1}, or compiled afresh, it is fine
        raise RuntimeError(
            "party_meshes would split one TPU host into multi-chip "
            "sub-slices while the persistent compilation cache is on; a "
            "sub-slice program loaded from that cache halts the chip. "
            "Run this process with the cache off "
            "(jax.config.update('jax_enable_compilation_cache', False)).")
    out = []
    for p in range(num_parties):
        devs = np.asarray(devices[p * per:(p + 1) * per]).reshape(per)
        out.append(Mesh(devs, (axis,)))
    return out
