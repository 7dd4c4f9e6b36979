"""JAX/XLA merge backend: party aggregation on the device mesh.

The ROADMAP's founding premise is that a TPU pod slice acts as one
GeoMX "data center" — yet the host numpy path merged every intra-DC
gradient on CPU.  This backend lowers the server merge lanes onto the
device:

- each push is **staged exactly once** (one H2D ``device_put`` of the
  zero-copy recv view; ``h2d_bytes`` counts them) into a pinned f32
  device buffer;
- with a single device, pushes fold in arrival order through a jitted
  **donated-argument** accumulate (``donate_argnums=(0,)`` — XLA
  reuses the accumulator buffer, no per-push allocation), the device
  analog of the native axpy path;
- with a **multi-device mesh** (``parallel/mesh.py``) and big tensors,
  each push parks pre-reduced on a round-robin device slot and the
  round close reduces across slots with ``shard_map`` +
  ``jax.lax.psum`` — whole-party aggregation as one XLA collective
  over ICI, exactly how ``dp.make_party_step`` reduces inside a jit;
- the opt-in EQuARX rung (``Config.merge_quantized``) routes that
  collective through the int8 block-quantized psum, and (since
  ISSUE 11) keeps a per-key **error-feedback residual** per device
  slot (``Config.merge_residual``, default on): residual = pre-quant
  minus dequantized, folded into the next round's contribution before
  quantizing, so the int8 collective is accuracy-neutral over a run
  instead of systematically zeroing sub-threshold components (see
  :func:`geomx_tpu.parallel.quantized_allreduce.quantized_psum_mean_ef`);
- the **device-resident optimizer stage** (``Config.merge_opt_device``,
  default on): for the supported family (plain/momentum SGD, NAG,
  Adam) the round close no longer materializes the accumulator to
  host — :class:`DeviceOptimizer` holds per-key weights and moments on
  device and applies one jitted ``donate_argnums`` update over the
  device accumulator.  Host copies happen only at *events*: pulls /
  dissemination (serve), checkpoint slabs, replication snapshots and
  HANDOFF drains, all of which go through ``export_state`` /
  ``DeviceWeight.host()`` and bill ``d2h_bytes`` — the steady-state
  training contract is that ``d2h_bytes`` stays flat between such
  events (asserted by tests/test_device_opt.py).

Accumulators are :class:`_DeviceAccum` handles; the servers only touch
them through the backend methods plus ``.nbytes``.  Row-sparse
scatters stay host-side (``np.add.at`` has no device analog worth the
transfer) — :meth:`materialize` hands host arrays through unchanged
and :meth:`accumulate` falls back to the host kernel when it meets
one, so mixed dense/row-sparse rounds of one key stay correct (the
device optimizer re-stages a host-seeded round's accumulator, one H2D,
and carries on device-resident).

Bit-compatibility: every :class:`DeviceOptimizer` update mirrors its
numpy reference (:mod:`geomx_tpu.optim.server_opt`) operation-for-
operation — same op order, same f32 scalar casts — so for
exact-representable gradients the device trajectory is BITWISE equal
to the host one (pinned by tests/test_device_opt.py), and a trajectory
exported at a failover/handoff snapshot restores into either engine.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from geomx_tpu.kvstore.backend import (MergeBackend, _accumulate_kernel,
                                       _adopt_or_copy)
from geomx_tpu.trace.recorder import _NULL_SPAN, get_tracer

# below this many elements the mesh collective loses to a plain add
# (dispatch + cross-device assembly dominate); overridable so the CPU
# test mesh can exercise the psum path on small tensors
_MESH_MIN_ELEMS = int(os.environ.get("GEOMX_MERGE_MESH_MIN_ELEMS",
                                     str(1 << 16)))


class _DeviceAccum:
    """One key's in-flight round on the device: up to one pre-reduced
    buffer per mesh device (``spread`` mode) or a single folded buffer
    (single-device mode).  Confined to the key's merge lane — no lock.
    ``key`` anchors cross-round backend state (the quantized rung's
    error-feedback residual); None when the server predates the keyed
    seed API."""

    __slots__ = ("parts", "elems", "spread", "count", "key")

    def __init__(self, part, elems: int, spread: bool, key=None):
        self.parts: List = [part]
        self.elems = elems
        self.spread = spread
        self.count = 1
        self.key = key

    @property
    def nbytes(self) -> int:  # device-resident f32 bytes (stats())
        return 4 * self.elems * len(self.parts)

    def tobytes(self) -> bytes:
        """White-box escape hatch (tests snapshot ``accum.tobytes()``):
        the pending parts as the host bytes a numpy accumulator would
        hold.  Single-part accums transfer without reducing; multi-part
        (mesh-spread) accums fold host-side so peeking never perturbs
        the device-resident round state."""
        if len(self.parts) == 1:
            return np.asarray(self.parts[0]).tobytes()
        total = np.zeros(self.elems, np.float32)
        for p in self.parts:
            total += np.asarray(p)
        return total.tobytes()


# Bytes of one backend's round closes that may be on their way off the
# chip together (:meth:`JaxBackend.materialize_async`).  Four of the
# flagship's largest leaves (16.7M float32, 67 MB): one v5e chip gave
# 0.9 GB/s with one copy in flight, 1.85 with three and 2.9 with eight
# (PERF.md section 6, PR 41), and a worker's slice edge feeds a local
# server 1.8 GB/s, so three or four keep up with the feed; more would
# only hold staged device buffers and host buffers for longer.  A key
# larger than the bound is admitted alone.
_COPIES_IN_FLIGHT_BYTES = 256 << 20


class _HostCopy:
    """One closed round on its way to the host: the reduction and the
    copy were issued when this was built
    (:meth:`JaxBackend.materialize_async`), :meth:`land` waits for the
    rest of it.  ONE thread lands it (the local server's closer);
    whoever asks again is handed the landed value."""

    __slots__ = ("_be", "_dev", "_host", "_reserved", "key", "inflight")

    def __init__(self, be: "JaxBackend", dev, key, reserved: int):
        self._be = be
        self._dev = dev
        self._host: Optional[np.ndarray] = None
        self._reserved = reserved   # bytes held against the bound
        self.key = key
        self.inflight = be._issue_copy(dev)

    def land(self) -> np.ndarray:
        """The round as host bytes nothing else sees (what
        :meth:`JaxBackend.materialize` returns): the time a thread is
        held for it is the span ``be.d2h``, as ever."""
        if self._host is not None:
            return self._host
        be, dev = self._be, self._dev
        try:
            with be._timed("be.d2h", "merge_device_ms", self.key,
                           dev.nbytes) as sp:
                host = be._land(dev, sp, self.inflight)
                be._bill_d2h(host.nbytes)
                if be._platform == "cpu":
                    # jax hands out a READ-ONLY array on every platform.
                    # On an accelerator it is a fresh host buffer nothing
                    # else sees, and goes on frozen: whoever builds in the
                    # round copies at that point (count_cow).  On the CPU
                    # client it is a VIEW of the device buffer, which may
                    # itself alias the sender's non-donated payload (see
                    # accumulate): this copy is the isolation copy
                    host = host.copy()
        finally:
            self._dev = None   # the staged device buffer is let go
            be._copy_done(self._reserved)
        self._host = host
        return host


class _Timed:
    """One clock pair for a site that feeds an operator gauge
    (``merge_device_ms`` / ``opt_device_ms``) and, in a sampled round,
    is a tracer span: the span's own duration is billed when there is
    one, the site's clock when the tracer hands back ``_NULL_SPAN``.
    Host clock around asynchronous dispatch and staging — NOT device
    time (the profiler's ``XLA Modules`` line has that)."""

    __slots__ = ("_be", "_gauge", "_span", "_t0")

    def __init__(self, be: "JaxBackend", gauge: str, span):
        self._be = be
        self._gauge = gauge
        self._span = span

    def __enter__(self):
        """The span (``_NULL_SPAN`` in an unsampled round), for a site
        that tells it something while it is open."""
        if self._span is _NULL_SPAN:
            self._t0 = time.perf_counter()
        else:
            self._span.__enter__()
        return self._span

    def __exit__(self, *exc):
        span = self._span
        if span is _NULL_SPAN:
            ms = (time.perf_counter() - self._t0) * 1e3
        else:
            span.__exit__(*exc)
            ms = span.dur_us * 1e-3
        be = self._be
        with be._mu:
            setattr(be, self._gauge, getattr(be, self._gauge) + ms)
        return False


class JaxBackend(MergeBackend):
    name = "jax"
    # a device stream serializes dispatch; more lanes than this only
    # contend on the dispatch lock without overlapping device work
    max_lanes = 4
    # a round close need not hold its thread for the copy off the chip
    # (:meth:`materialize_async`): the local server keeps a closer
    async_copies = True

    def __init__(self, config=None, tracer=None):
        import jax  # deliberate: constructing this backend IS the opt-in
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        # the backend has no node of its own: the server that builds it
        # hands it its Tracer, and the spans below (be.* / opt.step)
        # land under that server's handler spans
        self._tr = tracer if tracer is not None else get_tracer("backend")
        self._devices = list(jax.devices())
        self._threads = int(getattr(config, "server_merge_threads", 0)
                            or 0)
        self._quantized = bool(getattr(config, "merge_quantized", False))
        from geomx_tpu.kvstore.backend import resolve_opt_device

        self._ef = (self._quantized
                    and bool(getattr(config, "merge_residual", True)))
        self._opt_device = resolve_opt_device(config)
        self._platform = self._devices[0].platform
        # donated-argument accumulate: XLA writes the sum back into the
        # accumulator's buffer — the device analog of acc += v
        # (every jitted server program is a NAMED function: the
        # profiler's ``XLA Modules`` line calls it ``jit_<name>``, and
        # the benchmark tells merge from optimizer from codec by it)
        def geomx_merge_add(a, b):
            return a + b

        self._add = jax.jit(geomx_merge_add, donate_argnums=(0,))

        # scale takes the factor as an f32 ARRAY argument: a python
        # float would be baked into the jaxpr and retrace per distinct
        # HFA renormalization value
        def geomx_merge_scale(a, s):
            return a * s

        self._scale = jax.jit(geomx_merge_scale, donate_argnums=(0,))

        # gradient-hygiene screen: one fused device reduction to a
        # scalar — |x| <= m subsumes the finiteness check (NaN/inf
        # compare False), so both modes are a single pass and the only
        # host traffic is the bool
        def geomx_screen(x, m):
            return jnp.where(m > np.float32(0),
                             (jnp.abs(x) <= m).all(),
                             jnp.isfinite(x).all())

        self._screen = jax.jit(geomx_screen)
        self._mesh_cache: Dict[int, object] = {}
        self._reducers: Dict[tuple, object] = {}
        # per-key error-feedback residual for the quantized collective:
        # key -> (slot count, [k, elems] global array sharded over the
        # same devices the pre-reduced parts live on).  Mutated only on
        # the key's merge lane; the dict itself is GIL-safe per key.
        self._residuals: Dict[int, tuple] = {}
        self._mu = threading.Lock()  # counters + caches (leaf lock)
        # copies off the chip issued and not read yet, and the bytes of
        # them held against ``_COPIES_IN_FLIGHT_BYTES`` (``_room`` wakes
        # a round close that waits for its turn)
        self._room = threading.Condition(self._mu)
        self._copies_in_flight = 0
        self._bytes_in_flight = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # bytes of materialized rounds a consumer copied because it had
        # to write into a frozen one (the second copy of a round close:
        # 0 wherever the round is only read)
        self.cow_bytes = 0
        self.merge_device_ms = 0.0
        self.opt_device_ms = 0.0
        # codec-stage counters (ISSUE 20): wall spent in jitted codec
        # kernels, wire-ready compressed bytes materialized (the ONLY
        # D2H the device codec path pays), and full-tensor bytes that
        # crossed the host boundary for codec work (the quantity the
        # device path exists to eliminate; exactly 0 in steady state
        # with device codecs on)
        self.codec_device_ms = 0.0
        self.codec_d2h_bytes = 0
        self.codec_host_bytes = 0

    # ---- staging ------------------------------------------------------------
    def _stage(self, v: np.ndarray, device):
        """One H2D copy of the (possibly zero-copy wire view) payload,
        f32-promoted.  ``ascontiguousarray`` is the identity for the
        aligned f32 views wire format v2 decodes, so the device_put
        reads straight out of the receive buffer.  A payload that is
        ALREADY a device array (the codec stage's decode output) stages
        for free: no host round-trip, no ``h2d_bytes`` — placement is
        pinned with an intra-device (or D2D, under a mesh) transfer."""
        if isinstance(v, self._jax.Array):
            if v.dtype != self._jnp.float32:
                v = v.astype(self._jnp.float32)
            return self._jax.device_put(v, device)
        arr = np.ascontiguousarray(v, dtype=np.float32)
        staged = self._jax.device_put(arr, device)
        with self._mu:
            self.h2d_bytes += arr.nbytes
        return staged

    def seed(self, v: np.ndarray, donated: bool, key=None):
        # the donation contract is honored trivially here: the wire
        # buffer is consumed by the single staged H2D copy and never
        # aliased or mutated afterwards
        spread = (len(self._devices) > 1
                  and len(v) >= _MESH_MIN_ELEMS)
        with self._timed("be.h2d", "merge_device_ms", key, v.nbytes):
            part = self._stage(v, self._devices[0])
        return _DeviceAccum(part, len(v), spread, key=key)

    def accumulate(self, acc, v: np.ndarray):
        if isinstance(acc, np.ndarray):
            # a row-sparse scatter seeded this key host-side: stay on
            # the host kernel for the rest of the round
            _accumulate_kernel()(acc,
                                 np.ascontiguousarray(v, np.float32),
                                 self._threads)
            return acc
        # round-robin device slots under a mesh: contribution i lands
        # on device i % n, pre-reduced per slot in arrival order; the
        # round close psums ACROSS the slots
        slot = acc.count % len(self._devices) if acc.spread else 0
        with self._timed("be.h2d", "merge_device_ms", acc.key, v.nbytes):
            staged = self._stage(v, self._devices[slot])
        if slot < len(acc.parts):
            with self._timed("be.add", "merge_device_ms", acc.key,
                             v.nbytes):
                acc.parts[slot] = self._add(acc.parts[slot], staged)
                if self._platform == "cpu":
                    # the CPU client stages an aligned host buffer by
                    # ALIASING it, and runs the add some time later: a
                    # sender that reused its buffer after the ack
                    # changed the merge, by the luck of malloc.  Here
                    # the add has read the buffer before the push is
                    # acked; an accelerator copies, and stays async
                    acc.parts[slot].block_until_ready()
        else:
            acc.parts.append(staged)
        acc.count += 1
        return acc

    # ---- round close --------------------------------------------------------
    def scale(self, acc, s: float):
        if isinstance(acc, np.ndarray):
            np.multiply(acc, s, out=acc)
            return acc
        with self._timed("be.scale", "merge_device_ms", acc.key,
                         4 * acc.elems):
            part = self._reduced(acc)
            acc.parts = [self._scale(part, np.float32(s))]
        return acc

    def materialize(self, acc) -> np.ndarray:
        if isinstance(acc, np.ndarray):
            return acc
        return _HostCopy(self, self._reduced(acc), acc.key, 0).land()

    def materialize_async(self, acc: _DeviceAccum) -> _HostCopy:
        """:meth:`materialize` of a device round in two halves: the
        round's reduction and its copy off the chip are issued here,
        and whoever needs the bytes waits for the rest in
        :meth:`_HostCopy.land`, so that the caller's thread goes on to
        the next message (whose H2D then runs beside this D2H) and
        several keys' copies are in flight together.  Waits first, with
        NO lock of the server held (the local tier calls it stripe
        released), until the copies issued here and not landed leave
        room under ``_COPIES_IN_FLIGHT_BYTES``.  Room is made by
        :meth:`_HostCopy.land` alone: the caller hands each copy to the
        thread that lands it BEFORE it asks for the next."""
        nbytes = 4 * acc.elems
        if not self._take_room(nbytes, wait=False):
            # the thread is held for earlier copies to land: a
            # ``be.d2h`` of its own (no ``inflight``: nothing was
            # issued), opened with no lock held, so that the span's sum
            # stays every second a thread was held for a landing
            with self._tr.span("be.d2h", key=acc.key, nbytes=nbytes):
                self._take_room(nbytes, wait=True)
        return _HostCopy(self, self._reduced(acc), acc.key, nbytes)

    def _take_room(self, nbytes: int, wait: bool) -> bool:
        """``nbytes`` more in flight if the bound leaves room (a key
        larger than the whole bound goes alone); else wait for it, or
        say no."""
        with self._room:
            while (self._bytes_in_flight and self._bytes_in_flight + nbytes
                   > _COPIES_IN_FLIGHT_BYTES):
                if not wait:
                    return False
                self._room.wait()
            self._bytes_in_flight += nbytes
        return True

    # ---- copies off the chip ------------------------------------------------
    def _issue_copy(self, dev) -> int:
        """Start ``dev``'s copy to the host (it begins once the value
        exists; nothing waits here).  Returns the copies of this backend
        in flight now, this one included (``inflight`` of its
        ``be.d2h``)."""
        with self._mu:
            self._copies_in_flight += 1
            n = self._copies_in_flight
        dev.copy_to_host_async()
        return n

    def _land(self, dev, sp, inflight: int) -> np.ndarray:
        """The host value of ``dev`` inside its open ``be.d2h`` span
        ``sp``: blocks for what is left of the copy.  A sampled round
        says how much of the span is the wait for the programs that
        produce the value (``wait_us``; the copy would block there
        anyway) and how many copies were in flight when this one was
        issued (``inflight``)."""
        if sp is not _NULL_SPAN:
            sp.await_device(dev)
            sp.add("inflight", inflight)
        return np.asarray(dev)

    def _copy_done(self, reserved: int = 0) -> None:
        """A copy of :meth:`_issue_copy` was read (or its handle
        replaced unread)."""
        with self._room:
            self._copies_in_flight -= 1
            if reserved:
                self._bytes_in_flight -= reserved
                self._room.notify_all()

    def _d2h(self, dev) -> np.ndarray:
        """One value off the chip while the caller waits (the codec
        stage's frames): span ``be.d2h`` around the issue and the
        landing."""
        inflight = self._issue_copy(dev)
        try:
            with self._tr.span("be.d2h", nbytes=dev.nbytes) as sp:
                return self._land(dev, sp, inflight)
        finally:
            self._copy_done()

    def count_cow(self, nbytes: int) -> None:
        with self._mu:
            self.cow_bytes += int(nbytes)

    def _reduced(self, acc: "_DeviceAccum"):
        if len(acc.parts) == 1:
            return acc.parts[0]
        part = self._mesh_reduce(acc.parts, acc.elems, acc.key)
        acc.parts = [part]
        return part

    # ---- mesh collective ----------------------------------------------------
    def _submesh(self, k: int):
        """A ``{"party": k}`` mesh over the first k devices (cached):
        slot i's pre-reduced buffer is already resident on device i, so
        the global array assembles below with zero copies."""
        mesh = self._mesh_cache.get(k)
        if mesh is None:
            from geomx_tpu.parallel.mesh import make_mesh

            mesh = make_mesh({"party": k}, devices=self._devices[:k])
            with self._mu:
                self._mesh_cache[k] = mesh
        return mesh

    def _reducer(self, k: int, elems: int, ef: bool):
        key = (k, elems, self._quantized, ef)
        red = self._reducers.get(key)
        if red is not None:
            return red
        from jax.sharding import PartitionSpec as P

        jax = self._jax
        shard_map = jax.shard_map
        mesh = self._submesh(k)
        if self._quantized and ef:
            from geomx_tpu.parallel.quantized_allreduce import (
                quantized_psum_mean_ef)

            def geomx_mesh_reduce(x, r):  # [1, elems] + residual per slot
                out, r_new = quantized_psum_mean_ef(x[0], r[0], "party", k)
                # quantized mean * k = the party SUM the round-close
                # consumers expect; the residual is already in that
                # weight-1 contribution domain
                return (out * np.float32(k))[None], r_new[None]

            red = jax.jit(shard_map(
                geomx_mesh_reduce, mesh=mesh,
                in_specs=(P("party"), P("party")),
                out_specs=(P("party"), P("party")), check_vma=False))
        elif self._quantized:
            from geomx_tpu.parallel.quantized_allreduce import (
                quantized_psum_mean)

            def geomx_mesh_reduce(x):  # [1, elems] per device
                # quantized mean * k = the party SUM the round-close
                # consumers expect (the global optimizer divides by
                # num_contributors itself)
                return (quantized_psum_mean(x[0], "party", k)
                        * np.float32(k))[None]

            red = jax.jit(shard_map(geomx_mesh_reduce, mesh=mesh,
                                    in_specs=P("party"),
                                    out_specs=P("party"), check_vma=False))
        else:
            def geomx_mesh_reduce(x):
                return jax.lax.psum(x, "party")

            red = jax.jit(shard_map(geomx_mesh_reduce, mesh=mesh,
                                    in_specs=P("party"),
                                    out_specs=P("party"), check_vma=False))
        with self._mu:
            self._reducers[key] = red
        return red

    def _residual_for(self, key, k: int, elems: int):
        """The [k, elems] error-feedback residual global array for this
        key, sharded over the first k devices like the pre-reduced
        parts; fresh zeros when the slot count changed (a party fold
        re-shapes the round — stale per-slot residuals for a different
        k would compensate the wrong shards)."""
        ent = self._residuals.get(key)
        if ent is not None and ent[0] == k and ent[1].shape[1] == elems:
            return ent[1]
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self._submesh(k), P("party"))
        zeros = [self._jax.device_put(np.zeros((1, elems), np.float32),
                                      self._devices[i]) for i in range(k)]
        r = self._jax.make_array_from_single_device_arrays(
            (k, elems), sharding, zeros)
        self._residuals[key] = (k, r)
        return r

    def _mesh_reduce(self, parts: List, elems: int, key=None):
        """Cross-slot party aggregation as one XLA collective: assemble
        the [k, elems] global array from the per-device resident
        buffers (no copies — each shard is already where the sharding
        wants it) and psum over the ``party`` axis.  Under the
        quantized rung with error feedback the per-slot residual rides
        in and the updated residual is kept for the key's next round.
        Returns the summed [elems] buffer on device 0."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        k = len(parts)
        # a plain span: the site that closes the round (be.scale,
        # be.d2h, opt.step) holds the gauge's clock around it
        with self._tr.span("be.reduce", key=key, nbytes=4 * elems * k):
            mesh = self._submesh(k)
            sharding = NamedSharding(mesh, P("party"))
            global_arr = self._jax.make_array_from_single_device_arrays(
                (k, elems), sharding,
                [p.reshape(1, elems) for p in parts])
            ef = self._ef and key is not None
            if ef:
                r = self._residual_for(key, k, elems)
                out, r_new = self._reducer(k, elems, True)(global_arr, r)
                self._residuals[key] = (k, r_new)
            else:
                out = self._reducer(k, elems, False)(global_arr)
            # out is [k, elems] with equal rows; commit row 0 to device 0
            # so downstream single-device consumers (the jitted optimizer
            # update, the donated scale) see one device, not the mesh
            return self._jax.device_put(out[0], self._devices[0])

    def screen_finite(self, v: np.ndarray, mag_max: float = 0.0) -> bool:
        """Device screen: the jitted fused reduction ships one scalar
        back (single sync) instead of round-tripping the tensor.  A
        device-resident payload (codec-stage decode output) is screened
        in place — ``ascontiguousarray`` on it would silently D2H the
        whole tensor."""
        if isinstance(v, self._jax.Array):
            if v.dtype != self._jnp.float32:
                v = v.astype(self._jnp.float32)
            return bool(self._screen(v, np.float32(mag_max)))
        arr = np.ascontiguousarray(v, dtype=np.float32)
        return bool(self._screen(arr, np.float32(mag_max)))

    # ---- codec stage --------------------------------------------------------
    def make_codec_stage(self, config):
        """A :class:`CodecStage` when ``codec_device`` resolves on (see
        :func:`geomx_tpu.kvstore.backend.resolve_codec_device`), else
        None — the servers keep the host numpy codecs, the bit-compat
        reference."""
        from geomx_tpu.kvstore.backend import resolve_codec_device

        if not resolve_codec_device(config):
            return None
        return CodecStage(self)

    # ---- optimizer stage ----------------------------------------------------
    def make_device_optimizer(self, spec: dict):
        """A :class:`DeviceOptimizer` for ``spec`` when the stage is
        enabled and the type is in the supported family, else None (the
        server keeps the host optimizer — DCASGD and friends need
        per-sender host bookkeeping the device stage doesn't model)."""
        if not self._opt_device:
            return None
        cls = _DEVICE_OPTS.get(str(spec.get("type", "")).lower())
        if cls is None:
            return None
        return cls(self, spec)

    # ---- observability ------------------------------------------------------
    def _timed(self, name: str, gauge: str, key, nbytes: int) -> _Timed:
        """Span ``name`` (in a sampled round) and ``gauge`` (always)
        from one clock pair."""
        return _Timed(self, gauge,
                      self._tr.span(name, key=key, nbytes=nbytes))

    def _bill_d2h(self, nbytes: int) -> None:
        with self._mu:
            self.d2h_bytes += int(nbytes)

    def stats(self) -> dict:
        with self._mu:
            return {"merge_backend": self.name,
                    "merge_device": self._platform,
                    "merge_devices": len(self._devices),
                    "merge_quantized": self._quantized,
                    "merge_residual": self._ef,
                    "merge_opt_device": self._opt_device,
                    "merge_device_ms": round(self.merge_device_ms, 3),
                    "opt_device_ms": round(self.opt_device_ms, 3),
                    "codec_device_ms": round(self.codec_device_ms, 3),
                    "codec_d2h_bytes": self.codec_d2h_bytes,
                    "codec_host_bytes": self.codec_host_bytes,
                    "h2d_bytes": self.h2d_bytes,
                    "d2h_bytes": self.d2h_bytes,
                    "cow_bytes": self.cow_bytes}


class DeviceWeight:
    """One key's weights, device-resident between round closes.

    The server's store holds this handle instead of a host ndarray
    while the device optimizer owns the key; any host consumer (pull
    serving, dissemination, checkpoint/replication/handoff snapshots,
    the pull compressor) goes through :meth:`host`, which performs —
    and bills to ``d2h_bytes`` — at most one device→host materialization
    per round close (cached until the next update replaces the handle).
    The update never donates the weight buffer: an in-flight pull
    response may still alias a previous ``host()`` view, and a donated
    (deleted) buffer under it would be a use-after-free on accelerator
    backends.

    ``prefetch``: the copy starts with the handle, at the round close
    that made the value, and :meth:`host` finds it landed or waits for
    the rest.  The round close asks for it where the handle this one
    replaces was read (:meth:`replaced`): a key whose last version was
    pulled will be pulled again, and a key updated several times
    between reads (the async tier, HFA's off-rounds) is not copied for
    nothing.  The one D2H is billed to ``d2h_bytes`` where it STARTS
    (at the close for a prefetch, else at the read): a copy nobody
    comes for has crossed all the same.  :meth:`host` is safe from
    several threads (the pull channel and a round close's parked
    pulls): the server holds no lock of its own across the copy."""

    __slots__ = ("ref", "_be", "_host", "key", "_mu", "_inflight",
                 "_counted")

    def __init__(self, be: "JaxBackend", ref, key=None,
                 prefetch: bool = False):
        self.ref = ref
        self._be = be
        self._host: Optional[np.ndarray] = None
        self.key = key  # for the be.d2h span of host()
        self._mu = threading.Lock()
        self._inflight = 0      # copies in flight when this one started
        self._counted = False   # this one is among the backend's still
        if prefetch:
            self._issue()

    @property
    def nbytes(self) -> int:  # store_bytes accounting without a D2H
        return int(self.ref.nbytes)

    def __len__(self) -> int:
        return int(self.ref.shape[0])

    @property
    def was_read(self) -> bool:
        return self._host is not None

    def _issue(self) -> None:
        self._inflight = self._be._issue_copy(self.ref)
        self._counted = True
        self._be._bill_d2h(self.ref.nbytes)

    def _uncount(self) -> None:
        if self._counted:
            self._counted = False
            self._be._copy_done()

    def replaced(self) -> bool:
        """The round close that swaps this handle out asks, once: was it
        read?  A prefetch nobody came for leaves the backend's count of
        copies in flight here.  Never waits: a reader that holds the
        handle for its landing right now IS the read."""
        if not self._mu.acquire(False):
            return True
        try:
            self._uncount()
            return self._host is not None
        finally:
            self._mu.release()

    def host(self) -> np.ndarray:
        if self._host is None:
            with self._mu:
                if self._host is None:
                    if not self._inflight:
                        self._issue()
                    with self._be._tr.span("be.d2h", key=self.key,
                                           nbytes=self.ref.nbytes) as sp:
                        # zero-copy view on cpu
                        h = self._be._land(self.ref, sp, self._inflight)
                    self._uncount()
                    self._host = h
        return self._host


class DeviceOptimizer:
    """Device-resident optimizer stage for the jax merge lanes.

    Holds per-key optimizer state (momentum / Adam moments) as device
    arrays and closes a round with ONE jitted update over the device
    accumulator — the gradient and the state buffers are donated, the
    weights are not (see :class:`DeviceWeight`), and nothing touches
    the host.  Confinement mirrors the merge contract: :meth:`step`
    runs only on the key's merge lane (stripe held); the snapshot hooks
    (:meth:`export_state` / :meth:`import_state` / :meth:`import_key`)
    run only under the server's all-stripes barrier.

    Every update mirrors its :mod:`geomx_tpu.optim.server_opt` numpy
    reference operation-for-operation (same op order, same weak-scalar
    f32 casts numpy 2.x applies), so exact-representable gradients
    produce BITWISE-identical trajectories on either engine — which is
    what lets a failover/handoff snapshot round-trip through the numpy
    pickle format and continue on a promoted standby with no
    trajectory discontinuity."""

    kind = "abstract"

    def __init__(self, be: "JaxBackend", spec: dict):
        self._be = be
        self._jax = be._jax
        self._jnp = be._jnp
        self.spec = dict(spec)
        self.lr = float(spec.get("lr", 0.01))
        self.wd = float(spec.get("wd", 0.0))
        self._st: Dict[int, dict] = {}

    # ---- hot path -----------------------------------------------------------
    def step(self, k: int, raw_w, accum, scale: float) -> DeviceWeight:
        """One round close for key ``k``: semantically
        ``ServerOptimizer.update_scaled(k, weight, accum, scale)`` with
        weights/state/accumulator all device-resident.  ``raw_w`` is
        the store's raw entry — a :class:`DeviceWeight` in steady state,
        a host ndarray on the key's first device round (adopted with
        one H2D); ``accum`` is the merge accumulator (device handle, or
        a host array when a row-sparse scatter seeded the round)."""
        with self._be._timed("opt.step", "opt_device_ms", k, raw_w.nbytes):
            w = self._weight_ref(raw_w)
            g = self._grad_ref(accum)
            new = self._update(k, w, g, float(scale))
        return DeviceWeight(self._be, new, key=k,
                            prefetch=self._was_read(raw_w))

    def add_delta(self, raw_w, accum) -> DeviceWeight:
        """HFA milestone-delta close: ``weight + accum`` on device (no
        optimizer state involved — the delta is pre-divided)."""
        key = getattr(raw_w, "key", None)  # a DeviceWeight knows its key
        with self._be._timed("opt.step", "opt_device_ms", key, raw_w.nbytes):
            w = self._weight_ref(raw_w)
            g = self._grad_ref(accum)
            new = w + g  # NOT the donated add: w must stay alive (aliases)
        return DeviceWeight(self._be, new, key=key,
                            prefetch=self._was_read(raw_w))

    @staticmethod
    def _was_read(raw) -> bool:
        """The prefetch rule: the handle a close replaces was read."""
        return isinstance(raw, DeviceWeight) and raw.replaced()

    def _weight_ref(self, raw):
        if isinstance(raw, DeviceWeight):
            return raw.ref
        return self._be._stage(np.ascontiguousarray(raw, np.float32),
                               self._be._devices[0])

    def _grad_ref(self, accum):
        if isinstance(accum, _DeviceAccum):
            return self._be._reduced(accum)
        # _stage handles host arrays (one billed H2D) and already-device
        # arrays (codec-stage decode output; no host round-trip) alike
        return self._be._stage(accum, self._be._devices[0])

    def _update(self, k: int, w, g, scale: float):
        raise NotImplementedError

    # ---- snapshot hooks (failover / reassignment / warm boot) ---------------
    def export_state(self):
        """The equivalent host :class:`ServerOptimizer` with all per-key
        state materialized (one D2H per state tensor, billed) — what
        every snapshot path (checkpoint, replication stream, HANDOFF
        drain) serializes, so the wire/slab format stays the numpy
        pickle and a standby on EITHER engine can restore it."""
        from geomx_tpu.optim import make_optimizer

        opt = make_optimizer(dict(self.spec))
        for k, st in self._st.items():
            out = {}
            for name, v in st.items():
                if isinstance(v, (int, float)):
                    out[name] = v
                else:
                    h = np.array(v)  # D2H + own the copy (pickled)
                    self._be._bill_d2h(h.nbytes)
                    out[name] = h
            opt.state[k] = out
        return opt

    def import_state(self, opt) -> None:
        """Adopt a restored host optimizer's per-key state wholesale
        (checkpoint restore / replication install / promotion)."""
        self._st.clear()
        for k, st in getattr(opt, "state", {}).items():
            self.import_key(int(k), st)

    def import_key(self, k: int, st: dict) -> None:
        """Adopt one key's host state (HANDOFF range merge — the
        shipped key's momentum/moments move with the range)."""
        out = {}
        for name, v in st.items():
            if isinstance(v, np.ndarray):
                out[name] = self._be._stage(v, self._be._devices[0])
            else:
                out[name] = v
        self._st[k] = out

    def drop_key(self, k: int) -> None:
        """Discard one key's trajectory (overwrite-INIT restore abort —
        mirrors ``self.optimizer.state.pop(k, None)``)."""
        self._st.pop(k, None)

    def stats(self) -> dict:
        return {"opt_device": self.kind, "opt_device_keys": len(self._st)}


class DeviceSgd(DeviceOptimizer):
    kind = "sgd"

    def __init__(self, be, spec):
        super().__init__(be, spec)
        self.momentum = float(spec.get("momentum", 0.0))
        jax = self._jax
        if self.momentum == 0.0 and self.wd == 0.0:
            # numpy Sgd.update_scaled's fast path: new_w = g·c + w with
            # c = f32(-(lr·scale)) — two passes, grad donated
            def geomx_sgd_plain(g, w, c):
                return g * c + w

            self._upd = jax.jit(geomx_sgd_plain, donate_argnums=(0,))
        elif self.momentum == 0.0:
            def geomx_sgd(w, g, scale, lr, wd):
                g = g * scale
                g = g + wd * w
                return w - lr * g

            self._upd = jax.jit(geomx_sgd, donate_argnums=(1,))
        else:
            def geomx_sgd(w, mom, g, scale, lr, wd, momentum):
                g = g * scale
                g = g + wd * w
                mom = momentum * mom - lr * g
                return w + mom, mom

            self._upd = jax.jit(geomx_sgd, donate_argnums=(1, 2))

    def _update(self, k, w, g, scale):
        if self.momentum == 0.0 and self.wd == 0.0:
            return self._upd(g, w, np.float32(-(self.lr * scale)))
        if self.momentum == 0.0:
            return self._upd(w, g, np.float32(scale),
                             np.float32(self.lr), np.float32(self.wd))
        st = self._st.get(k)
        if st is None:
            st = {"mom": self._jnp.zeros_like(w)}
            self._st[k] = st
        new_w, st["mom"] = self._upd(
            w, st["mom"], g, np.float32(scale), np.float32(self.lr),
            np.float32(self.wd), np.float32(self.momentum))
        return new_w


class DeviceNag(DeviceOptimizer):
    kind = "nag"

    def __init__(self, be, spec):
        super().__init__(be, spec)
        self.momentum = float(spec.get("momentum", 0.9))

        def geomx_nag(w, mom, g, scale, lr, wd, momentum):
            g = g * scale
            g = g + wd * w
            mom = momentum * mom + g
            return w - lr * (g + momentum * mom), mom

        self._upd = self._jax.jit(geomx_nag, donate_argnums=(1, 2))

    def _update(self, k, w, g, scale):
        st = self._st.get(k)
        if st is None:
            st = {"mom": self._jnp.zeros_like(w)}
            self._st[k] = st
        new_w, st["mom"] = self._upd(
            w, st["mom"], g, np.float32(scale), np.float32(self.lr),
            np.float32(self.wd), np.float32(self.momentum))
        return new_w


class DeviceAdam(DeviceOptimizer):
    kind = "adam"

    def __init__(self, be, spec):
        super().__init__(be, spec)
        self.beta1 = float(spec.get("beta1", 0.9))
        self.beta2 = float(spec.get("beta2", 0.999))
        self.eps = float(spec.get("eps", 1e-8))
        jnp = self._jnp

        def geomx_adam(w, m, v, g, scale, b1, one_b1, b2, one_b2, corr1,
                       corr2, lr, eps, wd):
            g = g * scale
            g = g + wd * w
            m = b1 * m + one_b1 * g
            v = b2 * v + (one_b2 * g) * g
            mhat = m / corr1
            vhat = v / corr2
            return w - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

        self._upd = self._jax.jit(geomx_adam, donate_argnums=(1, 2, 3))

    def _update(self, k, w, g, scale):
        st = self._st.get(k)
        if st is None:
            st = {"m": self._jnp.zeros_like(w),
                  "v": self._jnp.zeros_like(w), "t": 0}
            self._st[k] = st
        st["t"] += 1
        # bias corrections computed host-side in f64 then f32-cast —
        # precisely the weak-scalar cast numpy applies to the division
        new_w, st["m"], st["v"] = self._upd(
            w, st["m"], st["v"], g, np.float32(scale),
            np.float32(self.beta1), np.float32(1 - self.beta1),
            np.float32(self.beta2), np.float32(1 - self.beta2),
            np.float32(1 - self.beta1 ** st["t"]),
            np.float32(1 - self.beta2 ** st["t"]),
            np.float32(self.lr), np.float32(self.eps),
            np.float32(self.wd))
        return new_w


_DEVICE_OPTS = {"sgd": DeviceSgd, "nag": DeviceNag, "adam": DeviceAdam}


class CodecStage:
    """Device-resident WAN codec engine (ISSUE 20).

    One per server under the jax backend when ``codec_device`` resolves
    on.  The LOCAL tier uses :meth:`make_push_codec` to build the
    :class:`DeviceCodec` push family — encode reads the device merge
    accumulator directly (``round_value``) and materializes ONLY the
    wire-ready compressed payload (billed to ``codec_d2h_bytes``); the
    GLOBAL tier uses :meth:`decode` — structural validation runs
    host-side on the (already-host) compressed payload with the exact
    :mod:`geomx_tpu.compression.codecs` gates (truncation / bit-flips
    land the same typed :class:`CodecError`, never an OOB scatter), then
    jitted dequantize/scatter kernels land the gradient as a device
    array that :meth:`JaxBackend.seed` recognizes and never re-stages.

    Wire frames are bit-identical to the numpy reference in both
    directions: fp16/2bit device ENCODERS emit byte-identical frames
    for identical state; the BSC device encoder picks the exact top-k
    of the accumulated mass (k = ratio·n, by a counted threshold and a
    compaction: :func:`_topk_support`) instead of the reference's
    sampled-threshold scan — a legal selection under the same
    ``[f32 values ‖ int32 indices bit-cast to f32]`` layout — and every
    DECODER (device or numpy) reconstructs any legal frame bitwise
    identically (tests/test_device_codec.py pins the full cross-decode
    matrix).  The stage is stateless on the decode side, so the
    epoch-fence ``DecoderBank.clear()`` semantics need no device
    analog."""

    device = True

    def __init__(self, be: "JaxBackend"):
        self._be = be
        jax, jnp = be._jax, be._jnp
        self._jax, self._jnp = jax, jnp
        # decode kernels (receiver side; shape/length-cached by jit)
        def geomx_fp16_dec(p):
            return p.astype(jnp.float32)

        self._dec_f16 = jax.jit(geomx_fp16_dec)

        # ``_scatter`` (and the Bi-Sparse encoder's ``enc``) keep their
        # names: the benchmark's codec_dev_ms_per_step matches on them
        def _scatter(vals, idx, n):
            return jnp.zeros(n, jnp.float32).at[idx].set(vals)

        self._dec_bsc = jax.jit(_scatter, static_argnums=(2,))

        def geomx_2bit_dec(b, t, n):
            q = jnp.stack([b & 3, (b >> 2) & 3, (b >> 4) & 3,
                           (b >> 6) & 3], axis=1).reshape(-1)[:n]
            z = jnp.zeros((), jnp.float32)
            return jnp.where(q == 1, t, jnp.where(q == 2, -t, z))

        self._dec_2bit = jax.jit(geomx_2bit_dec, static_argnums=(2,))

    # ---- residency helpers (server-side seam) -------------------------------
    def is_device(self, v) -> bool:
        return isinstance(v, self._jax.Array)

    def round_value(self, accum):
        """The completed round as a single device array, WITHOUT the
        host materialization ``MergeBackend.materialize`` would pay —
        the zero-D2H handoff from the merge lanes to the encoder."""
        if isinstance(accum, _DeviceAccum):
            return self._be._reduced(accum)
        return accum  # host-seeded (row-sparse) rounds pass through

    def concat(self, vs):
        """Multi-key round packing on device (``np.concatenate`` over
        device arrays would silently round-trip every value host-side)."""
        return self._jnp.concatenate(
            [self._jnp.asarray(v, self._jnp.float32) for v in vs])

    def to_host(self, v) -> np.ndarray:
        """Full-tensor D2H for the fallback event paths (degraded-round
        absorb, adaptive raw stash) — billed to ``codec_host_bytes`` so
        the steady-state "host copies == 0" contract stays auditable."""
        host = self._be._d2h(v)
        with self._be._mu:
            self._be.codec_host_bytes += host.nbytes
        return host

    def _ensure_device(self, arr):
        """Encoder input residency: device arrays pass through; a host
        array (row-sparse or re-encode fallback) pays one H2D, billed as
        a codec host copy."""
        if isinstance(arr, self._jax.Array):
            if arr.dtype != self._jnp.float32:
                arr = arr.astype(self._jnp.float32)
            return arr
        host = np.ascontiguousarray(arr, dtype=np.float32)
        with self._be._mu:
            self._be.codec_host_bytes += host.nbytes
        return self._jax.device_put(host, self._be._devices[0])

    def _wire(self, payload) -> np.ndarray:
        """Materialize one encoded frame as the wire-ready host buffer —
        THE single D2H of the device encode path (compressed bytes only,
        billed to ``codec_d2h_bytes``).  The returned view keeps the
        device buffer alive; senders ship it donated and never mutate."""
        host = self._be._d2h(payload)  # its ``wait_us``: the encoder
        with self._be._mu:
            self._be.codec_d2h_bytes += host.nbytes
        return host

    def _bill(self, t0: float) -> None:
        dt = (time.perf_counter() - t0) * 1e3
        with self._be._mu:
            self._be.codec_device_ms += dt

    # ---- push-codec factory (sender side) -----------------------------------
    def make_push_codec(self, config: dict):
        """Device analog of :func:`geomx_tpu.compression.make_push_codec`
        — same config schema, same ValueError on unknown types, device
        implementations for the full family."""
        typ = config.get("type", "none")
        if typ == "none":
            return None
        if typ == "fp16":
            return DeviceFp16Codec(self)
        if typ == "2bit":
            return DeviceTwoBitCodec(
                self, threshold=config.get("threshold", 0.5))
        if typ == "bsc":
            return DeviceBscCodec(self, ratio=config.get("ratio", 0.01),
                                  momentum=config.get("momentum", 0.9))
        if typ == "mpq":
            return DeviceMpqSelector(
                self, size_bound=config.get("size_bound", 200_000),
                ratio=config.get("ratio", 0.01),
                momentum=config.get("momentum", 0.9))
        raise ValueError(f"unknown compression type '{typ}'")

    # ---- decode (receiver side) ---------------------------------------------
    def decode(self, compr: str, key: int, payload: np.ndarray,
               orig_len: int, threshold: float = 0.5):
        """Tag-dispatched decode to a DEVICE f32 array — drop-in for
        :func:`geomx_tpu.compression.decompress_payload` with identical
        structural gates (host-side, on the small compressed buffer,
        BEFORE any device work or scatter)."""
        from geomx_tpu.compression.codecs import (CodecError,
                                                  _check_index_bounds,
                                                  unpack_sparse)

        t0 = time.perf_counter()
        dev0 = self._be._devices[0]
        if compr == "fp16":
            if len(payload) != orig_len:
                raise CodecError(
                    f"fp16 payload carries {len(payload)} values for a "
                    f"{orig_len}-element tensor", tag="fp16", key=key)
            p = self._jax.device_put(
                np.ascontiguousarray(payload, np.float16), dev0)
            out = self._dec_f16(p)
        elif compr == "bsc":
            vals, idx = unpack_sparse(payload, key=key)
            _check_index_bounds(idx, orig_len, "bsc", key)
            out = self._dec_bsc(
                self._jax.device_put(vals, dev0),
                self._jax.device_put(idx.astype(np.int32), dev0),
                int(orig_len))
        elif compr == "2bit":
            b = np.ascontiguousarray(payload, dtype=np.uint8)
            if len(b) < (orig_len + 3) // 4:
                raise CodecError(
                    f"2bit payload holds {len(b) * 4} codes for a "
                    f"{orig_len}-element tensor", tag="2bit", key=key)
            out = self._dec_2bit(self._jax.device_put(b, dev0),
                                 np.float32(threshold), int(orig_len))
        else:
            raise CodecError(f"unknown compr tag '{compr}'", tag=compr,
                             key=key)
        self._bill(t0)
        return out


class DeviceCodec:
    """Push-direction device codec base: same duck-typed surface as
    :class:`geomx_tpu.compression.codecs.Codec` (``name`` /
    ``compress`` / ``decompress`` / ``dense_delta``), plus ``device``
    so the round-close can tell the server it may skip the accumulator
    materialization.  ``compress`` accepts a device array (the hot
    path) or a host ndarray (fallback re-encodes) and always returns
    the wire-ready HOST payload; jitted kernels never donate the
    gradient input — it may alias an in-flight view (pull responses,
    white-box test snapshots), only stage-private state is donated."""

    device = True
    name = "abstract"

    def __init__(self, stage: CodecStage):
        self._stage = stage
        self._jax = stage._jax
        self._jnp = stage._jnp

    @property
    def dense_delta(self) -> bool:
        return False


class DeviceFp16Codec(DeviceCodec):
    name = "fp16"

    def __init__(self, stage):
        super().__init__(stage)
        jnp = self._jnp

        def geomx_fp16_enc(x):
            return x.astype(jnp.float16)

        self._enc = self._jax.jit(geomx_fp16_enc)

    def compress(self, key, arr):
        t0 = time.perf_counter()
        out = self._enc(self._stage._ensure_device(arr))
        self._stage._bill(t0)
        return self._stage._wire(out)

    def decompress(self, key, payload, orig_len):
        return self._stage.decode("fp16", key, payload, orig_len)


class DeviceTwoBitCodec(DeviceCodec):
    """{−t, 0, +t} with device-resident per-key residual; byte-packed
    4 codes/byte exactly like the numpy/native encoders — for identical
    residual state the emitted frame is BYTE-identical (the quantize
    decisions are exact f32 comparisons on IEEE-identical sums)."""

    name = "2bit"

    def __init__(self, stage, threshold: float = 0.5):
        super().__init__(stage)
        self.threshold = float(threshold)
        self._residual: Dict[int, object] = {}
        jnp = self._jnp

        def geomx_2bit_enc(r, g, t):
            r = r + g
            pos = r > t
            neg = r < -t
            q = jnp.where(pos, np.uint8(1),
                          jnp.where(neg, np.uint8(2), np.uint8(0)))
            # untouched elements keep their exact residual bits (a
            # blanket r - t*pos would flip -0.0 to +0.0)
            r = jnp.where(pos, r - t, jnp.where(neg, r + t, r))
            pad = (-q.shape[0]) % 4
            qp = jnp.pad(q, (0, pad)).reshape(-1, 4)
            packed = (qp[:, 0] | (qp[:, 1] << 2) | (qp[:, 2] << 4)
                      | (qp[:, 3] << 6))
            return packed.astype(jnp.uint8), r

        self._enc = self._jax.jit(geomx_2bit_enc, donate_argnums=(0,))

    def compress(self, key, arr):
        t0 = time.perf_counter()
        g = self._stage._ensure_device(arr)
        n = int(g.shape[0])
        r = self._residual.get(key)
        if r is None or int(r.shape[0]) != n:
            r = self._jnp.zeros(n, self._jnp.float32)
        packed, r = self._enc(r, g, np.float32(self.threshold))
        self._residual[key] = r
        self._stage._bill(t0)
        return self._stage._wire(packed)

    def decompress(self, key, payload, orig_len):
        return self._stage.decode("2bit", key, payload, orig_len,
                                  self.threshold)


# elements a block of the support compaction holds: the (k, block) row
# gather stays under the 8 bytes an element the pair sort took, and the
# scatter over n / block entries costs what that gather does (v5e, PR 31)
_SUPPORT_BLOCK = 256


def _topk_support(u, k: int):
    """The exact top-``k`` of ``|u|`` without a sort: ``(idx, mask)``,
    ``idx`` the ``k`` selected positions ascending (int32), ``mask``
    their indicator over ``u``.  The set is the one a stable descending
    sort of ``|u|`` picks: ties at the k-th magnitude go to the lowest
    index, NaN ranks above ``inf``.

    ``|u|`` orders like its bits as int32, so the k-th largest comes from
    a radix search, 4 bits a pass, by counting (reductions over ``n``
    only; one loop body for the 8 passes).  The mask is compacted at the
    scale of ``k`` and ``n / B``: block counts, their prefix sums, each
    output slot's block and its rank there from two ``k``-long arrays
    that mark where a block ends, one row gather of ``(k, B)`` mask bits
    and the position of that rank in the row.  No sort, and no scatter or
    element gather over ``n``: a TPU prices those at 7-11 ns an element.

    What a run pays for beside the device's time is the number of
    operations the program EXECUTES: every one is an event that a
    profiler has to convert and write when it stops (150 an encode cost
    the benchmark 7 s of set-up, 100 cost nothing: PR 32).  So whatever
    is a scalar stays a scalar (the scalar core runs it inside the
    neighbouring operation), and two prefix sums ride on one scan."""
    import jax
    import jax.numpy as jnp

    i32 = jnp.int32
    n, B = u.shape[0], _SUPPORT_BLOCK
    nb = -(-n // B)
    mag = jnp.abs(u)
    bits = jnp.where(mag != mag, np.int32(0x7FFFFFFF),
                     jax.lax.bitcast_convert_type(mag, i32))
    bits = jnp.pad(bits, (0, nb * B - n), constant_values=-1).reshape(nb, B)

    def radix_pass(i, carry):
        # t: the k-th largest's bits above this pass's, zeros below;
        # gt: how many are >= t + (16 << s).  XLA fuses the 15 counts
        # into one pass over the tensor (0.13 ms for 16.7M elements on the
        # v5e; a (15, nb, B) comparison summed over its last two axes
        # takes 0.23, ONE reduce with 15 results 0.15: PR 32)
        t, gt = carry
        s = 28 - 4 * i
        counts = []
        for d in range(1, 16):
            cand = t | (np.int32(d) << s)
            count = jnp.sum(bits >= cand, dtype=i32)
            # bit 31 is the sign: a candidate that sets it counts nothing
            counts.append(jnp.where(cand < 0, 0, count) if d > 7 else count)
        digit = sum((c >= k).astype(i32) for c in counts)
        for c in counts:  # the largest count under k belongs to digit + 1
            gt = jnp.maximum(gt, jnp.where(c < k, c, 0))
        return t | (digit << s), gt

    # t: the k-th largest; gt: how many are larger
    t, gt = jax.lax.fori_loop(0, 8, radix_pass, (jnp.zeros((), i32),) * 2)

    # everything above t, and the first k - gt of the ties by index
    tie = bits == t
    c_tie = jnp.sum(tie, axis=1, dtype=i32)
    pre = jax.lax.cumsum(
        jnp.stack([c_tie, jnp.sum(bits > t, axis=1, dtype=i32)]), axis=1)
    p_tie = pre[0]
    b_cut = jnp.sum(p_tie < k - gt, dtype=i32)     # block of the last tie
    left = k - gt - p_tie[b_cut] + c_tie[b_cut]    # ties taken inside it
    cut = b_cut * B + jnp.sum(jax.lax.cumsum(tie[b_cut].astype(i32)) < left,
                              dtype=i32)
    flat = jnp.arange(nb * B, dtype=i32).reshape(nb, B)
    mask = (bits > t) | (tie & (flat <= cut))

    # p[b]: selected up to and with block b, which is where it ends among
    # the output's slots.  A 1 there counts the blocks before a slot, the
    # block's size there sums to the slot its block starts at
    p = pre[1] + jnp.minimum(p_tie, k - gt)
    sizes = jnp.diff(p, prepend=0)
    ends = jnp.zeros(k, i32)
    at = jax.lax.cumsum(
        jnp.stack([ends.at[p[:-1]].add(1, mode="drop"),
                   ends.at[p[:-1]].add(sizes[:-1], mode="drop")]), axis=1)
    blk, rank = at[0], jnp.arange(k, dtype=i32) - at[1]
    # in-row prefix counts on the MXU (0/1 in bf16, f32 sums: exact)
    upto = jnp.dot(mask[blk].astype(jnp.bfloat16),
                   jnp.triu(jnp.ones((B, B), jnp.bfloat16)),
                   preferred_element_type=jnp.float32)
    pos = jnp.sum(upto.astype(i32) <= rank[:, None], axis=1, dtype=i32)
    return blk * B + pos, mask.reshape(-1)[:n]


@functools.lru_cache(maxsize=None)
def _bsc_encoder():
    """The Bi-Sparse encoder, jitted ONCE for the process: it is pure
    (state and momentum are arguments, ``k`` static), so every
    :class:`DeviceBscCodec` calls this one and a tensor length is
    traced, lowered and fetched once however many servers encode it."""
    import jax
    import jax.numpy as jnp

    def enc(v, u, g, m, k):
        v = m * v + g
        u = u + v
        idx, mask = _topk_support(u, k)
        vals = u[idx]
        v = jnp.where(mask, np.float32(0.0), v)
        u = jnp.where(mask, np.float32(0.0), u)
        # the frame leaves the device as int32 WORDS: XLA:TPU lowers
        # a float concatenate to maximum(pad, pad), which flushes the
        # index bits (denormals as f32) next to the halves' seam
        wire = jnp.concatenate([
            jax.lax.bitcast_convert_type(vals, jnp.int32), idx])
        return wire, v, u

    return jax.jit(enc, static_argnums=(4,), donate_argnums=(0, 1))


class DeviceBscCodec(DeviceCodec):
    """DGC-style Bi-Sparse push compressor on device: momentum velocity
    + accumulated mass exactly like :class:`BscCodec`, but the support
    is the exact top-k of |accum| (k = ratio·n, floor 1;
    :func:`_topk_support`, indices ascending) instead of the
    sampled-threshold scan — no host RNG, no full-array host pass,
    deterministic payload size.  The frame is the same ``[f32 values ‖
    int32 indices bit-cast to f32]`` layout, so either family's decoder
    reconstructs it bitwise."""

    name = "bsc"

    def __init__(self, stage, ratio: float = 0.01,
                 momentum: float = 0.9):
        super().__init__(stage)
        self.ratio = float(ratio)
        self.momentum = float(momentum)
        self._velocity: Dict[int, object] = {}
        self._accum: Dict[int, object] = {}
        self._enc = _bsc_encoder()

    def compress(self, key, arr):
        t0 = time.perf_counter()
        g = self._stage._ensure_device(arr)
        n = int(g.shape[0])
        v = self._velocity.get(key)
        u = self._accum.get(key)
        if v is None or int(v.shape[0]) != n:
            # placed as ``g`` is, which is how ``enc`` returns the state:
            # a key's first encode runs the program every later one runs
            v = self._jnp.zeros_like(g)
            u = self._jnp.zeros_like(g)
        k = max(1, int(self.ratio * n))
        wire, v, u = self._enc(v, u, g, np.float32(self.momentum), k)
        self._velocity[key] = v
        self._accum[key] = u
        self._stage._bill(t0)
        return self._stage._wire(wire).view(np.float32)

    def decompress(self, key, payload, orig_len):
        return self._stage.decode("bsc", key, payload, orig_len)

    @property
    def dense_delta(self) -> bool:
        return True


def _mpq_base():
    from geomx_tpu.compression.codecs import MpqSelector

    return MpqSelector


class DeviceMpqSelector(_mpq_base()):
    """Mixed-precision selector over the DEVICE family: same
    ``size_bound`` split and pick counters as the numpy
    :class:`MpqSelector` (it subclasses it, so the server's
    ``isinstance`` dispatch and QUERY_STATS counters keep working),
    with the two rungs swapped for their device implementations."""

    device = True

    def __init__(self, stage, size_bound: int = 200_000,
                 ratio: float = 0.01, momentum: float = 0.9):
        super().__init__(size_bound=size_bound, ratio=ratio,
                         momentum=momentum)
        self.fp16 = DeviceFp16Codec(stage)
        self.bsc = DeviceBscCodec(stage, ratio=ratio, momentum=momentum)
