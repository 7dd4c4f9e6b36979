"""The Tracer's spans on the profiler's clock (ISSUE 26).

A sampled round under a ``jax.profiler`` session leaves every span as a
``geomx:<node>:<name>`` event, with its causal ids and the site's
arguments, on the host plane of the trace the device's operations are
in; ``handle`` carries the message's wait as ``queued_us``; the merge
backend's sites are spans under the server that built it and feed the
operator gauges from the same clock pair; and every jitted server
program lowers to a module with a name of its own.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

from geomx_tpu.core.config import Config, Topology
from geomx_tpu.kvstore import Simulation
from geomx_tpu.ps.customer import Customer
from geomx_tpu.trace.recorder import ANNOTATION_PREFIX
from geomx_tpu.transport.message import Domain, Message


def _cfg(**kw):
    kw.setdefault("trace_sample_every", 1)
    return Config(topology=Topology(num_parties=2, workers_per_party=1),
                  merge_backend="jax", **kw)


def _events(trace_dir):
    """[(plane, thread line index, name, start_ns, end_ns, args)] of the
    program's annotations in the newest profile under ``trace_dir``."""
    from jax.profiler import ProfileData

    f = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(f).planes:
        for i, line in enumerate(plane.lines):
            out += [(plane.name, i, e.name, e.start_ns,
                     e.start_ns + e.duration_ns, dict(e.stats))
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)]
    return out


SLEEP_S = 0.05


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two FSA rounds of a 2-party Simulation on the jax backend, and two
    messages to a slow customer, all under one profiler session."""
    import jax

    from geomx_tpu.training import _edge_to_host

    trace_dir = str(tmp_path_factory.mktemp("profile"))
    sim = Simulation(_cfg())
    handled = threading.Semaphore(0)
    try:
        ws = sim.all_workers()
        ws[0].set_optimizer({"type": "adam", "lr": 0.1})
        for w in ws:
            w.init(0, np.zeros(4096, np.float32))
        server = sim.local_servers[0]

        def slow(msg):
            time.sleep(SLEEP_S)
            handled.release()

        Customer(99, 0, slow, server.po, owns_app=True)
        jax.profiler.start_trace(trace_dir)
        for r in range(2):
            for w in ws:
                with w.trace_round(r):
                    g = jax.numpy.full(4096, 0.1, jax.numpy.float32)
                    w.push(0, _edge_to_host(w, 0, g, 0.5))
                    w.pull(0, lambda t, a: None)
            for w in ws:
                w.wait_all()
        with ws[0].trace_round(2):
            for _ in range(2):   # the second waits for the first's sleep
                ws[0].po.van.send(Message(
                    recipient=server.po.node, domain=Domain.LOCAL,
                    app_id=99, customer_id=0, request=True))
        for _ in range(2):
            assert handled.acquire(timeout=30)
        time.sleep(0.05)   # the second handle span's exit
        jax.profiler.stop_trace()
        stats = [s.stats() for s in sim.local_servers + sim.global_servers]
    finally:
        sim.shutdown()
    return _events(trace_dir), stats


def test_spans_are_on_a_host_plane_with_their_arguments(traced):
    events, _ = traced
    assert events and all(p.startswith("/host:") for p, *_ in events)
    by_name = {}
    for _, _, name, _, _, args in events:
        node, _, span = name[len(ANNOTATION_PREFIX):].rpartition(":")
        by_name.setdefault(span, []).append((node, args))
    # every tier's sites, the backend's under the server that built it
    for span, role in [("round", "worker"), ("edge.d2h", "worker"),
                       ("edge.scale", "worker"), ("worker.push", "worker"),
                       ("worker.pull", "worker"),
                       ("worker.pull_decode", "worker"),
                       ("handle", "server"), ("local.push", "server"),
                       ("local.close", "server"), ("be.h2d", "server"),
                       ("be.d2h", "server"), ("local.pull_down", "server"),
                       ("handle", "global_server"),
                       ("global.push", "global_server"),
                       ("global.close", "global_server"),
                       ("global.opt", "global_server"),
                       ("be.add", "global_server"),
                       ("opt.step", "global_server"),
                       ("lan.send", "worker"), ("wan.send", "server")]:
        nodes = {n.split(":")[0] for n, _ in by_name.get(span, [])}
        assert role in nodes, (span, sorted(by_name))
    for span in ("edge.d2h", "worker.push", "be.h2d", "be.add", "opt.step",
                 "local.push", "global.push"):
        for _, args in by_name[span]:
            assert args["key"] == 0 and args["nbytes"] >= 4096 * 4, \
                (span, args)
            assert args["trace_id"] in (1, 2) and args["span"] > 0
    assert all(a["contributors"] == 2 for _, a in by_name["global.close"])
    assert all(a["contributors"] == 1 for _, a in by_name["local.close"])
    ops = {a["op"] for _, a in by_name["handle"]}
    assert {"push", "pull", "ctrl"} <= ops


def test_handle_reports_how_long_the_message_waited(traced):
    events, _ = traced
    waits = sorted(a["queued_us"] for _, _, name, _, _, a in events
                   if name.endswith(":handle") and a.get("cmd") == 0
                   and a.get("op") == "ctrl" and a["trace_id"] == 3)
    assert len(waits) == 2
    # the second message sat in the customer's queue for the first's sleep
    assert waits[1] >= SLEEP_S * 1e6 * 0.9 and waits[0] < waits[1]


def test_a_child_lies_inside_its_parent(traced):
    events, _ = traced
    # (an instant is an empty annotation under its MESSAGE's span id:
    # wan.send / wan.recv / lan.send are edges, not intervals)
    spans = {a["span"]: (plane, line, name, t0, t1)
             for plane, line, name, t0, t1, a in events
             if not name.endswith((".send", ".recv"))}
    nested = 0
    for plane, line, name, t0, t1, a in events:
        parent = spans.get(a["parent"])
        if parent is None or parent[:2] != (plane, line):
            continue   # a message's id, or a parent on another thread
        assert parent[3] <= t0 and t1 <= parent[4], (name, parent[2])
        nested += 1
    assert nested > 20


def test_the_gauges_are_fed_from_the_spans_clock(traced):
    _, stats = traced
    for st in stats:
        assert st["merge_backend"] == "jax" and st["merge_device_ms"] > 0
    assert stats[-1]["opt_device_ms"] > 0       # the global server


def test_threaded_merge_lanes_open_a_lane_span_with_their_wait():
    """Where the lanes are threads the bound item carries its own wait,
    submit to start; the reactor default (inline lanes) opens none."""
    names = {}
    for light in (False, True):
        sim = Simulation(Config(
            topology=Topology(num_parties=1, workers_per_party=1),
            trace_sample_every=1, server_shards=3), lightweight=light)
        try:
            w = sim.all_workers()[0]
            w.set_optimizer({"type": "sgd", "lr": 0.1})
            w.init(0, np.zeros(64, np.float32))
            with w.trace_round(0):
                w.push(0, np.ones(64, np.float32))
                w.pull(0, lambda t, a: None)
            w.wait_all()
            sim.flush_traces()
            evs = sim.trace_collector.merged_events()
            names[light] = {e["name"] for e in evs}
            lanes = [e for e in evs if e["name"] == "lane"]
            assert bool(lanes) == (not light)
            for e in lanes:
                assert e["args"]["queued_us"] >= 0 and "key" in e["args"]
                assert e["pid"].split(":")[0] in ("server", "global_server")
        finally:
            sim.shutdown()
    assert names[False] - {"lane"} == names[True]


def _lowered(program, *args):
    return program.lower(*args).as_text()


def _programs():
    """(the module name expected, a thunk that lowers the program)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from geomx_tpu.kvstore.jax_backend import (DeviceAdam, DeviceNag,
                                               DeviceSgd, JaxBackend)

    def backend(**kw):
        return JaxBackend(Config(topology=Topology(), merge_backend="jax",
                                 **kw))

    x = jnp.ones(8, jnp.float32)
    s = np.float32(0.5)
    yield "geomx_merge_add", lambda: _lowered(backend()._add, x, x)
    yield "geomx_merge_scale", lambda: _lowered(backend()._scale, x, s)
    yield "geomx_screen", lambda: _lowered(backend()._screen, x, s)

    def reducer(ef, **kw):
        be = backend(**kw)
        arr = jax.ShapeDtypeStruct(
            (2, 1 << 16), jnp.float32,
            sharding=NamedSharding(be._submesh(2), P("party")))
        return _lowered(be._reducer(2, 1 << 16, ef), *([arr] * (1 + ef)))

    yield "geomx_mesh_reduce", lambda: reducer(False)
    yield "geomx_mesh_reduce", lambda: reducer(False, merge_quantized=True,
                                               merge_residual=False)
    yield "geomx_mesh_reduce", lambda: reducer(True, merge_quantized=True)
    yield "geomx_adam", lambda: _lowered(
        DeviceAdam(backend(), {"type": "adam"})._upd, x, x, x, x,
        *([s] * 10))
    yield "geomx_sgd_plain", lambda: _lowered(
        DeviceSgd(backend(), {"type": "sgd"})._upd, x, x, s)
    yield "geomx_sgd", lambda: _lowered(
        DeviceSgd(backend(), {"type": "sgd", "wd": 0.1})._upd, x, x, s, s, s)
    yield "geomx_sgd", lambda: _lowered(
        DeviceSgd(backend(), {"type": "sgd", "momentum": 0.9})._upd,
        x, x, x, s, s, s, s)
    yield "geomx_nag", lambda: _lowered(
        DeviceNag(backend(), {"type": "nag"})._upd, x, x, x, s, s, s, s)

    def stage():
        cfg = Config(topology=Topology(), merge_backend="jax")
        return JaxBackend(cfg).make_codec_stage(cfg)

    h = jnp.ones(8, jnp.float16)
    b = jnp.ones(2, jnp.uint8)
    idx = jnp.zeros(2, jnp.int32)
    yield "geomx_fp16_dec", lambda: _lowered(stage()._dec_f16, h)
    yield "geomx_2bit_dec", lambda: _lowered(stage()._dec_2bit, b, s, 8)
    yield "geomx_fp16_enc", lambda: _lowered(
        stage().make_push_codec({"type": "fp16"})._enc, x)
    yield "geomx_2bit_enc", lambda: _lowered(
        stage().make_push_codec({"type": "2bit"})._enc, x, x, s)
    # the two the benchmark's codec_dev_ms_per_step matches on keep
    # their names
    yield "_scatter", lambda: _lowered(stage()._dec_bsc, x[:2], idx, 8)
    yield "enc", lambda: _lowered(
        stage().make_push_codec({"type": "bsc"})._enc, x, x, x, s, 1)


_PROGRAM_IDS = ["merge_add", "merge_scale", "screen", "mesh_reduce",
                "mesh_reduce_quantized", "mesh_reduce_quantized_ef", "adam",
                "sgd_plain", "sgd_wd", "sgd_momentum", "nag", "fp16_dec",
                "2bit_dec", "fp16_enc", "2bit_enc", "bsc_scatter", "bsc_enc"]


@pytest.mark.parametrize("i", range(len(_PROGRAM_IDS)), ids=_PROGRAM_IDS)
def test_a_server_program_lowers_to_a_module_with_its_name(i):
    name, lower = list(_programs())[i]
    assert f"module @jit_{name} " in lower(), name
