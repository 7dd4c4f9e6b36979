"""Acceptance matrix over real OS processes (VERDICT r2 item 6).

The reference's de-facto acceptance suite is its script matrix
(`/root/reference/scripts/cpu/run_tsengine.sh`, `run_p3.sh`,
`run_hfa.sh`, `run_mpq.sh` ...): launch role processes, train, eyeball
the logs.  These tests do the same through ``geomx_tpu.launch``
subprocesses over real TCP — and then assert the *feature's mechanism
fired*, not just that training finished:

- TSEngine  → workers received overlay relays (``ts_relays=``)
- P3        → the van's priority queue reordered sends
  (``pq_overtakes=``) while the staged loop trained
- HFA       → the K2 gate kept key-rounds party-local
  (``hfa_gated_key_rounds=``)
- MPQ       → the size split sent big tensors BSC and small ones FP16
  (``mpq_bsc=``/``mpq_fp16=``)
- ESync     → heterogeneous workers received *different* local-step
  assignments and the reach-server spread shrank (``esync_rounds=``)
- DGT mode 3 → unimportant chunks were 4-bit requantized on the wire and
  decoded on the far tier (``dgt4_tx=``/``dgt4_rx=``)

DGT mode 1 (real lossy UDP) and vanilla topologies are covered the same
way in test_tcp.py; mid-run SIGKILL + relaunch of the global server is
test_recovery.py::test_global_server_crash_restart_midtraining_resumes_checkpoint.
"""

import os
import re
import subprocess
import sys
import time

import pytest

from geomx_tpu.core.config import Topology

from tests.test_tcp import free_base_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launch_matrix(parties, workers, extra_args, extra_env=None,
                   steps=3, timeout=180):
    """Run one topology as real processes; returns {role: output}."""
    topo = Topology(num_parties=parties, workers_per_party=workers)
    base = free_base_port()
    env = dict(os.environ)
    env["JAX_PLATFORM_NAME"] = "cpu"
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    roles = [str(n) for n in topo.all_nodes()]
    procs = {}
    try:
        for r in roles:
            procs[r] = subprocess.Popen(
                [sys.executable, "-m", "geomx_tpu.launch", "--role", r,
                 "--parties", str(parties), "--workers", str(workers),
                 "--base-port", str(base), "--steps", str(steps)]
                + extra_args,
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs.values()):
                break
            time.sleep(0.5)
        outputs = {}
        for r, p in procs.items():
            if p.poll() is None:
                p.kill()
            outputs[r] = p.communicate()[0]
        for r, p in procs.items():
            assert p.returncode == 0, \
                f"{r} rc={p.returncode}: {outputs[r][-800:]}"
        for w in topo.workers(0):
            assert f"steps={steps}" in outputs[str(w)], outputs[str(w)]
        return topo, outputs
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()


def _stat(outputs, pattern):
    """Sum an integer exit-stat (e.g. r"ts_relays=(\\d+)") over roles."""
    total = 0
    for out in outputs.values():
        for m in re.finditer(pattern, out):
            total += int(m.group(1))
    return total


@pytest.mark.slow
def test_tsengine_topology_relays_over_real_sockets():
    """ref: scripts/cpu/run_tsengine.sh — 1 party x 2 workers so the
    intra-party overlay has someone to relay to."""
    _topo, outputs = _launch_matrix(1, 2, ["--tsengine"])
    relays = _stat(outputs, r"ts_relays=(\d+)")
    assert relays > 0, f"overlay never relayed: {outputs}"


@pytest.mark.slow
def test_p3_overlap_topology_priority_inversions():
    """ref: scripts/cpu/run_p3.sh — staged loop pushes deepest-first, so
    shallow stages' pushes must overtake queued deep slices."""
    _topo, outputs = _launch_matrix(1, 1, ["--p3"])
    overtakes = _stat(outputs, r"pq_overtakes=(\d+)")
    assert overtakes > 0, \
        f"priority queue never reordered: {outputs}"


@pytest.mark.slow
def test_hfa_topology_k2_gating():
    """ref: scripts/cpu/run_hfa.sh — with K2=2 half the rounds stay
    party-local (the server's milestone gate)."""
    _topo, outputs = _launch_matrix(
        1, 1, ["--hfa"], extra_env={"GEOMX_HFA_K2": "2"}, steps=4)
    gated = _stat(outputs, r"hfa_gated_key_rounds=(\d+)")
    assert gated > 0, f"K2 gate never fired: {outputs}"


@pytest.mark.slow
def test_esync_topology_heterogeneous_assignments():
    """ref: README.md:45 (ESync, planned-but-unintegrated upstream) —
    one party, two workers, rank 1 slowed 60 ms/step.  The state server
    must hand the fast worker MORE local steps than the slow one, and
    the party's reach-server spread must shrink once the planner has
    samples."""
    # 150 ms injected slowdown: the margin must survive a fully loaded
    # single-core host (under `pytest tests/` the fast worker's natural
    # step time inflates toward ~50 ms, and a 60 ms injection left the
    # per-step ratio assertion within noise — observed flake)
    _topo, outputs = _launch_matrix(
        1, 2, ["--esync"], steps=6,
        extra_env={"GEOMX_TEST_STEP_SLEEP_MS": '{"worker:1@p0": 150}'})
    rounds = {}  # node -> [(assigned_steps, reach_s), ...]
    for node, out in outputs.items():
        m = re.search(r"esync_rounds=(\[.*\])", out)
        if m:
            rounds[node] = eval(m.group(1))  # noqa: S307 — our own repr
    assert set(rounds) == {"worker:0@p0", "worker:1@p0"}, outputs
    fast, slow = rounds["worker:0@p0"], rounds["worker:1@p0"]
    # the planner hands the fast worker MORE local steps than the slow
    # one over the planned tail (round 0 runs before any samples exist)
    fast_steps = sum(r[0] for r in fast[1:])
    slow_steps = sum(r[0] for r in slow[1:])
    assert fast_steps > slow_steps, (fast, slow)
    # reach-server spread shrinks: in the last round the two workers
    # reach the server within 2x of each other even though their
    # PER-STEP times differ by far more — i.e. the fast worker's extra
    # local steps absorbed the heterogeneity instead of barrier idling.
    # (Absolute |fast-slow| of round 0 is useless as a baseline: both
    # pay one-off jit compile there.)
    f_ran, f_reach = fast[-1]
    s_ran, s_reach = slow[-1]
    per_step_ratio = (s_reach / max(s_ran, 1)) / max(
        f_reach / max(f_ran, 1), 1e-9)
    reach_ratio = max(f_reach, s_reach) / max(min(f_reach, s_reach), 1e-9)
    assert per_step_ratio > 2.0, (fast, slow)   # heterogeneity was real
    assert reach_ratio < 2.0, (fast, slow)      # ...and got balanced


@pytest.mark.slow
def test_dgt_mode3_topology_4bit_requant():
    """ref: scripts/cpu/run_dgt.sh + ENABLE_DGT=3 (van.cc:750-824 TCP +
    4-bit requant) — unimportant WAN chunks must actually ride the wire
    4-bit-requantized and be decoded on the global tier."""
    _topo, outputs = _launch_matrix(1, 1, ["--dgt", "3"])
    tx = _stat(outputs, r"dgt4_tx=(\d+)")
    rx = _stat(outputs, r"dgt4_rx=(\d+)")
    assert tx > 0, f"no chunk was 4-bit requantized: {outputs}"
    assert rx > 0, f"no 4-bit chunk was decoded: {outputs}"


@pytest.mark.slow
def test_lm_flagship_tcp_topology():
    """VERDICT r3 item 5: the flagship transformer (>=10 M params)
    through the real-process TCP topology with MPQ compression —
    tokens/s reported, WAN bytes accounted, the size split active."""
    _topo, outputs = _launch_matrix(
        1, 1, ["--workload", "lm", "--compression", "mpq", "--batch", "4"],
        steps=3, timeout=420,
        # size bound tuned to the flagship's leaf sizes (the reference's
        # MXNET_KVSTORE_SIZE_LOWER_BOUND knob): 147k-element qkv/wo
        # belong on BSC, not fp16
        extra_env={"GEOMX_MPQ_SIZE_BOUND": "100000"})
    worker_out = outputs["worker:0@p0"]
    m = re.search(r"n_params=(\d+)", worker_out)
    assert m and int(m.group(1)) >= 10_000_000, worker_out
    assert re.search(r"tokens_per_sec=[\d.]+", worker_out), worker_out
    # MPQ actually split (big tensors BSC, small fp16) on the WAN hop
    assert _stat(outputs, r"mpq_bsc=(\d+)") > 0, outputs
    assert _stat(outputs, r"mpq_fp16=(\d+)") > 0, outputs
    # and the WAN ledger recorded the compressed traffic
    assert _stat(outputs, r"wan_tx=(\d+)") > 0, outputs


@pytest.mark.slow
def test_lm_moe_flagship_tcp_topology():
    """EP through the real PS stack: the flagship LM with top-k routed
    MoE layers (expert gradients are ordinary dense leaves to the
    kvstore) trains through the process topology.  Smaller dims than
    the dense flagship — the point is the MoE param/grad path over real
    sockets, not the 10M size (covered by the dense test)."""
    _topo, outputs = _launch_matrix(
        1, 1, ["--workload", "lm", "--compression", "mpq", "--batch", "4"],
        steps=3, timeout=420,
        extra_env={"GEOMX_LM_MOE_EXPERTS": "4",
                   "GEOMX_LM_DMODEL": "128", "GEOMX_LM_HEADS": "4",
                   "GEOMX_LM_DFF": "512", "GEOMX_LM_VOCAB": "1024",
                   "GEOMX_MPQ_SIZE_BOUND": "100000"})
    worker_out = outputs["worker:0@p0"]
    assert re.search(r"tokens_per_sec=[\d.]+", worker_out), worker_out
    # the experts must actually exist in the pushed set: at these dims
    # the MoE model is 1,722,496 params vs ~935k for its dense twin
    # (ln params included — a bound below the dense count would pass
    # even if GEOMX_LM_MOE_EXPERTS were silently ignored)
    m = re.search(r"n_params=(\d+)", worker_out)
    assert m and int(m.group(1)) > 1_500_000, worker_out


@pytest.mark.slow
def test_mpq_topology_size_split():
    """ref: scripts/cpu/run_mpq.sh — tensors >= the size bound must go
    BSC while small ones go FP16.  The launcher's demo CNN is tiny, so
    the bound is lowered (the reference tunes the same knob,
    MXNET_KVSTORE_SIZE_LOWER_BOUND) to put its dense kernels above it
    and its biases below."""
    _topo, outputs = _launch_matrix(
        1, 1, ["--compression", "mpq"],
        extra_env={"GEOMX_MPQ_SIZE_BOUND": "2000"})
    bsc = _stat(outputs, r"mpq_bsc=(\d+)")
    fp16 = _stat(outputs, r"mpq_fp16=(\d+)")
    assert bsc > 0 and fp16 > 0, \
        f"MPQ split did not exercise both codecs: {outputs}"
