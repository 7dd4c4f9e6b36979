"""Test configuration: force an 8-device virtual CPU mesh.

The tests run where there is no accelerator, and a chip belongs to one
process anyway, so every sharding test runs on 8 virtual CPU devices and
every pallas kernel under TPU interpret mode.  Both settings are read
when jax first initializes a backend (``JAX_PLATFORMS`` on import,
``XLA_FLAGS`` at first backend use), so they are pinned here, before
any test module imports jax.  The uninterpreted kernels and the real
device path are checked by ``chip_smoke.py`` on the chip.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# Thread names that are allowed to outlive a Simulation: process-
# lifetime shared pools (fixed-size, O(1) in node count, by design
# never torn down) plus interpreter/jax internals.  Per-node loops
# (van-recv/van-send/van-resend/ts-dissem/heartbeat/monitors) are NOT
# listed: under the reactor default they are timer-wheel entries, and
# under GEOMX_TRANSPORT=threads they must stop with their Simulation.
_PROCESS_LIFETIME_THREADS = (
    "geomx-reactor",   # shared reactor loops + handler pool
    "geomx-codec",     # shared codec pool (kvstore/common.py)
    "axpy-calibrate",  # eager native-merge calibration
    "fabric-serial",   # deterministic-mode dispatcher (shut by fabric)
    "pydevd", "ThreadPoolExecutor",  # debugger / stdlib internals
)


def _leaked_threads(before):
    import threading

    out = []
    for t in threading.enumerate():
        if t in before or not t.is_alive():
            continue
        if any(t.name.startswith(p) for p in _PROCESS_LIFETIME_THREADS):
            continue
        out.append(t)
    return out


@pytest.fixture
def thread_leak_guard():
    """Snapshot ``threading.enumerate()`` before the test body and
    assert the process returns to baseline after it (ISSUE 12
    satellite): per-connection recv threads, per-node van/customer/
    timer threads and monitor loops must all be gone once the
    Simulation/fabric shuts down.  Stop-flagged sleep loops exit within
    their interval, so the check polls briefly before failing."""
    import threading
    import time

    before = set(threading.enumerate())
    yield
    deadline = time.monotonic() + 15.0
    leaked = _leaked_threads(before)
    while leaked and time.monotonic() < deadline:
        time.sleep(0.05)
        leaked = _leaked_threads(before)
    assert not leaked, (
        "threads leaked past shutdown: "
        + ", ".join(sorted(t.name for t in leaked)))


@pytest.fixture(autouse=True)
def _fresh_system_metrics():
    """Every test starts from an empty system-metrics registry.

    The registry is process-global by design (readers and writers need
    no setup ordering), so counters bleed across sequential Simulations
    in one pytest run — historically forcing every test to assert via
    snapshot deltas.  Resetting between tests gives each a clean slate;
    metric handles already held by a previous test's (stopped) objects
    keep working, they just stop being visible to new snapshots.
    """
    yield
    from geomx_tpu.utils.metrics import reset_system_metrics

    reset_system_metrics()
