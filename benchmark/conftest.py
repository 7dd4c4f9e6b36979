"""The benchmark's own tests run on the CPU: 4 virtual devices for the
dp2x2 layout, set before any test module imports jax."""

import os
import sys
from pathlib import Path

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
