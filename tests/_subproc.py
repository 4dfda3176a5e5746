"""Run test snippets in a subprocess with N simulated host devices.

`--xla_force_host_platform_device_count` must be set before jax
initializes, and the pytest process has jax imported already — so every
multi-device test ships its body to a fresh interpreter and reads one
JSON line back. Keeping this per-test (instead of forcing the whole suite
onto a simulated mesh via conftest) leaves the tier-1 suite's jax setup
untouched.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, "src"))


def run_with_devices(code: str, n_devices: int = 4,
                     timeout: int = 900) -> dict:
    """Execute `code` under `n_devices` simulated host devices; the code
    must print a JSON object as its last stdout line."""
    env = dict(os.environ)
    # A CPU virtual-device simulation; the chip's four-device path is
    # `chip_smoke.py --chips 4`.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout)
    assert out.returncode == 0, (
        f"subprocess failed (rc={out.returncode})\n"
        f"--- stdout ---\n{out.stdout}\n--- stderr ---\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])
