"""Helpers for tests that need multiple (host CPU) devices.

jax locks the device count at first init, so multi-device tests run in a
subprocess with XLA_FLAGS set. Scripts print their assertions; a
non-zero exit fails the test with the captured output.
"""
from __future__ import annotations

import os
import subprocess
import sys

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multidevice(script: str, n_devices: int = 8,
                    timeout: int = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout
