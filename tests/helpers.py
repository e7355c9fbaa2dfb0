"""Test helpers: multi-device subprocess runner.

jax locks the host device count at first init, and the main pytest
process must see ONE device (smoke tests). Anything needing a mesh runs
in a child process with XLA_FLAGS set before jax imports.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_multidevice(code: str, devices: int = 8, timeout: int = 900) -> str:
    """Run ``code`` in a child python with N host devices; returns stdout.
    Raises on nonzero exit (stderr tail included)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout[-3000:]}\n"
            f"--- stderr ---\n{proc.stderr[-3000:]}")
    return proc.stdout
