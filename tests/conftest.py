"""Shared fixtures. NOTE: no XLA_FLAGS here — tests see the real single CPU
device (the 512-device override is exclusively the dry-run's, per the
assignment). Multi-device sharding tests spawn subprocesses that set their
own XLA_FLAGS before importing jax."""
import os
import sys
import subprocess
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"

# sys.path comes from pyproject's `pythonpath = ["src", "."]` (or the
# tier-1 command's PYTHONPATH=src).


@pytest.fixture(scope="session")
def repo_root() -> Path:
    return REPO


def _as_text(s) -> str:
    if s is None:
        return "<none captured>"
    if isinstance(s, bytes):
        return s.decode(errors="replace")
    return s


def run_subprocess_devices(code: str, n_devices: int = 8, timeout: int = 600):
    """Run python code in a subprocess with n host devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = str(SRC)
    try:
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # subprocess.run kills the child on timeout, but TimeoutExpired
        # would otherwise escape with no captured output — surface the
        # partial stdout/stderr so a hung multi-device test is diagnosable
        # in CI instead of a bare timeout traceback
        raise AssertionError(
            f"subprocess timed out after {timeout}s (child killed):\n"
            f"PARTIAL STDOUT:\n{_as_text(e.stdout)}\n"
            f"PARTIAL STDERR:\n{_as_text(e.stderr)}") from e
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}")
    return out.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)
