"""Shared subprocess harness for multi-device tests.

Multi-device tests need >1 host device, and jax locks the device count at
first initialization, so each test runs in a fresh subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=<devices> while the main
pytest process keeps its default single device.  Inline test programs should
go through ``repro.compat`` (make_mesh / use_mesh / shard_map) so they run on
every supported jax version.

A child that exits non-zero or outlives ``timeout`` fails the test at once:
the programs are seeded, so a failure repeats, and a hang repeated is still
a hang.  The one retry is for what a loaded box does to a launch: a child
killed by a signal (e.g. the OOM killer) or one that failed to spawn.  The
assertion message carries the child's stderr tail, so the real error lands
in the pytest report.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 240,
           extra_env: dict | None = None) -> str:
    """Run ``code`` (dedented) in a subprocess with ``devices`` forced host
    devices and PYTHONPATH=src; assert exit 0 and return stdout.

    ``timeout`` (seconds) is the test's own limit.  Only a child killed by
    a signal or a failed spawn is run a second time."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # One persistent compile cache for every child, however short the
    # compile: the inline programs compile the same small programs many
    # times over (a fresh closure or an eager op each time), within a child
    # and across children, and a hit is a file read, not an XLA compile.
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    if extra_env:
        env.update(extra_env)
    program = textwrap.dedent(code)
    outcomes: list[str] = []
    err = ""
    for _ in range(2):
        try:
            out = subprocess.run([sys.executable, "-c", program],
                                 capture_output=True, text=True,
                                 timeout=timeout, env=env)
        except subprocess.TimeoutExpired as e:
            outcomes.append(f"timeout after {timeout}s")
            err = (e.stderr or b"").decode(errors="replace")
            break
        except OSError as e:                  # failed to spawn: retry once
            outcomes.append(f"spawn failed: {e}")
            continue
        if out.returncode == 0:
            return out.stdout
        outcomes.append(f"exit {out.returncode}")
        err = out.stderr
        if out.returncode > 0:                # raised or sys.exit: no retry
            break
    raise AssertionError(f"subprocess failed ({'; '.join(outcomes)})"
                         f"\nstderr tail:\n{err[-3000:]}")
