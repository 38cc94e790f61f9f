"""The four-worker mesh cell at a tiny size on four host devices (in a
child process, since the device count is fixed when JAX starts): the check
fails when the exchange between workers is left out."""
import os
import subprocess
import sys

from bench.lib import cells

CHILD = """
import sys
sys.path[:0] = {paths!r}
from rehearsal import run, tiny
r = run(tiny("mamba2-130m.4chip-gather-byrd-saga", chips=4),
        "reference:no_exchange")
assert r["correct"] is False, r["checks"]
assert r["device"]["count"] == 4
print("ok")
"""


def test_four_workers_fail_without_the_exchange():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    paths = [here, cells.REPO, os.path.join(cells.REPO, "src")]
    proc = subprocess.run([sys.executable, "-c", CHILD.format(paths=paths)],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0 and proc.stdout.strip().endswith("ok"), \
        proc.stderr[-3000:]
