"""The retry policy of tests/mesh_harness.run_py, on children that import
no jax: a failing or hanging child runs once, a child killed by a signal
runs a second time."""
import time

import pytest

from mesh_harness import run_py


def _runs(log) -> int:
    return len(log.read_text().splitlines()) if log.exists() else 0


def test_failing_child_runs_once_and_reports_its_stderr(tmp_path):
    log = tmp_path / "runs.log"
    with pytest.raises(AssertionError) as err:
        run_py(f"""
            import sys
            with open({str(log)!r}, "a") as f:
                f.write("run\\n")
            sys.stderr.write("CHILD_FAILED_ON_PURPOSE\\n")
            sys.exit(1)
        """, devices=1)
    assert _runs(log) == 1
    assert "exit 1" in str(err.value)
    assert "CHILD_FAILED_ON_PURPOSE" in str(err.value)


def test_hanging_child_fails_at_its_timeout_and_runs_once(tmp_path):
    log = tmp_path / "runs.log"
    start = time.monotonic()
    with pytest.raises(AssertionError, match="timeout after 2s"):
        run_py(f"""
            import time
            with open({str(log)!r}, "a") as f:
                f.write("run\\n")
            time.sleep(60)
        """, devices=1, timeout=2)
    assert time.monotonic() - start < 10
    assert _runs(log) == 1


def test_child_killed_by_a_signal_is_retried_once(tmp_path):
    log, marker = tmp_path / "runs.log", tmp_path / "killed_once"
    out = run_py(f"""
        import os, signal
        with open({str(log)!r}, "a") as f:
            f.write("run\\n")
        if not os.path.exists({str(marker)!r}):
            open({str(marker)!r}, "w").close()
            os.kill(os.getpid(), signal.SIGKILL)
        print("SECOND_RUN_OK")
    """, devices=1)
    assert out.strip() == "SECOND_RUN_OK"
    assert _runs(log) == 2
