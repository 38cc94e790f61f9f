"""CPU rehearsals of whole runs at tiny sizes: the harness's loop and the
check, which has to fail the control and each fault that a cell can have
(a state left unchanged, half of the batch left out).
"""
import pytest

from rehearsal import run, tiny

NAME = "logreg-ijcnn1.byrd-saga-signflip"

IMPLS = [
    ("program", True),
    ("reference:control", False),
    ("reference:unchanged", False),
    ("reference:half_batch", False),
]


@pytest.mark.parametrize("impl,correct", IMPLS)
def test_a_run_is_correct_only_when_the_timed_path_is(impl, correct):
    cell = tiny(NAME)
    r = run(cell, impl)
    assert r["correct"] is correct, r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert r["attempted"] > 0
