"""The trace reduction on a trace recorded on one TPU v5e: 100 traced
rounds of ``logreg-ijcnn1.byrd-saga-signflip`` (seed 13), with the compiled
step's HLO text.  The numbers are the ones that run printed on the chip."""
import gzip
import os

import pytest

from bench.lib import cells, harness, hlo, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def reduced():
    with gzip.open(os.path.join(DATA, "logreg-signflip.hlo.txt.gz"), "rt") as f:
        text = f.read()
    ops, spans = trace.read_xplane(
        os.path.join(DATA, "logreg-signflip.xplane.pb.gz"))
    return trace.reduce(ops, spans, hlo.op_phases(text),
                        hlo.module_name(text), 1)


def test_window_busy_time_and_rounds(reduced):
    assert reduced["rounds"] == 100
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.037723801)
    assert reduced["busy_s"] == pytest.approx(0.007524331)
    assert reduced["collective_s"] == 0.0


def test_per_layer_metrics_as_the_chip_printed_them(reduced):
    cell = cells.load_cell("logreg-ijcnn1.byrd-saga-signflip")
    got = {k: v["value"] for k, v in harness.per_layer(
        cell, reduced, None, {"bf16_flops": 197e12}).items()}
    assert got == pytest.approx({
        "device_idle_pct.sim": 80.05415467015108,
        "round_device_ms.sim": 0.07524331,
        "grads_ms.sim": 0.00086709,
        "vr_ms.sim": 0.00510795,
        "aggregate_ms.sim": 0.01574351})


def test_phases_add_up_to_the_step(reduced):
    # Every device op of the window is in one phase, a while loop's span
    # not counted twice, so the phases add up to no more than the busy time
    # (the rest is the loops' own control between their bodies' ops).
    total = sum(reduced["phase_s"].values())
    assert 0.8 * reduced["busy_s"] < total <= reduced["busy_s"] * (1 + 1e-9)
    assert reduced["idle_gaps"] and reduced["device_ops"]
    assert len(reduced["device_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10


def test_union_and_exposure_arithmetic():
    ms = 1_000_000
    op = trace.OPS_LINE
    ops = {0: [(0, 4 * ms, "fusion.1", "m", op),
               # a while loop: its span encloses its body's operations
               (int(4.5 * ms), int(9.5 * ms), "while.1", "m", op),
               (5 * ms, 6 * ms, "fusion.2", "m", op),
               (6 * ms, 9 * ms, "all-reduce.3", "m", op),
               (1 * ms, 5 * ms, "all-gather-start.4", "m", trace.ASYNC_LINE)]}
    spans = [(0, 1 * ms, "bench.round"), (9 * ms, 10 * ms, "bench.block")]
    phases = {"fusion.1": "grads", "fusion.2": "vr", "all-reduce.3": "aggregate",
              "while.1": "aggregate"}
    red = trace.reduce(ops, spans, phases, "m", 1)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0095)          # [0, 9.5] ms
    assert red["collective_s"] == pytest.approx(0.007)     # [1, 5] + [6, 9]
    # Exposed: [4, 5] of the async gather and all of the all-reduce, which
    # the enclosing while loop does not hide.
    assert red["collective_exposed_s"] == pytest.approx(0.004)
    assert red["phase_s"] == pytest.approx(
        {"grads": 0.004, "vr": 0.001, "aggregate": 0.003})
    assert red["idle_gaps"] == [["block", pytest.approx(0.0005)]]
