"""Device time per round of the step's operations whose HLO stack frames
lie in robust aggregation, the attack and the message packing
(``repro/core/geomed.py``, ``aggregators.py``, ``attacks.py``,
``packing.py``), averaged over the chips; nothing where no operation is
attributed to them."""

PHASE = "aggregate"


def read(ctx):
    s = ctx["phase_s"].get(PHASE, 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
