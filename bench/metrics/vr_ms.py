"""Device time per round of the step's operations whose HLO stack frames
lie in variance reduction (``repro/core/saga.py``, ``variance.py``),
averaged over the chips; nothing where no operation is attributed to it."""

PHASE = "vr"


def read(ctx):
    s = ctx["phase_s"].get(PHASE, 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
