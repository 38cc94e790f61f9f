"""Device time per round of the step's operations whose HLO stack frames
lie in the per-client gradients (the model's forward and backward, the
loss: ``repro/models``, ``repro/data``), averaged over the chips; nothing
where no operation is attributed to them."""

PHASE = "grads"


def read(ctx):
    s = ctx["phase_s"].get(PHASE, 0.0)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
