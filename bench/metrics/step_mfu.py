"""The whole step's share of the chips' bf16 peak: the model FLOPs of a
round (forward and backward of every worker's tokens, counted from shapes
by the configuration; nothing recomputed) times the rounds in the traced
window, over the window and the chips' peak.  Nothing where the
configuration counts no model FLOPs."""


def read(ctx):
    if not ctx["flops_per_round"] or ctx["peak"] is None:
        return None
    rate = ctx["flops_per_round"] * ctx["rounds"] / ctx["window_s"]
    return 100.0 * rate / (ctx["chips"] * ctx["peak"]["bf16_flops"])
