"""Device busy time per round: the union of the chip's operation intervals
in the traced window over the rounds in it, averaged over the chips."""


def read(ctx):
    return 1e3 * ctx["busy_s"] / ctx["rounds"]
