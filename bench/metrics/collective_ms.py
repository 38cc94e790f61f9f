"""Device time per round of the collective operations (all-gather,
all-reduce, all-to-all, collective-permute, reduce-scatter), averaged over
the chips; nothing on one chip or where none ran."""


def read(ctx):
    if ctx["chips"] < 2 or ctx["collective_s"] <= 0:
        return None
    return 1e3 * ctx["collective_s"] / ctx["rounds"]
