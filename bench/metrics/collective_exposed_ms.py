"""The part of ``collective_ms`` during which no other operation ran on
that chip, per round, averaged over the chips; nothing on one chip or
where no collective ran."""


def read(ctx):
    if ctx["chips"] < 2 or ctx["collective_s"] <= 0:
        return None
    return 1e3 * ctx["collective_exposed_s"] / ctx["rounds"]
