"""Reduce a profiler trace of the traced window to per-layer numbers.

The trace holds, per chip, the device operations ("XLA Ops" lines of the
``/device:TPU:<n>`` planes) and, on the host plane, the benchmark's own
``bench.*`` spans (``jax.profiler.TraceAnnotation`` around making a batch,
dispatching a round and the block at the window's end).  The window is the
first ``bench.round`` span's start to the ``bench.block`` span's end.

Busy time is the union of a chip's operation intervals inside the window;
idle gaps are what is left, each labelled by the innermost host span open
at its middle.  Layer times add the durations of the step's operations
that the compiled HLO attributes to a layer (``bench.lib.hlo``).  Collective
time is the union of the collective operations (the async line's spans
included); its exposed part is what no compute operation on that chip
overlaps.  Durations and overlaps count innermost operations only: a while
loop's span encloses its body's operations, collectives included.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import os
from collections import defaultdict

COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str):
    """(device ops, host spans) of a trace: ``path`` is a profiler output
    directory (its newest ``*.xplane.pb``) or one ``.xplane.pb[.gz]`` file.
    Returns ``{chip: [(start_ns, end_ns, op, module, line)]}`` (the async
    line's collectives included) and ``[(start_ns, end_ns, name)]``."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no trace under {path}")
        path = files[-1]
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    ops, spans = defaultdict(list), []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = int(plane.name.rsplit(":", 1)[1])
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           e.name.split("(", 1)[0])
                          for e in lines.get(MODULES_LINE, ()))
            starts = [m[0] for m in mods]
            for name in (OPS_LINE, ASYNC_LINE):
                for e in lines.get(name, ()):
                    op = op_name(e.name)
                    if name == ASYNC_LINE and not is_collective(op):
                        continue
                    i = bisect.bisect_right(starts, e.start_ns) - 1
                    mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] \
                        else ""
                    ops[chip].append((e.start_ns, e.start_ns + e.duration_ns,
                                      op, mod, name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return dict(ops), spans


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def _overlap(a, b_union):
    """Length of interval ``a`` covered by the sorted disjoint ``b_union``."""
    s, e = a
    tot = 0
    for bs, be in b_union:
        if be <= s:
            continue
        if bs >= e:
            break
        tot += min(e, be) - max(s, bs)
    return tot


def _leaves(events):
    """Drop events that enclose a later one (a while loop's span around its
    body's operations), so that durations are not counted twice."""
    ev = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, x in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt[0] < x[1] and nxt[1] <= x[1]:
            continue
        out.append(x)
    return out


def reduce(ops: dict, spans: list, phases: dict, step_module: str,
           chips: int) -> dict:
    """The traced window's numbers, averaged over the ``chips`` used.

    ``ops``: ``{chip: [(start_ns, end_ns, op, module, line)]}``;
    ``phases``: ``{op: layer}`` of the step's compiled module, whose name is
    ``step_module``.  Operations of other modules (the batch generator, key
    folding) count as busy time and as ``outside step``."""
    rounds = [s for s in spans if s[2] == HOST_PREFIX + "round"]
    blocks = [s for s in spans if s[2] == HOST_PREFIX + "block"]
    if not rounds or not blocks:
        raise ValueError("trace has no bench.round / bench.block spans")
    lo, hi = min(s[0] for s in rounds), max(s[1] for s in blocks)
    used = sorted(ops)[:chips]
    busy, gaps = 0, []
    phase_ns, op_ns = defaultdict(int), defaultdict(int)
    coll_ns = exposed_ns = 0
    for chip in used:
        evs = _clip(ops[chip], lo, hi)
        uni = _union([(x[0], x[1]) for x in evs])
        busy += sum(e - s for s, e in uni)
        prev = lo
        for s, e in uni:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        leaves = _leaves([x for x in evs if x[4] == OPS_LINE])
        compute = _union([(x[0], x[1]) for x in leaves
                          if not is_collective(x[2])])
        coll = _union([(x[0], x[1]) for x in evs if is_collective(x[2])])
        coll_ns += sum(e - s for s, e in coll)
        exposed_ns += sum((e - s) - _overlap((s, e), compute) for s, e in coll)
        for s, e, op, mod, _ in leaves:
            phase = (phases.get(op, "unattributed") if mod == step_module
                     else "outside step")
            phase_ns[phase] += e - s
            op_ns[f"{phase}:{op}"] += e - s
    n = max(len(used), 1)
    label = lambda g: _label(spans, (g[0] + g[1]) // 2)
    idle = defaultdict(int)
    for g in gaps:
        idle[label(g)] += g[1] - g[0]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "rounds": len(rounds),
        "chips": len(used),
        "phase_s": {k: v * 1e-9 / n for k, v in phase_ns.items()},
        "collective_s": coll_ns * 1e-9 / n,
        "collective_exposed_s": exposed_ns * 1e-9 / n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in sorted(
            op_ns.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in longest],
        "idle_by_host_s": {k: v * 1e-9 / n for k, v in idle.items()},
    }


def _label(spans, t) -> str:
    """The innermost ``bench.*`` host span open at ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(HOST_PREFIX):] if best else "outside spans"
