"""The numbers that decide ``correct``, and their limits.

A run's training readings are, for the first ``check_steps`` rounds of the
object the window then drives: each round's loss, the norm of each leaf of
the first aggregated gradient as the optimizer got it, and the norm of each
leaf's change over the rounds.  The reference yields the same readings, and
each number compared is the worst gap between the two:

* ``loss_gap``   -- max over rounds of |loss - ref| / |ref|;
* ``grad_gap``   -- max over leaves of | ||g|| - ||g_ref|| | / max(||g_ref||,
  median leaf ||g_ref||);
* ``change_gap`` -- the same for the parameters' change, over the leaves
  whose reference gradient is at least ``MOVED`` x the median leaf's (a leaf
  whose gradient is nought to rounding, such as an unused norm, moves under
  Adam by round-off alone).
"""
from __future__ import annotations

import math
import statistics

import jax
import jax.numpy as jnp

MOVED = 1e-3


@jax.jit
def _norms(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in leaves]


def leaf_norms(tree) -> dict:
    """{leaf path: float32 L2 norm} of a pytree (one device round trip)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.device_get(_norms([x for _, x in flat]))
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(flat, norms)}


def _norm_gap(prog: dict, ref: dict, keep) -> float:
    keys = [k for k in ref if keep(k)]
    if set(prog) != set(ref):
        return math.inf
    med = statistics.median(ref[k] for k in keys)
    worst = 0.0
    for k in keys:
        denom = max(ref[k], med)
        gap = abs(prog[k] - ref[k]) / denom if denom > 0 else (
            0.0 if prog[k] == ref[k] else math.inf)
        if not math.isfinite(prog[k]):
            gap = math.inf
        worst = max(worst, gap)
    return worst


def gaps(prog: dict, ref: dict) -> dict:
    """The three numbers compared, from two readings dicts."""
    loss = max((abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
               for p, r in zip(prog["loss"], ref["loss"]))
    grad = _norm_gap(prog["grad"], ref["grad"], lambda k: True)
    med_g = statistics.median(ref["grad"].values())
    moved = lambda k: ref["grad"][k] >= MOVED * med_g
    change = _norm_gap(prog["change"], ref["change"], moved)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}); a number over its limit, or
    not finite, makes the run not correct."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = values[name]
        out[name] = {"value": v, "limit": limit}
        if not (math.isfinite(v) and v <= limit):
            ok = False
    return ok, out
