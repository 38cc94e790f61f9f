"""One run of one cell: set-up, the measured (or traced) window, the check.

    set-up   the seed's data and weights on the device, the program's step
             compiled, and its first ``check_steps`` rounds driven through
             the window's own call (their readings kept for the check);
    window   rounds dispatched back to back for ``seconds`` (``--trace 0``),
             or ``trace_rounds`` rounds under the profiler (``--trace 1``),
             then one block until the device is done;
    check    the program's state freed, the plain reference run on the same
             inputs, and each number compared against its limit.

Rounds go out with no per-round host sync, as in ``launch/train.py``
(which syncs once per 32 rounds, at its RunLogger's batched
``device_get``).  A round first waits for the one ``MAX_LAG`` rounds back.
Where the host sets the pace that round is long done; where the device
does, the host waits there with ``MAX_LAG`` rounds queued, so a pause of
the host up to that long leaves the device busy.  The lag also bounds how
far the window overruns ``seconds``: dispatch stops there, then one block
waits for at most ``MAX_LAG`` rounds.  The objects that set-up made are
frozen out of the garbage collector's sweeps before the window, which
then collects only what the rounds allocate.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time

from bench.lib import check as check_lib
from bench.lib import device as device_lib
from bench.lib.cells import BENCH, base_name, metric_reader
from bench.lib.compiles import CompileCounter

CACHE_DIR = os.path.join(BENCH, ".jax_cache")
OUT_DIR = os.path.join(BENCH, "_out")
GIB = 2.0 ** 30
MAX_LAG = 8


def enable_compile_cache():
    """JAX's persistent compilation cache at ``bench/.jax_cache`` (a fixed
    path inside the checkout), every program cached."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class GcPauses:
    """The garbage collector's passes while the block runs: how many, and
    the longest and total seconds."""

    def __enter__(self):
        self.pauses, self._t = [], None
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)

    def summary(self) -> dict:
        return {"count": len(self.pauses),
                "longest_s": max(self.pauses, default=0.0),
                "total_s": sum(self.pauses)}


def make_runner(cell, seed, devices, impl):
    if cell["traffic"]["path"] == "sim":
        from bench.lib.sim import SimCell
        return SimCell(cell, seed, devices, impl)
    from bench.lib.mesh import MeshCell
    return MeshCell(cell, seed, devices, impl)


def _timed_window(runner, seconds: float) -> tuple[int, float, dict]:
    """Rounds back to back for ``seconds``, then one block; also the
    longest host pass between two dispatches (a pause of the host shows
    there, not in the device's work)."""
    import jax
    pending = []
    n = 0
    longest = (0.0, 0)
    t0 = time.perf_counter()
    t_prev = t0
    while t_prev - t0 < seconds:
        pending.append(runner.round())
        runner.next_input()
        n += 1
        if len(pending) > MAX_LAG:
            jax.block_until_ready(pending.pop(0))
        t = time.perf_counter()
        if t - t_prev > longest[0]:
            longest = (t - t_prev, n)
        t_prev = t
    jax.block_until_ready(pending)
    return n, time.perf_counter() - t0, {"longest_pass_s": longest[0],
                                         "at_round": longest[1]}


def _traced_window(runner, rounds: int, trace_dir: str) -> int:
    import jax
    pending = []
    with jax.profiler.trace(trace_dir):
        for _ in range(rounds):
            with jax.profiler.TraceAnnotation("bench.round"):
                pending.append(runner.round())
            with jax.profiler.TraceAnnotation("bench.batch"):
                runner.next_input()
            if len(pending) > MAX_LAG:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(pending.pop(0))
        with jax.profiler.TraceAnnotation("bench.block"):
            jax.block_until_ready(pending)
    return rounds


def per_layer(cell, red: dict, flops_per_round, peak: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    ctx = dict(red, flops_per_round=flops_per_round, peak=peak)
    out = {}
    for m in cell["per_layer"]:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, impl: str = "program",
        log=sys.stderr) -> dict:
    """One run; returns the result object (``checks`` last)."""
    import jax
    if require_tpu:
        devices = device_lib.chips(cell["chips"])
        enable_compile_cache()
    else:
        devices = jax.devices()[: cell["chips"]]
    t = cell["traffic"]
    runner = make_runner(cell, seed, devices, impl)
    t_devices = time.perf_counter()
    with CompileCounter() as setup_compiles:
        runner.setup()
        t_built = time.perf_counter()
        prog = runner.first_steps()
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.3f} s: start to devices "
          f"{t_devices - t_start:.3f} s, build {t_built - t_devices:.3f} s, "
          f"first rounds {t_start + setup_s - t_built:.3f} s; "
          f"{setup_compiles.count} compiles ({setup_compiles.seconds:.3f} s)",
          file=log)
    trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
    gc.collect()
    gc.freeze()
    with CompileCounter() as window_compiles, GcPauses() as gc_pauses:
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            rounds = _traced_window(runner, t["trace_rounds"], trace_dir)
            elapsed = None
        else:
            rounds, elapsed, host = _timed_window(runner, seconds)
    gc.unfreeze()
    mem, parts = device_lib.memory_peak_bytes(devices)
    print(f"memory of the fullest chip: {json.dumps(parts)}; garbage "
          f"collections in the window: {json.dumps(gc_pauses.summary())}",
          file=log)
    desc = device_lib.describe(devices)
    desc["memory_peak_bytes"] = mem
    metrics, breakdown = {}, None
    if trace:
        from bench.lib import hlo, trace as trace_lib
        text = runner.hlo_text()
        phases = hlo.op_phases(text) if text else {}
        ops, spans = trace_lib.read_xplane(trace_dir)
        red = trace_lib.reduce(ops, spans, phases,
                               hlo.module_name(text) if text else "",
                               len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        peak = device_lib.peaks(desc["kind"]) if require_tpu else None
        metrics = per_layer(cell, red, runner.flops_per_round(), peak)
        desc["busy_s"] = red["busy_s"]
        desc["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        print("trace: " + json.dumps({k: red[k] for k in (
            "window_s", "busy_s", "rounds", "phase_s", "collective_s",
            "collective_exposed_s", "idle_by_host_s")}), file=log)
    else:
        measured = {"rounds_per_s": rounds / elapsed,
                    "peak_hbm_gib": mem / GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": measured[base_name(m["name"])],
                               "unit": m["unit"]}
                   for m in cell["end_to_end"]}
        print(f"window {elapsed:.3f} s, {rounds} rounds; longest host "
              f"pass {host['longest_pass_s']:.4f} s at round "
              f"{host['at_round']}", file=log)
    runner.release()
    ref = runner.reference("f32")
    values = check_lib.gaps(prog, ref)
    values["window_compiles"] = float(window_compiles.count)
    limits = dict(cell["limits"]["limits"], window_compiles=0.0)
    correct, checks = check_lib.judge(values, limits)
    print("readings: " + json.dumps({"program": prog["loss"],
                                     "reference": ref["loss"]}), file=log)
    for name, c in checks.items():
        ok = math.isfinite(c["value"]) and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} <= {c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=log)
    result = {"correct": correct, "attempted": rounds,
              "failed": 0 if correct else rounds, "metrics": metrics,
              "device": desc}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
