"""The devices a run is on: the chip check, the peaks table, peak memory."""
from __future__ import annotations

import os

from bench.lib.cells import BENCH, read_json


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def chips(n: int):
    """The first ``n`` TPU devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, found {len(devs)}")
    return devs[:n]


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind`` (``peaks.json``, which names
    its source); a kind that is not in the table is an error."""
    table = read_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "bench/peaks.json")
    return table[device_kind]


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> tuple[int, dict]:
    """The peak footprint of the fullest device: ``peak_bytes_in_use`` (the
    buffers: weights, optimizer and SAGA state, batches) plus
    ``peak_bytes_reserved`` (what the runtime holds back for the loaded
    programs' temporaries, which ``peak_bytes_in_use`` leaves out).  Also
    those two readings of that device.  0 where the backend keeps no
    statistics, as the CPU does."""
    peak, parts = 0, {}
    for d in devs:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if in_use + reserved >= peak:
            peak = in_use + reserved
            parts = {"peak_bytes_in_use": in_use,
                     "peak_bytes_reserved": reserved}
    return peak, parts
