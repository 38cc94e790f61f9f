"""Parse collective traffic out of compiled (SPMD-partitioned) HLO text.

``cost_analysis()`` does not expose collective bytes, so we scan the HLO for
``all-gather`` / ``all-reduce`` / ``reduce-scatter`` / ``all-to-all`` /
``collective-permute`` ops.  The post-optimization HLO print omits operand
shapes, so byte accounting works from the RESULT shape plus the collective's
replica-group size S with a ring model (bytes received per device):

    all-gather          result * (S-1)/S
    all-reduce          2 * result * (S-1)/S     (reduce-scatter + all-gather)
    reduce-scatter      result * (S-1)            (operand = S * result)
    all-to-all          result * (S-1)/S
    collective-permute  result

The HLO module is the per-device program, so parsed bytes are already
per-device; the roofline collective term is bytes / link_bw.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_SRC_RE = re.compile(r'source_file="([^"]+)"')
_FRAME_RE = re.compile(r"stack_frame_id=(\d+)")
_ROW_RE = re.compile(r"^(\d+)\s+(.*)$")
_FIELD_RE = re.compile(r"(\w+)=(\d+)")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        # replica_groups=[G,S]: G groups of size S.
        return max(int(m.group(2)), 1)
    m = _LIST_GROUPS_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 2  # unknown: conservative smallest nontrivial group


def _frame_files(hlo_text: str) -> Dict[int, str]:
    """{stack_frame_id: file of that frame} from the module's
    ``FileNames`` / ``FileLocations`` / ``StackFrames`` tables."""
    tables: Dict[str, Dict[int, dict]] = {}
    cur = None
    for line in hlo_text.splitlines():
        if line in ("FileNames", "FileLocations", "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = _ROW_RE.match(line) if cur is not None else None
        if not m:
            cur = None
            continue
        fields = dict((k, int(v)) for k, v in _FIELD_RE.findall(m.group(2)))
        cur[int(m.group(1))] = fields or {"name": m.group(2).strip('"')}
    names = tables.get("FileNames", {})
    locs = tables.get("FileLocations", {})
    out = {}
    for k, frame in tables.get("StackFrames", {}).items():
        loc = locs.get(frame.get("file_location_id"), {})
        out[k] = names.get(loc.get("file_name_id"), {}).get("name", "")
    return out


def collective_ops(hlo_text: str) -> List[dict]:
    """One record per collective instruction (a ``-done`` half is skipped:
    its payload is the matching ``-start``'s).

    ``kind`` (one of the five above), ``result_bytes`` (the
    result part only), ``elements`` (of the largest array in the result),
    ``group`` (replica-group size) and ``source`` -- the file of the
    innermost Python frame that traced it (``""`` without metadata): the
    partitioner's collectives carry the frame of the op they serve.  The
    frame tables are read as ``bench/lib/hlo.py``'s ``frame_files`` reads
    them (the program does not import the benchmark): change both together."""
    frames = _frame_files(hlo_text)
    out: List[dict] = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        eq = ls.index("=")
        m = re.search(r"\s([\w-]+)\(", ls[eq:])
        if not m:
            continue
        op = m.group(1)
        kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
        if kind is None or op.endswith("-done"):
            continue
        shapes = _SHAPE_RE.findall(ls[eq + 1 : eq + m.start(1)])
        result_b = sum(_shape_bytes(d, s) for d, s in shapes)
        if op.startswith(("all-gather-start", "all-reduce-start")):
            # tuple result (operand, result): halve to get the result part.
            result_b //= 2
        src = _SRC_RE.search(ls)
        frame = _FRAME_RE.search(ls)
        out.append({
            "kind": kind,
            "result_bytes": result_b,
            "elements": max((math.prod(int(d) for d in s.split(",") if d)
                             for _, s in shapes), default=0),
            "group": _group_size(ls),
            "source": (src.group(1) if src else
                       frames.get(int(frame.group(1)), "") if frame else ""),
        })
    return out


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Bytes moved per device, by collective kind + 'total'."""
    out: Dict[str, float] = defaultdict(float)
    for c in collective_ops(hlo_text):
        kind, result_b, s = c["kind"], c["result_bytes"], c["group"]
        if kind == "all-gather":
            moved = result_b * (s - 1) / s
        elif kind == "all-reduce":
            moved = 2 * result_b * (s - 1) / s
        elif kind == "reduce-scatter":
            moved = result_b * (s - 1)
        elif kind == "all-to-all":
            moved = result_b * (s - 1) / s
        else:  # collective-permute
            moved = result_b
        out[kind] += moved
        out["total"] += moved
        out[f"count:{kind}"] += 1
    return dict(out)


def collective_summary(hlo_text: str) -> str:
    b = collective_bytes(hlo_text)
    parts = [f"{k}={b.get(k, 0) / 1e9:.3f}GB(n={int(b.get('count:' + k, 0))})"
             for k in _COLLECTIVES if k in b]
    return f"total={b.get('total', 0) / 1e9:.3f}GB " + " ".join(parts)
