"""Attribute the compiled step's operations to the program's layers.

Each instruction of the compiled HLO text carries ``metadata={...
stack_frame_id=N}``; the module's ``StackFrames``, ``FileLocations`` and
``FileNames`` tables turn N into the file of the innermost frame that traced
it (JAX leaves its own frames out, and keeps the primal's frames on the
backward pass).  Older text spells the file out as ``source_file="..."``.  A top-level operation's sources are
its own and those of every instruction fused into it.  An operation whose
sources all lie in one layer's modules is that layer's; one that fuses
several layers is ``mixed``, and one with no source ``unattributed``:
nothing is guessed.
"""
from __future__ import annotations

import re

# The layers, by the modules that make them (matched on the path's end, so
# that the checkout's location does not matter).
PHASES = (
    ("grads", ("/repro/models/", "/repro/data/")),
    ("vr", ("/repro/core/saga.py", "/repro/core/variance.py")),
    ("aggregate", ("/repro/core/geomed.py", "/repro/core/aggregators.py",
                   "/repro/core/attacks.py", "/repro/core/packing.py")),
    ("optimizer", ("/repro/optim/",)),
)

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_INST = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_SRC = re.compile(r'source_file="([^"]+)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_ROW = re.compile(r"^(\d+)\s+(.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")


def frame_files(text: str) -> dict:
    """{stack frame id: file name} from the module's frame tables."""
    tables, cur = {}, None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = _ROW.match(line) if cur is not None else None
        if not m:
            if line.strip() == "" or line.startswith(("HloModule", "%", "ENTRY")):
                cur = None
            continue
        cur[int(m.group(1))] = m.group(2)
    names = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locs = {k: dict((a, int(b)) for a, b in _FIELD.findall(v))
            for k, v in tables.get("FileLocations", {}).items()}
    out = {}
    for k, v in tables.get("StackFrames", {}).items():
        f = dict((a, int(b)) for a, b in _FIELD.findall(v))
        loc = locs.get(f.get("file_location_id"), {})
        out[k] = names.get(loc.get("file_name_id"), "")
    return out


def parse(text: str) -> dict:
    """{computation: [(instruction, source_file or None, fused callee or
    None)]} of an HLO module's text."""
    comps, cur = {}, None
    frames = frame_files(text)
    for line in text.splitlines():
        if cur is None:
            m = _HEADER.match(line)
            if m and line.rstrip().endswith("{") and " = " not in line:
                cur = m.group(1)
                comps[cur] = []
            continue
        if line.strip() == "}":
            cur = None
            continue
        m = _INST.match(line)
        if not m:
            continue
        src = _SRC.search(line)
        frame = _FRAME.search(line)
        file = (src.group(1) if src else
                frames.get(int(frame.group(1))) if frame else None)
        call = _CALLS.search(line) if " fusion(" in line else None
        comps[cur].append((m.group(1), file or None,
                           call.group(1) if call else None))
    return comps


def op_sources(text: str) -> dict:
    """{instruction name: set of source files}, fusions including what they
    fuse."""
    comps = parse(text)
    memo = {}

    def comp_sources(name, depth=0):
        if name in memo:
            return memo[name]
        out = set()
        for _, src, call in comps.get(name, ()):
            if src:
                out.add(src)
            if call and depth < 16:
                out |= comp_sources(call, depth + 1)
        memo[name] = out
        return out

    result = {}
    for insts in comps.values():
        for inst, src, call in insts:
            s = {src} if src else set()
            if call:
                s |= comp_sources(call)
            result[inst] = s
    return result


def phase_of(sources) -> str:
    phases = {p for s in sources for p, pats in PHASES
              if any(x in s for x in pats)}
    if len(phases) == 1:
        return phases.pop()
    if len(phases) > 1:
        return "mixed"
    return "other" if sources else "unattributed"


def op_phases(text: str) -> dict:
    """{instruction name: layer} for every instruction of the module."""
    return {name: phase_of(srcs) for name, srcs in op_sources(text).items()}


def module_name(text: str) -> str:
    """``HloModule jit_train_step, ...`` -> ``jit_train_step``."""
    first = text.split("\n", 1)[0]
    return first.split()[1].rstrip(",") if first.startswith("HloModule") else ""
