"""The repo's one gate onto jax's mesh / shard_map / profiler surface.

The distributed federation path (shard_map aggregation, ambient-mesh
contexts, axis types, multi-axis collectives) sits on jax names that have
moved between releases.  The repo targets the one installed jax; call sites
in core/, launch/, models/, examples/ and the tests import these wrappers
and never touch the moving names directly (enforced by
tests/test_substrates.py::test_compat_layer_is_the_only_jax_version_gate),
so a future move changes this module only.  See DESIGN.md Sec. 3.

================  =====================================================
export            jax surface
================  =====================================================
``make_mesh``     ``jax.make_mesh(..., axis_types=, devices=)``
``use_mesh``      ``jax.set_mesh`` context
``active_mesh``   ``jax.sharding.get_abstract_mesh`` (None when unset)
``shard_map``     ``jax.shard_map(axis_names=, check_vma=)``
``AxisType``      ``jax.sharding.AxisType``
================  =====================================================
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable, Optional, Sequence

import jax

__all__ = [
    "AxisType", "make_mesh", "use_mesh", "active_mesh", "shard_map",
    "axis_size", "axis_group", "axis_index", "all_gather", "all_to_all",
    "psum", "pmax", "cost_analysis", "profiler_trace", "require_distributed",
]

AxisType = jax.sharding.AxisType


def _shortfall(what: str, need: int, devs: Sequence[Any]) -> str:
    """Too-few-devices message: chips on an accelerator; on the CPU backend
    it names the host-device flag that rehearses a mesh without one."""
    platform = devs[0].platform if devs else jax.default_backend()
    if platform != "cpu":
        return f"{what} needs {need} chips, found {len(devs)}"
    return (f"{what} needs {need} devices, found {len(devs)} -- on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "before importing jax")


# ---------------------------------------------------------------------------
# Mesh construction and the ambient mesh
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None,
              axis_types: Optional[Sequence[Any]] = None):
    """``jax.make_mesh`` over the first prod(axis_shapes) of ``devices``
    (default: all), with a clear error when there are too few; axis types
    default to Auto everywhere."""
    shape = tuple(axis_shapes)
    n = 1
    for s in shape:
        n *= s
    devs = list(jax.devices() if devices is None else devices)
    if len(devs) < n:
        raise RuntimeError(_shortfall(f"mesh {shape}", n, devs))
    types = (tuple(axis_types) if axis_types is not None
             else (AxisType.Auto,) * len(shape))
    return jax.make_mesh(shape, tuple(axis_names), axis_types=types,
                         devices=devs[:n])


def active_mesh():
    """The mesh entered via :func:`use_mesh` (as jax's abstract mesh, which
    is also readable under ``jit``), else None."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.axis_names else None


@contextlib.contextmanager
def use_mesh(mesh):
    """``with use_mesh(mesh):`` -- enter ``mesh`` as the ambient mesh
    (``jax.set_mesh``), so bare-PartitionSpec APIs and :func:`shard_map`
    without ``mesh=`` resolve against it."""
    with jax.set_mesh(mesh):
        yield mesh


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f: Callable, *, mesh=None, in_specs, out_specs,
              axis_names: Optional[Any] = None, check_vma: bool = True):
    """``jax.shard_map``.

    ``mesh``: defaults to :func:`active_mesh` (enter :func:`use_mesh` first).
    ``axis_names``: the MANUAL mesh axes; None means all axes.
    """
    if mesh is None:
        mesh = active_mesh()
        if mesh is None:
            raise RuntimeError(
                "compat.shard_map: no mesh -- pass mesh= explicitly or enter "
                "a `with repro.compat.use_mesh(mesh):` context first")
    all_names = frozenset(mesh.axis_names)
    manual = all_names if axis_names is None else frozenset(axis_names)
    unknown = manual - all_names
    if unknown:
        raise ValueError(f"axis_names {sorted(unknown)} not in mesh axes "
                         f"{sorted(all_names)}")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual),
                         check_vma=check_vma)


def profiler_trace(log_dir: str):
    """``with profiler_trace(dir):`` -- ``jax.profiler.trace``.  Remember to
    ``block_until_ready`` inside the window: dispatch returns early, and an
    empty trace is the classic symptom."""
    return jax.profiler.trace(log_dir)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when the backend
    reports none)."""
    return compiled.cost_analysis() or {}


def axis_size(axis_name):
    """``jax.lax.axis_size``: the static size of a mesh axis."""
    return jax.lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Multi-axis collectives over the federation's worker axes
#
# The worker dimension of the Byzantine-robust federation may span SEVERAL
# mesh axes (("pod", "data") on multi-pod meshes, launch/mesh.py).  jax's
# collectives accept a tuple of axis names and treat it as one collapsed
# axis whose index is row-major over the tuple (pod-major) for
# all_gather / all_to_all / axis_index inside fully-manual shard_map.
# These wrappers are the single guard point for that surface.
# ---------------------------------------------------------------------------

def axis_group(axis_names):
    """Normalize a worker-axis spec -- one name or a sequence of names -- to
    the form jax collectives accept: the bare name for a single axis, a
    tuple for several (treated as one collapsed axis, row-major order)."""
    if isinstance(axis_names, str):
        return axis_names
    names = tuple(axis_names)
    return names[0] if len(names) == 1 else names


def axis_index(axis_names):
    """Linear index along (possibly several) mesh axes, row-major.  Only use
    inside FULLY-manual shard_map (DESIGN.md Sec. 2 -- partial-manual
    regions use a sharded iota instead)."""
    return jax.lax.axis_index(axis_group(axis_names))


def all_gather(x, axis_names, *, axis: int = 0, tiled: bool = False):
    """``jax.lax.all_gather`` over one or several worker axes.  With several
    names the gathered dimension arrives as ONE collapsed axis of size
    prod(sizes) in row-major worker order -- not one nested axis per name."""
    return jax.lax.all_gather(x, axis_group(axis_names), axis=axis, tiled=tiled)


def all_to_all(x, axis_names, *, split_axis: int, concat_axis: int,
               tiled: bool = False):
    """``jax.lax.all_to_all`` over one or several worker axes, splitting
    ``split_axis`` into prod(sizes) blocks in row-major worker order."""
    return jax.lax.all_to_all(x, axis_group(axis_names), split_axis,
                              concat_axis, tiled=tiled)


def psum(x, axis_names):
    """``jax.lax.psum`` over one or several mesh axes."""
    return jax.lax.psum(x, axis_group(axis_names))


def pmax(x, axis_names):
    """``jax.lax.pmax`` over one or several mesh axes."""
    return jax.lax.pmax(x, axis_group(axis_names))


def require_distributed(*, min_devices: int = 0, what: str = "distributed path") -> None:
    """Fail up front, with the fix spelled out, when the multi-device
    federation path lacks devices -- instead of a mesh-size error from deep
    inside jit tracing."""
    devs = jax.devices()
    if min_devices and len(devs) < min_devices:
        raise RuntimeError(_shortfall(what, min_devices, devs))
