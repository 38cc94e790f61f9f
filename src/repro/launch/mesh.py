"""Production meshes.

Kept as FUNCTIONS so importing this module never touches jax device state.

* single pod : (16, 16)   axes ("data", "model")  -- 256 chips (v5e pod)
* multi-pod  : (P, 16, 16) axes ("pod", "data", "model") -- P x 256 chips

Workers of the Byzantine-robust federation are the indices along the
WORKER AXES ``("pod", "data")`` (the axes :func:`worker_axes` reports): 16
workers single-pod, P*16 multi-pod; each worker owns ``model``-parallel
chips and its own finite local dataset + SAGA table.  The global worker id
is the row-major linear index over the worker axes (pod-major) -- the order
every collective in ``core/robust_step.py`` collapses those axes to
(``repro.compat.all_gather`` / ``all_to_all`` / ``axis_index``).

All mesh construction funnels through ``repro.compat.make_mesh`` (explicit
AxisType.Auto axes, a clear error when the mesh needs more devices).
"""
from __future__ import annotations

from typing import Optional

from repro import compat


def make_production_mesh(*, multi_pod: bool = False,
                         num_pods: Optional[int] = None,
                         data_per_pod: int = 16, model: int = 16):
    """Build the production mesh.

    ``num_pods``: explicit pod count; >= 2 adds the leading "pod" axis,
    1 builds the flat single-pod mesh.  Defaults to the legacy boolean
    ``multi_pod`` (False -> 1 pod, True -> 2 pods).
    """
    if num_pods is None:
        num_pods = 2 if multi_pod else 1
    if num_pods < 1:
        raise ValueError(f"num_pods must be >= 1, got {num_pods}")
    if num_pods > 1:
        return compat.make_mesh((num_pods, data_per_pod, model),
                                ("pod", "data", "model"))
    return compat.make_mesh((data_per_pod, model), ("data", "model"))


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests/examples)."""
    return compat.make_mesh(shape, axes)


def worker_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def num_workers(mesh) -> int:
    n = 1
    for a in worker_axes(mesh):
        n *= axis_sizes(mesh)[a]
    return n
