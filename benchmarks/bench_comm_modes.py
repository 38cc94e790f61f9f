"""Gather-vs-sharded aggregation wall-clock comparison (DESIGN.md Sec. 2).

For every (mesh, aggregator, comm mode) combination this times the jitted
shard_map'd aggregation step on synthetic worker gradients and emits
``BENCH_comm_modes.json`` plus a markdown table on stdout.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python benchmarks/bench_comm_modes.py [--quick] \\
        [--coords N] [--reps R] [--out BENCH_comm_modes.json]

The meshes need 8 devices.  On the CPU the 8 forced host devices (set by
the caller, as above, never on a chip host) share one machine, so the
numbers characterize compute + memory-movement volume, not TPU interconnect
latency: ``gather`` runs the full-vector rule redundantly on every device
(O(W * p) work and O(W * p_shard) bytes per device) while ``sharded`` runs
it on a 1/W coordinate slice (O(p) work, O(2 * p_shard) bytes) -- the
ordering between the modes is the scale-independent claim being validated.
Per-device collective-byte estimates from that model are included in the
JSON next to the measured wall-clock (schema: benchmarks/README.md).
"""
import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import AGGREGATOR_NAMES, RobustConfig
from repro.core.robust_step import distributed_aggregate, sharded_aggregate
from repro.launch.compile_cache import enable_compile_cache

SCHEMA = "BENCH_comm_modes/v1"

# (label, mesh shape, mesh axes, worker axes) -- both worker-axis layouts
# the federation supports (launch/mesh.py), shrunk to 8 host devices.
MESHES = [
    ("4x2", (4, 2), ("data", "model"), ("data",)),
    ("2x2x2", (2, 2, 2), ("pod", "data", "model"), ("pod", "data")),
]

QUICK_AGGREGATORS = ("geomed", "krum", "geomed_blockwise")


def make_payload(key, num_workers: int, coords: int):
    """Synthetic per-worker gradients: a 3-leaf pytree (two model-sharded
    matrices + a replicated bias) totalling ~``coords`` coordinates."""
    c1 = max(coords // 2 // 8, 8)
    c2 = max(coords // 4 // 8, 8)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wq": jax.random.normal(k1, (num_workers, c1, 8)),
        "wk": jax.random.normal(k2, (num_workers, c2, 8)),
        "bias": jax.random.normal(k3, (num_workers, 128)),
    }


def payload_specs(worker_axes):
    wa = worker_axes if len(worker_axes) > 1 else worker_axes[0]
    return {
        "wq": P(wa, None, "model"),
        "wk": P(wa, None, "model"),
        "bias": P(wa),
    }, {
        "wq": P(None, "model"),
        "wk": P(None, "model"),
        "bias": P(),
    }


def model_bytes_per_device(comm: str, num_workers: int, coords: int,
                           model: int) -> int:
    """Analytic per-device collective volume (f32): the gather master moves
    O(W * p_shard), the sharded master O(2 * p_shard) (all_to_all out +
    all_gather in), ignoring the small per-iteration norm psums."""
    p_shard = coords // model
    if comm == "gather":
        return 4 * num_workers * p_shard
    return 4 * 2 * p_shard


def time_call(fn, args, reps: int) -> dict:
    jax.block_until_ready(fn(*args))  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return {
        "wall_us_mean": sum(times) / len(times) * 1e6,
        "wall_us_min": min(times) * 1e6,
    }


def bench_one(mesh, mesh_axes, worker_axes, name: str, comm: str,
              payload, reps: int) -> dict:
    w = 1
    sizes = dict(zip(mesh_axes, mesh.devices.shape))
    for a in worker_axes:
        w *= sizes[a]
    cfg = RobustConfig(aggregator=name, weiszfeld_iters=32,
                       weiszfeld_tol=1e-9, num_byzantine=1, comm=comm)
    in_specs, out_specs = payload_specs(worker_axes)

    def agg_fn(msgs):
        local = jax.tree_util.tree_map(lambda z: z[0], msgs)
        if comm == "sharded":
            return sharded_aggregate(local, cfg, worker_axes=worker_axes,
                                     model_axes=("model",), num_workers=w)
        return distributed_aggregate(local, cfg, worker_axes=worker_axes,
                                     model_axes=("model",))

    fn = jax.jit(compat.shard_map(
        agg_fn, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
        check_vma=False))
    return time_call(fn, (payload,), reps)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"only {QUICK_AGGREGATORS} (the CI artifact setting)")
    ap.add_argument("--coords", type=int, default=1 << 16,
                    help="approximate parameter count of the payload")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default="BENCH_comm_modes.json")
    args = ap.parse_args()
    enable_compile_cache()

    names = QUICK_AGGREGATORS if args.quick else AGGREGATOR_NAMES
    rows = []
    for label, shape, axes, wa in MESHES:
        mesh = compat.make_mesh(shape, axes)
        sizes = dict(zip(axes, shape))
        w = functools.reduce(lambda a, b: a * b, (sizes[a] for a in wa), 1)
        payload = make_payload(jax.random.PRNGKey(0), w, args.coords)
        coords = sum(int(l[0].size) for l in jax.tree_util.tree_leaves(payload))
        for name in names:
            for comm in ("gather", "sharded"):
                t = bench_one(mesh, axes, wa, name, comm, payload, args.reps)
                rows.append({
                    "mesh": label, "axes": list(axes),
                    "worker_axes": list(wa), "num_workers": w,
                    "aggregator": name, "comm": comm, "coords": coords,
                    "reps": args.reps,
                    "model_bytes_per_device": model_bytes_per_device(
                        comm, w, coords, sizes["model"]),
                    **t,
                })
                print(f"  {label:6s} {name:18s} {comm:8s} "
                      f"{t['wall_us_mean']:10.0f} us/step")

    report = {
        "schema": SCHEMA,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "coords_requested": args.coords,
        "weiszfeld_iters": 32,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"\nwrote {args.out} ({len(rows)} rows)\n")

    # Markdown summary: gather vs sharded side by side.
    print("| mesh | aggregator | gather us | sharded us | sharded/gather |")
    print("|------|------------|-----------|------------|----------------|")
    by_key = {(r["mesh"], r["aggregator"], r["comm"]): r for r in rows}
    for label, _, _, _ in MESHES:
        for name in names:
            g = by_key[(label, name, "gather")]["wall_us_mean"]
            s = by_key[(label, name, "sharded")]["wall_us_mean"]
            print(f"| {label} | {name} | {g:.0f} | {s:.0f} | {s / g:.2f} |")


if __name__ == "__main__":
    main()
