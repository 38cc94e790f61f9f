"""End-to-end train-step wall-clock: flat-packed vs per-leaf hot path.

The DESIGN.md Sec. 8 claim made measurable: for every (aggregator, path)
cell this times the FULL Byzantine-robust training step -- per-worker
grads, variance-reduction correction, attack injection, robust
aggregation, optimizer -- with the flat-packed pipeline
(``RobustConfig.packed=True``, the default) against the pre-refactor
per-leaf pipeline (``packed=False``), and emits ``BENCH_step.json`` plus a
markdown ratio table.  Since schema v2 the sim rows also carry the
resident variance-reduction state bytes, and a saga-vs-lsvrg trade-off
pair at fixed (W, J, D) quantifies the O((J+1)D)-table vs O(2D)-snapshot
memory/step story (DESIGN.md Sec. 9).  Schema v3 adds cohort-size scaling
cells: client-scale virtualization (DESIGN.md Sec. 10) at
``num_clients`` in {64, 256} with the same 16-slot cohort, measuring what
the per-round cohort gather/scatter and staleness weighting cost on top
of the fixed-width aggregation (packed path only -- the per-leaf baseline
has no weighted rules, so the gate ignores these cells).  Schema v4 adds
the robustness characterization grid (DESIGN.md Sec. 12): attack x wire
format x robust rule (``path="grid"`` rows) on the Sec. V-A logreg
federation, each cell reporting the final honest-data loss of a short
Byrd-SAGA run -- the quantized wire formats (int8 per-block scales,
sign1 + error feedback) must keep every rule's error floor, not just
survive attack-free.  Gate keys carry ``message_dtype`` since v4.
Schema v5 adds the fault-containment grid (DESIGN.md Sec. 13):
fault attack (nan / inf_overflow / bitflip) x robust rule x guards
on/off (``path="fault"`` rows), each cell reporting ``loss_finite``
plus the final honest loss when it IS finite -- the in-graph guards
must keep every rule's run finite under faults that destroy the
unguarded step, at the usual wall-clock readout.

    PYTHONPATH=src python benchmarks/bench_step.py [--quick] [--gate] \\
        [--steps N] [--reps R] [--out BENCH_step.json]

Paths:

* ``sim``     -- the single-host simulated federation
  (``make_federated_step``) on a deep-MLP workload with MANY small
  parameter blocks -- the regime the packing targets (per-leaf dispatch
  multiplies kernel launches by num_leaves).
* ``gather`` / ``sharded`` -- the distributed ``make_train_step`` on a
  4 x (devices // 4) mesh of the devices present (4x2 on 8 forced host
  devices, 4x1 on a four-chip host), reduced mamba2 model.  The sharded
  comm path re-shards by coordinate inside shard_map either way, so its
  packed/per-leaf cells differ only in the attack/packing stage.

``--gate`` turns the run into the STEP-LEVEL PERF GATE (wired into CI with
``--quick``): it fails the job if any cell's packed path is slower than
per-leaf beyond a noise margin (on ``wall_us_min``, the noise-robust
statistic), or if the sim geomed/krum cells -- the aggregation-dominated
ones -- fall short of the 1.3x speedup floor.

Process layout: every cell runs in this one process, on the devices it
finds (one process per chip); it never starts a second one.  The mesh is
checked before the first cell.  On the CPU the sim cells time on the
natural single device (forcing 8 host devices splits the XLA threadpool
and drowns them in scheduler noise), so CI runs them with
``--skip-distributed`` and the mesh cells in a second invocation,
``--distributed-only --out BENCH_step_mesh.json`` under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import argparse
import json
import math
import time

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import AGGREGATOR_NAMES, RobustConfig, make_federated_step
from repro.data import ijcnn1_like, partition
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import get_optimizer

SCHEMA = "BENCH_step/v5"

QUICK_AGGREGATORS = ("geomed", "krum", "mean")
# Robustness characterization grid (schema v4, DESIGN.md Sec. 12): every
# (attack, wire format) pair for the three headline robust rules, scored
# by the honest-data loss a short Byrd-SAGA run reaches.
GRID_ATTACKS = ("none", "gaussian", "sign_flip", "straggler")
GRID_DTYPES = ("float32", "bfloat16", "int8", "sign1")
GRID_AGGREGATORS = ("geomed", "krum", "trimmed_mean")
GRID_STEPS = 150
# Fault-containment grid (schema v5, DESIGN.md Sec. 13): the fault
# injections that produce garbage rows rather than adversarial ones, run
# with the in-graph guards off and on.  bitflip_prob is raised well past
# the registry default so the D=22 logreg rows actually take hits.
FAULT_ATTACKS = ("nan", "inf_overflow", "bitflip")
FAULT_BITFLIP_PROB = 0.5
# Cohort-size scaling cells (schema v3): the packed sim geomed/saga step
# with num_clients virtual clients feeding the same 16-slot cohort --
# gather/scatter + staleness weighting cost as C grows past W.
COHORT_CLIENTS = (64, 256)
# The memory/step trade-off cells (schema v2): saga vs lsvrg at the SAME
# (W, J, D) on the sim geomed workload, reporting resident VR-state bytes
# next to wall-clock (the O((J+1)D) table vs O(2D) snapshot story).
VR_TRADEOFF_VRS = ("saga", "lsvrg")
# The gate's speedup floor applies to the aggregation-dominated sim cells
# (vr=saga -- the lsvrg cells are a trade-off readout, not a packing claim).
GATE_SPEEDUP_CELLS = ("geomed", "krum")
GATE_SPEEDUP_FLOOR = 1.3
# "No slower" allows this much wall-clock noise on ~1.0x cells.
GATE_NOISE_MARGIN = 1.15
# The gather/sharded cells time 8 forced XLA host devices time-slicing
# the runner's real cores, so their wall-clock is scheduler-dominated:
# repeated runs of the SAME binary spread the per-cell min statistic by
# ~20% (e.g. gather/geomed per-leaf min 560-662ms across five runs on a
# 1-core container).  They get a correspondingly wider "no slower"
# margin; the tight margin + speedup floor above remain the claims on
# the single-device sim cells, where the measurement is clean.
GATE_DIST_NOISE_MARGIN = 1.35

# Simulated-federation workload: a deep MLP with MANY small parameter
# blocks (34 leaves) -- per-leaf dispatch cost scales with the block count,
# packed cost does not.
MLP_LAYERS, MLP_HIDDEN = 16, 16
SIM_HONEST, SIM_BYZANTINE = 16, 4


def mlp_params(key, din=22, h=MLP_HIDDEN):
    p = {}
    ks = jax.random.split(key, MLP_LAYERS + 1)
    for i in range(MLP_LAYERS):
        p[f"w{i}"] = 0.3 * jax.random.normal(ks[i], (din if i == 0 else h, h))
        p[f"b{i}"] = jnp.zeros((h,))
    p["wout"] = 0.3 * jax.random.normal(ks[-1], (h,))
    p["bout"] = jnp.zeros(())
    return p


def mlp_loss(params, batch):
    x, y = batch["a"], batch["b"]
    for i in range(MLP_LAYERS):
        x = jnp.tanh(x @ params[f"w{i}"] + params[f"b{i}"])
    logit = x @ params["wout"] + params["bout"]
    return jnp.mean(jnp.logaddexp(0.0, -y * logit))


def sim_cfg(name: str, packed: bool, vr: str = "saga",
            num_clients: int = 0) -> RobustConfig:
    return RobustConfig(aggregator=name, vr=vr, attack="sign_flip",
                        num_byzantine=SIM_BYZANTINE, weiszfeld_iters=32,
                        num_groups=4, packed=packed, lsvrg_p=0.05,
                        num_clients=num_clients,
                        cohort_size=SIM_HONEST if num_clients else 0)


def time_steps(jstep, state, step_args, steps: int, reps: int) -> dict:
    """Per-step wall-clock: ``reps`` measurements of ``steps`` steps each
    (state threaded through, so donation works like the real loop)."""
    state = jstep(state, *step_args)[0]  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, _ = jstep(state, *step_args)
        jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
        times.append((time.perf_counter() - t0) / steps)
    return {"wall_us_mean": sum(times) / len(times) * 1e6,
            "wall_us_min": min(times) * 1e6}


def bench_sim(name: str, packed: bool, steps: int, reps: int, wd,
              vr: str = "saga", num_clients: int = 0) -> dict:
    cfg = sim_cfg(name, packed, vr, num_clients)
    init_fn, step_fn = make_federated_step(mlp_loss, wd, cfg,
                                           get_optimizer("sgd", 0.05))
    state = init_fn(mlp_params(jax.random.PRNGKey(1)), jax.random.PRNGKey(3))
    # Resident VR-state bytes (the schema-v2 memory column of the saga vs
    # lsvrg trade-off), cross-checked against the reducer's own accounting.
    # Under client-scale virtualization the tables are per CLIENT, so the
    # effective row count is num_clients, not the cohort width.
    vr_leaves = jax.tree_util.tree_leaves(state.vr)
    vr_bytes = sum(int(l.size) * l.dtype.itemsize for l in vr_leaves)
    p = mlp_params(jax.random.PRNGKey(1))
    coords = sum(int(x.size) for x in jax.tree_util.tree_leaves(p))
    j = jax.tree_util.tree_leaves(wd)[0].shape[1]
    expect = cfg.reducer().memory_elems(num_clients or SIM_HONEST, j, coords)
    got = sum(int(l.size) for l in vr_leaves)
    assert got == expect, f"memory_elems drift for {vr}: {got} != {expect}"
    jstep = steps_lib.compile_train_step(step_fn)
    t = time_steps(jstep, state, (), steps, reps)
    return {
        "path": "sim", "aggregator": name, "packed": packed,
        "num_workers": SIM_HONEST + SIM_BYZANTINE,
        "num_byzantine": SIM_BYZANTINE, "vr": cfg.vr, "attack": cfg.attack,
        "num_samples": j, "vr_state_bytes": vr_bytes,
        "num_clients": num_clients, "message_dtype": cfg.message_dtype,
        "leaves": len(jax.tree_util.tree_leaves(p)),
        "coords": coords,
        "steps": steps, "reps": reps, **t,
    }


def bench_distributed(name: str, comm: str, packed: bool, steps: int,
                      reps: int, dist) -> dict:
    from repro.configs.base import TrainConfig
    from repro.launch.train import make_batch
    model, mesh, cfg_model = dist
    w = mesh_lib.num_workers(mesh)
    robust = RobustConfig(aggregator=name, vr="sgd", attack="sign_flip",
                          num_byzantine=1, comm=comm, weiszfeld_iters=16,
                          num_groups=2, packed=packed)
    step_fn, _, _ = steps_lib.make_train_step(
        model, robust, TrainConfig(optimizer="sgd", lr=0.05), mesh)
    with compat.use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": (),
                 "step": jnp.zeros((), jnp.int32)}
        batch = make_batch(jax.random.PRNGKey(5), cfg_model, w, 1, 32)
        jstep = steps_lib.compile_train_step(step_fn)
        t = time_steps(jstep, state, (batch, jax.random.PRNGKey(9)),
                       steps, reps)
    leaves = jax.tree_util.tree_leaves(model.param_structs())
    return {
        "path": comm, "aggregator": name, "packed": packed,
        "num_workers": w, "num_byzantine": 1, "vr": "sgd",
        "vr_state_bytes": 0, "message_dtype": robust.message_dtype,
        "attack": "sign_flip", "leaves": len(leaves),
        "coords": sum(math.prod(s.shape) for s in leaves),
        "steps": steps, "reps": reps, **t,
    }


def bench_grid(wd, batch, steps: int = GRID_STEPS) -> list:
    """The schema-v4 robustness grid: attack x wire format x rule cells on
    the Sec. V-A logreg federation (SIM_HONEST honest + SIM_BYZANTINE
    Byzantine when the attack is live), each reporting the honest-data
    loss after ``steps`` Byrd-SAGA steps plus the usual wall-clock."""
    from repro.data import logreg_loss
    loss = logreg_loss(0.01)
    j = jax.tree_util.tree_leaves(wd)[0].shape[1]
    rows = []
    for name in GRID_AGGREGATORS:
        for attack in GRID_ATTACKS:
            nb = 0 if attack == "none" else SIM_BYZANTINE
            for dtype in GRID_DTYPES:
                cfg = RobustConfig(aggregator=name, vr="saga", attack=attack,
                                   num_byzantine=nb, weiszfeld_iters=32,
                                   trim=SIM_BYZANTINE, straggler_k=4,
                                   message_dtype=dtype)
                init_fn, step_fn = make_federated_step(
                    loss, wd, cfg, get_optimizer("sgd", 0.05))
                # Fresh params per cell: the compiled step DONATES its
                # state, so a shared init tree would be a dead buffer by
                # the second cell.
                state = init_fn({"w": jnp.zeros((22,), jnp.float32)},
                                jax.random.PRNGKey(3))
                jstep = steps_lib.compile_train_step(step_fn)
                state = jstep(state)[0]          # compile + warm
                t0 = time.perf_counter()
                for _ in range(steps):
                    state, _ = jstep(state)
                jax.block_until_ready(state.params["w"])
                wall_us = (time.perf_counter() - t0) / steps * 1e6
                final = float(loss(state.params, batch))
                rows.append({
                    "path": "grid", "aggregator": name, "packed": True,
                    "num_workers": SIM_HONEST + nb, "num_byzantine": nb,
                    "vr": cfg.vr, "attack": attack, "message_dtype": dtype,
                    "vr_state_bytes": sum(
                        int(l.size) * l.dtype.itemsize
                        for l in jax.tree_util.tree_leaves(state.vr)),
                    "num_samples": j, "num_clients": 0,
                    "leaves": 1, "coords": 22, "steps": steps, "reps": 1,
                    "wall_us_mean": wall_us, "wall_us_min": wall_us,
                    "final_honest_loss": final,
                })
                print(f"  grid    {name:14s} {attack:10s} {dtype:9s} "
                      f"loss={final:.4f}")
    return rows


def bench_fault(wd, batch, steps: int = GRID_STEPS) -> list:
    """The schema-v5 fault-containment grid: fault x rule x guards cells on
    the same logreg federation as :func:`bench_grid`.  Guards-on runs must
    stay finite (the poisoned rows get aggregation weight exactly 0);
    guards-off nan runs go non-finite, which the row records as
    ``loss_finite`` instead of a NaN loss value the schema checker (and
    JSON) cannot represent."""
    import math as _math

    from repro.data import logreg_loss
    loss = logreg_loss(0.01)
    j = jax.tree_util.tree_leaves(wd)[0].shape[1]
    rows = []
    for name in GRID_AGGREGATORS:
        for attack in FAULT_ATTACKS:
            for guards in (False, True):
                cfg = RobustConfig(aggregator=name, vr="saga", attack=attack,
                                   num_byzantine=SIM_BYZANTINE,
                                   weiszfeld_iters=32, trim=SIM_BYZANTINE,
                                   bitflip_prob=FAULT_BITFLIP_PROB,
                                   guards=guards)
                init_fn, step_fn = make_federated_step(
                    loss, wd, cfg, get_optimizer("sgd", 0.05))
                state = init_fn({"w": jnp.zeros((22,), jnp.float32)},
                                jax.random.PRNGKey(3))
                jstep = steps_lib.compile_train_step(step_fn)
                state = jstep(state)[0]          # compile + warm
                t0 = time.perf_counter()
                for _ in range(steps):
                    state, _ = jstep(state)
                jax.block_until_ready(state.params["w"])
                wall_us = (time.perf_counter() - t0) / steps * 1e6
                final = float(loss(state.params, batch))
                finite = _math.isfinite(final)
                row = {
                    "path": "fault", "aggregator": name, "packed": True,
                    "num_workers": SIM_HONEST + SIM_BYZANTINE,
                    "num_byzantine": SIM_BYZANTINE, "vr": cfg.vr,
                    "attack": attack, "message_dtype": cfg.message_dtype,
                    "guards": guards, "loss_finite": finite,
                    "vr_state_bytes": sum(
                        int(l.size) * l.dtype.itemsize
                        for l in jax.tree_util.tree_leaves(state.vr)),
                    "num_samples": j, "num_clients": 0,
                    "leaves": 1, "coords": 22, "steps": steps, "reps": 1,
                    "wall_us_mean": wall_us, "wall_us_min": wall_us,
                }
                if finite:
                    row["final_honest_loss"] = final
                rows.append(row)
                print(f"  fault   {name:14s} {attack:12s} "
                      f"guards={guards!s:5s} loss="
                      f"{final if finite else float('nan'):.4f}")
    return rows


def run_gate(rows) -> list:
    """The step-level perf gate: packed must never lose beyond noise, and
    must beat the floor on the aggregation-dominated sim cells.  Gates on
    ``wall_us_min`` -- the minimum over reps is the standard noise-robust
    microbenchmark statistic (scheduler interference only ever ADDS
    time).  Cells are keyed by (path, aggregator, vr, num_clients,
    message_dtype, packed) since v4 (the lsvrg trade-off, cohort-scaling
    and wire-format cells must not collide with the saga sweep); the
    speedup floor stays a vr=saga f32 full-participation claim, and the
    packed-only cohort/grid cells have no per-leaf pair so the gate skips
    them."""
    by_key = {(r["path"], r["aggregator"], r["vr"], r.get("num_clients", 0),
               r.get("message_dtype", "float32"), r["packed"]):
              r["wall_us_min"] for r in rows}
    failures = []
    for (path, name, vr, nc, dtype, packed), us in sorted(by_key.items()):
        if packed:
            continue
        packed_us = by_key.get((path, name, vr, nc, dtype, True))
        if packed_us is None:
            continue
        ratio = us / packed_us
        margin = GATE_NOISE_MARGIN if path == "sim" else GATE_DIST_NOISE_MARGIN
        if packed_us > us * margin:
            failures.append(
                f"{path}/{name}/{vr}: packed {packed_us:.0f}us is slower "
                f"than per-leaf {us:.0f}us beyond the "
                f"{margin}x margin")
        if path == "sim" and vr == "saga" and nc == 0 \
                and dtype == "float32" \
                and name in GATE_SPEEDUP_CELLS \
                and ratio < GATE_SPEEDUP_FLOOR:
            failures.append(
                f"sim/{name}/{vr}: packed speedup {ratio:.2f}x is below "
                f"the {GATE_SPEEDUP_FLOOR}x floor")
    return failures


def distributed_rows(names, steps: int, reps: int, mesh) -> list:
    from repro.configs import get_config
    from repro.models.api import build_model
    cfg_model = get_config("mamba2-130m").reduced()
    model = build_model(cfg_model, remat=False, q_chunk=32, kv_chunk=32,
                        loss_chunk=32)
    dist = (model, mesh, cfg_model)
    rows = []
    for name in names:
        for comm in ("gather", "sharded"):
            for packed in (False, True):
                r = bench_distributed(name, comm, packed,
                                      max(steps // 5, 2), reps, dist)
                rows.append(r)
                print(f"  {comm:7s} {name:18s} packed={packed!s:5s} "
                      f"{r['wall_us_mean']:10.0f} us/step")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"only {QUICK_AGGREGATORS} (the CI artifact setting)")
    ap.add_argument("--gate", action="store_true",
                    help="fail (exit 1) on packed-path perf regressions")
    ap.add_argument("--steps", type=int, default=30,
                    help="steps per timing rep (sim; distributed uses 1/5)")
    ap.add_argument("--reps", type=int, default=5)
    cells = ap.add_mutually_exclusive_group()
    cells.add_argument("--skip-distributed", action="store_true",
                       help="simulation cells only (no mesh)")
    cells.add_argument("--distributed-only", action="store_true",
                       help="gather/sharded cells only; needs >= 4 devices")
    ap.add_argument("--out", default="BENCH_step.json")
    args = ap.parse_args()
    enable_compile_cache()

    names = QUICK_AGGREGATORS if args.quick else AGGREGATOR_NAMES
    mesh = None
    if not args.skip_distributed:
        mesh = mesh_lib.make_host_mesh((4, max(jax.device_count() // 4, 1)),
                                       ("data", "model"))
    rows = []
    if not args.distributed_only:
        data = ijcnn1_like(jax.random.PRNGKey(0), n=400)
        wd = partition({"a": data.x, "b": data.y}, SIM_HONEST, seed=1)
        for name in names:
            for packed in (False, True):
                r = bench_sim(name, packed, args.steps, args.reps, wd)
                rows.append(r)
                print(f"  sim     {name:18s} packed={packed!s:5s} "
                      f"{r['wall_us_mean']:10.0f} us/step")
        # Memory/step trade-off cells (v2): lsvrg on the geomed workload at
        # the same (W, J, D) as the saga sweep above -- BENCH_step.json then
        # holds both VRs' resident state bytes and wall-clock side by side.
        for packed in (False, True):
            r = bench_sim("geomed", packed, args.steps, args.reps, wd,
                          vr="lsvrg")
            rows.append(r)
            print(f"  sim     geomed/lsvrg      packed={packed!s:5s} "
                  f"{r['wall_us_mean']:10.0f} us/step "
                  f"(state {r['vr_state_bytes']} B)")
        # Cohort-size scaling cells (v3): client-scale virtualization on
        # the packed geomed/saga workload -- C virtual clients, 16-slot
        # cohort.  Packed only: staleness row_weights route every rule
        # through the flat engines, so there is no per-leaf pair.
        for n_clients in COHORT_CLIENTS:
            cwd = partition({"a": data.x, "b": data.y}, n_clients, seed=1)
            r = bench_sim("geomed", True, args.steps, args.reps, cwd,
                          num_clients=n_clients)
            rows.append(r)
            print(f"  sim     geomed/C={n_clients:<5d}    packed=True  "
                  f"{r['wall_us_mean']:10.0f} us/step "
                  f"(state {r['vr_state_bytes']} B)")
        # Robustness grid cells (v4): attack x wire format x rule.
        rows += bench_grid(wd, {"a": data.x, "b": data.y},
                           steps=GRID_STEPS if not args.quick else 100)
        # Fault-containment cells (v5): fault x rule x guards.
        rows += bench_fault(wd, {"a": data.x, "b": data.y},
                            steps=GRID_STEPS if not args.quick else 100)
    if mesh is not None:
        rows += distributed_rows(names, args.steps, args.reps, mesh)

    report = {
        "schema": SCHEMA,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "sim_workers": [SIM_HONEST, SIM_BYZANTINE],
        "gate": {"speedup_cells": list(GATE_SPEEDUP_CELLS),
                 "speedup_floor": GATE_SPEEDUP_FLOOR,
                 "noise_margin": GATE_NOISE_MARGIN,
                 "dist_noise_margin": GATE_DIST_NOISE_MARGIN,
                 "keyed_by": ["path", "aggregator", "vr", "num_clients",
                              "message_dtype", "packed"]},
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"\nwrote {args.out} ({len(rows)} rows)\n")

    print("| path | aggregator | vr | per-leaf us | packed us | speedup | state bytes |")
    print("|------|------------|----|-------------|-----------|---------|-------------|")
    by_key = {(r["path"], r["aggregator"], r["vr"],
               r.get("num_clients", 0), r["packed"]): r
              for r in rows if r["path"] not in ("grid", "fault")}
    for (path, name, vr, nc, packed), r in sorted(by_key.items()):
        if packed:
            continue
        pk = by_key[(path, name, vr, nc, True)]
        print(f"| {path} | {name} | {vr} | {r['wall_us_mean']:.0f} | "
              f"{pk['wall_us_mean']:.0f} | "
              f"{r['wall_us_mean'] / pk['wall_us_mean']:.2f}x | "
              f"{pk.get('vr_state_bytes', 0)} |")
    cohort = sorted((k, r) for k, r in by_key.items() if k[3])
    if cohort:
        print("\n| clients | cohort | packed us | state bytes |")
        print("|---------|--------|-----------|-------------|")
        for (path, name, vr, nc, packed), r in cohort:
            print(f"| {nc} | {SIM_HONEST} | {r['wall_us_mean']:.0f} | "
                  f"{r['vr_state_bytes']} |")
    grid = [r for r in rows if r["path"] == "grid"]
    if grid:
        print("\n| aggregator | attack | " +
              " | ".join(GRID_DTYPES) + " |  (final honest loss)")
        print("|------------|--------|" + "----|" * len(GRID_DTYPES))
        cell = {(r["aggregator"], r["attack"], r["message_dtype"]):
                r["final_honest_loss"] for r in grid}
        for name in GRID_AGGREGATORS:
            for attack in GRID_ATTACKS:
                vals = " | ".join(f"{cell[(name, attack, d)]:.4f}"
                                  for d in GRID_DTYPES)
                print(f"| {name} | {attack} | {vals} |")

    fault = [r for r in rows if r["path"] == "fault"]
    if fault:
        print("\n| aggregator | fault | guards off | guards on |"
              "  (final honest loss; -- = non-finite)")
        print("|------------|-------|------------|-----------|")
        cell = {(r["aggregator"], r["attack"], r["guards"]):
                (f"{r['final_honest_loss']:.4f}" if r["loss_finite"]
                 else "--") for r in fault}
        for name in GRID_AGGREGATORS:
            for attack in FAULT_ATTACKS:
                print(f"| {name} | {attack} | {cell[(name, attack, False)]} "
                      f"| {cell[(name, attack, True)]} |")

    if args.gate:
        failures = run_gate(rows)
        if failures:
            # Up to two retry rounds for failing cells: on a loaded small
            # container a background burst during either side's timing
            # window can fake a regression; fresh measurements settle it
            # (min-across-runs -- scheduler interference only ever ADDS
            # time, so the min converges while a TRUE regression keeps
            # failing every round).  Sim cells re-time just the failing
            # pairs; a distributed failure re-times the mesh cells (the
            # noisiest on the CPU -- forced host devices time-slice the
            # real cores, so a single scheduler burst skews one side of a
            # pair by 20%+).  The retried rows are folded back into the
            # report and the JSON is re-dumped, so the uploaded artifact
            # always matches the gate verdict.
            retried = False

            def fold(fresh_rows):
                nonlocal retried
                fresh_by_key = {
                    (f["path"], f["aggregator"], f["vr"],
                     f.get("num_clients", 0),
                     f.get("message_dtype", "float32"), f["packed"]): f
                    for f in fresh_rows}
                for r in rows:
                    fresh = fresh_by_key.get(
                        (r["path"], r["aggregator"], r["vr"],
                         r.get("num_clients", 0),
                         r.get("message_dtype", "float32"), r["packed"]))
                    if fresh and fresh["wall_us_min"] < r["wall_us_min"]:
                        r.update(wall_us_min=fresh["wall_us_min"],
                                 wall_us_mean=fresh["wall_us_mean"])
                        retried = True

            for _ in range(2):
                failing = {tuple(f.split(":")[0].split("/"))
                           for f in failures}             # (path, name, vr)
                for path, name, vr in sorted(failing):
                    if path != "sim":
                        continue
                    # 3x reps on retry: the sim cells are ms-scale, so
                    # extra samples are nearly free and min-of-more-reps
                    # is the stronger form of the same noise-floor
                    # statistic.
                    fold([bench_sim(name, packed, args.steps,
                                    args.reps * 3, wd, vr=vr)
                          for packed in (False, True)])
                if any(p in ("gather", "sharded") for p, _, _ in failing):
                    fold(distributed_rows(names, args.steps, args.reps, mesh))
                failures = run_gate(rows)
                if not failures:
                    break
            if retried:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)
                print(f"rewrote {args.out} with retried cells")
        if failures:
            print("\nSTEP PERF GATE FAILED:")
            for fmsg in failures:
                print(" ", fmsg)
            raise SystemExit(1)
        print("\nstep perf gate OK")


if __name__ == "__main__":
    main()
