"""Topology x aggregator x attack x gossip x schedule sweep for
decentralized training.

For every cell this runs the simulated decentralized federation
(``repro.topology.make_decentralized_step``, DESIGN.md Secs. 6-7) on the
paper's logistic-regression workload, times the jitted per-step wall-clock,
and records the final mean honest loss plus the honest consensus distance.
Two grids are swept:

* the PR-3 fixed-graph grid: (topology, aggregator, attack) with gradient
  gossip on a static schedule;
* the gossip grid: (gossip mode x graph schedule) -- gradient vs PARAMETER
  gossip on static / cyclic / per-round-resampled erdos_renyi graphs,
  geomed under sign_flip (the arXiv:2308.05292 setting).

Emits ``BENCH_topologies.json`` and a markdown table on stdout; any cell
that RAISES aborts the script with a non-zero exit, which is exactly how CI
uses it (a registry aggregator or gossip mode that stops working on some
graph/schedule fails the job, not just a test marker).

    PYTHONPATH=src python benchmarks/bench_topologies.py [--quick] \\
        [--steps N] [--reps R] [--out BENCH_topologies.json]

``--quick`` (the CI artifact setting) restricts to the structurally
distinct corners: {ring, complete} x {geomed, krum, mean} x {none,
sign_flip}, plus both gossip modes on {static, erdos_renyi} schedules.
The full sweep covers every registry aggregator on ring / torus2d /
complete / erdos_renyi under none / sign_flip / alie, and both gossip
modes on all three schedules.

Reading the numbers: the star-free claims being validated are orderings --
robust rules keep the final loss near the attack-free value on every
connected graph while ``mean`` degrades, consensus distance shrinks as the
(joint) spectral gap grows, and parameter gossip tracks gradient gossip's
error floor under attack.  Wall-clock on this CPU container characterizes
the dense (N, N, p) exchange + masked-rule compute, not network latency.
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AGGREGATOR_NAMES, RobustConfig, make_federated_step
from repro.core.robust_step import resolve_schedule
from repro.data import ijcnn1_like, logreg_loss, partition
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import get_optimizer

SCHEMA = "BENCH_topologies/v2"

HONEST, BYZ = 10, 2
TOPOLOGIES = ("ring", "torus2d", "complete", "erdos_renyi")
ATTACKS = ("none", "sign_flip", "alie")

QUICK_TOPOLOGIES = ("ring", "complete")
QUICK_AGGREGATORS = ("geomed", "krum", "mean")
QUICK_ATTACKS = ("none", "sign_flip")

# The gossip grid: (gossip mode x schedule) cells.  "cyclic" rotates the
# named list; "erdos_renyi" resamples per round (period below).
GOSSIP_MODES = ("gradient", "params")
SCHEDULES = ("static", "cyclic", "erdos_renyi")
QUICK_SCHEDULES = ("static", "erdos_renyi")
SCHEDULE_PERIOD = 3
SCHEDULE_TOPOLOGY = {"static": "ring", "cyclic": "ring,complete",
                     "erdos_renyi": "ring"}


def bench_cell(topo_name: str, agg: str, attack: str, *, steps: int,
               reps: int, seed: int, gossip: str = "gradient",
               schedule: str = "static") -> dict:
    data = ijcnn1_like(jax.random.PRNGKey(0), n=1200)
    wd = partition({"a": data.x, "b": data.y}, HONEST, seed=1)
    loss_fn = logreg_loss(0.01)
    b = BYZ if attack != "none" else 0
    cfg = RobustConfig(aggregator=agg, vr="saga", attack=attack,
                       num_byzantine=b, weiszfeld_iters=32,
                       topology=topo_name, topology_seed=seed,
                       gossip=gossip, schedule=schedule,
                       schedule_period=SCHEDULE_PERIOD)
    sched = resolve_schedule(cfg, HONEST + b)
    init_fn, step_fn = make_federated_step(
        loss_fn, wd, cfg, get_optimizer("sgd", 0.02), schedule=sched)
    state = init_fn({"w": jnp.zeros((22,), jnp.float32)},
                    jax.random.PRNGKey(2))
    step = jax.jit(step_fn)
    state, metrics = step(state)        # compile + warm
    jax.block_until_ready(state.params)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, metrics = step(state)
        jax.block_until_ready(state.params)
        times.append(time.perf_counter() - t0)
    for _ in range(max(steps - reps - 1, 0)):
        state, metrics = step(state)
    final_loss = float(np.mean([
        loss_fn({"w": state.params["w"][i]},
                {"a": wd["a"][i], "b": wd["b"][i]})
        for i in range(HONEST)]))
    return {
        "topology": topo_name, "aggregator": agg, "attack": attack,
        "gossip": gossip, "schedule": schedule,
        "schedule_period": sched.period,
        "num_nodes": HONEST + b, "num_byzantine": b, "steps": steps,
        "reps": reps, "spectral_gap": sched.joint_spectral_gap(),
        "wall_us_mean": sum(times) / len(times) * 1e6,
        "wall_us_min": min(times) * 1e6,
        "final_honest_loss": final_loss,
        "consensus_dist": float(metrics["consensus_dist"]),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"only {QUICK_TOPOLOGIES} x {QUICK_AGGREGATORS} x "
                    f"{QUICK_ATTACKS} (the CI artifact setting)")
    ap.add_argument("--steps", type=int, default=120,
                    help="training steps per cell (final-loss horizon)")
    ap.add_argument("--reps", type=int, default=10,
                    help="timed steps per cell")
    ap.add_argument("--seed", type=int, default=0,
                    help="erdos_renyi topology seed")
    ap.add_argument("--out", default="BENCH_topologies.json")
    args = ap.parse_args()
    enable_compile_cache()

    topologies = QUICK_TOPOLOGIES if args.quick else TOPOLOGIES
    aggregators = QUICK_AGGREGATORS if args.quick else AGGREGATOR_NAMES
    attacks = QUICK_ATTACKS if args.quick else ATTACKS
    schedules = QUICK_SCHEDULES if args.quick else SCHEDULES

    rows = []
    for topo_name in topologies:
        for agg in aggregators:
            for attack in attacks:
                r = bench_cell(topo_name, agg, attack, steps=args.steps,
                               reps=args.reps, seed=args.seed)
                rows.append(r)
                print(f"  {topo_name:12s} {agg:18s} {attack:10s} "
                      f"{r['wall_us_mean']:9.0f} us/step "
                      f"loss={r['final_honest_loss']:.4f} "
                      f"consensus={r['consensus_dist']:.5f}")

    # The gossip-mode x schedule grid (geomed under sign_flip): parameter
    # gossip must hold an error floor comparable to gradient gossip on
    # every schedule, and a raising cell fails CI like any other.
    for gossip in GOSSIP_MODES:
        for schedule in schedules:
            r = bench_cell(SCHEDULE_TOPOLOGY[schedule], "geomed",
                           "sign_flip", steps=args.steps, reps=args.reps,
                           seed=args.seed, gossip=gossip, schedule=schedule)
            rows.append(r)
            print(f"  gossip={gossip:8s} schedule={schedule:12s} "
                  f"{r['wall_us_mean']:9.0f} us/step "
                  f"loss={r['final_honest_loss']:.4f} "
                  f"consensus={r['consensus_dist']:.5f}")

    report = {
        "schema": SCHEMA,
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "num_honest": HONEST,
        "num_byzantine": BYZ,
        "steps": args.steps,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"\nwrote {args.out} ({len(rows)} rows)\n")

    print("| topology | aggregator | attack | gossip | schedule | us/step "
          "| final loss | consensus |")
    print("|----------|------------|--------|--------|----------|---------"
          "|------------|-----------|")
    for r in rows:
        print(f"| {r['topology']} | {r['aggregator']} | {r['attack']} | "
              f"{r['gossip']} | {r['schedule']} | "
              f"{r['wall_us_mean']:.0f} | {r['final_honest_loss']:.4f} | "
              f"{r['consensus_dist']:.5f} |")


if __name__ == "__main__":
    main()
