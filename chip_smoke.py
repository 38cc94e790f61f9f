#!/usr/bin/env python3
"""Chip smoke: drive the Byzantine-robust trainer's main paths once on a TPU.

    python3 chip_smoke.py               # one chip: the sim and train phases
    python3 chip_smoke.py --four-chips  # four chips: the mesh path only

Phases (one process holds the chip; no phase's exception is caught):

* ``sim``   -- the paper's workload (``configs/byrd_logreg.py``: 50 honest +
  20 sign-flip Byzantine workers, IJCNN1-sized data) through
  ``repro.core.make_federated_step`` with Byrd-SAGA (SAGA + geomed), on the
  chip and, as the reference, on the host CPU in the same process.
* ``train`` -- ``repro.launch.train.main`` on full-width mamba2-130m
  (~129M params), default mesh, SAGA + geomed, seq 128.
* ``--four-chips`` -- the same trainer on a 4x1 mesh (one worker per chip,
  one sign-flip Byzantine worker) with ``--comm gather`` vs ``--comm
  sharded``, the ring topology whose sharded trimmed mean takes the fused
  ``masked_neighbor_reduce`` kernel, and that kernel against the jnp masked
  engine on one full-width gradient stack.

The last line of stdout is the JSON result, printed only when every check
passed on a TPU; anything else exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Tolerances (reasons in CHANGES.md).
SIM_RTOL = 1e-4         # final honest loss, chip vs host CPU
SIM_PARAM_RTOL = 5e-4   # ||w_chip - w_cpu|| / ||w_cpu|| after the run
LOSS0_ATOL = 0.5        # step-0 LM loss vs ln(vocab_size), nats
COMM_RTOL = 1e-3        # per-step loss and agg_norm, gather vs sharded
KERNEL_TOL = 1e-5       # |fused - jnp| <= tol * (1 + |jnp|)

SIM_STEPS = 300
IJCNN1_ROWS = 49_990    # the real IJCNN1 training set (data/synthetic.py)
TRAIN_STEPS = 6
MESH_STEPS = 4


def _import_repo():
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chip_smoke.py: no repro package under {src}; "
                         "run it from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke.py: FAILED: {what}")


# ---------------------------------------------------------------------------
# sim: the simulated federation, chip vs host CPU
# ---------------------------------------------------------------------------

def _sim_run(device, data, steps: int):
    """Final params of ``steps`` Byrd-SAGA steps with everything on ``device``."""
    import jax
    import jax.numpy as jnp
    from repro.configs.byrd_logreg import CONFIG as LR
    from repro.core import RobustConfig, make_federated_step
    from repro.data import logreg_loss, partition
    from repro.optim import get_optimizer

    with jax.default_device(device):
        batch = {"a": jnp.asarray(data["a"]), "b": jnp.asarray(data["b"])}
        worker_data = partition(batch, LR.num_honest, seed=1)
        cfg = RobustConfig(aggregator="geomed", vr="saga", attack="sign_flip",
                           num_byzantine=LR.num_byzantine)
        init_fn, step_fn = make_federated_step(
            logreg_loss(LR.rho), worker_data, cfg,
            get_optimizer("sgd", LR.lr_saga))
        state = init_fn({"w": jnp.zeros((data["a"].shape[1],), jnp.float32)},
                        jax.random.PRNGKey(1))
        jstep = jax.jit(step_fn)
        for _ in range(steps):
            state, _ = jstep(state)
        return jax.device_get(state.params)


def sim_phase(*, n: int = IJCNN1_ROWS, steps: int = SIM_STEPS) -> dict:
    """Byrd-SAGA on the default device and on the host CPU; both final
    params are scored on the CPU in f32, so the comparison sees only the
    training trajectories."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.byrd_logreg import CONFIG as LR
    from repro.data import ijcnn1_like, logreg_loss

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ds = ijcnn1_like(jax.random.PRNGKey(0), n=n)
        data = {"a": jax.device_get(ds.x), "b": jax.device_get(ds.y)}
    dev = jax.devices()[0]
    p_dev = _sim_run(dev, data, steps)
    p_cpu = _sim_run(cpu, data, steps)
    loss = logreg_loss(LR.rho)
    with jax.default_device(cpu):
        full = {"a": jnp.asarray(data["a"]), "b": jnp.asarray(data["b"])}
        l_dev = float(loss(jax.device_put(p_dev, cpu), full))
        l_cpu = float(loss(jax.device_put(p_cpu, cpu), full))
    w_dev, w_cpu = np.asarray(p_dev["w"]), np.asarray(p_cpu["w"])
    out = {"workers": [LR.num_honest, LR.num_byzantine], "rows": n,
           "steps": steps, "device": dev.platform, "loss_device": l_dev,
           "loss_cpu": l_cpu, "rel_diff": abs(l_dev - l_cpu) / abs(l_cpu),
           "param_rel_diff": float(np.linalg.norm(w_dev - w_cpu)
                                   / np.linalg.norm(w_cpu))}
    print("sim:", json.dumps(out))
    _check(math.isfinite(l_dev), f"sim loss on {dev.platform} not finite")
    _check(out["rel_diff"] <= SIM_RTOL,
           f"sim final honest loss {l_dev} vs CPU reference {l_cpu} "
           f"(rel {out['rel_diff']:.3g} > {SIM_RTOL})")
    _check(out["param_rel_diff"] <= SIM_PARAM_RTOL,
           f"sim final params vs CPU reference: rel "
           f"{out['param_rel_diff']:.3g} > {SIM_PARAM_RTOL}")
    return out


# ---------------------------------------------------------------------------
# train: the LM trainer through its CLI entry point
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _compile_seconds():
    """Yield a one-element list that sums jax's backend-compile durations
    while the context is open."""
    import jax
    total = [0.0]

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def run_train(args: list[str], *, label: str, every_step: bool = True
              ) -> list[dict]:
    """``repro.launch.train.main(args)``; returns its metric rows -- every
    step's from a metrics log, or with ``every_step=False`` only the last
    (no log, so no second compile for its meta) -- after checking that each
    returned loss is finite."""
    from repro.launch import train as train_lib

    with tempfile.TemporaryDirectory() as log_dir, _compile_seconds() as comp:
        log = ["--log-dir", log_dir, "--log-every", "1"] if every_step else []
        t0 = time.perf_counter()
        last = train_lib.main(args + log)
        wall = time.perf_counter() - t0
        rows = [last]
        if every_step:
            with open(os.path.join(log_dir, "metrics.jsonl")) as f:
                rows = [json.loads(line) for line in f]
    _check(bool(last) and rows[-1]["step"] == last["step"],
           f"{label}: metrics log and main()'s last row disagree")
    print(f"{label}: " + " ".join(
        f"step {r['step']} loss={r['loss']!r} agg_norm={r['agg_norm']!r}"
        for r in rows))
    # Information only, not a measurement: the host wall of the whole call
    # less its backend compiles, per step, bounds the steady step from above
    # (it still holds model init and data set-up).
    steps = last["step"] + 1
    print(f"{label}: compile_s {comp[0]:.3f} wall_s {wall:.3f} "
          f"steady_step_s<= {(wall - comp[0]) / steps:.3f}")
    _check(all(math.isfinite(r["loss"]) for r in rows),
           f"{label}: non-finite loss")
    return rows


def _mamba2(reduced: bool):
    """(config, parameter count) of mamba2-130m, counted from shapes."""
    import jax
    from repro.configs import get_config
    from repro.models.api import build_model

    cfg = get_config("mamba2-130m")
    if reduced:
        cfg = cfg.reduced()
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    return cfg, sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))


def _train_args(*extra: str, steps: int, seq: int, reduced: bool) -> list[str]:
    return (["--arch", "mamba2-130m", "--steps", str(steps), "--seq", str(seq),
             "--vr", "saga", *extra] + (["--reduced"] if reduced else []))


def train_phase(*, reduced: bool = False, steps: int = TRAIN_STEPS,
                seq: int = 128) -> list[dict]:
    """Full-width mamba2-130m on the default mesh: step-0 loss near
    ln(vocab_size), every loss finite."""
    cfg, n_params = _mamba2(reduced)
    print(f"train: mamba2-130m{' (reduced)' if reduced else ''} "
          f"{n_params} params, vocab {cfg.vocab_size}")
    rows = run_train(_train_args("--aggregator", "geomed", steps=steps,
                                 seq=seq, reduced=reduced), label="train")
    ln_v = math.log(cfg.vocab_size)
    _check(abs(rows[0]["loss"] - ln_v) <= LOSS0_ATOL,
           f"train: step-0 loss {rows[0]['loss']} vs ln(V)={ln_v:.4f}")
    return rows


# ---------------------------------------------------------------------------
# four chips: comm modes, the ring kernel path, and the kernel check
# ---------------------------------------------------------------------------

def _devices_of(x) -> str:
    return ",".join(str(d.id) for d in sorted(x.sharding.device_set,
                                               key=lambda d: d.id))


def _ring_aggregate(mesh, use_kernel: bool, trim: int = 1):
    """jit of ``decentralized_aggregate`` on a ring over the mesh's workers
    (sharded comm, trimmed mean, one sign-flip Byzantine node), mapping a
    (W, dim) gradient stack to the (W, dim) per-node aggregates."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import RobustConfig
    from repro.topology import graphs
    from repro.topology.decentralized_step import decentralized_aggregate

    w = mesh.devices.shape[0]
    cfg = RobustConfig(aggregator="trimmed_mean", trim=trim, topology="ring",
                       comm="sharded", attack="sign_flip", num_byzantine=1)
    ring = graphs.ring(w)

    def node(g):
        out = decentralized_aggregate(
            {"g": g[0]}, cfg, ring, comm="sharded", worker_axes=("data",),
            model_axes=("model",), num_workers=w, key=jax.random.PRNGKey(3),
            use_topology_kernel=use_kernel)
        return out["g"][None]

    return jax.jit(compat.shard_map(node, mesh=mesh, in_specs=P("data"),
                                    out_specs=P("data"), check_vma=False))


def kernel_check(mesh, dim: int) -> float:
    """The ring aggregate with the fused kernel vs the jnp masked engine on
    one seeded (W, dim) gradient stack; returns the worst scaled gap."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    w = mesh.devices.shape[0]
    grads = jax.jit(lambda k: jax.random.normal(k, (w, dim), jnp.float32),
                    out_shardings=NamedSharding(mesh, P("data")))(
                        jax.random.PRNGKey(7))
    fused = _ring_aggregate(mesh, True)(grads)
    ref = _ring_aggregate(mesh, False)(grads)
    err = float(jax.jit(
        lambda a, b: jnp.max(jnp.abs(a - b) / (1.0 + jnp.abs(b))))(fused, ref))
    for name, x in (("grads", grads), ("fused", fused), ("jnp", ref)):
        print(f"kernel_check: {name} {x.shape} {x.dtype} on devices "
              f"[{_devices_of(x)}]")
    print(f"kernel_check: W={w} dim={dim} max scaled |fused - jnp| = {err:.3g}")
    _check(math.isfinite(err) and err <= KERNEL_TOL,
           f"fused masked_neighbor_reduce vs jnp engine: {err} > {KERNEL_TOL}")
    return err


def four_chip_phase(*, reduced: bool = False, steps: int = MESH_STEPS,
                    seq: int = 128) -> None:
    """The 4x1 mesh: gather vs sharded geomed, the ring's sharded trimmed
    mean (fused kernel by default on TPU), and the kernel check on a
    gradient stack of the model's width."""
    from repro import compat

    def mesh_args(aggregator, comm, *extra):
        return _train_args("--mesh", "4x1", "--attack", "sign_flip",
                           "--byzantine", "1", "--aggregator", aggregator,
                           "--comm", comm, *extra, steps=steps, seq=seq,
                           reduced=reduced)

    (g,) = run_train(mesh_args("geomed", "gather"), label="mesh/gather",
                     every_step=False)
    (s,) = run_train(mesh_args("geomed", "sharded"), label="mesh/sharded",
                     every_step=False)
    for key in ("loss", "agg_norm"):
        rel = abs(g[key] - s[key]) / max(abs(g[key]), 1e-12)
        _check(rel <= COMM_RTOL,
               f"last step {key}: gather {g[key]} vs sharded {s[key]} "
               f"(rel {rel:.3g} > {COMM_RTOL})")
    print(f"mesh: gather and sharded agree at step {g['step']} within rtol "
          f"{COMM_RTOL}")
    run_train(mesh_args("trimmed_mean", "sharded", "--topology", "ring"),
              label="mesh/ring-trimmed_mean", every_step=False)
    kernel_check(compat.make_mesh((4, 1), ("data", "model")),
                 _mamba2(reduced)[1])


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the four-chip mesh path and its comparisons "
                    "only")
    args = ap.parse_args(argv)
    _import_repo()
    # The sim phase's reference runs on the host CPU of the same process.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: no TPU (jax.devices()[0] is {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    print(f"jax {jax.__version__}, {len(jax.devices())} x {dev.device_kind}, "
          f"compile cache {enable_compile_cache()}")
    if args.four_chips:
        _check(len(jax.devices()) >= 4,
               f"--four-chips needs 4 chips, found {len(jax.devices())}")
        four_chip_phase()
        count = 4
    else:
        sim_phase()
        train_phase()
        count = len(jax.devices())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
