"""Runnable distributed training driver.

Entry point for the same code path the dry-run lowers: builds a mesh over
the local devices (chips, or forced host devices on the CPU), initializes
real params, and runs Byzantine-robust data-parallel training on synthetic
LM data.  On a four-chip host:

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \\
        --steps 20 --mesh 4x1 --aggregator geomed --attack sign_flip --byzantine 1

A CPU rehearsal of a mesh forces host devices from the caller's side
(never on a chip host):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --reduced \\
        --steps 20 --mesh 4x2 --aggregator geomed --attack sign_flip --byzantine 1

``main(argv)`` is also callable in-process and returns the last flushed
metric row.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro import telemetry
from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import packing
from repro.core.robust_step import RobustConfig
from repro.data.synthetic import token_stream
from repro.core import guards as guards_lib
from repro.launch import health as health_lib
from repro.launch import hlo_analysis
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.launch import sharding as shard_lib
from repro.launch import steps as steps_lib
from repro.models.api import build_model


def make_batch(key, cfg, num_workers: int, per_worker: int, seq: int):
    toks = jax.random.randint(key, (num_workers, per_worker, seq + 1),
                              0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    if cfg.family == "vlm":
        batch["image_emb"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 1),
            (num_workers, per_worker, cfg.num_prefix_tokens, cfg.d_model),
            jnp.float32).astype(cfg.dtype)
    if cfg.family == "audio":
        batch["audio_emb"] = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 2),
            (num_workers, per_worker, cfg.encoder_seq, cfg.d_model),
            jnp.float32).astype(cfg.dtype)
    return batch


def main(argv: Optional[Sequence[str]] = None) -> dict:
    """Run the CLI on ``argv`` (default: ``sys.argv[1:]``) and return the
    last flushed metric row (empty when no step ran)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-sized variant (CPU friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--per-worker-batch", type=int, default=2)
    ap.add_argument("--mesh", default="", help="e.g. 4x2 (data x model) or "
                    "2x2x2 (pod x data x model, multi-pod worker axes); "
                    "default: all devices on the data axis")
    ap.add_argument("--aggregator", default="geomed")
    ap.add_argument("--attack", default="none")
    ap.add_argument("--byzantine", type=int, default=0)
    ap.add_argument("--comm", default="gather", choices=["gather", "sharded"])
    ap.add_argument("--topology", default="star",
                    help="communication graph (repro.topology): star keeps "
                    "the master path; ring/torus2d/complete/erdos_renyi "
                    "train decentralized (per-node params)")
    ap.add_argument("--topology-seed", type=int, default=0,
                    help="seed for erdos_renyi draws")
    ap.add_argument("--topology-p", type=float, default=0.5,
                    help="edge probability for erdos_renyi")
    ap.add_argument("--gossip", default="gradient",
                    choices=["gradient", "params"],
                    help="decentralized message channel: gossip gradients "
                    "(aggregate then step) or parameters (local step then "
                    "robust model aggregation, arXiv:2308.05292)")
    ap.add_argument("--schedule", default="static",
                    choices=["static", "cyclic", "erdos_renyi"],
                    help="time-varying graph schedule: static keeps "
                    "--topology fixed; cyclic rotates a comma-separated "
                    "--topology list; erdos_renyi resamples a seeded "
                    "G(N, p) per round")
    ap.add_argument("--schedule-period", type=int, default=4,
                    help="rounds per erdos_renyi schedule period")
    ap.add_argument("--per-leaf", action="store_true",
                    help="disable the flat-packed hot path (DESIGN.md "
                    "Sec. 8) and run the pre-refactor per-leaf pipeline")
    ap.add_argument("--message-dtype", default="float32",
                    choices=list(packing.WIRE_FORMAT_NAMES),
                    help="wire format of the packed worker messages "
                    "(repro.core.packing.WIRE_FORMATS): bfloat16 halves "
                    "communication volume, int8 quarters it with per-block "
                    "symmetric scales, sign1 sends 1-bit signs with "
                    "per-client error feedback (robust rules still "
                    "accumulate in f32)")
    from repro.core.variance import VR_NAMES
    ap.add_argument("--vr", default="sgd", choices=list(VR_NAMES),
                    help="variance reduction (repro.core.variance): sgd "
                    "(none), minibatch, saga (per-sample table, O(J*D)/"
                    "client), lsvrg (loopless-SVRG snapshots, O(D)/client)")
    ap.add_argument("--saga-samples", type=int, default=4)
    ap.add_argument("--lsvrg-p", type=float, default=0.1,
                    help="per-step Bernoulli snapshot-refresh probability "
                    "for --vr lsvrg")
    ap.add_argument("--num-clients", type=int, default=0,
                    help="client-scale virtualization: total logical "
                    "clients; a seeded cohort the size of the worker count "
                    "participates per round (0 = full participation)")
    ap.add_argument("--participation-seed", type=int, default=0,
                    help="seed for the shuffled-epoch cohort sampler")
    ap.add_argument("--max-staleness", type=int, default=64,
                    help="staleness cutoff: rows at or beyond this many "
                    "rounds stale get aggregation weight exactly 0")
    ap.add_argument("--staleness-decay", type=float, default=1.0,
                    help="per-round staleness weight decay (1.0 keeps "
                    "weights 0/1: pure dropout masking)")
    ap.add_argument("--straggler-k", type=int, default=4,
                    help="how stale the straggler attack reports itself")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest checkpoint in --checkpoint-dir "
                    "(full train state: params + opt + VR state + step) and "
                    "continue from there")
    ap.add_argument("--guards", action="store_true",
                    help="self-healing training (DESIGN.md Sec. 13): "
                    "in-graph per-row fault containment (non-finite / "
                    "magnitude-outlier messages get aggregation weight "
                    "exactly 0) plus the round-health verdict that holds "
                    "the train state on rejected rounds")
    ap.add_argument("--guard-multiplier", type=float, default=10.0,
                    help="magnitude gate: quarantine rows whose norm "
                    "exceeds this multiple of the median honest norm")
    ap.add_argument("--reject-ema", type=float, default=0.9,
                    help="decay of the aggregate-norm EMA behind the "
                    "round-health verdict")
    ap.add_argument("--reject-zmax", type=float, default=6.0,
                    help="reject a round when the aggregate norm's z-score "
                    "vs the EMA exceeds this (<=0: non-finite-only gate)")
    ap.add_argument("--rollback-patience", type=int, default=5,
                    help="consecutive bad rounds (rejected / non-finite "
                    "loss / loss blow-up) before rolling back to the last "
                    "good checkpoint")
    ap.add_argument("--loss-blowup", type=float, default=1e3,
                    help="treat a round as bad when the loss exceeds this "
                    "multiple of the best loss seen")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="stop restoring checkpoints after this many "
                    "rollbacks (the run continues degraded instead of "
                    "ping-ponging forever)")
    ap.add_argument("--degradation-ladder", default="",
                    help="escalation per rollback: semicolon-separated "
                    "RobustConfig override groups, e.g. "
                    "'trim=0.3;aggregator=trimmed_mean,trim=0.4' "
                    "(repro.launch.health)")
    ap.add_argument("--diagnostics", action="store_true",
                    help="compute in-graph aggregation diagnostics "
                    "(per-worker distance / implicit weight / krum scores, "
                    "DESIGN.md Sec. 11) inside the compiled step and log "
                    "them alongside the loss")
    ap.add_argument("--log-dir", default="",
                    help="run-telemetry directory (repro.telemetry): writes "
                    "<dir>/metrics.jsonl + <dir>/meta.json; empty keeps the "
                    "console-only progress line")
    ap.add_argument("--log-every", type=int, default=1,
                    help="keep every N-th step in metrics.jsonl")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="capture a profiler trace of this many post-warmup "
                    "steps into <log-dir>/profile (needs --log-dir)")
    args = ap.parse_args(argv)
    if args.resume and not args.checkpoint_dir:
        raise SystemExit("--resume needs --checkpoint-dir")
    if args.profile_steps and not args.log_dir:
        raise SystemExit("--profile-steps needs --log-dir")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    ndev = len(jax.devices())
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
    else:
        shape = (ndev, 1)
    if len(shape) not in (2, 3):
        raise SystemExit(f"--mesh must have 2 or 3 axes, got {args.mesh!r}")
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    mesh = mesh_lib.make_host_mesh(shape, axes)
    w = mesh_lib.num_workers(mesh)

    model = build_model(cfg, remat=False, q_chunk=min(args.seq, 512),
                        kv_chunk=min(args.seq, 512), loss_chunk=128)
    robust = RobustConfig(
        aggregator=args.aggregator, vr=args.vr, attack=args.attack,
        num_byzantine=args.byzantine, comm=args.comm, weiszfeld_iters=16,
        topology=args.topology, topology_seed=args.topology_seed,
        topology_p=args.topology_p, gossip=args.gossip,
        schedule=args.schedule, schedule_period=args.schedule_period,
        packed=not args.per_leaf, message_dtype=args.message_dtype,
        lsvrg_p=args.lsvrg_p, num_clients=args.num_clients,
        participation_seed=args.participation_seed,
        max_staleness=args.max_staleness,
        staleness_decay=args.staleness_decay,
        straggler_k=args.straggler_k,
        diagnostics=args.diagnostics,
        guards=args.guards, guard_multiplier=args.guard_multiplier,
        reject_ema=args.reject_ema, reject_zmax=args.reject_zmax)
    train = TrainConfig(optimizer=args.optimizer, lr=args.lr)
    from repro.core.robust_step import resolve_schedule
    sched = resolve_schedule(robust, w)
    decentralized = sched is not None
    reducer = robust.reducer()
    from repro.core import participation as participation_lib
    plan = participation_lib.resolve_participation(robust, w)
    if plan is not None:
        print(plan.describe())
    saga_samples = args.saga_samples if reducer.uses_sample_idx else 0
    def build_step(rcfg):
        """Step builder keyed on the (possibly ladder-escalated) robust
        config; the state STRUCTURE must not change across rebuilds
        (launch/health.py forbids structure-changing ladder fields).
        Returns the jitted step and the state's shardings."""
        if decentralized:
            fn, specs, _ = steps_lib.make_decentralized_train_step(
                model, rcfg, train, mesh, sched,
                saga_num_samples=saga_samples)
        else:
            fn, specs, _ = steps_lib.make_train_step(
                model, rcfg, train, mesh, saga_num_samples=saga_samples)
        return steps_lib.compile_train_step(fn), shard_lib.named(mesh, specs)

    if decentralized:
        # Schedule-level report: per-round spectral gaps + the joint gap.
        print(f"schedule: {sched.describe()}")

    key = jax.random.PRNGKey(0)
    with compat.use_mesh(mesh):
        params0 = model.init(key)
        params = params0
        if decentralized:
            # Every node starts from the same init; copies drift apart only
            # as far as the robust gossip lets them (consensus_dist metric).
            params = jax.tree_util.tree_map(
                lambda p: jnp.broadcast_to(p[None], (w,) + p.shape) + 0,
                params0)
        from repro.optim import get_optimizer
        opt = get_optimizer(args.optimizer, args.lr)
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        if reducer.wants_state(saga_samples):
            # Cold-start VR state (zero SAGA table / zero lsvrg anchor):
            # warms up over the first steps instead of paying a J-pass
            # init sweep at LLM scale.  Under client-scale virtualization the
            # tables are resident per CLIENT, not per slot.
            rows = plan.num_clients if plan is not None else w
            state["vr"] = reducer.init_zeros(params0, rows, saga_samples)
        if plan is not None:
            state["staleness"] = participation_lib.init_staleness(
                plan.num_clients)
        if robust.guards:
            state["health"] = guards_lib.init_health()
        wspec = robust.message_spec(params0, batch_ndim=0)
        if robust.wire_format().error_feedback:
            # Per-client error-feedback residual for 1-bit wire formats.
            # Resident per CLIENT (like the VR tables): sampled cohorts
            # gather/scatter their rows alongside the SAGA/LSVRG state.
            rows = plan.num_clients if plan is not None else w
            state["ef"] = jnp.zeros((rows, wspec.padded_dim), jnp.float32)
        ckpt = CheckpointManager(args.checkpoint_dir) if args.checkpoint_dir else None
        start = 0
        if args.resume:
            step0, state = ckpt.restore_latest(state)
            if step0 is not None:
                start = step0
                print(f"resumed full train state from step {step0}")
        # State donation lives in the step compiler (launch/steps.py):
        # params, opt moments and the VR state are all in arg 0.
        jstep, state_shardings = build_step(robust)
        # Lay the state out as the step's specs say (node axis and
        # per-client tables over the worker axes): created under the mesh it
        # is a full replica on every device, which costs each chip the whole
        # federation's state and the first step a compile of its own.
        state = jax.device_put(state, state_shardings)
        log_dir = args.log_dir or None
        t0 = time.time()

        def console(step_i, row):
            # Progress line, fired from RunLogger.flush so the loop itself
            # never syncs on a metric value per step.
            extra = (f" consensus={row['consensus_dist']:.5f}"
                     if decentralized else "")
            wall = row.get("time_wall_s", time.time() - t0)
            print(f"step {step_i:4d} loss={row['loss']:.4f} "
                  f"agg_norm={row['agg_norm']:.4f}{extra} "
                  f"({wall/(step_i-start+1):.2f}s/step)")

        # Run-health monitor (DESIGN.md Sec. 13): consumes every flushed
        # metric row; guards runs flush in small batches so verdicts reach
        # the host within a few steps of being issued in-graph.
        monitor = health_lib.RunHealth(
            patience=args.rollback_patience, blowup=args.loss_blowup,
            ladder=args.degradation_ladder) if args.guards else None
        last_row: dict = {}

        def on_row(row):
            last_row.update(row)
            if monitor is not None:
                monitor.observe(row)

        logger = telemetry.RunLogger(
            log_dir, log_every=args.log_every,
            flush_every=4 if args.guards else 32, on_row=on_row,
            console=console, console_every=max(args.steps // 10, 1))
        if log_dir is not None:
            # AOT-lower the step once so meta.json records the compiled
            # executable's cost analysis + parsed collective traffic.  The
            # throwaway Compiled never executes, so argument donation in the
            # hot-loop jit is untouched (second compile is the price).
            batch0 = make_batch(jax.random.fold_in(key, 1000 + start), cfg,
                                w, args.per_worker_batch, args.seq)
            compiled = jstep.lower(state, batch0,
                                   jax.random.fold_in(key, start)).compile()
            ca = compat.cost_analysis(compiled)
            logger.write_meta(
                config=vars(args), jax_version=jax.__version__,
                backend=jax.default_backend(), device_count=ndev,
                mesh_shape=dict(zip(mesh.axis_names,
                                    (int(s) for s in mesh.devices.shape))),
                num_workers=w, start_step=start,
                cost_analysis={k: float(v) for k, v in sorted(ca.items())
                               if isinstance(v, (int, float))},
                collective_bytes=hlo_analysis.collective_bytes(
                    compiled.as_text()),
                wire={"message_dtype": args.message_dtype,
                      "bits_per_coord": wspec.wire_format.bits_per_coord,
                      "coords": wspec.padded_dim,
                      "bytes_per_message": wspec.wire_bytes(),
                      "bytes_per_round": wspec.wire_bytes() * w})
            del compiled, batch0

        timer = telemetry.PhaseTimer()
        prof = None
        profile_until = 0
        i = start
        while i < args.steps:
            if args.profile_steps and i == start + 1:
                # Skip the compile step, then trace N steady-state steps.
                prof = compat.profiler_trace(os.path.join(log_dir, "profile"))
                prof.__enter__()
                profile_until = i + args.profile_steps
            with timer.phase("data"):
                bkey = jax.random.fold_in(key, 1000 + i)
                batch = make_batch(bkey, cfg, w, args.per_worker_batch,
                                   args.seq)
            with timer.phase("step"):
                state, metrics = jstep(state, batch,
                                       jax.random.fold_in(key, i))
            with timer.phase("host"):
                host = timer.snapshot()
                host["time_wall_s"] = round(time.time() - t0, 3)
                logger.log_step(i, metrics, host=host)
                if ckpt and args.checkpoint_every and (i + 1) % args.checkpoint_every == 0:
                    ckpt.save_train_state(i + 1, state)
                    if monitor is None or monitor.healthy:
                        # Healthy as of the last flush -> rollback anchor.
                        ckpt.mark_good(i + 1)
            if prof is not None and i + 1 >= profile_until:
                jax.block_until_ready(jax.tree_util.tree_leaves(state))
                prof.__exit__(None, None, None)
                prof = None
            i += 1
            if (monitor is not None and monitor.rollback_pending
                    and ckpt is not None
                    and monitor.rollbacks < args.max_rollbacks):
                # Auto-rollback (DESIGN.md Sec. 13): drain the logger so the
                # monitor has seen every issued verdict, restore the last
                # good checkpoint, climb one ladder rung, and re-descend
                # with the SAME seeded key schedule -- deterministic, so
                # the continuation is bit-exact with a fresh resumed run
                # (tests/test_rollback.py).
                logger.flush()
                gstep, state = ckpt.restore_last_good(state)
                state = jax.device_put(state, state_shardings)
                monitor.on_rollback()
                if gstep is None:
                    print("run unhealthy but no restorable checkpoint; "
                          "continuing without rollback")
                else:
                    escalated = monitor.escalate(robust)
                    if escalated != robust:
                        robust = escalated
                        jstep, _ = build_step(robust)
                        print(f"rollback #{monitor.rollbacks}: restored "
                              f"step {gstep}, escalated to "
                              f"aggregator={robust.aggregator} "
                              f"trim={robust.trim} "
                              f"guard_multiplier={robust.guard_multiplier}")
                    else:
                        print(f"rollback #{monitor.rollbacks}: restored "
                              f"step {gstep} (ladder exhausted or empty)")
                    i = gstep
            elif monitor is not None and monitor.rollback_pending:
                # No checkpointing or rollback budget spent: reset the
                # counter so the warning does not fire every step.
                monitor.dismiss()
                print("run unhealthy; no rollback available "
                      f"(checkpointing={'on' if ckpt else 'off'}, "
                      f"rollbacks={monitor.rollbacks}/{args.max_rollbacks})")
        if prof is not None:
            jax.block_until_ready(jax.tree_util.tree_leaves(state))
            prof.__exit__(None, None, None)
        logger.close()
        if log_dir is not None and args.guards:
            # Fold the resilience outcome into meta.json so offline tooling
            # (and the CI chaos job) can assert on it without parsing the
            # whole metrics stream.
            meta_path = os.path.join(log_dir, "meta.json")
            try:
                with open(meta_path) as f:
                    meta = json.load(f)
            except (OSError, json.JSONDecodeError):
                meta = {}
            meta["resilience"] = {
                "rejected_rounds": last_row.get("rejected_rounds", 0.0),
                "final_loss": last_row.get("loss"),
                **monitor.summary(),
            }
            with open(meta_path, "w") as f:
                json.dump(meta, f, indent=2, sort_keys=True, default=str)
                f.write("\n")
    print(f"done ({args.steps - start} steps, {time.time() - t0:.1f}s)")
    return last_row


if __name__ == "__main__":
    main()
