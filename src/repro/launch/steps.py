"""Step builders for the distributed runtime.

``make_train_step`` composes the Byzantine-robust data-parallel training
step of DESIGN.md Sec. 2:

  1. per-worker gradients -- ``vmap(grad)`` over the leading worker axis of
     the batch (sharded over the pod/data mesh axes);
  2. optional variance-reduction correction via the
     :mod:`repro.core.variance` registry (SAGA tables / lsvrg snapshots
     sharded like the gradients);
  3. Byzantine attack injection (mask-replace the first B workers);
  4. robust aggregation (every registry aggregator runs on both paths):
       * ``comm="gather"``  -- paper-faithful master under the auto-sharded
         jit (the packed full-vector rules run on the (W, D) buffer laid
         out by coordinate, DESIGN.md Sec. 8);
       * ``comm="sharded"`` -- beyond-paper coordinate resharding (shard_map
         all_to_all; psum'd norms / partial Gram / per-block segments --
         DESIGN.md Sec. 2);
  5. optimizer update (paper update is plain SGD, eq. (11)).

With ``robust.packed`` (default) steps 3-4 run on ONE packed (W, D)
message buffer (DESIGN.md Sec. 8) -- a single sharding constraint, a
single attack pass, and the flat aggregation engine -- instead of walking
the gradient pytree leaf-by-leaf; ``packed=False`` keeps the pre-refactor
per-leaf pipeline (the ``benchmarks/bench_step.py`` baseline).  Compile
the returned step with :func:`compile_train_step` to DONATE the train
state (params + opt moments + variance-reduction state): the input
buffers are reused for the outputs instead of holding two state
generations live.

Worker axes may be a single ``data`` axis or multi-pod ``(pod, data)``
(``launch/mesh.py``); the step builder is agnostic -- it forwards
``mesh_lib.worker_axes(mesh)`` everywhere.

``make_prefill_step`` / ``make_serve_step`` build the inference paths,
including the sequence-sharded long-context decode.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro import telemetry
from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core import attacks as attack_lib
from repro.core import guards as guards_lib
from repro.core import participation as participation_lib
from repro.core.robust_step import RobustConfig, sharded_aggregate
from repro.core import aggregators as agg_lib
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.models.api import Model
from repro.optim import optimizers as optim_lib

Pytree = Any


def _opt_specs_like(optimizer_name: str, pspecs: Pytree) -> Pytree:
    """Optimizer-state PartitionSpecs mirroring the parameter specs.  Shared
    by the master and decentralized step builders so a new optimizer is
    reflected in both (the decentralized caller passes node-stacked specs)."""
    if optimizer_name == "sgd":
        return ()
    if optimizer_name == "momentum":
        return pspecs
    return optim_lib.AdamState(mu=pspecs, nu=pspecs)


def _opt_structs_like(optimizer_name: str, ps: Pytree) -> Pytree:
    """Optimizer-state ShapeDtypeStructs for parameter structs ``ps`` (Adam
    moments are always f32); same sharing contract as `_opt_specs_like`."""
    if optimizer_name == "sgd":
        return ()
    if optimizer_name == "momentum":
        return ps
    f32 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32)
    return optim_lib.AdamState(mu=jax.tree_util.tree_map(f32, ps),
                               nu=jax.tree_util.tree_map(f32, ps))


# The auto-jit gather master packs only the rules that need FULL-VECTOR
# message geometry (norms over the whole message, so one packed (W, D)
# buffer laid out by coordinate serves them all); coordinate-separable and
# per-leaf rules stay leaf-sharded (see the dispatch comment inside
# make_train_step).
PACKED_GATHER_RULES = frozenset(
    {"geomed", "geomed_groups", "krum", "centered_clip"})


def make_train_step(model: Model, robust: RobustConfig, train: TrainConfig,
                    mesh, *, saga_num_samples: int = 0):
    """Returns (train_step, state_specs, make_state_structs).

    ``train_step(state, batch, key) -> (state, metrics)`` where ``state`` is
    a dict {params, opt, vr?, step, staleness?}.  Batch leaves carry a
    leading worker axis of size num_workers(mesh).

    With ``robust.num_clients > 0`` (client-scale virtualization, DESIGN.md
    Sec. 10) the variance-reduction state is resident PER CLIENT -- leading
    (num_clients,) axis, still sharded over the worker mesh axes (so
    num_clients should be a multiple of the worker count) -- and each round
    the seeded cohort of W clients mans the mesh's worker slots: their VR
    rows are gathered/scattered in the auto-jit region around the
    shard-mapped aggregation, and the cohort's staleness counters produce
    the replicated (W,) per-slot weights the flat rules consume.  The batch
    stays per-SLOT (the data pipeline feeds whatever the round's cohort
    should see; this builder virtualizes optimizer-relevant state, not the
    input pipeline).
    """
    cfg = model.cfg
    if robust.comm not in ("gather", "sharded"):
        raise ValueError(f"RobustConfig.comm must be 'gather' or 'sharded', "
                         f"got {robust.comm!r}")
    if robust.comm == "sharded":
        compat.require_distributed(what="comm='sharded' aggregation")
    wa = mesh_lib.worker_axes(mesh)
    w = mesh_lib.num_workers(mesh)
    plan = participation_lib.resolve_participation(robust, w)
    num_clients = plan.num_clients if plan is not None else w
    weighted = participation_lib.uses_staleness(robust, plan)
    optimizer = optim_lib.get_optimizer(train.optimizer, train.lr)
    attack_cfg = robust.attack_config()
    reducer = robust.reducer()
    use_vr = reducer.wants_state(saga_num_samples)
    wire_fmt = robust.wire_format()
    use_ef = wire_fmt.error_feedback
    if wire_fmt.quantized and not robust.packed:
        raise ValueError(
            f"message_dtype={robust.message_dtype!r} is a quantized wire "
            "format and needs the packed path (robust.packed=True)")

    def row_weights_for(state):
        """Replicated (W,) staleness weights of the mesh's message slots
        (Byzantine slots are the FIRST B -- mask-replace convention), plus
        this round's cohort, or (None, None, None) on the bit-exact
        unweighted path."""
        cohort = None if plan is None else plan.cohort_at(state["step"])
        if not weighted:
            return None, None, cohort
        if plan is None:
            honest_stal = jnp.zeros((w,), jnp.int32)
        else:
            honest_stal = jnp.take(state["staleness"], cohort, axis=0)
        slot_stal = participation_lib.slot_staleness(
            honest_stal, robust.attack,
            robust.num_byzantine if robust.attack != "none" else 0,
            straggler_k=robust.straggler_k,
            max_staleness=robust.max_staleness, byz_first=True)
        rw = participation_lib.staleness_weights(
            slot_stal, decay=robust.staleness_decay,
            max_staleness=robust.max_staleness)
        return rw, slot_stal, cohort

    def train_step(state, batch, key):
        params = state["params"]
        rw, slot_stal, cohort = row_weights_for(state)

        def worker_loss(p, wb):
            return model.loss(p, wb)

        losses, grads = jax.vmap(jax.value_and_grad(worker_loss),
                                 in_axes=(None, 0))(params, batch)
        # Keep the worker axis sharded over the worker mesh axes.
        waxes = wa if len(wa) > 1 else wa[0]
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.with_sharding_constraint(
                g, jax.sharding.NamedSharding(mesh, P(waxes))), grads)

        if use_vr:
            # Table reducers (saga) draw this step's sample index; batch
            # reducers (lsvrg) correct the batch gradient directly, with
            # the snapshot oracle re-running the grad vmap at the
            # snapshot params and no full-gradient oracle (the anchor
            # refreshes from the current batch gradient -- the practical
            # large-scale variant, DESIGN.md Sec. 9).
            idx = None
            if reducer.uses_sample_idx:
                idx = reducer.draw_indices(jax.random.fold_in(key, 1), w,
                                           saga_num_samples)
            vr_rows = (participation_lib.gather_rows(state["vr"], cohort)
                       if plan is not None else state["vr"])
            msgs, vr_rows, vr_metrics = reducer.correct(
                vr_rows, grads, idx, jax.random.fold_in(key, 3),
                params=jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p[None], (w,) + p.shape),
                    params),
                grads_at=lambda snap: jax.vmap(
                    jax.grad(worker_loss))(snap, batch))
            vr_state = (participation_lib.scatter_rows(state["vr"], cohort,
                                                       vr_rows)
                        if plan is not None else vr_rows)
        else:
            msgs, vr_state, vr_metrics = grads, state.get("vr"), {}

        # Sender-side wire step (DESIGN.md Sec. 12): quantized formats pack
        # the messages once, fold in / bank the per-slot error-feedback
        # residual (sign1), and continue with the DEQUANTIZED wire -- what
        # the attacks observe and the variance metric measures, mirroring
        # the sim path.  The EF state is updated for ALL w slots HERE,
        # before the comm-mode branch, so gather and sharded runs carry
        # bit-identical residual tables.
        ef_state = state.get("ef")
        if wire_fmt.quantized:
            wspec = robust.message_spec(msgs, batch_ndim=1)
            wbuf = jax.lax.with_sharding_constraint(
                wspec.pack(msgs),
                jax.sharding.NamedSharding(mesh, P(wa if len(wa) > 1
                                                   else wa[0])))
            ef_rows = ef_state
            if use_ef and plan is not None:
                ef_rows = participation_lib.gather_rows(state["ef"], cohort)
            wbuf, ef_rows = wspec.transmit(wbuf, ef_rows)
            if use_ef:
                ef_state = (participation_lib.scatter_rows(
                    state["ef"], cohort, ef_rows)
                    if plan is not None else ef_rows)
            msgs = wspec.unpack(wbuf)

        # Honest-message variance BEFORE attack injection (mask-replace hits
        # the FIRST B slots, so the honest workers are the slots >= B).
        b = robust.num_byzantine if robust.attack != "none" else 0
        hmask = (jnp.arange(w) >= b).astype(jnp.float32)
        honest_var = lambda m: telemetry.consensus_dist(m, hmask, max(w - b, 1))

        diag = None
        quarantined = None
        if robust.comm == "gather" and (weighted or robust.diagnostics or
                                        robust.guards or (
                robust.packed and (wire_fmt.quantized or
                                   robust.aggregator in PACKED_GATHER_RULES))):
            # Flat-packed hot path (DESIGN.md Sec. 8): one (W, D) buffer
            # carries the messages through attack + aggregation.  The
            # FULL-VECTOR rules route here by default: packing collapses
            # their per-leaf launches.  The VR state stays per-leaf so its
            # tables/snapshots keep their model-axis sharding (DESIGN.md
            # Sec. 4).  When staleness weights OR diagnostics are active
            # EVERY gather rule routes here: both are flat-engine features
            # (the per-leaf baseline predates them).
            #
            # Layout: the rows are pinned where the workers made them, then
            # the buffer is resharded ONCE by coordinate -- each device holds
            # all W rows of D/W coordinates.  Every reduction over W is then
            # local, and the rules need only (W,)/(W, W) norm partials and
            # scalar stopping statistics across devices; left row-sharded,
            # XLA all-reduces a D-sized sum per Weiszfeld iteration.
            spec = robust.message_spec(msgs, batch_ndim=1)
            by_coord = lambda x: jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P(None, waxes)))
            buf = jax.lax.with_sharding_constraint(
                spec.pack(msgs), jax.sharding.NamedSharding(mesh, P(waxes)))
            # The quantized wire's round-trip slices the buffer at every
            # leaf boundary; on coordinate shards the partitioner realigns
            # each slice across devices (collective-permutes that grow with
            # D: 6.6 against 1.5 GB per device a round for mamba2-130m on a
            # v5e 2x2, compiled), so those formats reshard after it and pay
            # one D-sized all-reduce for the attack's honest mean instead.
            if not wire_fmt.quantized:
                buf = by_coord(buf)
            # On the packed wire values: by coordinate its sum over W is
            # local; by rows it shares the attack's honest mean.
            var = honest_var(buf)
            buf = attack_lib.apply_attack_stacked(
                attack_cfg, buf, jax.random.fold_in(key, 2), spec=spec)
            if wire_fmt.quantized:
                # Byzantine payloads are wire-constrained too (the honest
                # rows are already a fixed point of the round-trip); the
                # sharded branch gets the same treatment inside
                # sharded_aggregate's encode.
                buf = by_coord(spec.wire_roundtrip(buf))
            flat_fn = robust.flat_aggregator_fn(spec)
            if robust.guards:
                # Containment on the DEQUANTIZED wire (dequantize-then-
                # guard, DESIGN.md Sec. 13); quarantined rows fold into the
                # flat rule as zero row_weights.
                gmask = guards_lib.guard_mask(
                    buf, multiplier=robust.guard_multiplier, base_weights=rw)
                out = guards_lib.guarded_flat_call(flat_fn, buf, gmask,
                                                   row_weights=rw)
                quarantined = jnp.sum(1.0 - gmask)
            else:
                out = flat_fn(buf) if rw is None else flat_fn(
                    buf, row_weights=rw)
            if robust.diagnostics:
                agg_vec, diag = out
            else:
                agg_vec = out
            # One all-gather of the f32 aggregate; the optimizer then runs
            # in the parameters' own layout.
            agg_vec = jax.lax.with_sharding_constraint(
                agg_vec, jax.sharding.NamedSharding(mesh, P()))
            agg = spec.unpack(agg_vec, batch_ndim=0)
        else:
            # Everything else keeps per-leaf messages: comm="sharded" is
            # ALREADY coordinate-packed internally (it flattens each
            # device's leaf shards before the all_to_all, DESIGN.md
            # Sec. 2), and the coordinate-separable rules (mean/median/
            # trimmed_mean; geomed_blockwise is per-leaf by definition)
            # act shard-locally under the auto-sharded jit -- packing them
            # into one buffer sharded over the workers alone would DESTROY
            # their model-axis sharding for zero algorithmic gain.
            var = honest_var(msgs)
            msgs = attack_lib.apply_attack_stacked(
                attack_cfg, msgs, jax.random.fold_in(key, 2))
            if robust.comm == "sharded":
                out = _sharded_agg(msgs, robust, mesh, pspecs,
                                   row_weights=rw,
                                   diagnostics=robust.diagnostics)
                agg, diag = out if robust.diagnostics else (out, None)
            else:
                agg = _gather_agg(msgs, robust)

        updates, opt_state = optimizer.update(agg, state["opt"], params,
                                              state["step"])
        params = optim_lib.apply_updates(params, updates)
        agg_norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(agg)))
        health = state.get("health")
        health_metrics = {}
        if robust.guards:
            # Round-health verdict (DESIGN.md Sec. 13): a rejected round
            # holds params/opt/VR/EF via select (donation-safe, no host
            # sync); step/staleness/health always advance.
            accept, health = guards_lib.round_verdict(
                agg_norm, health, decay=robust.reject_ema,
                zmax=robust.reject_zmax, warmup=robust.reject_warmup)
            params, opt_state, vr_state, ef_state = guards_lib.select_tree(
                accept, (params, opt_state, vr_state, ef_state),
                (state["params"], state["opt"], state.get("vr"),
                 state.get("ef")))
            health_metrics = telemetry.health_metrics(health, accept)
        new_state = {"params": params, "opt": opt_state, "step": state["step"] + 1}
        if use_vr:
            new_state["vr"] = vr_state
        if use_ef:
            new_state["ef"] = ef_state
        if robust.guards:
            new_state["health"] = health
        if plan is not None:
            new_state["staleness"] = participation_lib.tick_staleness(
                state["staleness"], cohort)
        metrics = {
            "loss": jnp.mean(losses),
            "honest_variance": var,
            "agg_norm": agg_norm,
            **vr_metrics,
            **telemetry.staleness_metrics(slot_stal),
            **health_metrics,
        }
        if quarantined is not None:
            metrics["quarantined_rows"] = quarantined
        if diag is not None:
            metrics.update(telemetry.diagnostics_metrics(diag))
        return new_state, metrics

    # ---- specs / structs -------------------------------------------------
    szs = mesh_lib.axis_sizes(mesh)
    pspecs = model.param_specs(szs)
    wa_spec = wa if len(wa) > 1 else wa[0]

    def state_specs():
        sp = {"params": pspecs, "opt": _opt_specs_like(train.optimizer, pspecs),
              "step": P()}
        if use_vr:
            sp["vr"] = reducer.state_specs(pspecs, wa_spec)
        if use_ef:
            # (num_clients, D) residual rows sharded over the worker axes,
            # like the per-client VR tables (DESIGN.md Sec. 12).
            sp["ef"] = P(wa_spec)
        if robust.guards:
            sp["health"] = P()   # (HEALTH_WIDTH,) f32, replicated
        if plan is not None:
            sp["staleness"] = P()   # (num_clients,) int32, replicated
        return sp

    def state_structs():
        ps = model.param_structs()
        st = {"params": ps, "opt": _opt_structs_like(train.optimizer, ps),
              "step": jax.ShapeDtypeStruct((), jnp.int32)}
        if robust.guards:
            st["health"] = jax.ShapeDtypeStruct(
                (guards_lib.HEALTH_WIDTH,), jnp.float32)
        if use_vr:
            # Per-client resident rows under partial participation.
            st["vr"] = reducer.state_structs(ps, num_clients,
                                             saga_num_samples)
        if use_ef:
            st["ef"] = jax.ShapeDtypeStruct(
                (num_clients,
                 robust.message_spec(ps, batch_ndim=0).padded_dim),
                jnp.float32)
        if plan is not None:
            st["staleness"] = jax.ShapeDtypeStruct((num_clients,), jnp.int32)
        return st

    return train_step, state_specs(), state_structs


def make_decentralized_train_step(model: Model, robust: RobustConfig,
                                  train: TrainConfig, mesh, topology, *,
                                  saga_num_samples: int = 0):
    """Server-free variant of :func:`make_train_step` (DESIGN.md Secs. 6-7):
    every worker-axis index is a graph NODE owning its own parameter /
    optimizer copy (state leaves grow a leading node axis sharded over the
    worker axes), gradients are computed at each node's own parameters, and
    the aggregation step is the per-node masked neighborhood rule of
    :func:`repro.topology.decentralized_aggregate` -- per-edge Byzantine
    attacks included, so ``apply_attack_stacked`` is NOT used here.  Both
    ``comm="gather"`` and ``comm="sharded"`` run on 1-axis and (pod, data)
    worker meshes.

    ``topology`` may be a graph name, a :class:`repro.topology.Topology`,
    or a :class:`repro.topology.GraphSchedule`; with ``robust.schedule``
    != "static" the schedule is built around it and the state's step
    counter selects each round's stacked mask/mixing constants inside the
    compiled step (no per-round retrace).  ``robust.gossip`` picks the
    message channel: ``"gradient"`` aggregates neighbor gradients then
    applies the optimizer; ``"params"`` applies the optimizer locally first
    and robust-aggregates the neighbors' half-stepped models.

    Returns ``(train_step, state_specs, make_state_structs)`` like
    :func:`make_train_step`; metrics add ``consensus_dist`` (mean squared
    drift of the honest nodes' parameters from their average).
    """
    from repro.core.robust_step import resolve_schedule
    from repro.topology import (GOSSIP_MODES, decentralized_aggregate,
                                validate_schedule)

    cfg = model.cfg
    if robust.comm not in ("gather", "sharded"):
        raise ValueError(f"RobustConfig.comm must be 'gather' or 'sharded', "
                         f"got {robust.comm!r}")
    if robust.gossip not in GOSSIP_MODES:
        raise ValueError(f"RobustConfig.gossip must be one of {GOSSIP_MODES}, "
                         f"got {robust.gossip!r}")
    compat.require_distributed(what="decentralized topology training")
    wa = mesh_lib.worker_axes(mesh)
    w = mesh_lib.num_workers(mesh)
    sched = resolve_schedule(robust, w, topology)
    if sched is None:
        raise ValueError(
            "topology 'star' with a static schedule is the master "
            "federation -- use launch/steps.make_train_step (the bit-exact "
            "paper path)")
    validate_schedule(robust, sched, w)  # fail at build time, not first jit
    optimizer = optim_lib.get_optimizer(train.optimizer, train.lr)
    reducer = robust.reducer()
    use_vr = reducer.wants_state(saga_num_samples)
    wire_fmt = robust.wire_format()
    use_ef = wire_fmt.error_feedback
    if wire_fmt.quantized and not robust.packed:
        raise ValueError(
            f"message_dtype={robust.message_dtype!r} is a quantized wire "
            "format and needs the packed path (robust.packed=True)")
    b = robust.num_byzantine if robust.attack != "none" else 0
    honest = (jnp.arange(w) >= b).astype(jnp.float32)  # first B nodes attack
    wh = max(w - b, 1)
    plan = participation_lib.resolve_participation(robust, w)
    num_clients = plan.num_clients if plan is not None else w
    weighted = participation_lib.uses_staleness(robust, plan)

    def row_weights_for(state):
        """Replicated (W,) per-sender staleness weights + the round's cohort
        (first-B-Byzantine node convention), or Nones on the unweighted
        bit-exact path."""
        cohort = None if plan is None else plan.cohort_at(state["step"])
        if not weighted:
            return None, None, cohort
        if plan is None:
            honest_stal = jnp.zeros((w,), jnp.int32)
        else:
            honest_stal = jnp.take(state["staleness"], cohort, axis=0)
        slot_stal = participation_lib.slot_staleness(
            honest_stal, robust.attack, b,
            straggler_k=robust.straggler_k,
            max_staleness=robust.max_staleness, byz_first=True)
        rw = participation_lib.staleness_weights(
            slot_stal, decay=robust.staleness_decay,
            max_staleness=robust.max_staleness)
        return rw, slot_stal, cohort

    szs = mesh_lib.axis_sizes(mesh)
    pspecs = model.param_specs(szs)
    wa_spec = wa if len(wa) > 1 else wa[0]
    node_specs = jax.tree_util.tree_map(
        lambda s: P(wa_spec, *tuple(s)), pspecs,
        is_leaf=lambda x: isinstance(x, P))

    def train_step(state, batch, key):
        params = state["params"]  # leaves (W, ...): one copy per node
        rw, slot_stal, cohort = row_weights_for(state)

        losses, grads = jax.vmap(jax.value_and_grad(model.loss))(params, batch)
        grads = jax.tree_util.tree_map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, jax.sharding.NamedSharding(mesh, s)), grads, node_specs)

        if use_vr:
            # Same oracle binding as make_train_step, but the params/
            # snapshot gradients are per-NODE (each node corrects against
            # its own iterate); under partial participation the round's
            # cohort rows are gathered from the per-client resident state.
            idx = None
            if reducer.uses_sample_idx:
                idx = reducer.draw_indices(jax.random.fold_in(key, 1), w,
                                           saga_num_samples)
            vr_rows = (participation_lib.gather_rows(state["vr"], cohort)
                       if plan is not None else state["vr"])
            msgs, vr_rows, vr_metrics = reducer.correct(
                vr_rows, grads, idx, jax.random.fold_in(key, 3),
                params=params,
                grads_at=lambda snap: jax.vmap(
                    jax.grad(model.loss))(snap, batch))
            vr_state = (participation_lib.scatter_rows(state["vr"], cohort,
                                                       vr_rows)
                        if plan is not None else vr_rows)
        else:
            msgs, vr_state, vr_metrics = grads, state.get("vr"), {}

        # With diagnostics the shard_map emits a second output: the
        # replicated per-sender AggDiagnostics summary (all-P() specs).
        out_specs = node_specs
        if robust.diagnostics:
            out_specs = (node_specs,
                         telemetry.AggDiagnostics(
                             *(P() for _ in telemetry.AggDiagnostics._fields)))

        def node_agg(local_msgs, t, k, weights=None):
            local = jax.tree_util.tree_map(lambda z: z[0], local_msgs)
            out = decentralized_aggregate(
                local, robust, sched, comm=robust.comm, worker_axes=wa,
                model_axes=("model",), num_workers=w, key=k,
                round_index=t, row_weights=weights,
                diagnostics=robust.diagnostics)
            if robust.diagnostics:
                out, d = out
                return jax.tree_util.tree_map(lambda a: a[None], out), d
            return jax.tree_util.tree_map(lambda a: a[None], out)

        if rw is None:
            def gossip_agg(wire_msgs):
                return compat.shard_map(
                    node_agg, mesh=mesh, in_specs=(node_specs, P(), P()),
                    out_specs=out_specs, check_vma=False,
                )(wire_msgs, state["step"], jax.random.fold_in(key, 2))
        else:
            # Staleness weighting: the replicated (W,) sender weights ride
            # into the shard_map as a P() input and multiply the mask's
            # sender columns inside decentralized_aggregate.
            def gossip_agg(wire_msgs):
                return compat.shard_map(
                    node_agg, mesh=mesh, in_specs=(node_specs, P(), P(), P()),
                    out_specs=out_specs, check_vma=False,
                )(wire_msgs, state["step"], jax.random.fold_in(key, 2), rw)

        # Sender-side wire step for the gossiped channel (DESIGN.md
        # Sec. 12): same packed transmit as the master step, applied to
        # whichever tree goes on the wire -- gradients in gradient gossip,
        # the half-stepped models in params gossip.  Updated in the
        # auto-jit region before the shard_map, so both comm modes carry
        # bit-identical residual tables; decentralized_aggregate then
        # ships/dequantizes the (idempotently re-encoded) wire.
        ef_state = state.get("ef")

        def wire_transmit(tree):
            nonlocal ef_state
            if not wire_fmt.quantized:
                return tree
            wspec = robust.message_spec(tree, batch_ndim=1)
            wbuf = wspec.pack(tree)
            ef_rows = state.get("ef")
            if use_ef and plan is not None:
                ef_rows = participation_lib.gather_rows(state["ef"], cohort)
            wbuf, ef_rows = wspec.transmit(wbuf, ef_rows)
            if use_ef:
                ef_state = (participation_lib.scatter_rows(
                    state["ef"], cohort, ef_rows)
                    if plan is not None else ef_rows)
            return wspec.unpack(wbuf)

        if robust.gossip != "params":
            msgs = wire_transmit(msgs)

        # Honest-message variance BEFORE the gossip (first B nodes attack).
        var = telemetry.consensus_dist(msgs, honest, wh)

        diag = None
        if robust.gossip == "params":
            # Local optimizer step with each node's own corrected gradient,
            # then robust PARAMETER gossip: the wire carries half-stepped
            # models and the aggregate IS the new iterate.  agg_norm keeps
            # gradient-scale meaning across modes by reporting the per-step
            # MOVEMENT (aggregate minus previous iterate), not the iterate.
            updates, opt_state = optimizer.update(msgs, state["opt"], params,
                                                  state["step"])
            half = optim_lib.apply_updates(params, updates)
            agg = gossip_agg(wire_transmit(half))
            if robust.diagnostics:
                agg, diag = agg
            agg_move = jax.tree_util.tree_map(
                lambda a, p: a.astype(jnp.float32) - p.astype(jnp.float32),
                agg, params)
            params = agg
        else:
            agg = gossip_agg(msgs)
            if robust.diagnostics:
                agg, diag = agg
            agg_move = agg
            updates, opt_state = optimizer.update(agg, state["opt"], params,
                                                  state["step"])
            params = optim_lib.apply_updates(params, updates)
        agg_norm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree_util.tree_leaves(agg_move)) / w)
        health = state.get("health")
        health_metrics = {}
        if robust.guards:
            # Verdict on the per-step MOVEMENT norm (mode-independent
            # scale); a rejected round holds every node's params/opt/VR/EF.
            accept, health = guards_lib.round_verdict(
                agg_norm, health, decay=robust.reject_ema,
                zmax=robust.reject_zmax, warmup=robust.reject_warmup)
            params, opt_state, vr_state, ef_state = guards_lib.select_tree(
                accept, (params, opt_state, vr_state, ef_state),
                (state["params"], state["opt"], state.get("vr"),
                 state.get("ef")))
            health_metrics = telemetry.health_metrics(health, accept)
        new_state = {"params": params, "opt": opt_state,
                     "step": state["step"] + 1}
        if use_vr:
            new_state["vr"] = vr_state
        if use_ef:
            new_state["ef"] = ef_state
        if robust.guards:
            new_state["health"] = health
        if plan is not None:
            new_state["staleness"] = participation_lib.tick_staleness(
                state["staleness"], cohort)

        metrics = {
            "loss": jnp.sum(honest * losses) / wh,
            "honest_variance": var,
            # Consensus drift of the honest nodes' parameter copies.
            "consensus_dist": telemetry.consensus_dist(params, honest, wh),
            "agg_norm": agg_norm,
            **vr_metrics,
            **telemetry.staleness_metrics(slot_stal),
            **health_metrics,
        }
        if diag is not None:
            metrics.update(telemetry.diagnostics_metrics(diag))
        return new_state, metrics

    # ---- specs / structs: every leaf gains the leading node axis ---------
    def state_specs():
        sp = {"params": node_specs,
              "opt": _opt_specs_like(train.optimizer, node_specs),
              "step": P()}
        if use_vr:
            sp["vr"] = reducer.state_specs(pspecs, wa_spec)
        if use_ef:
            sp["ef"] = P(wa_spec)
        if robust.guards:
            sp["health"] = P()   # (HEALTH_WIDTH,) f32, replicated
        if plan is not None:
            sp["staleness"] = P()   # (num_clients,) int32, replicated
        return sp

    def state_structs():
        ps = model.param_structs()
        node = lambda s: jax.ShapeDtypeStruct((w,) + s.shape, s.dtype)
        nps = jax.tree_util.tree_map(node, ps)
        st = {"params": nps, "opt": _opt_structs_like(train.optimizer, nps),
              "step": jax.ShapeDtypeStruct((), jnp.int32)}
        if robust.guards:
            st["health"] = jax.ShapeDtypeStruct(
                (guards_lib.HEALTH_WIDTH,), jnp.float32)
        if use_vr:
            st["vr"] = reducer.state_structs(ps, num_clients,
                                             saga_num_samples)
        if use_ef:
            st["ef"] = jax.ShapeDtypeStruct(
                (num_clients,
                 robust.message_spec(ps, batch_ndim=0).padded_dim),
                jnp.float32)
        if plan is not None:
            st["staleness"] = jax.ShapeDtypeStruct((num_clients,), jnp.int32)
        return st

    return train_step, state_specs(), state_structs


def _gather_agg(msgs: Pytree, robust: RobustConfig) -> Pytree:
    """Paper-faithful master, per-leaf baseline (robust.packed=False):
    plain stacked aggregation; under jit the Weiszfeld forces an
    all-gather of the worker axis on every device."""
    name = robust.aggregator
    agg = agg_lib.get_aggregator(
        name, perleaf=True,
        max_iters=robust.weiszfeld_iters, tol=robust.weiszfeld_tol,
        num_groups=robust.num_groups, trim=robust.trim,
        num_byzantine=robust.num_byzantine, clip_radius=robust.clip_radius)
    return agg(msgs)


def _sharded_agg(msgs: Pytree, robust: RobustConfig, mesh,
                 param_specs: Pytree, *,
                 row_weights: Optional[jnp.ndarray] = None,
                 diagnostics: bool = False) -> Pytree:
    """Beyond-paper: all_to_all coordinate resharding + slice-local rules
    inside a FULLY-manual shard_map (worker axes and model axis): every leaf
    arrives as its local shard, the flatten/all_to_all stay local, and global
    geometry is restored by small psums over (worker + model) axes --
    W-float norms per Weiszfeld/clip iteration, one (W, W) partial Gram for
    krum, a (W, num_leaves) per-block matrix for geomed_blockwise.  Bytes
    moved per device: O(2 * p_shard) instead of the gather master's
    O(W * p_shard).  ``row_weights``: optional (W,) staleness weights,
    passed in REPLICATED (``P()``) so every device's slice rule sees the
    same per-row mass (DESIGN.md Sec. 10).

    With ``diagnostics`` the shard_map also returns the replicated
    :class:`repro.telemetry.AggDiagnostics` struct (every field rides out
    as a ``P()`` output -- the in-graph psums already made it identical on
    all devices)."""
    wa = mesh_lib.worker_axes(mesh)
    w = mesh_lib.num_workers(mesh)
    waxes = wa if len(wa) > 1 else wa[0]

    in_specs = jax.tree_util.tree_map(
        lambda s: P(waxes, *tuple(s)), param_specs,
        is_leaf=lambda x: isinstance(x, P))
    out_specs = param_specs
    if diagnostics:
        out_specs = (param_specs,
                     telemetry.AggDiagnostics(
                         *(P() for _ in telemetry.AggDiagnostics._fields)))

    if row_weights is None:
        def agg_fn(local_msgs):
            local = jax.tree_util.tree_map(lambda z: z[0], local_msgs)
            return sharded_aggregate(local, robust, worker_axes=wa,
                                     model_axes=("model",), num_workers=w,
                                     diagnostics=diagnostics)

        return compat.shard_map(agg_fn, mesh=mesh, in_specs=(in_specs,),
                                out_specs=out_specs, check_vma=False)(msgs)

    def agg_fn_w(local_msgs, rw):
        local = jax.tree_util.tree_map(lambda z: z[0], local_msgs)
        return sharded_aggregate(local, robust, worker_axes=wa,
                                 model_axes=("model",), num_workers=w,
                                 row_weights=rw, diagnostics=diagnostics)

    return compat.shard_map(agg_fn_w, mesh=mesh, in_specs=(in_specs, P()),
                            out_specs=out_specs,
                            check_vma=False)(msgs, row_weights)


def compile_train_step(step_fn, *, donate_state: bool = True):
    """jit a train step with the TRAIN STATE DONATED (arg 0).

    The state -- params, optimizer moments, the variance-reduction state
    (for SAGA the largest buffer in the federation: W x J x p), and
    per-node copies on the decentralized path -- is consumed and
    re-emitted every step, so
    donating it lets XLA reuse the input buffers for the outputs instead
    of holding both generations live (halves peak state memory; in-place
    updates on backends that support donation).  Works for both state
    conventions: the dict state of the distributed steps
    (``step(state, batch, key)``) and the :class:`FederatedState` of the
    simulation steps (``step(state)``).

    CONTRACT: after calling the compiled step, the caller must treat the
    passed-in state as dead (its buffers may be deleted) and continue from
    the returned state -- the standard training-loop pattern.  Never feed
    the same state object twice (``tests/test_donation.py`` pins the
    re-use-after-donation behaviour), and batches/keys are NOT donated
    (callers may reuse them across steps).
    """
    return jax.jit(step_fn, donate_argnums=(0,) if donate_state else ())


# ---------------------------------------------------------------------------
# Inference steps
# ---------------------------------------------------------------------------

def make_prefill_step(model: Model, mesh):
    def prefill_step(params, batch):
        return model.prefill(params, batch)
    return prefill_step


def make_serve_step(model: Model, shape: ShapeConfig, mesh, *,
                    window: Optional[int] = None):
    """One-token decode step.  For long_500k (batch=1) the KV cache is
    sequence-sharded over 'data' and attention LSE-combines across shards."""
    cfg = model.cfg
    seq_sharded = shape.global_batch == 1 and any(
        bs.kind == "attn" for bs in cfg.resolve_pattern()[0])
    if seq_sharded:
        compat.require_distributed(what="sequence-sharded decode")

    def serve_step(params, cache, tokens, pos):
        return model.decode_step(
            params, cache, tokens, pos, window=window,
            seq_shard_axis="data" if seq_sharded else None)

    return serve_step
