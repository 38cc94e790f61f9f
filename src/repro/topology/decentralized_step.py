"""Decentralized Byzantine-robust training over an explicit network graph.

Server-free counterpart of :mod:`repro.core.robust_step` (DESIGN.md
Secs. 6-7): there is no master -- every node keeps ITS OWN parameters,
computes its own (variance-reduced) stochastic gradient, exchanges messages
only with its graph neighbors, and robustly aggregates its masked
neighborhood with any registry aggregator (:mod:`repro.topology.masked`).
The message channel is configurable (``cfg.gossip``): GRADIENTS (aggregate
then apply the optimizer, PR-3 behaviour) or PARAMETERS (apply the
optimizer locally, then robust-aggregate the neighbors' half-stepped
models -- arXiv:2308.05292).  The graph itself may be time-varying: every
path accepts a :class:`repro.topology.GraphSchedule` whose per-round
mask/mixing constants are selected by the traced round counter
(``topology/schedule.py``).  Byzantine nodes attack PER EDGE: the message
a Byzantine sender injects toward receiver i is crafted from receiver i's
own honest-neighborhood statistics, so two receivers see different poison
(strictly stronger than the master-path attacks, which send one identical
vector to the single aggregation point).

Three execution paths share the math, mirroring the master layout:

* :func:`make_decentralized_step` -- single-host simulation (dense
  (N, N, ...) exchange tensor), the path behind
  ``make_federated_step(..., topology=..., schedule=...)``;
* :func:`decentralized_aggregate` with ``comm="gather"`` -- inside
  ``shard_map``: all_gather the worker axes, pick this node's mask row at
  its linear worker index, aggregate its own neighborhood (per-iteration
  psums over the model axes, worker-axis pmax keeping the Weiszfeld loops
  in collective lockstep);
* ``comm="sharded"`` -- the coordinate-resharded path: the Sec. 2
  all_to_all gives every device a p/W slice of ALL messages, per-edge
  attacks and ALL receivers' masked aggregations run slice-locally with
  (R, S)-shaped psums restoring global geometry, and a second all_to_all
  routes each receiver its own aggregate's slices.

``topology="star"`` (with a static schedule) is deliberately NOT routed
here: the training entry points special-case it onto the existing master
implementations so the default path stays bit-exact with the paper
reproduction.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro import telemetry
from repro.core import attacks as attack_lib
from repro.core import guards as guards_lib
from repro.core import packing
from repro.core.robust_step import (FederatedState, _flatten_concat,
                                    _local_leaf_ids)
from repro.optim import optimizers as optim_lib
from repro.topology.graphs import Topology
from repro.topology.masked import (masked_aggregate, masked_aggregate_flat,
                                   masked_weiszfeld_segments)
from repro.topology.schedule import as_schedule, validate_schedule

Pytree = Any

GOSSIP_MODES = ("gradient", "params")


def _check_gossip(cfg) -> str:
    gossip = getattr(cfg, "gossip", "gradient")
    if gossip not in GOSSIP_MODES:
        raise ValueError(f"RobustConfig.gossip must be one of {GOSSIP_MODES}, "
                         f"got {gossip!r}")
    return gossip


def _bcast_rows(tree: Pytree, r: int) -> Pytree:
    """Leaves (S, ...) -> (R, S, ...) by broadcast (honest senders say the
    same thing to every receiver)."""
    return jax.tree_util.tree_map(
        lambda z: jnp.broadcast_to(z[None], (r,) + z.shape), tree)


def build_exchange(
    msgs: Pytree,
    cfg: attack_lib.AttackConfig,
    mask: jnp.ndarray,
    is_byz: jnp.ndarray,
    key: Optional[jax.Array] = None,
    *,
    spec: Optional[packing.PackSpec] = None,
) -> Pytree:
    """Materialize the per-edge message exchange.

    ``msgs``: leaves (S, ...) -- the honestly computed messages (rows of
    Byzantine senders are ignored).  ``mask``: (R, S) neighbor-mask rows of
    the receivers being built.  ``is_byz``: (S,) marks Byzantine senders.
    Returns leaves (R, S, ...): row r is receiver r's view, with every
    Byzantine sender's entry replaced by an attack vector crafted from
    receiver r's masked HONEST statistics (mask-select; the omniscient
    threat model of DESIGN.md Sec. 1 already grants attackers these stats).

    All rules are coordinate-separable, so the same construction runs on
    full messages (simulation), model shards (gather), coordinate slices
    (sharded) AND the packed (S, D) message buffer of DESIGN.md Sec. 8
    with no communication; only the ``gaussian`` attack's draws are
    layout-dependent -- pass the buffer's PackSpec as ``spec=`` and they
    mirror the per-leaf draws bit-for-bit (same caveat/fix as the
    master-path attack variants).
    """
    r = mask.shape[0]
    if cfg.name not in attack_lib.ATTACK_NAMES:
        raise ValueError(f"unknown attack {cfg.name!r}; known: "
                         f"{', '.join(sorted(attack_lib.ATTACK_NAMES))}")
    if cfg.name == "none" or cfg.num_byzantine == 0:
        return _bcast_rows(msgs, r)

    byz_f = is_byz.astype(jnp.float32)                    # (S,)
    hon_w = mask * (1.0 - byz_f)[None, :]                 # (R, S)
    h_cnt = jnp.maximum(jnp.sum(hon_w, axis=1), 1.0)      # (R,)
    b_cnt = jnp.maximum(jnp.sum(mask * byz_f[None, :], axis=1), 1.0)

    def nbr_mean(fn):
        def leaf(z):
            w = hon_w.reshape(hon_w.shape + (1,) * (z.ndim - 1))
            acc = jnp.sum(w * fn(z.astype(jnp.float32))[None], axis=1)
            return acc / h_cnt.reshape((-1,) + (1,) * (z.ndim - 1))
        return jax.tree_util.tree_map(leaf, msgs)

    mean = nbr_mean(lambda z: z)                          # leaves (R, ...)

    name = cfg.name
    if name == "sign_flip":
        byz = jax.tree_util.tree_map(
            lambda m: cfg.sign_flip_magnitude * m, mean)
    elif name == "zero_gradient":
        # Each receiver's masked neighborhood mean becomes exactly zero.
        ratio = h_cnt / b_cnt
        byz = jax.tree_util.tree_map(
            lambda m: -ratio.reshape((-1,) + (1,) * (m.ndim - 1)) * m, mean)
    elif name == "ipm":
        byz = jax.tree_util.tree_map(lambda m: -cfg.ipm_eps * m, mean)
    elif name == "alie":
        sq = nbr_mean(jnp.square)
        byz = jax.tree_util.tree_map(
            lambda m, s: m + cfg.alie_z * jnp.sqrt(
                jnp.maximum(s - m * m, 0.0)), mean, sq)
    elif name == "straggler":
        # Stale-by-k report, per receiver: a scaled copy of receiver r's own
        # honest-neighborhood mean stands in for a message computed
        # ``straggler_k`` rounds ago (the same deterministic proxy as the
        # master-path attack, receiver-localized).
        byz = jax.tree_util.tree_map(
            lambda m: (1.0 + 0.25 * cfg.straggler_k) * m, mean)
    elif name == "dropout":
        # Absent sender: its edges carry zero payload toward every receiver;
        # the bounded-staleness weights (sender staleness = max_staleness ->
        # weight exactly 0 on its mask COLUMN) remove it from each masked
        # aggregation without slicing the sender axis.
        byz = jax.tree_util.tree_map(jnp.zeros_like, mean)
    elif name == "nan":
        # Fault injection (DESIGN.md Sec. 13): every real coordinate of the
        # Byzantine edges is NaN; packed padding stays 0 (the trajectory
        # pin rationale of attacks._fault_fill).
        byz = attack_lib._fault_fill(
            lambda m: jnp.full_like(m, jnp.nan), mean, spec)
    elif name == "inf_overflow":
        byz = attack_lib._fault_fill(
            lambda m: jnp.where(m < 0, -attack_lib.OVERFLOW_MAGNITUDE,
                                attack_lib.OVERFLOW_MAGNITUDE
                                ).astype(m.dtype), mean, spec)
    elif name == "bitflip":
        # Seeded coordinate corruption, hashed per SENDER: (R, S, ...)
        # payloads built from each receiver's neighborhood mean.
        byz = attack_lib.bitflip_edges(
            mean, jnp.arange(mask.shape[1], dtype=jnp.int32),
            prob=cfg.bitflip_prob, seed=cfg.bitflip_seed, spec=spec)
    elif name == "gaussian":
        if key is None:
            raise ValueError("gaussian attack needs a key")
        std = jnp.sqrt(cfg.gaussian_variance)
        s = mask.shape[1]
        if spec is not None:
            byz = jax.tree_util.tree_map(
                lambda m: m[:, None] + attack_lib.packed_gaussian_noise(
                    spec, key, (r, s), std), mean)
        else:
            leaves, treedef = jax.tree_util.tree_flatten(mean)
            keys = jax.random.split(key, len(leaves))
            byz = jax.tree_util.tree_unflatten(treedef, [
                m[:, None] + std * jax.random.normal(
                    k, (r, s) + m.shape[1:], jnp.float32)
                for m, k in zip(leaves, keys)])
    else:
        # Reachable for a name that IS in the registry: every attack needs
        # an explicit per-edge generalization here (receiver-local stats),
        # so a newly registered master-path attack fails loudly with the
        # gap named instead of silently passing through unattacked.
        raise NotImplementedError(
            f"attack {name!r} is registered in core.attacks but has no "
            "per-edge decentralized form in topology.build_exchange -- add "
            "its receiver-neighborhood construction here")

    def select(z, bz):
        zb = jnp.broadcast_to(z[None].astype(jnp.float32),
                              (r,) + z.shape)
        # Per-receiver attack values broadcast over senders unless the
        # attack already drew per-edge values (gaussian).
        bz_rows = bz[:, None] if bz.ndim == z.ndim else bz
        sel = is_byz.reshape((1, -1) + (1,) * (z.ndim - 1))
        return jnp.where(sel, bz_rows, zb).astype(z.dtype)

    return jax.tree_util.tree_map(select, msgs, byz)


def _agg_opts(cfg, mixing, axis_names=(), sync_axes=()):
    return dict(
        max_iters=cfg.weiszfeld_iters, tol=cfg.weiszfeld_tol,
        num_groups=cfg.num_groups, trim=cfg.trim,
        num_byzantine=cfg.num_byzantine, clip_radius=cfg.clip_radius,
        mixing=mixing, axis_names=tuple(axis_names),
        sync_axes=tuple(sync_axes))


def validate_topology(cfg, topo: Topology, num_nodes: int) -> None:
    """Static feasibility checks against a FIXED graph (trace-time, so they
    raise with context instead of producing NaN aggregates).  Delegates to
    ``validate_schedule`` on the graph's static schedule, so the fixed and
    time-varying validation paths cannot drift apart."""
    validate_schedule(cfg, as_schedule(topo), num_nodes)


# ---------------------------------------------------------------------------
# Simulation path (single host, dense exchange)
# ---------------------------------------------------------------------------

def make_decentralized_step(
    loss_fn: Callable[[Pytree, Pytree], jnp.ndarray],
    worker_data: Pytree,
    cfg,
    optimizer: optim_lib.Optimizer,
    topology,
):
    """Build ``(init_fn, step_fn)`` for the simulated decentralized
    federation; drop-in shaped like
    :func:`repro.core.robust_step.make_federated_step` but with PER-NODE
    parameters.

    ``topology``: a fixed :class:`Topology` or a time-varying
    :class:`GraphSchedule` (DESIGN.md Sec. 7) -- round ``t`` uses the
    schedule's ``t % period`` graph, selected from stacked compile-time
    mask/mixing constants by the traced step counter.

    Gossip modes (``cfg.gossip``):

    * ``"gradient"`` (PR-3 behaviour) -- nodes exchange (variance-reduced)
      GRADIENT messages, robust-aggregate the masked neighborhood, and
      apply the optimizer to the aggregate;
    * ``"params"`` (arXiv:2308.05292's setting) -- each node first takes a
      LOCAL optimizer step with its own corrected gradient, then the
      half-stepped PARAMETERS are exchanged and each node's new iterate is
      the robust aggregate of its neighborhood's models.  Byzantine nodes
      poison the parameter channel per edge with the same receiver-local
      constructions (``build_exchange`` is message-agnostic).

    Graph nodes are ``N = W_h + B``: the first W_h ids are the honest
    workers (rows of ``worker_data``), the LAST B are Byzantine (matching
    the simulation convention of ``attacks.apply_attack``, which appends
    Byzantine rows; the distributed path replaces the FIRST B workers,
    matching ``apply_attack_stacked``).  State leaves carry a leading node
    axis: every node owns its own parameter/optimizer copy, and
    ``consensus_dist`` in the metrics tracks how far the honest copies have
    drifted apart.

    With ``cfg.num_clients > 0`` (DESIGN.md Sec. 10) ``worker_data`` holds
    one shard per VIRTUAL CLIENT -- (num_clients, J, ...) -- and each round
    a seeded cohort of ``cfg.cohort_size`` clients mans the W_h honest node
    slots: the cohort's data + VR-state rows are gathered into the round
    view, scattered back after, and the cohort's staleness counters weight
    the sender COLUMNS of the neighbor mask (exact down-weighting for the
    weight-based rules; with the default ``staleness_decay=1.0`` weights
    are 0/1 so the count-based rules' neighbor counts stay integral).
    Node parameters stay per-SLOT (the physical gossip network); clients
    contribute data and variance-reduction memory.
    """
    sched = as_schedule(topology)
    num_rows = jax.tree_util.tree_leaves(worker_data)[0].shape[0]
    if cfg.num_clients:
        if cfg.num_clients != num_rows:
            raise ValueError(
                f"num_clients={cfg.num_clients} but worker_data has "
                f"{num_rows} client shards")
        if not cfg.cohort_size:
            raise ValueError(
                "partial participation in the decentralized simulation "
                "needs an explicit cohort_size")
    from repro.core import participation as participation_lib
    plan = participation_lib.resolve_participation(
        cfg, cfg.cohort_size if cfg.num_clients else num_rows)
    wh = plan.cohort_size if plan is not None else num_rows
    num_clients = plan.num_clients if plan is not None else num_rows
    weighted = participation_lib.uses_staleness(cfg, plan)
    j = jax.tree_util.tree_leaves(worker_data)[0].shape[1]
    b = cfg.num_byzantine if cfg.attack != "none" else 0
    n = wh + b
    validate_schedule(cfg, sched, n)
    gossip = _check_gossip(cfg)
    grad_fn = jax.grad(loss_fn)
    attack_cfg = cfg.attack_config()
    reducer = cfg.reducer()
    wire_fmt = cfg.wire_format()
    if wire_fmt.quantized and not cfg.packed:
        raise ValueError(
            f"message_dtype={cfg.message_dtype!r} is a quantized wire "
            "format and needs the packed path (cfg.packed=True)")
    is_byz = jnp.arange(n) >= wh

    def sample_batch(data_w, idx):
        return jax.tree_util.tree_map(lambda d: d[idx], data_w)

    def per_worker_grad(params_w, data_w, idx):
        return grad_fn(params_w, sample_batch(data_w, idx))

    def full_local_grads(params_per_worker, data):
        """(W, ...) full local gradients at per-NODE honest params (the
        lsvrg anchor oracle); ``data`` rows pair with the param rows."""
        return jax.vmap(grad_fn)(params_per_worker, data)

    pack_fn = None
    if cfg.packed:
        def pack_fn(tree, batch_ndim):
            spec = cfg.message_spec(tree, batch_ndim=batch_ndim)
            return spec.pack(tree, batch_ndim=batch_ndim)

    def init_fn(params, key):
        nodes = jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape) + 0, params)
        opt_state = optimizer.init(nodes)

        def per_sample_table():
            def worker_tab(data_w):
                return jax.vmap(
                    lambda jj: grad_fn(params, sample_batch(data_w, jj[None]))
                )(jnp.arange(j))
            return jax.vmap(worker_tab)(worker_data)

        # VR state covers the HONEST workers only (the first wh node ids;
        # Byzantine nodes fabricate messages, they keep no tables), in the
        # message layout -- same convention as the master path (Sec. 8).
        # Under partial participation the tables are resident PER CLIENT.
        vr_state = reducer.init_sim(
            params,
            per_sample_grads_fn=per_sample_table,
            full_grads_fn=lambda p: full_local_grads(
                jax.tree_util.tree_map(
                    lambda q: jnp.broadcast_to(
                        q[None], (num_clients,) + q.shape), p),
                worker_data),
            num_workers=num_clients, pack_fn=pack_fn)
        staleness = (participation_lib.init_staleness(num_clients)
                     if plan is not None else None)
        ef = None
        if wire_fmt.error_feedback:
            d = cfg.message_spec(params, batch_ndim=0).padded_dim
            ef = jnp.zeros((num_clients, d), jnp.float32)
        health = guards_lib.init_health() if cfg.guards else None
        return FederatedState(nodes, opt_state, vr_state,
                              jnp.zeros((), jnp.int32), key, staleness, ef,
                              health)

    def round_inputs(state):
        """The round's (data, vr rows, honest staleness, cohort) -- the
        participation layer's single gather (see robust_step)."""
        if plan is None:
            stal = jnp.zeros((wh,), jnp.int32) if weighted else None
            return worker_data, state.vr, stal, None
        cohort = plan.cohort_at(state.step)
        data = participation_lib.gather_rows(worker_data, cohort)
        vr_rows = (participation_lib.gather_rows(state.vr, cohort)
                   if reducer.stateful else state.vr)
        return data, vr_rows, jnp.take(state.staleness, cohort, axis=0), cohort

    def finish_round(state, cohort, vr_rows):
        if plan is None:
            return vr_rows, state.staleness
        vr_state = (participation_lib.scatter_rows(state.vr, cohort, vr_rows)
                    if reducer.stateful else vr_rows)
        return vr_state, participation_lib.tick_staleness(state.staleness,
                                                          cohort)

    def sender_weights(honest_stal):
        """(N,) staleness weights over the node/sender axis (honest slots
        first, Byzantine LAST -- the sim node-id convention), or None on the
        unweighted bit-exact path."""
        if not weighted:
            return None, None
        slot_stal = participation_lib.slot_staleness(
            honest_stal, cfg.attack, b, straggler_k=cfg.straggler_k,
            max_staleness=cfg.max_staleness)
        return participation_lib.staleness_weights(
            slot_stal, decay=cfg.staleness_decay,
            max_staleness=cfg.max_staleness), slot_stal

    def honest_grads(state, k_idx, data):
        honest_params = jax.tree_util.tree_map(lambda x: x[:wh], state.params)
        idx = reducer.draw_indices(k_idx, wh, j)
        if idx.ndim == 2:       # minibatch layout: (W, B) sample draws
            honest = jax.vmap(per_worker_grad)(honest_params, data, idx)
            return honest, idx
        honest = jax.vmap(
            lambda p, d, i: per_worker_grad(p, d, i[None])
        )(honest_params, data, idx)
        return honest, idx

    def correct(state, vr, honest, idx, k_idx, *, data, spec=None):
        """Route the honest nodes' raw gradients through the reducer (the
        snapshot oracles evaluate against each node's OWN params)."""
        if not reducer.stateful:
            return honest, vr, {}
        k_vr = jax.random.fold_in(k_idx, 1)   # DCE'd unless the reducer draws
        honest_params = jax.tree_util.tree_map(lambda x: x[:wh], state.params)

        def as_tree(x):
            return spec.unpack(x) if spec is not None else x

        def as_msgs(tree):
            return spec.pack(tree, batch_ndim=1) if spec is not None else tree

        def grads_at(snapshot):
            snap = as_tree(snapshot)
            return as_msgs(jax.vmap(
                lambda p, d, i: per_worker_grad(p, d, i[None])
            )(snap, data, idx))

        def full_grads_at(p):
            return as_msgs(full_local_grads(as_tree(p), data))

        return reducer.correct(
            vr, honest, idx, k_vr,
            params=as_msgs(honest_params),
            grads_at=grads_at, full_grads_at=full_grads_at)

    def consensus(params):
        # Consensus drift IS the honest-variance formula applied to the
        # honest nodes' parameter copies (telemetry helper, Sec. 11).
        xh = jax.tree_util.tree_map(lambda x: x[:wh], params)
        return telemetry.honest_variance(xh, wh)

    def step_fn_perleaf(state):
        """Pre-refactor per-leaf pipeline (cfg.packed=False): the bench
        baseline.  When staleness weights are active they multiply the
        sender COLUMNS of the round's mask (mask-as-weight: exact for the
        weight-based rules, exact mask-out for dropped senders) before both
        the per-edge attack statistics and the masked aggregation."""
        key, k_idx, k_attack = jax.random.split(state.key, 3)
        mask = sched.mask_at(state.step)
        mixing = sched.mixing_at(state.step)
        data, vr_rows, honest_stal, cohort = round_inputs(state)
        honest, idx = honest_grads(state, k_idx, data)
        honest, vr_rows, vr_metrics = correct(state, vr_rows, honest, idx,
                                              k_idx, data=data)
        vr_state, staleness = finish_round(state, cohort, vr_rows)
        sw, slot_stal = sender_weights(honest_stal)
        wmask = mask if sw is None else mask * sw[None, :]

        # Honest-message variance (same metric as the master path).
        var = telemetry.honest_variance(honest, wh)

        # Byzantine node rows carry zeros until the attack replaces them.
        msgs = jax.tree_util.tree_map(
            lambda g: jnp.zeros((n,) + g.shape[1:], g.dtype).at[:wh].set(g),
            honest)

        guard_info = {}

        def gossip_agg(wire):
            exchange = build_exchange(wire, attack_cfg, wmask, is_byz,
                                      k_attack)
            gw = wmask
            if cfg.guards:
                # Per-edge containment (DESIGN.md Sec. 13): each receiver
                # quarantines its non-finite / over-magnitude in-edges; the
                # (R, S) validity mask folds into the neighbor mask (weight
                # exactly 0, clean rounds keep wmask bitwise).
                emask = guards_lib.pairwise_guard_mask(
                    exchange, wmask, multiplier=cfg.guard_multiplier)
                exchange = guards_lib.sanitize_rows(exchange, emask)
                gw = wmask * emask
                guard_info["quarantined_edges"] = jnp.sum(
                    (wmask > 0) * (1.0 - emask))
            out = masked_aggregate(
                cfg.aggregator, exchange, gw, perleaf=True,
                diagnostics=cfg.diagnostics,
                **_agg_opts(cfg, mixing * gw))
            return out if cfg.diagnostics else (out, None)

        if gossip == "params":
            # Local step first, then robust PARAMETER gossip: the messages
            # on the wire are each node's half-stepped model.
            updates, opt_state = optimizer.update(
                msgs, state.opt_state, state.params, state.step)
            half = optim_lib.apply_updates(state.params, updates)
            params, diag = gossip_agg(half)
            watch = params
        else:
            agg, diag = gossip_agg(msgs)
            updates, opt_state = optimizer.update(
                agg, state.opt_state, state.params, state.step)
            params = optim_lib.apply_updates(state.params, updates)
            watch = agg

        health = state.health
        if cfg.guards:
            # Round verdict on the gossip output's global norm; a rejected
            # round holds every node's params/opt/VR (same hold semantics
            # as the master step).
            accept, health = guards_lib.round_verdict(
                guards_lib.tree_norm(watch), state.health,
                decay=cfg.reject_ema, zmax=cfg.reject_zmax,
                warmup=cfg.reject_warmup)
            params, opt_state, vr_state = guards_lib.select_tree(
                accept, (params, opt_state, vr_state),
                (state.params, state.opt_state, state.vr))
            guard_info.update(telemetry.health_metrics(health, accept))

        new_state = FederatedState(params, opt_state, vr_state,
                                   state.step + 1, key, staleness, state.ef,
                                   health)
        metrics = {"honest_variance": var,
                   "consensus_dist": consensus(params), **vr_metrics,
                   **telemetry.staleness_metrics(slot_stal), **guard_info}
        if diag is not None:
            metrics.update(telemetry.diagnostics_metrics(
                telemetry.reduce_masked_diagnostics(diag, wmask)))
        return new_state, metrics

    def step_fn_packed(state):
        """Flat-packed pipeline (DESIGN.md Sec. 8): one (N, D) message
        buffer feeds the per-edge attack and the masked flat engine; the
        dense (N, N, D) exchange replaces the per-leaf exchange tensors.
        Staleness weights multiply the mask's sender columns, as in the
        per-leaf step."""
        key, k_idx, k_attack = jax.random.split(state.key, 3)
        mask = sched.mask_at(state.step)
        mixing = sched.mixing_at(state.step)
        data, vr_rows, honest_stal, cohort = round_inputs(state)
        honest_tree, idx = honest_grads(state, k_idx, data)
        spec = cfg.message_spec(honest_tree, batch_ndim=1)
        honest = spec.pack(honest_tree)                        # (W_h, D)
        honest, vr_rows, vr_metrics = correct(state, vr_rows, honest, idx,
                                              k_idx, data=data, spec=spec)
        vr_state, staleness = finish_round(state, cohort, vr_rows)
        sw, slot_stal = sender_weights(honest_stal)
        wmask = mask if sw is None else mask * sw[None, :]

        ef_state = state.ef

        def wire_transmit(rows):
            """Honest senders' wire step (DESIGN.md Sec. 12): fold in / bank
            the error-feedback residual and return the dequantized wire rows
            the neighbors would see.  Identity for the float formats.  The
            per-edge Byzantine payloads stay f32 -- build_exchange replaces
            Byzantine sender entries wholesale, so there is no honest wire
            to constrain them to (the master paths DO re-quantize their
            single shared attack vector)."""
            nonlocal ef_state
            if not wire_fmt.quantized:
                return rows
            ef_rows = state.ef
            if wire_fmt.error_feedback and plan is not None:
                ef_rows = participation_lib.gather_rows(state.ef, cohort)
            rows, ef_rows = spec.transmit(rows, ef_rows)
            if wire_fmt.error_feedback:
                ef_state = (participation_lib.scatter_rows(
                    state.ef, cohort, ef_rows)
                    if plan is not None else ef_rows)
            return rows

        if gossip == "gradient":
            # Gradient gossip transmits the VR-corrected gradients; params
            # gossip keeps them local and transmits the half-stepped models
            # (below), so only ONE of the two channels pays the wire.
            honest = wire_transmit(honest)

        var = telemetry.honest_variance(honest, wh)

        # Byzantine node rows carry zeros until the attack replaces them.
        msgs = jnp.zeros((n,) + honest.shape[1:], honest.dtype).at[:wh].set(honest)

        guard_info = {}

        def flat_gossip(wire_buf):
            exchange = build_exchange(wire_buf, attack_cfg, wmask, is_byz,
                                      k_attack, spec=spec)     # (N, N, D)
            gw = wmask
            if cfg.guards:
                # Per-edge containment on the dequantized wire (same fold
                # as the per-leaf step; guard AFTER the wire roundtrip so
                # the mask judges what the rules consume).
                emask = guards_lib.pairwise_guard_mask(
                    exchange, wmask, multiplier=cfg.guard_multiplier)
                exchange = guards_lib.sanitize_rows(exchange, emask)
                gw = wmask * emask
                guard_info["quarantined_edges"] = jnp.sum(
                    (wmask > 0) * (1.0 - emask))
            out = masked_aggregate_flat(
                cfg.aggregator, exchange, gw, spec=spec,
                diagnostics=cfg.diagnostics,
                **_agg_opts(cfg, mixing * gw))                 # (N, D) f32
            out, diag = out if cfg.diagnostics else (out, None)
            return spec.unpack(out, batch_ndim=1), diag

        if gossip == "params":
            updates, opt_state = optimizer.update(
                spec.unpack(msgs, batch_ndim=1), state.opt_state,
                state.params, state.step)
            half = optim_lib.apply_updates(state.params, updates)
            wire = spec.pack(half)                             # (N, D)
            # Honest nodes transmit their half-stepped model over the
            # quantized wire (EF residuals track the PARAM channel here);
            # sim node arrays are not mesh-sharded, so the row slice is
            # safe (the old-XLA hazard only bites sharded worker axes).
            wire = wire.at[:wh].set(wire_transmit(wire[:wh]))
            params, diag = flat_gossip(wire)
            watch = params
        else:
            agg, diag = flat_gossip(msgs)
            updates, opt_state = optimizer.update(
                agg, state.opt_state, state.params, state.step)
            params = optim_lib.apply_updates(state.params, updates)
            watch = agg

        health = state.health
        if cfg.guards:
            # Round verdict + hold (same semantics as the per-leaf step).
            accept, health = guards_lib.round_verdict(
                guards_lib.tree_norm(watch), state.health,
                decay=cfg.reject_ema, zmax=cfg.reject_zmax,
                warmup=cfg.reject_warmup)
            params, opt_state, vr_state, ef_state = guards_lib.select_tree(
                accept, (params, opt_state, vr_state, ef_state),
                (state.params, state.opt_state, state.vr, state.ef))
            guard_info.update(telemetry.health_metrics(health, accept))

        new_state = FederatedState(params, opt_state, vr_state,
                                   state.step + 1, key, staleness, ef_state,
                                   health)
        metrics = {"honest_variance": var,
                   "consensus_dist": consensus(params), **vr_metrics,
                   **telemetry.staleness_metrics(slot_stal), **guard_info}
        if diag is not None:
            metrics.update(telemetry.diagnostics_metrics(
                telemetry.reduce_masked_diagnostics(diag, wmask)))
        return new_state, metrics

    return init_fn, (step_fn_packed if cfg.packed else step_fn_perleaf)


# ---------------------------------------------------------------------------
# Distributed path (inside shard_map; one node per worker-axis index)
# ---------------------------------------------------------------------------

def decentralized_aggregate(
    grads: Pytree,
    cfg,
    topology,
    *,
    comm: str = "gather",
    worker_axes: tuple[str, ...] = ("data",),
    model_axes: tuple[str, ...] = ("model",),
    num_workers: int,
    key: Optional[jax.Array] = None,
    round_index: Optional[jax.Array] = None,
    use_topology_kernel: Optional[bool] = None,
    row_weights: Optional[jnp.ndarray] = None,
    diagnostics: Optional[bool] = None,
) -> Pytree:
    """Per-node robust neighborhood aggregation inside ``shard_map``.

    ``grads``: this node's message (leaves are local model shards) -- a
    gradient in gradient-gossip mode, the half-stepped parameters in
    params-gossip mode (the aggregation itself is message-agnostic).
    ``topology``: a fixed :class:`Topology` or a :class:`GraphSchedule`; a
    time-varying schedule needs the traced ``round_index`` to select the
    round's stacked mask/mixing constants (``lax.dynamic_index_in_dim``, no
    per-round retrace).  Nodes are the linear worker-axis indices
    (row-major over ``worker_axes``, the Sec. 2 convention); the FIRST
    ``cfg.num_byzantine`` nodes attack per edge.  Returns THIS node's
    aggregate (same local-shard geometry as the input) -- per-node results,
    unlike the master paths which return one shared aggregate.

    ``cfg.packed`` (default) packs the local shard once so the gather mode
    runs ONE collective + the flat masked engine on the (S, D) buffer; the
    sharded mode operates on coordinate slices either way (DESIGN.md
    Sec. 8).  ``use_topology_kernel`` routes the coordinate-separable
    masked trimmed-mean reduction of the SHARDED branch through the fused
    Pallas kernel ``kernels/topology.py`` (one HBM sweep, no sort; the
    mixing-weighted mean keeps the jnp path since the kernel reduces
    uniformly); default: on for TPU backends only, off elsewhere -- on
    CPU the interpret-mode kernel is slower than the jnp rules (it still
    runs under ``interpret=True`` when the flag is forced, for tests).

    ``diagnostics`` (default ``cfg.diagnostics``): when on, additionally
    returns the REPLICATED per-sender :class:`repro.telemetry.AggDiagnostics`
    summary (``reduce_masked_diagnostics`` folds the per-receiver fields
    with the psums matching each comm mode), so every node reports the
    same sender-suspicion trace.
    """
    if comm not in ("gather", "sharded"):
        raise ValueError(f"comm must be 'gather' or 'sharded', got {comm!r}")
    diag_on = (getattr(cfg, "diagnostics", False) if diagnostics is None
               else diagnostics)
    w = num_workers
    sched = as_schedule(topology)
    validate_schedule(cfg, sched, w)
    if not sched.is_static and round_index is None:
        raise ValueError(
            f"schedule {sched.name!r} is time-varying (period "
            f"{sched.period}); decentralized_aggregate needs round_index=")
    t = 0 if round_index is None else round_index
    attack_cfg = cfg.attack_config()
    mask_all = sched.mask_at(t)                               # (S, S)
    mixing_all = sched.mixing_at(t)
    if row_weights is not None:
        # Bounded-staleness weighting (DESIGN.md Sec. 10): the replicated
        # (S,) per-sender weights multiply the mask's sender COLUMNS, so
        # every receiver's masked rule down-weighs the same stale senders
        # and masks out the absent ones (mask-as-weight -- no sender-axis
        # slicing).
        mask_all = mask_all * row_weights.astype(jnp.float32)[None, :]
    is_byz = jnp.arange(w) < cfg.num_byzantine
    wid = compat.axis_index(worker_axes)
    packed = getattr(cfg, "packed", True)
    wire_fmt = packing.resolve_wire_format(
        getattr(cfg, "message_dtype", "float32"))
    if wire_fmt.quantized and not packed:
        raise ValueError(
            f"message_dtype={cfg.message_dtype!r} is a quantized wire "
            "format and needs the packed path (cfg.packed=True)")

    if comm == "gather":
        mask_row = jnp.take(mask_all, wid, axis=0)[None]      # (1, S)
        mix_row = jnp.take(mixing_all, wid, axis=0)[None]
        k = jax.random.fold_in(key, wid) if key is not None else None
        if packed:
            # One collective: pack the local shard, gather the (S, D_shard)
            # buffer, run the flat masked engine on this node's row.
            spec = cfg.message_spec(grads, batch_ndim=0)
            buf = spec.pack(grads, batch_ndim=0)
            if spec.quantized:
                # The quantized buffer crosses the wire; the receiver
                # dequantizes BEFORE building the exchange, so the per-edge
                # attacks observe the dequantized honest messages -- the
                # same view the sim path's build_exchange gets.
                codes, scales = spec.encode(buf, axis_names=model_axes)
                stacked = spec.decode(
                    compat.all_gather(codes, worker_axes, axis=0,
                                      tiled=False),
                    compat.all_gather(scales, worker_axes, axis=0,
                                      tiled=False))
            else:
                stacked = compat.all_gather(buf, worker_axes, axis=0,
                                            tiled=False)
            exchange = build_exchange(stacked, attack_cfg, mask_row, is_byz,
                                      k, spec=spec)           # (1, S, D)
            gm_row = mask_row
            if getattr(cfg, "guards", False):
                # Per-edge containment (DESIGN.md Sec. 13): this node's
                # (1, S) validity mask -- coordinate partials psum over the
                # MODEL axes (the gathered rows are model shards).
                emask = guards_lib.pairwise_guard_mask(
                    exchange, mask_row, multiplier=cfg.guard_multiplier,
                    axis_names=model_axes)
                exchange = guards_lib.sanitize_rows(exchange, emask)
                gm_row = mask_row * emask
            agg = masked_aggregate_flat(
                cfg.aggregator, exchange, gm_row, spec=spec,
                diagnostics=diag_on,
                **_agg_opts(cfg, mix_row * gm_row,
                            axis_names=model_axes, sync_axes=worker_axes))
            if diag_on:
                agg, diag = agg
                # Each device holds ONE receiver row; the cross-receiver
                # folds psum over the worker axes.
                return (spec.unpack(agg[0], batch_ndim=0),
                        telemetry.reduce_masked_diagnostics(
                            diag, mask_row, axis_names=worker_axes))
            return spec.unpack(agg[0], batch_ndim=0)
        stacked = jax.tree_util.tree_map(
            lambda g: compat.all_gather(g, worker_axes, axis=0, tiled=False),
            grads)
        exchange = build_exchange(stacked, attack_cfg, mask_row, is_byz, k)
        gm_row = mask_row
        if getattr(cfg, "guards", False):
            emask = guards_lib.pairwise_guard_mask(
                exchange, mask_row, multiplier=cfg.guard_multiplier,
                axis_names=model_axes)
            exchange = guards_lib.sanitize_rows(exchange, emask)
            gm_row = mask_row * emask
        agg = masked_aggregate(
            cfg.aggregator, exchange, gm_row, perleaf=True,
            diagnostics=diag_on,
            **_agg_opts(cfg, mix_row * gm_row,
                        axis_names=model_axes, sync_axes=worker_axes))
        if diag_on:
            agg, diag = agg
            return (jax.tree_util.tree_map(lambda a: a[0], agg),
                    telemetry.reduce_masked_diagnostics(
                        diag, mask_row, axis_names=worker_axes))
        return jax.tree_util.tree_map(lambda a: a[0], agg)

    # comm == "sharded": reuse the coordinate-resharding plumbing of
    # robust_step.sharded_aggregate, but aggregate ALL receivers' masked
    # neighborhoods on this device's slice and route each receiver its own
    # result with a second all_to_all (DESIGN.md Sec. 6).
    flat, _, leaf_sizes = _flatten_concat(grads)
    p = flat.shape[0]
    pad = (-p) % w

    def unflatten(vec):
        # Slice the f32 leaves out, fence them, THEN cast to the leaves'
        # dtypes: left free, the TPU compiler sinks the casts into the
        # return all_to_all's relayout, whose codegen then takes minutes at
        # model width (ROADMAP A9).  Values are unchanged.
        f32 = packing.pack_spec(jax.tree_util.tree_map(
            lambda g: jax.ShapeDtypeStruct(g.shape, jnp.float32), grads),
            batch_ndim=0).unpack(vec, batch_ndim=0)
        return jax.tree_util.tree_map(lambda o, g: o.astype(g.dtype),
                                      jax.lax.optimization_barrier(f32),
                                      grads)
    if wire_fmt.quantized:
        # Quantized coordinates through the first all_to_all (the comm
        # volume win): encode the full local message (block stats over the
        # model axes), ship int8 slices + the (S, num_leaves) scales, and
        # dequantize this device's slice per-coordinate BEFORE the per-edge
        # attack -- so attacks observe the dequantized honest wire.  The
        # second all_to_all (each receiver collecting its own aggregate)
        # routes f32 results, unchanged.
        wspec = packing.pack_spec(grads, batch_ndim=0, wire=wire_fmt)
        codes, scales = wspec.encode(flat, axis_names=model_axes)
        z_codes = compat.all_to_all(
            jnp.pad(codes, (0, pad)).reshape(w, -1), worker_axes,
            split_axis=0, concat_axis=0, tiled=False).reshape(w, -1)
        z_local = packing.dequantize_slice(
            z_codes,
            compat.all_gather(scales, worker_axes, axis=0, tiled=False),
            _local_leaf_ids(leaf_sizes, pad, w, worker_axes))
    else:
        flat = jnp.pad(flat, (0, pad))
        z_local = compat.all_to_all(flat.reshape(w, -1), worker_axes,
                                    split_axis=0, concat_axis=0, tiled=False)
        z_local = z_local.reshape(w, -1)                      # (S, chunk)
    comm_axes = tuple(worker_axes) + tuple(model_axes)
    k = jax.random.fold_in(key, wid) if key is not None else None
    exchange = build_exchange(z_local, attack_cfg, mask_all,
                              is_byz, k)                      # (S, S, chunk)
    gm_all = mask_all
    if getattr(cfg, "guards", False):
        # All receivers' (S, S) validity mask at once: the slice-local
        # partial stats psum over worker+model axes, so every device holds
        # the same replicated mask and the per-receiver folds agree.
        emask = guards_lib.pairwise_guard_mask(
            exchange, mask_all, multiplier=cfg.guard_multiplier,
            axis_names=comm_axes)
        exchange = guards_lib.sanitize_rows(exchange, emask)
        gm_all = mask_all * emask
    diag = None
    if cfg.aggregator == "geomed_blockwise":
        seg = _local_leaf_ids(leaf_sizes, pad, w, worker_axes)
        agg = masked_weiszfeld_segments(
            exchange, gm_all, seg, len(leaf_sizes) + 1,
            axis_names=comm_axes, max_iters=cfg.weiszfeld_iters,
            tol=cfg.weiszfeld_tol)
        if diag_on:
            # Generic distance/weight diagnostics against the segmented
            # aggregate (the per-block loop exposes no iteration info;
            # the neutral residual/iters defaults apply).
            diag = telemetry.masked_diagnostics(
                exchange, agg, gm_all, axis_names=comm_axes)
    elif diag_on:
        out = masked_aggregate_flat(
            cfg.aggregator, exchange, gm_all, diagnostics=True,
            **_agg_opts(cfg, mixing_all * gm_all,
                        axis_names=comm_axes))
        agg, diag = out
    elif _use_topology_kernel(use_topology_kernel) and (
            cfg.aggregator == "trimmed_mean") and row_weights is None:
        # (The fused kernel reduces by 0/1 mask counts, so fractional
        # staleness weights route to the jnp masked engine instead; the
        # guard mask stays 0/1, so guarded rounds keep the kernel.)
        # PR-3 leftover closed: the fused Pallas masked-neighborhood
        # reduction runs the coordinate-separable trimmed mean on the
        # (R, S, chunk) exchange slab in ONE HBM sweep -- no sort, no mask
        # broadcast materialization.  Slice-local (no psums needed:
        # coordinate-separable), so it drops straight into shard_map.
        from repro.kernels import ops as kernel_ops
        agg = kernel_ops.masked_neighbor_reduce(
            exchange, gm_all, trim=cfg.trim)
    else:
        agg = masked_aggregate_flat(
            cfg.aggregator, exchange, gm_all,
            **_agg_opts(cfg, mixing_all * gm_all,
                        axis_names=comm_axes))
    agg = agg.astype(jnp.float32)                             # (R, chunk)
    mine = compat.all_to_all(agg, worker_axes, split_axis=0,
                             concat_axis=0, tiled=False).reshape(-1)
    if diag_on:
        # The (R, S) fields already carry full-vector geometry (their sq
        # partials psum'd over comm_axes) and every device holds ALL
        # receiver rows, so the fold needs no further psum.
        return unflatten(mine[:p]), telemetry.reduce_masked_diagnostics(
            diag, mask_all)
    return unflatten(mine[:p])


def _use_topology_kernel(flag: Optional[bool]) -> bool:
    """Resolve the fused-kernel routing default: explicit flag wins; else
    on TPU only -- the Mosaic backend the kernel is shaped for.  On CPU
    the interpret-mode kernel is a correctness harness, not a speedup,
    and other backends (GPU/Triton) have never lowered it."""
    if flag is not None:
        return flag
    return jax.default_backend() == "tpu"
