"""Multi-device integration tests.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.aggregators import AGGREGATOR_NAMES
from repro.core.attacks import ATTACK_NAMES


def test_gather_vs_sharded_aggregation_agree():
    out = run_py("""
        import jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig, distributed_aggregate, sharded_aggregate
        from repro.core.aggregators import geomed_agg
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        cfg = RobustConfig(aggregator="geomed", weiszfeld_iters=100, weiszfeld_tol=1e-9)
        ref = geomed_agg({"a": g1, "b": g2}, max_iters=100, tol=1e-9)
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P("data","model"), P("data",None,"model")),
                     out_specs=(P("model"), P(None,"model")), check_vma=False)
        out1 = sm(lambda a, b: tuple(distributed_aggregate(
            {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",), model_axes=("model",)).values()))(g1, g2)
        out2 = sm(lambda a, b: tuple(sharded_aggregate(
            {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",), model_axes=("model",), num_workers=4).values()))(g1, g2)
        import numpy as np
        for o in (out1, out2):
            np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref["a"]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(o[1]), np.asarray(ref["b"]), atol=1e-5)
        print("AGREE")
    """)
    assert "AGREE" in out


def test_aggregator_names_covered_in_both_comm_modes():
    """Every name in AGGREGATOR_NAMES aggregates (no raising) in BOTH comm
    modes on the single-worker-axis mesh, matching the single-host reference
    aggregator."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import (AGGREGATOR_NAMES, GATHER_AGGREGATORS,
                                SHARDED_AGGREGATORS, RobustConfig,
                                distributed_aggregate, sharded_aggregate)
        # Since PR 2 both comm paths cover the whole registry.
        assert GATHER_AGGREGATORS == AGGREGATOR_NAMES
        assert SHARDED_AGGREGATORS == AGGREGATOR_NAMES
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P("data","model"), P("data",None,"model")),
                     out_specs=(P("model"), P(None,"model")), check_vma=False)
        for name in AGGREGATOR_NAMES:
            cfg = RobustConfig(aggregator=name, weiszfeld_iters=100,
                               weiszfeld_tol=1e-9, num_byzantine=1,
                               clip_radius=2.5)
            ref = cfg.aggregator_fn()({"a": g1, "b": g2})
            got = sm(lambda a, b: tuple(distributed_aggregate(
                {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",),
                model_axes=("model",)).values()))(g1, g2)
            got_s = sm(lambda a, b: tuple(sharded_aggregate(
                {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",),
                model_axes=("model",), num_workers=4).values()))(g1, g2)
            for comm, o in (("gather", got), ("sharded", got_s)):
                np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref["a"]),
                                           atol=2e-5, err_msg=f"{comm} {name} a")
                np.testing.assert_allclose(np.asarray(o[1]), np.asarray(ref["b"]),
                                           atol=2e-5, err_msg=f"{comm} {name} b")
        print("NAMES_COVERED")
    """, timeout=600)
    assert "NAMES_COVERED" in out


# One aggregator per subprocess: the (pod, data) worker-axis matrix case.
_MULTIPOD_CASE = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import RobustConfig, distributed_aggregate, sharded_aggregate
    wa = ("pod", "data")
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
    cfg = RobustConfig(aggregator=name, weiszfeld_iters=100, weiszfeld_tol=1e-9,
                       num_byzantine=1, clip_radius=2.5)
    ref = cfg.aggregator_fn()({"a": g1, "b": g2})
    sm = partial(compat.shard_map, mesh=mesh,
                 in_specs=(P(wa, "model"), P(wa, None, "model")),
                 out_specs=(P("model"), P(None, "model")), check_vma=False)
    outs = {}
    outs["gather"] = sm(lambda a, b: tuple(distributed_aggregate(
        {"a": a[0], "b": b[0]}, cfg, worker_axes=wa,
        model_axes=("model",)).values()))(g1, g2)
    outs["sharded"] = sm(lambda a, b: tuple(sharded_aggregate(
        {"a": a[0], "b": b[0]}, cfg, worker_axes=wa, model_axes=("model",),
        num_workers=4).values()))(g1, g2)
    # Both comm modes match the single-host reference AND each other.
    for comm, got in outs.items():
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref["a"]),
                                   atol=2e-5, err_msg=comm + " a")
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref["b"]),
                                   atol=2e-5, err_msg=comm + " b")
    for x, y in zip(outs["gather"], outs["sharded"]):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5)
    print("MULTIPOD_AGREE", name)
"""


@pytest.mark.parametrize("name", AGGREGATOR_NAMES)
def test_every_aggregator_gather_vs_sharded_on_pod_data_mesh(name):
    """Every registry aggregator produces gather-vs-sharded results within
    tolerance on a multi-pod (pod, data) worker-axis mesh (2, 2, 2)."""
    out = run_py(f"    name = {name!r}\n" + _MULTIPOD_CASE, timeout=600)
    assert f"MULTIPOD_AGREE {name}" in out


# Decentralized neighborhood aggregation on a multi-pod mesh: one
# aggregator per subprocess, every non-star topology inside, BOTH comm
# modes against the dense masked-reference (simulation semantics).
_DECENTRALIZED_CASE = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import RobustConfig
    from repro.topology import (build_exchange, decentralized_aggregate,
                                get_topology, masked_aggregate)
    wa = ("pod", "data")
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
    sm = partial(compat.shard_map, mesh=mesh,
                 in_specs=(P(wa, "model"), P(wa, None, "model")),
                 out_specs=(P(wa, "model"), P(wa, None, "model")),
                 check_vma=False)
    for tname in ("ring", "torus2d", "erdos_renyi"):
        topo = get_topology(tname, 4, seed=1, p=0.7)
        cfg = RobustConfig(aggregator=name, weiszfeld_iters=100,
                           weiszfeld_tol=1e-9, attack="sign_flip",
                           num_byzantine=1, clip_radius=2.5, trim=1)
        # Dense reference: per-edge attacks + masked rules on full arrays.
        M = jnp.asarray(topo.neighbor_mask)
        E = build_exchange({"a": g1, "b": g2}, cfg.attack_config(), M,
                           jnp.arange(4) < 1)
        ref = masked_aggregate(name, E, M, max_iters=100, tol=1e-9,
                               num_groups=4, trim=1, num_byzantine=1,
                               clip_radius=2.5,
                               mixing=jnp.asarray(topo.mixing, jnp.float32) * M)
        outs = {}
        for comm in ("gather", "sharded"):
            def agg_fn(a, b, comm=comm):
                out = decentralized_aggregate(
                    {"a": a[0], "b": b[0]}, cfg, topo, comm=comm,
                    worker_axes=wa, model_axes=("model",), num_workers=4)
                return out["a"][None], out["b"][None]
            outs[comm] = sm(agg_fn)(g1, g2)
        # Both comm modes match the dense reference AND each other,
        # PER NODE (each worker row is that node's own aggregate).
        for comm, o in outs.items():
            np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref["a"]),
                                       atol=5e-5, err_msg=tname + comm + " a")
            np.testing.assert_allclose(np.asarray(o[1]), np.asarray(ref["b"]),
                                       atol=5e-5, err_msg=tname + comm + " b")
        for x, y in zip(outs["gather"], outs["sharded"]):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=5e-5)
        print("DECENTRALIZED_AGREE", tname, name)

    # PARAMETER-channel messages over a TIME-VARYING schedule: the wire now
    # carries half-stepped models and round_index=1 must select the cyclic
    # schedule's SECOND graph inside shard_map in both comm modes, matching
    # the dense masked reference built from that same round's mask.
    from repro.topology import cyclic_schedule
    sched = cyclic_schedule([get_topology("ring", 4),
                             get_topology("torus2d", 4)])
    h1 = g1 - 0.05 * jax.random.normal(jax.random.PRNGKey(3), g1.shape)
    h2 = g2 - 0.05 * jax.random.normal(jax.random.PRNGKey(4), g2.shape)
    cfg = RobustConfig(aggregator=name, weiszfeld_iters=100,
                       weiszfeld_tol=1e-9, attack="sign_flip",
                       num_byzantine=1, clip_radius=2.5, trim=1,
                       gossip="params")
    topo1 = sched.topologies[1]
    M1 = jnp.asarray(topo1.neighbor_mask)
    E1 = build_exchange({"a": h1, "b": h2}, cfg.attack_config(), M1,
                        jnp.arange(4) < 1)
    ref1 = masked_aggregate(name, E1, M1, max_iters=100, tol=1e-9,
                            num_groups=4, trim=1, num_byzantine=1,
                            clip_radius=2.5,
                            mixing=jnp.asarray(topo1.mixing, jnp.float32) * M1)
    # ... on the (pod, data) mesh AND a 1-axis (data,) worker mesh.
    mesh1 = compat.make_mesh((4, 2), ("data", "model"))
    sm1 = partial(compat.shard_map, mesh=mesh1,
                  in_specs=(P("data", "model"), P("data", None, "model")),
                  out_specs=(P("data", "model"), P("data", None, "model")),
                  check_vma=False)
    for axes_label, waxes, smap in (("pod-data", wa, sm),
                                    ("data", ("data",), sm1)):
        for comm in ("gather", "sharded"):
            def agg_fn(a, b, comm=comm, waxes=waxes):
                out = decentralized_aggregate(
                    {"a": a[0], "b": b[0]}, cfg, sched, comm=comm,
                    worker_axes=waxes, model_axes=("model",), num_workers=4,
                    round_index=jnp.asarray(1, jnp.int32))
                return out["a"][None], out["b"][None]
            got = smap(agg_fn)(h1, h2)
            tag = "params " + axes_label + " " + comm
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(ref1["a"]),
                                       atol=5e-5, err_msg=tag + " a")
            np.testing.assert_allclose(np.asarray(got[1]),
                                       np.asarray(ref1["b"]),
                                       atol=5e-5, err_msg=tag + " b")
            print("PARAMS_SCHEDULE_AGREE", axes_label, comm, name)
"""


@pytest.mark.parametrize("name", AGGREGATOR_NAMES)
def test_every_aggregator_decentralized_on_pod_mesh(name):
    """Every registry aggregator aggregates decentralized on ring / torus2d
    / erdos_renyi in BOTH comm modes on a (2, 2, 2) multi-pod mesh, within
    tolerance of the dense masked reference (the acceptance matrix) -- for
    gradient messages on fixed graphs AND parameter messages over a
    time-varying cyclic schedule (round_index selection inside shard_map)."""
    out = run_py(f"    name = {name!r}\n" + _DECENTRALIZED_CASE, timeout=600)
    for tname in ("ring", "torus2d", "erdos_renyi"):
        assert f"DECENTRALIZED_AGREE {tname} {name}" in out
    for axes_label in ("pod-data", "data"):
        for comm in ("gather", "sharded"):
            assert f"PARAMS_SCHEDULE_AGREE {axes_label} {comm} {name}" in out


def test_decentralized_train_step_agrees_with_master_on_complete_graph():
    """Cross-path consistency for BOTH gossip modes: on the complete graph
    with the mean rule and no attack, every node's masked neighborhood is
    the whole federation with uniform Metropolis weights, so ONE
    decentralized train step from a replicated init must reproduce the
    master step's parameters on every node (and keep the copies in exact
    consensus).  For params gossip this additionally needs the LINEAR sgd
    optimizer: mean_i(x - lr*g_i) = x - lr*mean_i(g_i)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.topology import get_topology

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.1)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
            key = jax.random.PRNGKey(9)
            robust = RobustConfig(aggregator="mean", vr="sgd", attack="none")
            mstep, _, _ = steps_lib.make_train_step(model, robust, train, mesh)
            mstate = {"params": params, "opt": (), "step": jnp.zeros((), jnp.int32)}
            mstate, _ = jax.jit(mstep)(mstate, batch, key)
            for gossip in ("gradient", "params"):
                drobust = RobustConfig(aggregator="mean", vr="sgd",
                                       attack="none", gossip=gossip)
                dstep, _, _ = steps_lib.make_decentralized_train_step(
                    model, drobust, train, mesh, get_topology("complete", 4))
                nodes = jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p[None], (4,) + p.shape) + 0, params)
                dstate = {"params": nodes, "opt": (), "step": jnp.zeros((), jnp.int32)}
                dstate, dm = jax.jit(dstep)(dstate, batch, key)
                assert float(dm["consensus_dist"]) < 1e-8, (gossip, float(dm["consensus_dist"]))
                for m, d in zip(jax.tree_util.tree_leaves(mstate["params"]),
                                jax.tree_util.tree_leaves(dstate["params"])):
                    dn = np.asarray(d, np.float32)
                    mn = np.asarray(m, np.float32)
                    for node in range(4):
                        np.testing.assert_allclose(dn[node], mn, rtol=2e-3,
                                                   atol=2e-4, err_msg=gossip)
                print("COMPLETE_EQUALS_MASTER", gossip)
    """, timeout=600)
    assert "COMPLETE_EQUALS_MASTER gradient" in out
    assert "COMPLETE_EQUALS_MASTER params" in out


def test_params_gossip_train_step_gather_vs_sharded_on_schedule():
    """End-to-end params-gossip decentralized training over a time-varying
    erdos_renyi schedule on a 1-axis worker mesh: the gather and sharded
    comm modes must produce the same per-node parameters after two steps
    (the schedule's round counter advances inside the compiled step)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.05)
        outs = {}
        for comm in ("gather", "sharded"):
            robust = RobustConfig(aggregator="geomed", vr="sgd",
                                  attack="sign_flip", num_byzantine=1,
                                  comm=comm, weiszfeld_iters=32,
                                  weiszfeld_tol=1e-9, gossip="params",
                                  topology="ring", schedule="erdos_renyi",
                                  schedule_period=2, topology_p=0.7,
                                  topology_seed=1)  # seed 0 draws a
                                  # window-disconnected pair at N=4
            step_fn, _, _ = steps_lib.make_decentralized_train_step(
                model, robust, train, mesh, robust.topology)
            with compat.use_mesh(mesh):
                params = model.init(jax.random.PRNGKey(0))
                nodes = jax.tree_util.tree_map(
                    lambda p: jnp.broadcast_to(p[None], (4,) + p.shape) + 0,
                    params)
                state = {"params": nodes, "opt": (),
                         "step": jnp.zeros((), jnp.int32)}
                jstep = jax.jit(step_fn)
                for i in range(2):
                    batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
                    state, m = jstep(state, batch, jax.random.PRNGKey(9))
                outs[comm] = state["params"]
            assert np.isfinite(float(m["consensus_dist"]))
        for a, b in zip(jax.tree_util.tree_leaves(outs["gather"]),
                        jax.tree_util.tree_leaves(outs["sharded"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2e-3, atol=2e-4)
        print("PARAMS_SCHEDULE_TRAIN_EQUAL")
    """, timeout=600)
    assert "PARAMS_SCHEDULE_TRAIN_EQUAL" in out


@pytest.mark.parametrize("attack", ATTACK_NAMES)
def test_every_attack_runs_stacked_on_pod_data_mesh(attack):
    """Registry coverage (the _ATTACKS dict is the single source of truth):
    every attack name runs through apply_attack_stacked on messages sharded
    over a (pod, data) worker-axis mesh, leaving honest rows bit-intact and
    matching the unsharded result."""
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core.attacks import _ATTACKS, ATTACK_NAMES, AttackConfig, apply_attack_stacked
        assert ATTACK_NAMES == tuple(_ATTACKS)  # derived, not hand-spliced
        attack = {attack!r}
        cfg = AttackConfig(name=attack, num_byzantine=3,
                           gaussian_variance=9.0)
        mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
        msgs = {{"g": jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
                 "h": jax.random.normal(jax.random.PRNGKey(1), (8, 4, 4))}}
        key = jax.random.PRNGKey(2)
        ref = apply_attack_stacked(cfg, msgs, key)

        def attacked(m):
            m = jax.tree_util.tree_map(
                lambda z: jax.lax.with_sharding_constraint(
                    z, jax.sharding.NamedSharding(
                        mesh, P(("pod", "data")))), m)
            return apply_attack_stacked(cfg, m, key)

        with compat.use_mesh(mesh):
            got = jax.jit(attacked)(msgs)
        for k in msgs:
            g = np.asarray(got[k]); r = np.asarray(ref[k])
            if attack != "nan":   # the nan fault is non-finite by contract
                assert np.isfinite(g).all(), attack
            np.testing.assert_array_equal(g[3:], np.asarray(msgs[k])[3:])
            if attack == "gaussian":
                # Draw layout depends on how jit partitions the RNG; check
                # the structural contract (centered on the honest mean)
                # like tests/test_attacks.py::test_stacked_gaussian_rows.
                hm = np.asarray(msgs[k])[3:].mean(axis=0)
                assert np.abs((g[:3] - hm[None]).mean()) < 3.0, attack
            else:
                np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6,
                                           err_msg=attack + " " + k)
        print("ATTACK_OK", attack)
    """, timeout=600)
    assert f"ATTACK_OK {attack}" in out


def test_weiszfeld_blockwise_sharded_edge_cases():
    """geomed_blockwise on comm='sharded' with the shapes the happy-path
    sweep never hits: a SINGLE-leaf pytree (block count 1 < worker count)
    and a 3-leaf pytree (block count not a multiple of the 4 workers), both
    with total coordinate counts that force the padding/dummy-block path."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig, sharded_aggregate
        from repro.core.aggregators import geomed_blockwise_agg
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        cfg = RobustConfig(aggregator="geomed_blockwise", weiszfeld_iters=150,
                           weiszfeld_tol=1e-10)
        cases = {
            "single_leaf": {"only": jax.random.normal(jax.random.PRNGKey(0), (4, 10))},
            "three_leaves": {
                "a": jax.random.normal(jax.random.PRNGKey(1), (4, 6)),
                "b": jax.random.normal(jax.random.PRNGKey(2), (4, 3, 3)),
                "c": jax.random.normal(jax.random.PRNGKey(3), (4, 7)),
            },
        }
        for label, payload in cases.items():
            ref = geomed_blockwise_agg(payload, max_iters=150, tol=1e-10)
            in_specs = tuple(P("data", *([None] * (z.ndim - 1)))
                             for z in payload.values())
            out_specs = tuple(P(*([None] * (z.ndim - 1)))
                              for z in payload.values())
            keys = list(payload)
            def agg_fn(*leaves):
                local = {k: z[0] for k, z in zip(keys, leaves)}
                out = sharded_aggregate(local, cfg, worker_axes=("data",),
                                        model_axes=(), num_workers=4)
                return tuple(out[k] for k in keys)
            got = compat.shard_map(agg_fn, mesh=mesh, in_specs=in_specs,
                                   out_specs=out_specs, check_vma=False)(
                *payload.values())
            for k, o in zip(keys, got):
                np.testing.assert_allclose(np.asarray(o), np.asarray(ref[k]),
                                           atol=5e-5, err_msg=label + " " + k)
            print("BLOCKWISE_OK", label)
    """, timeout=600)
    assert "BLOCKWISE_OK single_leaf" in out
    assert "BLOCKWISE_OK three_leaves" in out


@pytest.mark.slow  # ~60s on a small runner: two full save/resume cycles
def test_distributed_resume_is_bit_exact():
    """Full-train-state checkpointing (params + Adam moments + SAGA
    table/avg + step): training 5 steps straight equals training 3 steps,
    checkpointing, restoring into a fresh state, and training 2 more --
    bit-exact on every leaf (same jitted step, same batches)."""
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.core.saga import saga_init_zeros
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        robust = RobustConfig(aggregator="geomed", vr="saga", attack="gaussian",
                              num_byzantine=1, weiszfeld_iters=8)
        step_fn, _, _ = steps_lib.make_train_step(
            model, robust, TrainConfig(optimizer="adamw", lr=1e-3), mesh,
            saga_num_samples=2)
        key = jax.random.PRNGKey(0)
        with compat.use_mesh(mesh):
            params = model.init(key)
            opt = get_optimizer("adamw", 1e-3)
            def fresh():
                return {"params": params, "opt": opt.init(params),
                        "step": jnp.zeros((), jnp.int32),
                        "vr": saga_init_zeros(params, 4, 2)}
            jstep = jax.jit(step_fn)
            def run(state, lo, hi):
                for i in range(lo, hi):
                    batch = make_batch(jax.random.fold_in(key, 100 + i), cfg, 4, 2, 32)
                    state, _ = jstep(state, batch, jax.random.fold_in(key, i))
                return state
            straight = run(fresh(), 0, 5)
            ckpt = CheckpointManager(tempfile.mkdtemp())
            ckpt.save_train_state(3, run(fresh(), 0, 3))
            step0, restored = ckpt.restore_latest(fresh())
            assert step0 == 3
            resumed = run(restored, 3, 5)
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(straight)[0]]
        for path, a, b in zip(paths, jax.tree_util.tree_leaves(straight),
                              jax.tree_util.tree_leaves(resumed)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32),
                                          err_msg=str(path))
        print("RESUME_BIT_EXACT")
    """, timeout=600)
    assert "RESUME_BIT_EXACT" in out


def test_sharded_krum_selection_index_regression():
    """Seeded gaussian attack, W=8 messages (5 honest + 3 Byzantine) on a
    (2, 4, 1) multi-pod mesh: krum's selection index is pinned to honest
    worker 2 by the seeds, and the sharded path (coordinate all_to_all +
    partial-Gram psum) must return exactly that worker's message."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig, krum_scores, sharded_aggregate
        from repro.core.aggregators import _pairwise_sq_dists
        from repro.core.attacks import AttackConfig, apply_attack
        honest = jax.random.normal(jax.random.PRNGKey(41), (5, 16))
        msgs = apply_attack(AttackConfig(name="gaussian", num_byzantine=3,
                                         gaussian_variance=100.0),
                            {"g": honest}, jax.random.PRNGKey(7))["g"]
        scores = krum_scores(_pairwise_sq_dists({"g": msgs}), 3)
        assert int(jnp.argmin(scores)) == 2, np.asarray(scores)  # seed-pinned
        mesh = compat.make_mesh((2, 4, 1), ("pod", "data", "model"))
        cfg = RobustConfig(aggregator="krum", num_byzantine=3)
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P(("pod", "data"), "model"),),
                     out_specs=P("model"), check_vma=False)
        got = sm(lambda g: sharded_aggregate(
            {"g": g[0]}, cfg, worker_axes=("pod", "data"),
            model_axes=("model",), num_workers=8)["g"])(msgs)
        # Krum SELECTS, so the sharded result is bit-exact, not just close.
        np.testing.assert_array_equal(np.asarray(got), np.asarray(msgs[2]))
        print("KRUM_SELECTS_2")
    """)
    assert "KRUM_SELECTS_2" in out


def test_train_cli_places_state_in_the_steps_layout(tmp_path):
    """launch/train.py lays the state out by the step's state_specs, so the
    first step already sees the layout every later step sees (the node
    axis sharded over the worker axes), instead of a full replica per
    device that costs each device the whole federation's state and the
    first step a program of its own."""
    out = run_py("""
        import jax
        from repro.launch import steps as steps_lib, train
        compile_step = steps_lib.compile_train_step
        def spy(fn, **kw):
            jstep = compile_step(fn, **kw)
            def step(state, *args):
                leaves = jax.tree_util.tree_leaves(state)
                before = [(x.sharding, x.ndim) for x in leaves]
                new_state, metrics = jstep(state, *args)
                after = [x.sharding for x in jax.tree_util.tree_leaves(new_state)]
                print("SAME_LAYOUT", all(a.is_equivalent_to(s, n) for (s, n), a
                                         in zip(before, after)),
                      "REPLICATED", sum(s.is_fully_replicated for s, _ in before))
                return new_state, metrics
            return step
        steps_lib.compile_train_step = spy
        train.main(["--arch", "mamba2-130m", "--reduced", "--steps", "2",
                    "--seq", "16", "--mesh", "4x1", "--topology", "ring",
                    "--aggregator", "trimmed_mean", "--comm", "sharded",
                    "--attack", "sign_flip", "--byzantine", "1", "--vr", "saga"])
    """, devices=4, timeout=300,
        extra_env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    lines = [l for l in out.splitlines() if l.startswith("SAME_LAYOUT")]
    # Two steps; only the replicated step counter (and no node-axis leaf)
    # is fully replicated.
    assert lines == ["SAME_LAYOUT True REPLICATED 1"] * 2, out


def test_train_step_runs_on_mesh_and_attack_is_neutralized():
    """Train on a FIXED batch so the learning signal is deterministic: with
    sign_flip magnitude -3 and W=4/B=1 the mean aggregate is exactly zero
    (the attack cancels the honest sum), so mean-aggregated training cannot
    move, while geomed discards the Byzantine row and learns."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("qwen2-7b").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        results = {}
        for agg in ("geomed", "mean"):
            robust = RobustConfig(aggregator=agg, vr="sgd", attack="sign_flip",
                                  num_byzantine=1, weiszfeld_iters=16)
            step_fn, _, _ = steps_lib.make_train_step(
                model, robust, TrainConfig(optimizer="adamw", lr=1e-3), mesh)
            with compat.use_mesh(mesh):
                params = model.init(jax.random.PRNGKey(0))
                opt = get_optimizer("adamw", 1e-3)
                state = {"params": params, "opt": opt.init(params),
                         "step": jnp.zeros((), jnp.int32)}
                jstep = jax.jit(step_fn)
                key = jax.random.PRNGKey(1)
                batch = make_batch(key, cfg, 4, 2, 32)
                losses = []
                for i in range(8):
                    state, m = jstep(state, batch, jax.random.fold_in(key, 100+i))
                    losses.append(float(m["loss"]))
            results[agg] = losses
        # geomed neutralizes the attack and fits the batch; the zeroed mean
        # aggregate leaves the model stuck at its initial loss.
        assert results["geomed"][-1] < results["geomed"][0] - 1.0, results["geomed"]
        assert results["geomed"][-1] < results["mean"][-1] - 1.0, results
        assert abs(results["mean"][-1] - results["mean"][0]) < 0.2, results["mean"]
        print("ROBUST", results["geomed"][0], "->", results["geomed"][-1],
              "| mean stuck at", results["mean"][-1])
    """)
    assert "ROBUST" in out


def test_sharded_comm_equals_gather_comm_training():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        outs = {}
        for comm in ("gather", "sharded"):
            robust = RobustConfig(aggregator="geomed", vr="sgd", attack="sign_flip",
                                  num_byzantine=1, comm=comm,
                                  weiszfeld_iters=32, weiszfeld_tol=1e-9)
            step_fn, _, _ = steps_lib.make_train_step(
                model, robust, TrainConfig(optimizer="sgd", lr=0.1), mesh)
            with compat.use_mesh(mesh):
                params = model.init(jax.random.PRNGKey(0))
                state = {"params": params, "opt": (), "step": jnp.zeros((), jnp.int32)}
                batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
                state, _ = jax.jit(step_fn)(state, batch, jax.random.PRNGKey(9))
                outs[comm] = state["params"]
        for a, b in zip(jax.tree_util.tree_leaves(outs["gather"]),
                        jax.tree_util.tree_leaves(outs["sharded"])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), rtol=2e-3, atol=2e-4)
        print("EQUAL")
    """)
    assert "EQUAL" in out


def test_saga_distributed_train_step():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.core.saga import saga_init_zeros
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        robust = RobustConfig(aggregator="geomed", vr="saga", attack="gaussian",
                              num_byzantine=1, weiszfeld_iters=8)
        step_fn, _, sstructs = steps_lib.make_train_step(
            model, robust, TrainConfig(optimizer="sgd", lr=0.05), mesh,
            saga_num_samples=4)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            state = {"params": params, "opt": (), "step": jnp.zeros((), jnp.int32),
                     "vr": saga_init_zeros(params, 4, 4)}
            jstep = jax.jit(step_fn)
            for i in range(3):
                batch = make_batch(jax.random.fold_in(jax.random.PRNGKey(2), i), cfg, 4, 2, 32)
                state, m = jstep(state, batch, jax.random.fold_in(jax.random.PRNGKey(3), i))
            assert jnp.isfinite(m["loss"])
            # table must have absorbed gradients (non-zero rows)
            tabs = jax.tree_util.tree_leaves(state["vr"].table)
            total = sum(float(jnp.sum(jnp.abs(t.astype(jnp.float32)))) for t in tabs)
            assert total > 0
        print("SAGA_OK", float(m["loss"]))
    """)
    assert "SAGA_OK" in out


def test_dryrun_single_combo_small_devices():
    """Exercise dryrun.lower_one end-to-end on an 8-device (2x4) stand-in
    via the same code path (mesh shrunk through make_host_mesh monkeypatch)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch import dryrun, mesh as mesh_lib
        mesh_lib.make_production_mesh = lambda multi_pod=False: (
            mesh_lib.make_host_mesh((2, 2, 2), ("pod", "data", "model")) if multi_pod
            else mesh_lib.make_host_mesh((4, 2), ("data", "model")))
        dryrun.mesh_lib = mesh_lib
        for mp in (False, True):
            rec = dryrun.lower_one("whisper-tiny", "train_4k", multi_pod=mp)
            assert rec["flops_per_device"] > 0
            assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
        print("DRYRUN_OK")
    """, timeout=600)
    assert "DRYRUN_OK" in out


@pytest.mark.slow
def test_packed_aggregation_matches_perleaf_distributed():
    """DESIGN.md Sec. 8 on the shard_map paths: for EVERY registry
    aggregator the packed gather master (one packed all_gather + flat
    engine) agrees with the per-leaf baseline (packed=False), and the
    sharded path (coordinate-packed internally either way) agrees with
    both; the selection rule (krum) is bit-exact.  Same sweep for the
    DECENTRALIZED per-node aggregation (masked flat engine) in both comm
    modes under a per-edge attack."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig, distributed_aggregate, sharded_aggregate
        from repro.core.aggregators import AGGREGATOR_NAMES
        from repro.topology import decentralized_aggregate, get_topology

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P("data","model"), P("data",None,"model")),
                     out_specs=(P("model"), P(None,"model")), check_vma=False)
        smd = partial(compat.shard_map, mesh=mesh,
                      in_specs=(P("data","model"), P("data",None,"model")),
                      out_specs=(P("data","model"), P("data",None,"model")),
                      check_vma=False)
        topo = get_topology("ring", 4)
        for name in AGGREGATOR_NAMES:
            cfg = RobustConfig(aggregator=name, weiszfeld_iters=60,
                               weiszfeld_tol=1e-9, num_byzantine=1,
                               clip_radius=2.5, num_groups=3,
                               attack="sign_flip")
            outs = {}
            for packed in (True, False):
                c = dataclasses.replace(cfg, packed=packed)
                outs[packed] = sm(lambda a, b: tuple(distributed_aggregate(
                    {"a": a[0], "b": b[0]}, c, worker_axes=("data",),
                    model_axes=("model",)).values()))(g1, g2)
            sh = sm(lambda a, b: tuple(sharded_aggregate(
                {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",),
                model_axes=("model",), num_workers=4).values()))(g1, g2)
            for label, got in (("perleaf", outs[False]), ("sharded", sh)):
                for x, y in zip(outs[True], got):
                    if name == "krum" and label == "perleaf":
                        np.testing.assert_array_equal(
                            np.asarray(x), np.asarray(y), err_msg=name)
                    else:
                        np.testing.assert_allclose(
                            np.asarray(x), np.asarray(y), atol=3e-5,
                            err_msg=f"{name} {label}")

            def dec(c, comm):
                def f(a, b):
                    out = decentralized_aggregate(
                        {"a": a[0], "b": b[0]}, c, topo, comm=comm,
                        worker_axes=("data",), model_axes=("model",),
                        num_workers=4, key=jax.random.PRNGKey(5))
                    return tuple(jax.tree_util.tree_map(
                        lambda x: x[None], out).values())
                return smd(f)(g1, g2)

            d_out = {}
            for packed in (True, False):
                d_out[packed] = dec(dataclasses.replace(cfg, packed=packed),
                                    "gather")
            d_sh = dec(cfg, "sharded")
            for label, got in (("perleaf", d_out[False]), ("sharded", d_sh)):
                for x, y in zip(d_out[True], got):
                    np.testing.assert_allclose(
                        np.asarray(x), np.asarray(y), atol=3e-5,
                        err_msg=f"decentralized {name} {label}")
            print("PACKED_OK", name)
    """, timeout=900)
    for name in AGGREGATOR_NAMES:
        assert f"PACKED_OK {name}" in out


def test_fused_topology_kernel_wired_into_sharded_path():
    """The PR-3 leftover closed: the sharded decentralized trimmed-mean
    routes through the fused Pallas masked-neighborhood kernel
    (use_topology_kernel=True, interpret mode on CPU) and agrees with the
    jnp flat path."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig
        from repro.topology import decentralized_aggregate, get_topology

        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        topo = get_topology("complete", 4)
        cfg = RobustConfig(aggregator="trimmed_mean", trim=1,
                           attack="sign_flip", num_byzantine=1)
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P("data","model"), P("data",None,"model")),
                     out_specs=(P("data","model"), P("data",None,"model")),
                     check_vma=False)

        def run(use_kernel):
            def f(a, b):
                out = decentralized_aggregate(
                    {"a": a[0], "b": b[0]}, cfg, topo, comm="sharded",
                    worker_axes=("data",), model_axes=("model",),
                    num_workers=4, key=jax.random.PRNGKey(5),
                    use_topology_kernel=use_kernel)
                return tuple(jax.tree_util.tree_map(
                    lambda x: x[None], out).values())
            return sm(f)(g1, g2)

        ref, ker = run(False), run(True)
        for x, y in zip(ker, ref):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=2e-5)
        print("TOPOLOGY_KERNEL_WIRED")
    """, timeout=600)
    assert "TOPOLOGY_KERNEL_WIRED" in out


@pytest.mark.slow
def test_train_step_packed_matches_perleaf_on_mesh():
    """End-to-end make_train_step: two steps of geomed training under
    sign_flip, packed vs per-leaf, on both comm modes (deterministic
    attack -- the gaussian RNG layout under auto-jit partitioning is the
    pre-existing caveat of test_every_attack_runs_stacked)."""
    out = run_py("""
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32,
                            loss_chunk=32)
        train = TrainConfig(optimizer="adamw", lr=1e-3)
        from repro.core.saga import saga_init_zeros
        for comm in ("gather", "sharded"):
            outs = {}
            for packed in (True, False):
                robust = RobustConfig(aggregator="geomed", vr="saga",
                                      attack="sign_flip", num_byzantine=1,
                                      comm=comm, weiszfeld_iters=16,
                                      weiszfeld_tol=1e-9, packed=packed)
                step_fn, _, _ = steps_lib.make_train_step(
                    model, robust, train, mesh, saga_num_samples=2)
                with compat.use_mesh(mesh):
                    params = model.init(jax.random.PRNGKey(0))
                    opt = get_optimizer("adamw", 1e-3)
                    state = {"params": params, "opt": opt.init(params),
                             "step": jnp.zeros((), jnp.int32),
                             "vr": saga_init_zeros(params, 4, 2)}
                    jstep = steps_lib.compile_train_step(step_fn)
                    key = jax.random.PRNGKey(1)
                    for i in range(2):
                        batch = make_batch(jax.random.fold_in(key, i), cfg,
                                           4, 2, 32)
                        state, m = jstep(state, batch,
                                         jax.random.fold_in(key, 100 + i))
                    outs[packed] = state["params"]
            for a, b in zip(jax.tree_util.tree_leaves(outs[True]),
                            jax.tree_util.tree_leaves(outs[False])):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           rtol=2e-3, atol=2e-4,
                                           err_msg=comm)
            print("TRAIN_PACKED_OK", comm)
    """, timeout=900)
    assert "TRAIN_PACKED_OK gather" in out
    assert "TRAIN_PACKED_OK sharded" in out


def test_require_distributed_and_comm_validation():
    """Capability probe degrades with a clear error, not an AttributeError
    from inside jit: bogus comm modes are rejected at step-build time."""
    out = run_py("""
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.models.api import build_model

        compat.require_distributed(min_devices=8)
        try:
            compat.require_distributed(min_devices=10**6)
        except RuntimeError as e:
            assert "device" in str(e)
        else:
            raise AssertionError("expected RuntimeError for device count")

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        try:
            steps_lib.make_train_step(
                model, RobustConfig(comm="bogus"), TrainConfig(), mesh)
        except ValueError as e:
            assert "gather" in str(e) and "sharded" in str(e)
        else:
            raise AssertionError("expected ValueError for bogus comm")
        print("PROBE_OK")
    """)
    assert "PROBE_OK" in out


# ---------------------------------------------------------------------------
# Client-scale virtualization (DESIGN.md Sec. 10): partial participation +
# bounded-staleness weighting across the execution paths.
# ---------------------------------------------------------------------------

def test_weighted_aggregation_sim_vs_gather_vs_sharded():
    """The weighted flat engines are ONE implementation surfaced three
    ways: the host (sim) packed engine, the gather master, and the
    sharded coordinate-slice master must agree for every registry
    aggregator under the same per-row staleness weights (incl. an exact
    weight-0 row -- the dropout mask-out)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import (AGGREGATOR_NAMES, RobustConfig,
                                distributed_aggregate, packing,
                                sharded_aggregate)
        mesh = compat.make_mesh((4, 2), ("data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        rw = jnp.asarray([1.0, 0.0, 1.0, 0.5], jnp.float32)
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P("data", "model"), P("data", None, "model"),
                               P()),
                     out_specs=(P("model"), P(None, "model")),
                     check_vma=False)
        for name in AGGREGATOR_NAMES:
            cfg = RobustConfig(aggregator=name, weiszfeld_iters=100,
                               weiszfeld_tol=1e-9, num_byzantine=1,
                               clip_radius=2.5)
            msgs = {"a": g1, "b": g2}
            spec = packing.pack_spec(msgs)
            vec = cfg.flat_aggregator_fn(spec)(spec.pack(msgs),
                                               row_weights=rw)
            ref = spec.unpack(vec, batch_ndim=0)
            got = sm(lambda a, b, w: tuple(distributed_aggregate(
                {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",),
                model_axes=("model",), row_weights=w).values()))(g1, g2, rw)
            got_s = sm(lambda a, b, w: tuple(sharded_aggregate(
                {"a": a[0], "b": b[0]}, cfg, worker_axes=("data",),
                model_axes=("model",), num_workers=4,
                row_weights=w).values()))(g1, g2, rw)
            for comm, o in (("gather", got), ("sharded", got_s)):
                np.testing.assert_allclose(np.asarray(o[0]),
                                           np.asarray(ref["a"]), atol=5e-5,
                                           err_msg=f"{comm} {name} a")
                np.testing.assert_allclose(np.asarray(o[1]),
                                           np.asarray(ref["b"]), atol=5e-5,
                                           err_msg=f"{comm} {name} b")
        print("WEIGHTED_AGREE")
    """, timeout=600)
    assert "WEIGHTED_AGREE" in out


@pytest.mark.slow
def test_full_participation_train_step_is_bit_exact_with_master():
    """The participation refactor's bit-exactness pin: num_clients equal to
    the worker count (and num_clients=0) must compile the SAME master step
    -- parameters AND resident VR state (saga table / lsvrg anchor)
    bitwise identical after 3 steps, for both VR methods."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.05)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
            for vr in ("saga", "lsvrg"):
                outs = {}
                for nc in (0, 4):
                    robust = RobustConfig(aggregator="geomed", vr=vr,
                                          attack="sign_flip", num_byzantine=1,
                                          weiszfeld_iters=8, num_clients=nc)
                    step_fn, _, sstructs = steps_lib.make_train_step(
                        model, robust, train, mesh, saga_num_samples=4)
                    st = sstructs()
                    assert "staleness" not in st, nc  # full-participation bypass
                    state = {"params": params, "opt": (),
                             "step": jnp.zeros((), jnp.int32),
                             "vr": jax.tree_util.tree_map(
                                 lambda s: jnp.zeros(s.shape, s.dtype),
                                 st["vr"])}
                    jstep = jax.jit(step_fn)
                    for i in range(3):
                        state, m = jstep(state, batch,
                                         jax.random.fold_in(jax.random.PRNGKey(3), i))
                    outs[nc] = state
                for a, b in zip(jax.tree_util.tree_leaves(outs[0]),
                                jax.tree_util.tree_leaves(outs[4])):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                                  err_msg=vr)
                print("BIT_EXACT", vr)
    """, timeout=600)
    assert "BIT_EXACT saga" in out
    assert "BIT_EXACT lsvrg" in out


@pytest.mark.slow
def test_sampled_cohort_train_gather_vs_sharded_agree():
    """Sampled-cohort training (8 virtual clients on the 4-slot mesh, with
    a staleness attack in the mix) must produce the same parameters and the
    IDENTICAL integer staleness counters via the gather and sharded comm
    paths, separately jitted."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.05)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
            outs = {}
            for comm in ("gather", "sharded"):
                robust = RobustConfig(aggregator="geomed", vr="saga",
                                      attack="straggler", num_byzantine=1,
                                      weiszfeld_iters=32, weiszfeld_tol=1e-9,
                                      comm=comm, num_clients=8)
                step_fn, _, sstructs = steps_lib.make_train_step(
                    model, robust, train, mesh, saga_num_samples=4)
                st = sstructs()
                assert st["staleness"].shape == (8,)
                state = {"params": params, "opt": (),
                         "step": jnp.zeros((), jnp.int32),
                         "vr": jax.tree_util.tree_map(
                             lambda s: jnp.zeros(s.shape, s.dtype), st["vr"]),
                         "staleness": jnp.zeros((8,), jnp.int32)}
                jstep = jax.jit(step_fn)
                for i in range(3):
                    state, m = jstep(state, batch,
                                     jax.random.fold_in(jax.random.PRNGKey(3), i))
                outs[comm] = state
                assert np.isfinite(float(m["loss"])), comm
            np.testing.assert_array_equal(
                np.asarray(outs["gather"]["staleness"]),
                np.asarray(outs["sharded"]["staleness"]))
            for a, b in zip(jax.tree_util.tree_leaves(outs["gather"]["params"]),
                            jax.tree_util.tree_leaves(outs["sharded"]["params"])):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           rtol=2e-3, atol=2e-4)
        print("COHORT_PATHS_AGREE")
    """, timeout=600)
    assert "COHORT_PATHS_AGREE" in out


@pytest.mark.slow
def test_every_attack_runs_with_participation_on_pod_mesh():
    """Attack x participation x topology coverage on the (2, 2, 2) pod
    mesh: every registry attack aggregates without raising (finite output)
    under full AND sampled-cohort row weights, through the star
    (distributed_aggregate) and ring (decentralized_aggregate) paths."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import RobustConfig, distributed_aggregate
        from repro.core.robust_step import distributed_attack
        from repro.core.attacks import _ATTACKS, ATTACK_NAMES, FAULT_ATTACKS
        from repro.core import participation as part
        from repro.topology import decentralized_aggregate, get_topology
        assert "straggler" in _ATTACKS and "dropout" in _ATTACKS
        wa = ("pod", "data")
        mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
        g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
        g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
        topo = get_topology("ring", 4)
        sm = partial(compat.shard_map, mesh=mesh,
                     in_specs=(P(wa, "model"), P(wa, None, "model"), P()),
                     out_specs=(P("model"), P(None, "model")),
                     check_vma=False)
        smd = partial(compat.shard_map, mesh=mesh,
                      in_specs=(P(wa, "model"), P(wa, None, "model"), P()),
                      out_specs=(P(wa, "model"), P(wa, None, "model")),
                      check_vma=False)
        stal = jnp.array([0, 2, 0, 1], jnp.int32)
        for attack in ATTACK_NAMES:
            # Fault attacks inject non-finite / overflow payloads the bare
            # rules cannot digest; they run with the containment guards on
            # (which is also their registry-coverage for this mesh).
            cfg = RobustConfig(aggregator="geomed", attack=attack,
                               num_byzantine=1, weiszfeld_iters=16,
                               gaussian_variance=4.0,
                               guards=attack in FAULT_ATTACKS)
            slot = part.slot_staleness(stal, attack, 1, straggler_k=4,
                                       max_staleness=64, byz_first=True)
            sampled = part.staleness_weights(slot, decay=1.0,
                                             max_staleness=64)
            for label, rw in (("full", None), ("sampled", sampled)):
                def star_fn(a, b, w, rw=rw):
                    m = distributed_attack({"a": a[0], "b": b[0]}, cfg,
                                           worker_axes=wa,
                                           key=jax.random.PRNGKey(7))
                    return tuple(distributed_aggregate(
                        m, cfg, worker_axes=wa, model_axes=("model",),
                        row_weights=None if rw is None else w).values())
                star = sm(star_fn)(g1, g2, sampled)
                ring = smd(lambda a, b, w, rw=rw: (lambda o:
                    (o["a"][None], o["b"][None]))(decentralized_aggregate(
                        {"a": a[0], "b": b[0]}, cfg, topo,
                        worker_axes=wa, model_axes=("model",), num_workers=4,
                        key=jax.random.PRNGKey(7),
                        row_weights=None if rw is None else w,
                    )))(g1, g2, sampled)
                for path, o in (("star", star), ("ring", ring)):
                    for arr in o:
                        assert np.isfinite(np.asarray(arr)).all(), \
                            (attack, label, path)
                print("COVERED", attack, label)
        print("MATRIX_OK")
    """, timeout=600)
    assert "MATRIX_OK" in out
    for attack in ATTACK_NAMES:
        assert f"COVERED {attack} sampled" in out


# ---------------------------------------------------------------------------
# Quantized wire formats across execution paths (DESIGN.md Sec. 12).
# ---------------------------------------------------------------------------

# One wire format per subprocess, every registry aggregator inside: the
# single-host reference (round-trip the packed rows, then the flat rule)
# vs gather vs sharded on the multi-pod (2, 2, 2) worker-axis mesh.
_QUANTIZED_MULTIPOD_CASE = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import (AGGREGATOR_NAMES, RobustConfig,
                            distributed_aggregate, sharded_aggregate)
    wa = ("pod", "data")
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
    sm = partial(compat.shard_map, mesh=mesh,
                 in_specs=(P(wa, "model"), P(wa, None, "model")),
                 out_specs=(P("model"), P(None, "model")), check_vma=False)
    for name in AGGREGATOR_NAMES:
        cfg = RobustConfig(aggregator=name, weiszfeld_iters=100,
                           weiszfeld_tol=1e-9, num_byzantine=1,
                           clip_radius=2.5, trim=1, message_dtype=dtype)
        # Single-host reference: quantize + dequantize the stacked rows
        # with the SAME spec the distributed paths build, then run the
        # plain pytree aggregator on what the receiver would see.
        spec = cfg.message_spec({"a": g1, "b": g2}, batch_ndim=1)
        assert spec.quantized
        wire = spec.unpack(spec.wire_roundtrip(spec.pack({"a": g1, "b": g2})))
        ref = cfg.aggregator_fn()(wire)
        got = sm(lambda a, b: tuple(distributed_aggregate(
            {"a": a[0], "b": b[0]}, cfg, worker_axes=wa,
            model_axes=("model",)).values()))(g1, g2)
        got_s = sm(lambda a, b: tuple(sharded_aggregate(
            {"a": a[0], "b": b[0]}, cfg, worker_axes=wa,
            model_axes=("model",), num_workers=4).values()))(g1, g2)
        # int8 block stats reduce to the exact same amax on-mesh; sign1
        # scales agree up to f32 summation order, hence allclose.
        for comm, o in (("gather", got), ("sharded", got_s)):
            np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref["a"]),
                                       atol=2e-4, err_msg=f"{comm} {name} a")
            np.testing.assert_allclose(np.asarray(o[1]), np.asarray(ref["b"]),
                                       atol=2e-4, err_msg=f"{comm} {name} b")
        for x, y in zip(got, got_s):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       atol=2e-4, err_msg=name)
        print("QUANTIZED_AGREE", dtype, name)
"""


@pytest.mark.parametrize("dtype", ["int8", "sign1"])
def test_every_aggregator_quantized_sim_gather_sharded_agree(dtype):
    """int8 / sign1 wire: every registry aggregator agrees (allclose)
    between the single-host round-trip reference and BOTH distributed comm
    paths on the multi-pod (pod, data) worker-axis mesh."""
    out = run_py(f"    dtype = {dtype!r}\n" + _QUANTIZED_MULTIPOD_CASE,
                 timeout=600)
    for name in AGGREGATOR_NAMES:
        assert f"QUANTIZED_AGREE {dtype} {name}" in out


# Quantized decentralized aggregation: the attacks must act on the
# DEQUANTIZED honest messages (the wire is what anyone -- including the
# adversary -- observes), so the dense masked reference round-trips the
# node rows before build_exchange.
_QUANTIZED_DECENTRALIZED_CASE = """
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.core import RobustConfig
    from repro.topology import (build_exchange, decentralized_aggregate,
                                get_topology, masked_aggregate)
    wa = ("pod", "data")
    mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    g2 = jax.random.normal(jax.random.PRNGKey(1), (4, 6, 4))
    sm = partial(compat.shard_map, mesh=mesh,
                 in_specs=(P(wa, "model"), P(wa, None, "model")),
                 out_specs=(P(wa, "model"), P(wa, None, "model")),
                 check_vma=False)
    for tname in ("ring", "torus2d"):
        topo = get_topology(tname, 4, seed=1, p=0.7)
        cfg = RobustConfig(aggregator="geomed", weiszfeld_iters=100,
                           weiszfeld_tol=1e-9, attack="sign_flip",
                           num_byzantine=1, message_dtype=dtype)
        spec = cfg.message_spec({"a": g1, "b": g2}, batch_ndim=1)
        wire = spec.unpack(spec.wire_roundtrip(spec.pack({"a": g1, "b": g2})))
        M = jnp.asarray(topo.neighbor_mask)
        E = build_exchange(wire, cfg.attack_config(), M, jnp.arange(4) < 1)
        ref = masked_aggregate("geomed", E, M, max_iters=100, tol=1e-9)
        for comm in ("gather", "sharded"):
            def agg_fn(a, b, comm=comm):
                out = decentralized_aggregate(
                    {"a": a[0], "b": b[0]}, cfg, topo, comm=comm,
                    worker_axes=wa, model_axes=("model",), num_workers=4)
                return out["a"][None], out["b"][None]
            o = sm(agg_fn)(g1, g2)
            np.testing.assert_allclose(np.asarray(o[0]), np.asarray(ref["a"]),
                                       atol=2e-4, err_msg=tname + comm + " a")
            np.testing.assert_allclose(np.asarray(o[1]), np.asarray(ref["b"]),
                                       atol=2e-4, err_msg=tname + comm + " b")
            print("QUANTIZED_DECENTRALIZED_AGREE", dtype, tname, comm)
"""


@pytest.mark.parametrize("dtype", ["int8", "sign1"])
def test_quantized_decentralized_attacks_act_on_dequantized(dtype):
    """Non-star topologies with a quantized wire: both comm modes match
    the dense masked reference built from the ROUND-TRIPPED node rows
    (sign_flip observes the dequantized honest messages)."""
    out = run_py(f"    dtype = {dtype!r}\n" + _QUANTIZED_DECENTRALIZED_CASE,
                 timeout=600)
    for tname in ("ring", "torus2d"):
        for comm in ("gather", "sharded"):
            assert f"QUANTIZED_DECENTRALIZED_AGREE {dtype} {tname} {comm}" \
                in out


@pytest.mark.slow
def test_sampled_cohort_sign1_ef_rides_participation_across_comm_modes():
    """sign1 + error feedback under client-scale virtualization: the
    per-client EF residual table is gathered/scattered with the cohort
    exactly like the VR state.  Within one jaxpr the state evolution is
    bit-identical (re-running the gather step from the same init
    reproduces the table bit for bit); ACROSS comm modes the standing
    invariant is allclose (different XLA programs reorder the gradient
    math), so after the first step the tables agree to a couple of ulps
    with the SAME set of touched (scattered) rows, and after 3 steps
    within the usual cross-engine tolerance.  Integer staleness counters
    stay bitwise equal throughout."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.05)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            batch = make_batch(jax.random.PRNGKey(5), cfg, 4, 2, 32)
            outs = {}
            for comm in ("gather", "sharded"):
                robust = RobustConfig(aggregator="geomed", vr="saga",
                                      attack="sign_flip", num_byzantine=1,
                                      weiszfeld_iters=32, weiszfeld_tol=1e-9,
                                      comm=comm, num_clients=8,
                                      message_dtype="sign1")
                step_fn, _, sstructs = steps_lib.make_train_step(
                    model, robust, train, mesh, saga_num_samples=4)
                st = sstructs()
                assert st["ef"].shape[0] == 8          # resident per CLIENT
                state = {"params": params, "opt": (),
                         "step": jnp.zeros((), jnp.int32),
                         "vr": jax.tree_util.tree_map(
                             lambda s: jnp.zeros(s.shape, s.dtype), st["vr"]),
                         "staleness": jnp.zeros((8,), jnp.int32),
                         "ef": jnp.zeros(st["ef"].shape, jnp.float32)}
                jstep = jax.jit(step_fn)

                def run3(state, jstep=jstep):
                    ef1 = None
                    for i in range(3):
                        state, m = jstep(state, batch,
                                         jax.random.fold_in(jax.random.PRNGKey(3), i))
                        if i == 0:
                            ef1 = np.asarray(state["ef"])
                    return state, ef1, m

                state0 = jax.tree_util.tree_map(lambda x: x + 0, state)
                state, ef1, m = run3(state)
                outs[comm] = state
                outs[comm + "_ef1"] = ef1
                assert np.isfinite(float(m["loss"])), comm
                if comm == "gather":
                    # Same jaxpr, same init: bit-identical EF evolution.
                    again, _, _ = run3(state0)
                    np.testing.assert_array_equal(np.asarray(state["ef"]),
                                                  np.asarray(again["ef"]))
            assert np.abs(outs["gather_ef1"]).max() > 0, "EF never updated"
            # Step 1: both modes scattered residuals into the SAME client
            # rows (identical cohort plan), agreeing to a couple of ulps.
            np.testing.assert_array_equal(
                np.abs(outs["gather_ef1"]).max(axis=1) > 0,
                np.abs(outs["sharded_ef1"]).max(axis=1) > 0)
            np.testing.assert_allclose(outs["gather_ef1"],
                                       outs["sharded_ef1"], atol=5e-7)
            np.testing.assert_allclose(np.asarray(outs["gather"]["ef"]),
                                       np.asarray(outs["sharded"]["ef"]),
                                       rtol=2e-2, atol=1e-2)
            np.testing.assert_array_equal(
                np.asarray(outs["gather"]["staleness"]),
                np.asarray(outs["sharded"]["staleness"]))
            for a, b in zip(jax.tree_util.tree_leaves(outs["gather"]["params"]),
                            jax.tree_util.tree_leaves(outs["sharded"]["params"])):
                np.testing.assert_allclose(np.asarray(a, np.float32),
                                           np.asarray(b, np.float32),
                                           rtol=2e-3, atol=2e-4)
        print("SIGN1_EF_COHORT_AGREE")
    """, timeout=600)
    assert "SIGN1_EF_COHORT_AGREE" in out


@pytest.mark.slow  # five 8-step model train runs in one subprocess
def test_nan_fault_contained_on_gather_and_sharded_within_2x_floor():
    """Acceptance pin for the in-graph containment layer on the DISTRIBUTED
    paths (the sim-path twin lives in tests/test_guards.py): with guards on,
    a nan-attacked run (byz < W/2) stays finite and lands within 2x the
    attack-free loss floor on both comm modes, because the poisoned rows get
    aggregation weight exactly 0; with guards off the very first nan row
    destroys the model."""
    out = run_py("""
        import math
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core import init_health
        from repro.core.robust_step import RobustConfig
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("qwen2-7b").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32,
                            loss_chunk=32)

        def train(robust, steps=8):
            step_fn, _, _ = steps_lib.make_train_step(
                model, robust, TrainConfig(optimizer="adamw", lr=1e-3), mesh)
            with compat.use_mesh(mesh):
                params = model.init(jax.random.PRNGKey(0))
                opt = get_optimizer("adamw", 1e-3)
                state = {"params": params, "opt": opt.init(params),
                         "step": jnp.zeros((), jnp.int32)}
                if robust.guards:
                    state["health"] = init_health()
                jstep = jax.jit(step_fn)
                key = jax.random.PRNGKey(1)
                batch = make_batch(key, cfg, 4, 2, 32)
                m = None
                for i in range(steps):
                    state, m = jstep(state, batch,
                                     jax.random.fold_in(key, 100 + i))
            return {k: float(v) for k, v in m.items()
                    if k in ("loss", "quarantined_rows", "round_accepted")}

        results = {}
        for comm in ("gather", "sharded"):
            floor = train(RobustConfig(aggregator="geomed", vr="sgd",
                                       comm=comm, weiszfeld_iters=16))
            guarded = train(RobustConfig(aggregator="geomed", vr="sgd",
                                         attack="nan", num_byzantine=1,
                                         comm=comm, guards=True,
                                         weiszfeld_iters=16))
            assert math.isfinite(guarded["loss"]), (comm, guarded)
            assert guarded["loss"] <= 2.0 * floor["loss"], (comm, guarded,
                                                            floor)
            if comm == "gather":
                # The sharded path quarantines inside sharded_aggregate and
                # does not surface the count; gather reports it.
                assert guarded["quarantined_rows"] == 1.0, (comm, guarded)
            assert guarded["round_accepted"] == 1.0, (comm, guarded)
            results[comm] = (floor["loss"], guarded["loss"])
        unguarded = train(RobustConfig(aggregator="geomed", vr="sgd",
                                       attack="nan", num_byzantine=1,
                                       comm="gather"), steps=4)
        assert not math.isfinite(unguarded["loss"]), unguarded
        print("NAN_CONTAINED", results)
    """, timeout=600)
    assert "NAN_CONTAINED" in out
