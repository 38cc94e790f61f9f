"""Multi-device integration tests: decentralized neighborhood aggregation on
the pod mesh, every registry aggregator on every non-star topology.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.aggregators import AGGREGATOR_NAMES


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
    out = run_py(f"    name = {name!r}\n" + _DECENTRALIZED_CASE, timeout=420)
    for tname in ("ring", "torus2d", "erdos_renyi"):
        assert f"DECENTRALIZED_AGREE {tname} {name}" in out
    for axes_label in ("pod-data", "data"):
        for comm in ("gather", "sharded"):
            assert f"PARAMS_SCHEDULE_AGREE {axes_label} {comm} {name}" in out
