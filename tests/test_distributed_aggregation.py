"""Multi-device integration tests: star aggregation across comm modes (gather
vs sharded) on one and two worker axes and under per-row weights, the
Weiszfeld and krum edge cases, the fused topology kernel, and comm
validation.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.aggregators import AGGREGATOR_NAMES


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
    """, timeout=120)
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
        # Both comm paths cover the whole registry.
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
    """, timeout=240)
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
    out = run_py(f"    name = {name!r}\n" + _MULTIPOD_CASE, timeout=120)
    assert f"MULTIPOD_AGREE {name}" in out


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
    """, timeout=120)
    assert "BLOCKWISE_OK single_leaf" in out
    assert "BLOCKWISE_OK three_leaves" in out


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
    """, timeout=120)
    assert "KRUM_SELECTS_2" in out


def test_fused_topology_kernel_wired_into_sharded_path():
    """The sharded decentralized trimmed-mean routes through the fused
    Pallas masked-neighborhood kernel (use_topology_kernel=True, interpret
    mode on CPU) and agrees with the jnp flat path."""
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
    """, timeout=120)
    assert "TOPOLOGY_KERNEL_WIRED" in out


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
    """, timeout=120)
    assert "PROBE_OK" in out


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
    """, timeout=240)
    assert "WEIGHTED_AGREE" in out
