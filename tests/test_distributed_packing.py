"""Multi-device integration tests: packed vs per-leaf aggregation (DESIGN.md
Sec. 8) on the shard_map paths and through the train step.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.aggregators import AGGREGATOR_NAMES


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
    """, timeout=480)
    for name in AGGREGATOR_NAMES:
        assert f"PACKED_OK {name}" in out


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
    """, timeout=300)
    assert "TRAIN_PACKED_OK gather" in out
    assert "TRAIN_PACKED_OK sharded" in out
