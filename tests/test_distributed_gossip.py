"""Multi-device integration tests: decentralized train steps: one step on the
complete graph against the master step, and params gossip over a
time-varying schedule in both comm modes.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
from mesh_harness import run_py


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
    """, timeout=120)
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
    """, timeout=180)
    assert "PARAMS_SCHEDULE_TRAIN_EQUAL" in out
