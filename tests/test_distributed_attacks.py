"""Multi-device integration tests: the attack registry on the pod mesh, and
NaN-fault containment on both comm modes.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.attacks import ATTACK_NAMES


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
    """, timeout=120)
    assert f"ATTACK_OK {attack}" in out


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
    """, timeout=300)
    assert "NAN_CONTAINED" in out
