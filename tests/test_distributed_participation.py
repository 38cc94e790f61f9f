"""Multi-device integration tests: client-scale virtualization (partial
participation and bounded-staleness weighting, DESIGN.md Sec. 10) across the
execution paths.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.attacks import ATTACK_NAMES


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
    """, timeout=300)
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
    """, timeout=210)
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
    """, timeout=480)
    assert "MATRIX_OK" in out
    for attack in ATTACK_NAMES:
        assert f"COVERED {attack} sampled" in out
