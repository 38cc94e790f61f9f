"""Multi-device integration tests: the train step on the mesh: resume, the
CLI's state layout, comm-mode agreement, SAGA, and dry-run lowering.

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py


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
    """, timeout=240)
    assert "RESUME_BIT_EXACT" in out


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
    """, devices=4, timeout=120,
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
    """, timeout=120)
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
    """, timeout=120)
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
    """, timeout=150)
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
    """, timeout=180)
    assert "DRYRUN_OK" in out
