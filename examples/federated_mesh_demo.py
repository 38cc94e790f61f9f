"""Multi-device federation demo: the REAL distributed code path (shard_map /
pjit) on a 4x2 mesh — one mesh index per worker, model-parallel inner axis,
geometric-median aggregation over the data axis, one Byzantine worker
mounting a sign-flip attack.  It needs 8 devices; on the CPU, force them
from the caller's side:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/federated_mesh_demo.py
"""
import jax
import jax.numpy as jnp

from repro import compat
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.core.robust_step import RobustConfig
from repro.launch import mesh as mesh_lib
from repro.launch import steps as steps_lib
from repro.launch.train import make_batch
from repro.models.api import build_model
from repro.optim import get_optimizer


def main() -> None:
    mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({len(jax.devices())} devices) — 4 workers, 2-way model parallel")
    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32, loss_chunk=32)

    for comm in ("gather", "sharded"):
        robust = RobustConfig(aggregator="geomed", vr="sgd", attack="sign_flip",
                              num_byzantine=1, comm=comm, weiszfeld_iters=16)
        step_fn, _, _ = steps_lib.make_train_step(
            model, robust, TrainConfig(optimizer="adamw", lr=1e-3), mesh)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            opt = get_optimizer("adamw", 1e-3)
            state = {"params": params, "opt": opt.init(params),
                     "step": jnp.zeros((), jnp.int32)}
            jstep = jax.jit(step_fn)
            key = jax.random.PRNGKey(1)
            print(f"\ncomm={comm} (paper-faithful gather vs sharded Weiszfeld):")
            for i in range(10):
                batch = make_batch(jax.random.fold_in(key, i), cfg, 4, 2, 32)
                state, m = jstep(state, batch, jax.random.fold_in(key, 50 + i))
                if i % 3 == 0 or i == 9:
                    print(f"  step {i}: honest-loss={float(m['loss']):.4f} "
                          f"agg_norm={float(m['agg_norm']):.4f}")


if __name__ == "__main__":
    main()
