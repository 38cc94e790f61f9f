"""Multi-device integration tests: quantized wire formats across execution
paths (DESIGN.md Sec. 12).

Each test runs an inline program in a subprocess via tests/mesh_harness.py
(8 forced host devices); programs use repro.compat for every mesh/shard_map
touch, the repo's one gate onto jax's moving mesh names."""
import pytest

from mesh_harness import run_py
from repro.core.aggregators import AGGREGATOR_NAMES


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
                 timeout=450)
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
                 timeout=210)
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
    """, timeout=270)
    assert "SIGN1_EF_COHORT_AGREE" in out
