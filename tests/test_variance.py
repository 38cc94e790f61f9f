"""Seam regression for the VarianceReducer strategy layer (DESIGN.md Sec. 9).

The refactor moved every ``cfg.vr`` string branch into the
``repro.core.variance`` registry.  These tests pin the seam:

* sgd / minibatch / saga through the interface are BIT-EXACT with an
  in-test oracle that re-implements the pre-refactor pipeline (direct
  ``jax.random.randint`` draws + ``saga_correct_scatter`` calls), on both
  the packed and the per-leaf hot paths;
* lsvrg carries O(D) per-client state -- snapshot + anchor, never a
  (W, J, ...) table -- and its first corrected message from a warm init
  equals the worker's FULL local gradient (the SVRG identity);
* the registry is the single source of truth: every ``VR_NAMES`` entry
  trains on the master sim, the decentralized sim, and both distributed
  comm modes without raising; unknown names fail with the derived error.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mesh_harness import run_py
from repro.core import RobustConfig, make_federated_step
from repro.core import attacks as attack_lib
from repro.core import saga as saga_lib
from repro.core.robust_step import FederatedState
from repro.core.variance import _REDUCERS, VR_NAMES, LsvrgState, get_reducer
from repro.data import ijcnn1_like, logreg_loss, partition
from repro.optim import get_optimizer
from repro.optim import optimizers as optim_lib

WH, B, J = 6, 2, 8


@pytest.fixture(scope="module")
def problem():
    data = ijcnn1_like(jax.random.PRNGKey(0), n=WH * J)
    wd = partition({"a": data.x, "b": data.y}, WH, seed=1)
    return logreg_loss(0.01), wd


def _params0(wd):
    p = jax.tree_util.tree_leaves(wd)[0].shape[-1]
    return {"w": jnp.zeros((p,), jnp.float32)}


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------

def test_registry_is_single_source_of_truth():
    assert VR_NAMES == tuple(_REDUCERS)  # derived, not hand-spliced
    assert set(VR_NAMES) == {"sgd", "minibatch", "saga", "lsvrg"}
    for name in VR_NAMES:
        r = get_reducer(RobustConfig(vr=name))
        assert r.name == name
        assert isinstance(r, _REDUCERS[name])


def test_unknown_name_error_is_derived():
    with pytest.raises(ValueError, match="unknown variance reducer 'svrg2'"):
        RobustConfig(vr="svrg2").reducer()
    with pytest.raises(ValueError, match="lsvrg"):  # lists the registry
        RobustConfig(vr="nope").reducer()


def test_historical_index_draw_shapes_bitwise():
    """The per-step sample draws must reproduce the pre-refactor
    ``jax.random.randint`` calls bit-for-bit -- they feed the trajectory."""
    key = jax.random.PRNGKey(42)
    for name in ("sgd", "saga", "lsvrg"):
        idx = get_reducer(RobustConfig(vr=name)).draw_indices(key, WH, J)
        np.testing.assert_array_equal(
            np.asarray(idx),
            np.asarray(jax.random.randint(key, (WH,), 0, J)))
    mb = get_reducer(RobustConfig(vr="minibatch", minibatch_size=5))
    np.testing.assert_array_equal(
        np.asarray(mb.draw_indices(key, WH, J)),
        np.asarray(jax.random.randint(key, (WH, 5), 0, J)))


# ---------------------------------------------------------------------------
# Bit-exactness vs the pre-refactor pipeline (in-test oracle)
# ---------------------------------------------------------------------------

def _oracle_run(loss, wd, cfg, opt, steps):
    """The PRE-refactor simulation pipeline, re-implemented inline as ONE
    jitted step: string dispatch on cfg.vr, direct randint draws, direct
    saga_lib calls, the same honest-variance metric.  Any change the
    strategy layer makes to RNG consumption, packing order or correction
    math shows up as a mismatch against this."""
    import repro.core.aggregators as agg_lib
    grad_fn = jax.grad(loss)
    j = jax.tree_util.tree_leaves(wd)[0].shape[1]
    attack_cfg = cfg.attack_config()

    def sample(d, i):
        return jax.tree_util.tree_map(lambda z: z[i], d)

    def pack(tree, bn):
        spec = cfg.message_spec(tree, batch_ndim=bn)
        return spec.pack(tree, batch_ndim=bn), spec

    params = _params0(wd)
    opt_state = opt.init(params)
    if cfg.vr == "saga":
        tab = jax.vmap(lambda d: jax.vmap(
            lambda jj: grad_fn(params, sample(d, jj[None])))(jnp.arange(j))
        )(wd)
        if cfg.packed:
            tab, _ = pack(tab, 2)
        vr = saga_lib.saga_init(tab)
    else:
        vr = None
    st = FederatedState(params, opt_state, vr,
                        jnp.zeros((), jnp.int32), jax.random.PRNGKey(7))

    @jax.jit
    def oracle_step(state):
        key, k_idx, k_attack = jax.random.split(state.key, 3)
        params, vr = state.params, state.vr
        if cfg.vr == "minibatch":
            idx = jax.random.randint(k_idx, (WH, cfg.minibatch_size), 0, j)
            honest = jax.vmap(
                lambda d, i: grad_fn(params, sample(d, i)))(wd, idx)
        else:
            idx = jax.random.randint(k_idx, (WH,), 0, j)
            honest = jax.vmap(
                lambda d, i: grad_fn(params, sample(d, i[None])))(wd, idx)
        if cfg.packed:
            honest, spec = pack(honest, 1)
            if cfg.vr == "saga":
                honest, vr = saga_lib.saga_correct_scatter(vr, honest, idx)
            h32 = honest.astype(jnp.float32)
            var = jnp.sum((h32 - jnp.mean(h32, axis=0)[None]) ** 2) / WH
            msgs = attack_lib.apply_attack(attack_cfg, honest, k_attack,
                                           spec=spec)
            agg = spec.unpack(cfg.flat_aggregator_fn(spec)(msgs),
                              batch_ndim=0)
        else:
            if cfg.vr == "saga":
                honest, vr = saga_lib.saga_correct_scatter(vr, honest, idx)
            hm = agg_lib.mean_agg_perleaf(honest)
            var = sum(
                jnp.sum((z.astype(jnp.float32)
                         - m.astype(jnp.float32)[None]) ** 2)
                for z, m in zip(jax.tree_util.tree_leaves(honest),
                                jax.tree_util.tree_leaves(hm))) / WH
            msgs = attack_lib.apply_attack(attack_cfg, honest, k_attack)
            agg = cfg.aggregator_fn(perleaf=True)(msgs)
        updates, opt_state = opt.update(agg, state.opt_state, params,
                                        state.step)
        params = optim_lib.apply_updates(params, updates)
        new_state = FederatedState(params, opt_state, vr, state.step + 1,
                                   key)
        return new_state, {"honest_variance": var}

    for _ in range(steps):
        st, _ = oracle_step(st)
    return st


@pytest.mark.parametrize("vr", ["sgd", "minibatch", "saga"])
@pytest.mark.parametrize("packed", [True, False])
def test_ported_reducers_bit_exact_vs_oracle(problem, vr, packed):
    """5 steps of attacked geomed + momentum through the strategy layer ==
    5 steps of the inlined pre-refactor pipeline, on EVERY state leaf
    (params, momenta, SAGA table/avg, PRNG key)."""
    loss, wd = problem
    cfg = RobustConfig(aggregator="geomed", vr=vr, attack="sign_flip",
                       num_byzantine=B, minibatch_size=3, packed=packed,
                       weiszfeld_iters=16)
    opt = get_optimizer("momentum", 0.05)
    init_fn, step_fn = make_federated_step(loss, wd, cfg, opt)
    st = init_fn(_params0(wd), jax.random.PRNGKey(7))
    jstep = jax.jit(step_fn)
    for _ in range(5):
        st, _ = jstep(st)
    ref = _oracle_run(loss, wd, cfg, opt, 5)
    got, want = st._asdict(), ref._asdict()
    for k in want:
        for a, b in zip(jax.tree_util.tree_leaves(got[k]),
                        jax.tree_util.tree_leaves(want[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{vr} packed={packed} {k}")


@pytest.mark.parametrize("vr", VR_NAMES)
def test_packed_and_perleaf_paths_agree(problem, vr):
    """The Sec.-8 packed buffer is a LAYOUT, not math: both hot paths land
    on the same trajectory for every reducer (lsvrg included -- its
    snapshot/anchor live in whichever layout the path uses)."""
    loss, wd = problem
    outs = {}
    for packed in (True, False):
        cfg = RobustConfig(aggregator="geomed", vr=vr, attack="gaussian",
                           num_byzantine=B, minibatch_size=3, lsvrg_p=0.5,
                           packed=packed, weiszfeld_iters=16)
        init_fn, step_fn = make_federated_step(
            loss, wd, cfg, get_optimizer("sgd", 0.05))
        st = init_fn(_params0(wd), jax.random.PRNGKey(7))
        jstep = jax.jit(step_fn)
        for _ in range(4):
            st, m = jstep(st)
        outs[packed] = st.params
        assert np.isfinite(float(m["honest_variance"]))
    np.testing.assert_allclose(np.asarray(outs[True]["w"]),
                               np.asarray(outs[False]["w"]),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# lsvrg: O(D) state + the SVRG correction identity
# ---------------------------------------------------------------------------

def test_lsvrg_state_is_o_of_d(problem):
    """The whole point vs SAGA: per-client state is snapshot + anchor
    (2 * W * D elements), never a (W, J, ...) table -- checked on both
    layouts and cross-checked against ``memory_elems`` (the dryrun/bench
    accounting term)."""
    loss, wd = problem
    d = jax.tree_util.tree_leaves(wd)[0].shape[-1]
    reducer = get_reducer(RobustConfig(vr="lsvrg"))
    for packed in (True, False):
        cfg = RobustConfig(vr="lsvrg", packed=packed)
        init_fn, _ = make_federated_step(loss, wd, cfg,
                                         get_optimizer("sgd", 0.05))
        st = init_fn(_params0(wd), jax.random.PRNGKey(0))
        assert isinstance(st.vr, LsvrgState)
        for leaf in jax.tree_util.tree_leaves(st.vr):
            assert leaf.shape[0] == WH
            assert J not in leaf.shape[1:], f"table-like axis: {leaf.shape}"
        elems = sum(l.size for l in jax.tree_util.tree_leaves(st.vr))
        assert elems == reducer.memory_elems(WH, J, d) == 2 * WH * d
    saga_state = make_federated_step(
        loss, wd, RobustConfig(vr="saga", packed=True),
        get_optimizer("sgd", 0.05))[0](_params0(wd), jax.random.PRNGKey(0)).vr
    assert saga_state.table.shape == (WH, J, d)  # what lsvrg shrinks away


def test_lsvrg_first_message_is_full_gradient(problem):
    """SVRG identity: from the warm init (snapshot = x0, anchor = full
    local grad at x0) the first corrected message is g_i(x0) - g_i(x0) +
    mu = mu exactly, so one mean-aggregated sgd step == one step of exact
    distributed gradient descent."""
    loss, wd = problem
    lr = 0.1
    cfg = RobustConfig(aggregator="mean", vr="lsvrg", attack="none",
                       lsvrg_p=0.0)
    init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                           get_optimizer("sgd", lr))
    st = init_fn(_params0(wd), jax.random.PRNGKey(7))
    st, _ = jax.jit(step_fn)(st)
    full = jax.vmap(jax.grad(loss))(
        jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (WH,) + p.shape),
            _params0(wd)), wd)
    want = _params0(wd)["w"] - lr * jnp.mean(full["w"], axis=0)
    np.testing.assert_allclose(np.asarray(st.params["w"]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_lsvrg_snapshot_refresh_probability(problem):
    """p=1 refreshes every step (snapshot tracks the iterate, rate metric
    1.0); p=0 never does (state frozen at init)."""
    loss, wd = problem
    for p, rate in ((1.0, 1.0), (0.0, 0.0)):
        cfg = RobustConfig(aggregator="geomed", vr="lsvrg", attack="none",
                           lsvrg_p=p, packed=False)
        init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                               get_optimizer("sgd", 0.05))
        st0 = init_fn(_params0(wd), jax.random.PRNGKey(7))
        st, m = jax.jit(step_fn)(st0)
        assert float(m["vr_snapshot_rate"]) == rate
        if p == 0.0:
            np.testing.assert_array_equal(np.asarray(st.vr.snapshot["w"]),
                                          np.asarray(st0.vr.snapshot["w"]))
        else:
            # Refreshed to the PRE-update iterate, broadcast per worker.
            np.testing.assert_allclose(
                np.asarray(st.vr.snapshot["w"]),
                np.broadcast_to(np.asarray(st0.params["w"])[None],
                                (WH, st0.params["w"].shape[0])))


def test_lsvrg_beats_sgd_variance(problem):
    """The Lemma-1 property the robust rule relies on: after the table
    warms up, lsvrg's honest-message variance sits well below plain
    sgd's (like SAGA's)."""
    loss, wd = problem
    var = {}
    for vr in ("sgd", "lsvrg"):
        cfg = RobustConfig(aggregator="geomed", vr=vr, attack="none",
                           lsvrg_p=0.3)
        init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                               get_optimizer("sgd", 0.05))
        st = init_fn(_params0(wd), jax.random.PRNGKey(7))
        jstep = jax.jit(step_fn)
        for _ in range(60):
            st, m = jstep(st)
        var[vr] = float(m["honest_variance"])
    assert var["lsvrg"] < 0.5 * var["sgd"], var


# ---------------------------------------------------------------------------
# Registry coverage: every name x every execution path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vr", VR_NAMES)
def test_every_reducer_trains_master_and_decentralized_sim(problem, vr):
    """Every registry name runs the master sim AND the decentralized sim
    (ring, both gossip modes) without raising, producing finite params."""
    loss, wd = problem
    for topology, gossip in (("star", "gradient"), ("ring", "gradient"),
                             ("ring", "params")):
        cfg = RobustConfig(aggregator="geomed", vr=vr, attack="sign_flip",
                           num_byzantine=B, minibatch_size=3, lsvrg_p=0.5,
                           topology=topology, gossip=gossip,
                           weiszfeld_iters=8)
        init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                               get_optimizer("sgd", 0.05))
        st = init_fn(_params0(wd), jax.random.PRNGKey(7))
        jstep = jax.jit(step_fn)
        for _ in range(2):
            st, _ = jstep(st)
        leaves = jax.tree_util.tree_leaves(st.params)
        assert all(np.isfinite(np.asarray(l)).all() for l in leaves), (
            vr, topology, gossip)


@pytest.mark.slow
def test_every_reducer_trains_distributed_both_comm_modes():
    """Launch-path coverage on the 8-device mesh: every VR_NAMES entry
    compiles and trains under make_train_step in BOTH comm modes, with
    finite loss; the stateful reducers carry their state through the
    donated step (lsvrg with NO sample axis -- O(D) on this path too)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.configs import get_config
        from repro.configs.base import TrainConfig
        from repro.core.robust_step import RobustConfig
        from repro.core.variance import VR_NAMES
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        from repro.launch.train import make_batch
        from repro.models.api import build_model
        from repro.optim import get_optimizer

        cfg = get_config("mamba2-130m").reduced()
        mesh = mesh_lib.make_host_mesh((4, 2), ("data", "model"))
        model = build_model(cfg, remat=False, q_chunk=32, kv_chunk=32,
                            loss_chunk=32)
        train = TrainConfig(optimizer="sgd", lr=0.05)
        with compat.use_mesh(mesh):
            params = model.init(jax.random.PRNGKey(0))
            nparams = sum(l.size for l in jax.tree_util.tree_leaves(params))
            for vr in VR_NAMES:
                for comm in ("gather", "sharded"):
                    robust = RobustConfig(aggregator="geomed", vr=vr,
                                          attack="sign_flip", num_byzantine=1,
                                          comm=comm, weiszfeld_iters=8,
                                          minibatch_size=2, lsvrg_p=0.5)
                    reducer = robust.reducer()
                    jj = 3 if reducer.uses_sample_idx else 0
                    step_fn, _, _ = steps_lib.make_train_step(
                        model, robust, train, mesh, saga_num_samples=jj)
                    # Copy params into the state: the compiled step DONATES
                    # arg 0, so each combo needs its own live buffers.
                    state = {"params": jax.tree_util.tree_map(
                                 lambda x: x + 0, params),
                             "opt": (), "step": jnp.zeros((), jnp.int32)}
                    if reducer.wants_state(jj):
                        state["vr"] = reducer.init_zeros(params, 4, jj)
                    jstep = steps_lib.compile_train_step(step_fn)
                    for i in range(2):
                        batch = make_batch(jax.random.fold_in(
                            jax.random.PRNGKey(5), i), cfg, 4, 2, 32)
                        state, m = jstep(state, batch,
                                         jax.random.fold_in(jax.random.PRNGKey(9), i))
                    assert np.isfinite(float(m["loss"])), (vr, comm)
                    if vr == "lsvrg":
                        elems = sum(l.size for l in
                                    jax.tree_util.tree_leaves(state["vr"]))
                        assert elems == 2 * 4 * nparams, (elems, nparams)
                        assert float(m["vr_snapshot_rate"]) >= 0.0
                    print("VRCOV_OK", vr, comm, float(m["loss"]))
    """, timeout=480)
    for vr in VR_NAMES:
        for comm in ("gather", "sharded"):
            assert f"VRCOV_OK {vr} {comm}" in out, out
