"""Flat-packed aggregation core (DESIGN.md Sec. 8).

Three layers of contracts:

* ``PackSpec`` round-trip/layout properties (hypothesis-driven with the
  seeded ``tests/_hypothesis_fallback.py`` shim): pack -> unpack is the
  identity for any tree of mixed dtypes/shapes -- scalar leaves, empty
  leaves, and padding included -- and independently built specs for the
  same tree agree (determinism).

* The PIN of the refactor: for every registry aggregator (and every
  masked topology counterpart) the pytree API is BIT-EXACT with the flat
  engine -- the pytree rules really are pack -> flat -> unpack shims, so
  packed callers and pytree callers can never drift apart.  The retained
  pre-refactor per-leaf implementations (``perleaf=True``) are the
  tolerance anchor: same math to within reduction-reassociation ulps.

* Step-level regressions: packed vs per-leaf simulated federation (master
  AND decentralized, every attack incl. the RNG-mirrored gaussian) stays
  bit-exact on the paper's logreg workload and within float tolerance on
  a many-leaf MLP; the bfloat16 message mode halves the wire and tracks
  the f32 trajectory.

The distributed (shard_map) packed-vs-per-leaf pins live in
``tests/test_distributed_packing.py`` (they need the 8-device harness).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
except ImportError:  # keep the suite collectable without the dev extra
    from _hypothesis_fallback import hypothesis, st

from repro.core import RobustConfig, make_federated_step, packing
from repro.core import aggregators as agg_lib
from repro.core.attacks import ATTACK_NAMES
from repro.data import ijcnn1_like, logreg_loss, partition
from repro.optim import get_optimizer
from repro.topology import graphs, masked_aggregate, masked_aggregate_flat

KEY = jax.random.PRNGKey(0)

AGG_OPTS = dict(max_iters=80, tol=1e-8, num_groups=3, trim=1,
                num_byzantine=1, clip_radius=2.0)


def _payload(w=9):
    """Mixed-shape f32 worker messages: matrix, 3-d, vector, scalar."""
    ks = jax.random.split(KEY, 4)
    return {
        "a": jax.random.normal(ks[0], (w, 7)),
        "b": jax.random.normal(ks[1], (w, 3, 2)),
        "c": jax.random.normal(ks[2], (w,)),
        "d": jax.random.normal(ks[3], (w, 2, 2, 2)),
    }


# ---------------------------------------------------------------------------
# PackSpec properties
# ---------------------------------------------------------------------------

@hypothesis.given(
    num_leaves=st.integers(1, 6),
    batch=st.integers(1, 5),
    seed=st.integers(0, 2**16),
    pad_to=st.integers(1, 7),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_pack_unpack_roundtrip_property(num_leaves, batch, seed, pad_to):
    rng = np.random.default_rng(seed)
    dtypes = [jnp.float32, jnp.bfloat16, jnp.float16]
    tree = {}
    for i in range(num_leaves):
        shape = tuple(int(s) for s in rng.integers(0, 4, rng.integers(0, 3)))
        dt = dtypes[int(rng.integers(0, len(dtypes)))]
        tree[f"leaf{i}"] = jnp.asarray(
            rng.standard_normal((batch,) + shape), jnp.float32).astype(dt)
    spec = packing.pack_spec(tree, pad_to=pad_to)
    buf = spec.pack(tree)
    assert buf.shape == (batch, spec.padded_dim)
    assert spec.padded_dim % pad_to == 0
    assert spec.padded_dim - spec.dim < pad_to
    back = spec.unpack(buf)
    for k in tree:
        # f32 wire: every supported leaf dtype survives the round trip
        # exactly (bf16/f16 -> f32 -> back is lossless).
        np.testing.assert_array_equal(
            np.asarray(back[k], np.float32), np.asarray(tree[k], np.float32),
            err_msg=k)
        assert back[k].dtype == tree[k].dtype


def test_pack_spec_deterministic_and_struct_built():
    tree = _payload()
    s1 = packing.pack_spec(tree)
    s2 = packing.pack_spec(tree)
    # Specs built independently (and from ShapeDtypeStructs instead of
    # concrete arrays) agree on the whole layout.
    structs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    s3 = packing.pack_spec(structs)
    for s in (s2, s3):
        assert s1.shapes == s.shapes and s1.dtypes == s.dtypes
        assert s1.offsets == s.offsets and s1.dim == s.dim
        assert s1.boundaries == s.boundaries
    np.testing.assert_array_equal(np.asarray(s1.seg_ids()),
                                  np.asarray(s3.seg_ids()))


def test_pack_edge_cases_scalar_empty_and_errors():
    w = 5
    tree = {"s": jnp.arange(w, dtype=jnp.float32),       # scalar messages
            "e": jnp.zeros((w, 0)),                      # empty leaf
            "m": jnp.ones((w, 2, 3))}
    spec = packing.pack_spec(tree)
    assert spec.sizes == (0, 2 * 3, 1)  # dict order: e, m, s
    buf = spec.pack(tree)
    assert buf.shape == (w, 7)
    back = spec.unpack(buf)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]), err_msg=k)
    # seg ids cover every leaf + the padding dummy block
    spec_p = packing.pack_spec(tree, pad_to=4)
    ids = np.asarray(spec_p.seg_ids())
    assert ids.shape == (8,)
    assert ids[-1] == spec_p.num_leaves  # dummy id on the padding
    # shape mismatch is rejected at pack time, dim mismatch at unpack time
    with pytest.raises(ValueError, match="does not match"):
        spec.pack({"s": tree["s"], "e": tree["e"], "m": jnp.ones((w, 3, 2))})
    with pytest.raises(ValueError, match="padded_dim"):
        spec.unpack(jnp.zeros((w, 9)))
    with pytest.raises(ValueError, match="message_dtype"):
        packing.resolve_message_dtype("float8")


def test_pack_empty_tree():
    spec = packing.pack_spec({})
    assert spec.dim == 0 and spec.num_leaves == 0
    assert spec.unpack(spec.pack({}), batch_ndim=0) == {}


def test_bf16_wire_halves_bytes_and_quantizes_once():
    tree = _payload()
    spec32 = packing.pack_spec(tree)
    spec16 = packing.pack_spec(tree, message_dtype=jnp.bfloat16)
    b32, b16 = spec32.pack(tree), spec16.pack(tree)
    assert b16.dtype == jnp.bfloat16
    assert b16.nbytes * 2 == b32.nbytes
    # unpack restores the leaf dtype; values are the one-time bf16 rounding
    back = spec16.unpack(b16)
    for k in tree:
        np.testing.assert_array_equal(
            np.asarray(back[k]),
            np.asarray(tree[k].astype(jnp.bfloat16).astype(tree[k].dtype)),
            err_msg=k)


# ---------------------------------------------------------------------------
# The pin: pytree aggregator API == flat engine, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", agg_lib.AGGREGATOR_NAMES)
def test_pytree_aggregator_is_bit_exact_with_flat_engine(name):
    tree = _payload()
    spec = packing.pack_spec(tree)
    shim = agg_lib.get_aggregator(name, **AGG_OPTS)(tree)
    flat = spec.unpack(
        agg_lib.get_flat_aggregator(name, spec, **AGG_OPTS)(spec.pack(tree)),
        batch_ndim=0)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(shim[k]),
                                      np.asarray(flat[k]),
                                      err_msg=f"{name} {k}")


@pytest.mark.parametrize("name", agg_lib.AGGREGATOR_NAMES)
def test_flat_engine_matches_perleaf_baseline(name):
    """The retained pre-refactor per-leaf implementations are the
    tolerance anchor: identical math modulo reduction reassociation."""
    tree = _payload()
    new = agg_lib.get_aggregator(name, **AGG_OPTS)(tree)
    old = agg_lib.get_aggregator(name, perleaf=True, **AGG_OPTS)(tree)
    for k in tree:
        np.testing.assert_allclose(np.asarray(new[k]), np.asarray(old[k]),
                                   atol=3e-5, err_msg=f"{name} {k}")


@pytest.mark.parametrize("name", agg_lib.AGGREGATOR_NAMES)
def test_masked_pytree_is_bit_exact_with_flat_engine(name):
    z = _payload(8)
    exchange = jax.tree_util.tree_map(
        lambda v: jnp.broadcast_to(v[None], (8,) + v.shape), z)
    mask = jnp.asarray(graphs.ring(8).neighbor_mask)
    spec = packing.pack_spec(exchange, batch_ndim=2)
    shim = masked_aggregate(name, exchange, mask, **AGG_OPTS)
    flat = spec.unpack(
        masked_aggregate_flat(name, spec.pack(exchange, batch_ndim=2), mask,
                              spec=spec, **AGG_OPTS), batch_ndim=1)
    legacy = masked_aggregate(name, exchange, mask, perleaf=True, **AGG_OPTS)
    for k in z:
        np.testing.assert_array_equal(np.asarray(shim[k]),
                                      np.asarray(flat[k]),
                                      err_msg=f"{name} {k}")
        np.testing.assert_allclose(np.asarray(shim[k]), np.asarray(legacy[k]),
                                   atol=5e-5, err_msg=f"{name} legacy {k}")


# ---------------------------------------------------------------------------
# Step-level packed-vs-per-leaf regressions (simulation paths)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def logreg():
    data = ijcnn1_like(jax.random.PRNGKey(0), n=400)
    wd = partition({"a": data.x, "b": data.y}, 8, seed=1)
    return logreg_loss(0.01), wd


def _run_sim(loss, wd, cfg, steps=5, topology=None):
    kwargs = {} if topology is None else {"topology": topology}
    init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                           get_optimizer("sgd", 0.02),
                                           **kwargs)
    st = init_fn({"w": jnp.zeros((22,), jnp.float32)}, jax.random.PRNGKey(3))
    jstep = jax.jit(step_fn)
    for _ in range(steps):
        st, metrics = jstep(st)
    return st, metrics


@pytest.mark.parametrize("attack", [a for a in ATTACK_NAMES if a != "none"])
def test_master_sim_step_packed_equals_perleaf_bitwise(logreg, attack):
    """Full Byrd-SAGA trajectories, packed vs per-leaf, bit-exact on the
    paper workload FOR EVERY ATTACK -- the gaussian case pins the
    RNG-mirrored packed draws (packed_gaussian_noise)."""
    loss, wd = logreg
    outs = {}
    for packed in (True, False):
        cfg = RobustConfig(aggregator="geomed", vr="saga", attack=attack,
                           num_byzantine=2, weiszfeld_iters=16, packed=packed)
        outs[packed], _ = _run_sim(loss, wd, cfg)
    np.testing.assert_array_equal(np.asarray(outs[True].params["w"]),
                                  np.asarray(outs[False].params["w"]))


@pytest.mark.parametrize("gossip", ["gradient", "params"])
def test_decentralized_sim_step_packed_equals_perleaf_bitwise(logreg, gossip):
    loss, wd = logreg
    outs = {}
    for packed in (True, False):
        cfg = RobustConfig(aggregator="geomed", vr="saga", attack="gaussian",
                           num_byzantine=2, weiszfeld_iters=16,
                           gossip=gossip, topology="ring", packed=packed)
        outs[packed], m = _run_sim(loss, wd, cfg, steps=4)
        assert np.isfinite(float(m["consensus_dist"]))
    np.testing.assert_array_equal(np.asarray(outs[True].params["w"]),
                                  np.asarray(outs[False].params["w"]))


def _mlp(key, layers=4, h=8, din=22):
    p = {}
    ks = jax.random.split(key, layers + 1)
    for i in range(layers):
        p[f"w{i}"] = 0.3 * jax.random.normal(ks[i], (din if i == 0 else h, h))
        p[f"b{i}"] = jnp.zeros((h,))
    p["wout"] = 0.3 * jax.random.normal(ks[-1], (h,))
    p["bout"] = jnp.zeros(())
    return p


def _mlp_loss(params, batch, layers=4):
    x, y = batch["a"], batch["b"]
    for i in range(layers):
        x = jnp.tanh(x @ params[f"w{i}"] + params[f"b{i}"])
    logit = x @ params["wout"] + params["bout"]
    return jnp.mean(jnp.logaddexp(0.0, -y * logit))


@pytest.mark.parametrize("name", agg_lib.AGGREGATOR_NAMES)
def test_multileaf_sim_step_packed_tracks_perleaf(logreg, name):
    """Many-leaf model (10 blocks incl. a scalar): every registry
    aggregator's packed trajectory tracks the per-leaf one to float
    tolerance over 4 steps (bitwise is not defined across the two engines
    -- XLA reassociates the cross-leaf norm reductions)."""
    _, wd = logreg
    outs = {}
    for packed in (True, False):
        cfg = RobustConfig(aggregator=name, vr="saga", attack="gaussian",
                           num_byzantine=2, weiszfeld_iters=16, num_groups=3,
                           packed=packed)
        init_fn, step_fn = make_federated_step(_mlp_loss, wd, cfg,
                                               get_optimizer("sgd", 0.05))
        st = init_fn(_mlp(jax.random.PRNGKey(1)), jax.random.PRNGKey(3))
        jstep = jax.jit(step_fn)
        for _ in range(4):
            st, _ = jstep(st)
        outs[packed] = st
    for a, b in zip(jax.tree_util.tree_leaves(outs[True].params),
                    jax.tree_util.tree_leaves(outs[False].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5, err_msg=name)


def test_bf16_message_mode_runs_and_tracks_f32(logreg):
    """message_dtype='bfloat16' halves the wire; the f32-accumulating
    robust rules keep the trajectory near the f32-wire run."""
    loss, wd = logreg
    outs = {}
    for mdt in ("float32", "bfloat16"):
        cfg = RobustConfig(aggregator="geomed", vr="saga", attack="sign_flip",
                           num_byzantine=2, weiszfeld_iters=16,
                           message_dtype=mdt)
        outs[mdt], m = _run_sim(loss, wd, cfg, steps=10)
        assert np.isfinite(float(m["honest_variance"]))
    w16 = np.asarray(outs["bfloat16"].params["w"])
    w32 = np.asarray(outs["float32"].params["w"])
    assert np.isfinite(w16).all()
    # bf16 has ~3 decimal digits; 10 steps of drift stays small
    np.testing.assert_allclose(w16, w32, atol=5e-2)
    # and the SAGA memory really lives on the half-width wire
    assert outs["bfloat16"].vr.table.dtype == jnp.bfloat16