"""Quantized wire-format properties (DESIGN.md Sec. 12).

Hypothesis-driven (with the seeded ``tests/_hypothesis_fallback.py`` shim
when the real package is absent) pins on the wire layer in isolation --
the cross-path step-level agreement pins live in
``tests/test_distributed_quantized.py`` and the convergence floors in
``tests/test_convergence.py``:

* int8 round-trip error is bounded per coordinate by the per-block
  symmetric scale: ``|decode(encode(v)) - v| <= amax_block / 254``.
* sign1 codes are EXACTLY +-1 on real coordinates (never 0; only padding
  encodes to 0), and the per-block scale is the EF-signSGD ``mean |v|``.
* Quantization is deterministic and batch-rank-agnostic: encoding a
  stacked ``(W, D)`` buffer row-by-row gives bitwise the same codes and
  scales as encoding the batch at once.
* ``message_dtype="float32"`` is a byte-identical bypass: the round-trip
  returns the SAME array object and every registry aggregator produces
  bitwise the same aggregate as the legacy raw-dtype spec.
* Error feedback: the sign1 residual carried through
  :meth:`PackSpec.transmit` conserves the message (wire + residual ==
  signal) and stays bounded over a simulated trajectory -- both a direct
  quantizer loop and a real ``make_federated_step`` run under attack.
* The :data:`WIRE_FORMATS` dict is the single registry: unknown names
  raise naming the registered set, from both the resolver and the config.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    import hypothesis
    import hypothesis.strategies as st
    import hypothesis.extra.numpy as hnp
except ImportError:  # keep the suite collectable without the dev extra
    from _hypothesis_fallback import hypothesis, st, hnp

from repro.core import RobustConfig, make_federated_step
from repro.core import aggregators as agg_lib
from repro.core import packing
from repro.data import ijcnn1_like, logreg_loss, partition
from repro.optim import get_optimizer

W = 8
OPTS = {"trimmed_mean": {"trim": 1}, "krum": {"num_byzantine": 2},
        "geomed_groups": {"num_groups": 4},
        "centered_clip": {"clip_radius": 1.0}}


def _spec(wire, pad_to=1):
    # Two-leaf tree so the per-block scales have real boundaries.
    tree = {"a": jnp.zeros((W, 20), jnp.float32),
            "b": jnp.zeros((W, 13), jnp.float32)}
    return packing.pack_spec(tree, batch_ndim=1, wire=wire, pad_to=pad_to)


def _buf(key, spec, scale=1.0):
    return scale * jax.random.normal(key, (W, spec.padded_dim), jnp.float32)


# ---------------------------------------------------------------------------
# int8: per-block symmetric scales.
# ---------------------------------------------------------------------------

@hypothesis.given(
    raw=hnp.arrays(np.float32, (W, 33),
                   elements=st.floats(min_value=-50.0, max_value=50.0,
                                      width=32)),
    gain=st.floats(min_value=1e-3, max_value=1e3))
@hypothesis.settings(deadline=None, max_examples=25)
def test_int8_roundtrip_error_bounded_by_block_scale(raw, gain):
    spec = _spec("int8")
    buf = jnp.asarray(raw * np.float32(gain))
    rt = np.asarray(spec.wire_roundtrip(buf))
    assert np.all(np.isfinite(rt))
    for a, b in spec.boundaries:
        v = np.asarray(buf)[:, a:b]
        amax = np.abs(v).max(axis=1, keepdims=True)
        err = np.abs(rt[:, a:b] - v)
        # Symmetric amax/127 scaling: worst case half a quantization bin,
        # plus a couple of ulps of slack for the f32 divide/round/multiply.
        bound = amax / 254.0 + 1e-6 * amax + 1e-30
        assert np.all(err <= bound), (err.max(), bound.max())


def test_int8_all_zero_block_is_exact():
    spec = _spec("int8")
    buf = jnp.zeros((W, spec.padded_dim), jnp.float32)
    codes, scales = spec.encode(buf)
    np.testing.assert_array_equal(np.asarray(codes), 0)
    np.testing.assert_array_equal(np.asarray(scales), 0.0)
    np.testing.assert_array_equal(np.asarray(spec.decode(codes, scales)), 0.0)


def test_int8_roundtrip_is_exactly_idempotent():
    # Receivers see decode(encode(v)); re-quantizing that wire value (what
    # the master paths do to attacked rows) must be a fixed point, so
    # honest rows pass the post-attack round-trip untouched.
    spec = _spec("int8")
    wire = spec.wire_roundtrip(_buf(jax.random.PRNGKey(0), spec))
    np.testing.assert_array_equal(np.asarray(spec.wire_roundtrip(wire)),
                                  np.asarray(wire))


# ---------------------------------------------------------------------------
# sign1: 1-bit codes + mean-magnitude scales.
# ---------------------------------------------------------------------------

@hypothesis.given(
    raw=hnp.arrays(np.float32, (W, 33),
                   elements=st.floats(min_value=-8.0, max_value=8.0,
                                      width=32)))
@hypothesis.settings(deadline=None, max_examples=25)
def test_sign1_codes_are_exactly_pm1(raw):
    spec = _spec("sign1", pad_to=64)   # force real padding coordinates
    buf = spec.pack({"a": jnp.asarray(raw[:, :20]),
                     "b": jnp.asarray(raw[:, 20:])})
    codes, scales = spec.encode(buf)
    assert codes.dtype == jnp.int8
    c = np.asarray(codes)
    assert np.all(np.isin(c[:, :spec.dim], (-1, 1))), "codes must be +-1"
    np.testing.assert_array_equal(c[:, spec.dim:], 0)  # padding encodes 0
    for i, (a, b) in enumerate(spec.boundaries):
        want = np.abs(raw[:, a:b]).mean(axis=1)
        np.testing.assert_allclose(np.asarray(scales)[:, i], want,
                                   rtol=1e-5, atol=1e-7)


def test_sign1_codes_idempotent_values_allclose():
    # The sign1 scale is a mean of |code * scale| = scale, recomputed as a
    # fresh f32 sum -- identical values in a different summation order --
    # so the VALUE round-trip is allclose (not bitwise) while the CODES
    # are exactly reproduced (sign(code * scale) == code for scale > 0).
    spec = _spec("sign1")
    buf = _buf(jax.random.PRNGKey(3), spec)
    codes, scales = spec.encode(buf)
    wire = spec.decode(codes, scales)
    codes2, scales2 = spec.encode(wire)
    np.testing.assert_array_equal(np.asarray(codes2), np.asarray(codes))
    np.testing.assert_allclose(np.asarray(spec.decode(codes2, scales2)),
                               np.asarray(wire), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Determinism / batch-rank agnosticism.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wire", ["int8", "sign1"])
def test_encode_deterministic_and_batch_rank_agnostic(wire):
    spec = _spec(wire, pad_to=16)
    buf = _buf(jax.random.PRNGKey(1), spec, scale=3.0)
    codes, scales = spec.encode(buf)
    codes_again, scales_again = spec.encode(buf)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes_again))
    np.testing.assert_array_equal(np.asarray(scales),
                                  np.asarray(scales_again))
    # Per-row (rank-1 batch-free) encode == the matching row of the batch
    # encode: block statistics are strictly per batch element.
    for i in range(W):
        ci, si = spec.encode(buf[i])
        np.testing.assert_array_equal(np.asarray(ci), np.asarray(codes)[i])
        np.testing.assert_array_equal(np.asarray(si), np.asarray(scales)[i])
    # And a higher-rank batch (masked-topology exchange shape) agrees too.
    ex = jnp.broadcast_to(buf[None], (2,) + buf.shape) + 0
    ce, se = spec.encode(ex)
    np.testing.assert_array_equal(np.asarray(ce[0]), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(se[1]), np.asarray(scales))


def test_dequantize_slice_matches_decode():
    # The sharded paths decode arbitrary coordinate slices per seg id; on
    # the full buffer that must agree with the blockwise decode exactly.
    for wire in ("int8", "sign1"):
        spec = _spec(wire, pad_to=64)
        codes, scales = spec.encode(_buf(jax.random.PRNGKey(5), spec))
        np.testing.assert_array_equal(
            np.asarray(packing.dequantize_slice(codes, scales,
                                                spec.seg_ids())),
            np.asarray(spec.decode(codes, scales)))


# ---------------------------------------------------------------------------
# float32 bypass: byte identical, zero copies, per registry aggregator.
# ---------------------------------------------------------------------------

def test_float32_roundtrip_is_the_same_object():
    spec = _spec("float32")
    buf = _buf(jax.random.PRNGKey(2), spec)
    assert spec.wire_roundtrip(buf) is buf
    wire, resid = spec.transmit(buf, None)
    assert wire is buf and resid is None


@pytest.mark.parametrize("name", agg_lib.AGGREGATOR_NAMES)
def test_float32_bypass_bitexact_per_aggregator(name):
    # wire="float32" through the new registry must aggregate bitwise the
    # same as the legacy raw-dtype spec -- the pre-registry behaviour.
    legacy = packing.pack_spec({"a": jnp.zeros((W, 20), jnp.float32),
                                "b": jnp.zeros((W, 13), jnp.float32)},
                               batch_ndim=1, message_dtype=jnp.float32)
    spec = _spec("float32")
    buf = _buf(jax.random.PRNGKey(4), spec)
    opts = OPTS.get(name, {})
    out_legacy = agg_lib.get_flat_aggregator(name, legacy, **opts)(buf)
    out_wire = agg_lib.get_flat_aggregator(name, spec, **opts)(
        spec.wire_roundtrip(buf))
    np.testing.assert_array_equal(np.asarray(out_legacy),
                                  np.asarray(out_wire))


# ---------------------------------------------------------------------------
# Error feedback.
# ---------------------------------------------------------------------------

def test_transmit_conserves_signal_and_requires_residual():
    spec = _spec("sign1")
    buf = _buf(jax.random.PRNGKey(6), spec)
    resid0 = jnp.zeros_like(buf)
    wire, resid1 = spec.transmit(buf, resid0)
    # wire + residual reconstructs the (EF-folded) signal.
    np.testing.assert_allclose(np.asarray(wire + resid1), np.asarray(buf),
                               rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="error feedback"):
        spec.transmit(buf, None)
    # int8 carries no EF: the residual passes through untouched.
    i8 = _spec("int8")
    wire8, resid8 = i8.transmit(buf, resid0)
    assert resid8 is resid0
    np.testing.assert_array_equal(np.asarray(wire8),
                                  np.asarray(i8.wire_roundtrip(buf)))


@hypothesis.given(gain=st.floats(min_value=0.1, max_value=10.0),
                  seed=st.integers(min_value=0, max_value=1000))
@hypothesis.settings(deadline=None, max_examples=10)
def test_sign1_ef_residual_bounded_direct_loop(gain, seed):
    # The mean-|v| sign quantizer is a contraction (delta-compressor with
    # delta = ||v||_1^2 / (D ||v||_2^2)), so the EF residual stays bounded
    # for a bounded gradient stream instead of accumulating.
    spec = _spec("sign1")
    key = jax.random.PRNGKey(seed)
    resid = jnp.zeros((W, spec.padded_dim), jnp.float32)
    norms = []
    for t in range(60):
        g = _buf(jax.random.fold_in(key, t), spec, scale=gain)
        _, resid = spec.transmit(g, resid)
        norms.append(float(jnp.max(jnp.linalg.norm(resid, axis=-1))))
    norms = np.asarray(norms)
    assert np.all(np.isfinite(norms))
    ref = float(gain) * np.sqrt(spec.padded_dim)   # ~ one gradient's norm
    assert norms.max() < 5.0 * ref, (norms.max(), ref)
    # No late-trajectory growth: the second half stays in the first
    # half's envelope.
    assert norms[30:].max() <= 1.5 * norms[:30].max() + 0.1 * ref


def test_sign1_ef_state_bounded_over_federated_trajectory():
    # End-to-end: the residual rows carried in FederatedState.ef under a
    # real sign_flip run stay bounded while training makes progress.
    key = jax.random.PRNGKey(0)
    data = ijcnn1_like(key, n=240)
    loss = logreg_loss(0.01)
    wd = partition({"a": data.x, "b": data.y}, W - 2, seed=1)
    cfg = RobustConfig(aggregator="geomed", vr="sgd", attack="sign_flip",
                       num_byzantine=2, message_dtype="sign1")
    init_fn, step_fn = make_federated_step(loss, wd, cfg,
                                           get_optimizer("sgd", 0.05))
    st_ = init_fn({"w": jnp.zeros((22,), jnp.float32)}, jax.random.PRNGKey(7))
    assert st_.ef is not None and st_.ef.shape[0] == W - 2
    jstep = jax.jit(step_fn)
    norms = []
    for _ in range(150):
        st_, _ = jstep(st_)
        norms.append(float(jnp.max(jnp.linalg.norm(st_.ef, axis=-1))))
    norms = np.asarray(norms)
    assert np.all(np.isfinite(norms))
    assert norms[75:].max() <= 2.0 * norms[:75].max() + 1e-3, \
        f"EF residual grew late in the trajectory: {norms.max()}"


def test_non_ef_formats_carry_no_ef_state():
    key = jax.random.PRNGKey(0)
    data = ijcnn1_like(key, n=120)
    wd = partition({"a": data.x, "b": data.y}, 4, seed=1)
    for dtype, wants_ef in (("float32", False), ("bfloat16", False),
                            ("int8", False), ("sign1", True)):
        cfg = RobustConfig(aggregator="mean", message_dtype=dtype)
        init_fn, _ = make_federated_step(logreg_loss(0.01), wd, cfg,
                                         get_optimizer("sgd", 0.05))
        st_ = init_fn({"w": jnp.zeros((22,), jnp.float32)},
                      jax.random.PRNGKey(1))
        assert (st_.ef is not None) == wants_ef, dtype


# ---------------------------------------------------------------------------
# Registry: single source of truth.
# ---------------------------------------------------------------------------

def test_unknown_wire_format_errors_name_the_registry():
    for bad_call in (lambda: packing.resolve_wire_format("int4"),
                     lambda: packing.resolve_message_dtype("int4"),
                     lambda: RobustConfig(message_dtype="int4").wire_format()):
        with pytest.raises(ValueError) as ei:
            bad_call()
        for name in packing.WIRE_FORMAT_NAMES:
            assert name in str(ei.value)
        assert "int4" in str(ei.value)


def test_registry_is_consistent():
    assert packing.WIRE_FORMAT_NAMES == tuple(packing.WIRE_FORMATS)
    for name, fmt in packing.WIRE_FORMATS.items():
        assert fmt.name == name
        assert packing.resolve_wire_format(name) is fmt
    # Raw-dtype spellings keep resolving (legacy callers).
    assert packing.resolve_wire_format(jnp.bfloat16).name == "bfloat16"
    assert packing.resolve_message_dtype("sign1") == jnp.dtype(jnp.float32)
    with pytest.raises(ValueError, match="not both"):
        packing.pack_spec({"a": jnp.zeros((2, 3))}, wire="int8",
                          message_dtype=jnp.float32)


def test_quantized_requires_packed_path():
    key = jax.random.PRNGKey(0)
    data = ijcnn1_like(key, n=120)
    wd = partition({"a": data.x, "b": data.y}, 4, seed=1)
    cfg = RobustConfig(aggregator="mean", message_dtype="int8", packed=False)
    with pytest.raises(ValueError, match="packed"):
        make_federated_step(logreg_loss(0.01), wd, cfg,
                            get_optimizer("sgd", 0.05))


def test_wire_bytes_accounting():
    sizes = {w: _spec(w).wire_bytes() for w in packing.WIRE_FORMAT_NAMES}
    d, leaves = 33, 2
    assert sizes["float32"] == 4 * d
    assert sizes["bfloat16"] == 2 * d
    assert sizes["int8"] == d + 4 * leaves
    assert sizes["sign1"] == (d + 7) // 8 + 4 * leaves
    assert sizes["sign1"] * 8 < sizes["float32"], "sign1 must be < 1/8 f32"
