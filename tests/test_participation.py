"""Client-scale virtualization properties (DESIGN.md Sec. 10).

Hypothesis-driven (with the seeded ``tests/_hypothesis_fallback.py`` shim
when the real package is absent) pins on the participation layer in
isolation -- the cross-path step-level pins live in
``tests/test_distributed_participation.py`` and the convergence story
in ``tests/test_convergence.py``:

* Cohort sampling is a pure function of (seed, round): rebuilt plans agree
  element-wise, different seeds give different epoch shuffles, and
  ``cohort_at`` under jit matches the precomputed stack.
* Every cohort has exactly W DISTINCT members (the per-client state
  scatter must be alias-free).
* Deterministic coverage: every client participates at least once per
  shuffled epoch -- within ceil(C/W) rounds, not a coupon-collector tail.
* Staleness counters never go negative, reset to 0 exactly for the
  cohort, and grow by 1 per missed round; the weight map sends
  counters at/beyond ``max_staleness`` to exactly 0.
* ``slot_staleness`` places the attack sentinel on the right rows under
  both buffer conventions (sim append vs distributed first-B replace).
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

from repro.core import participation as part
from repro.core.robust_step import RobustConfig


# ---------------------------------------------------------------------------
# Cohort sampling.
# ---------------------------------------------------------------------------

@hypothesis.given(c=st.integers(min_value=1, max_value=97),
                  w=st.integers(min_value=1, max_value=97),
                  seed=st.integers(min_value=0, max_value=10_000))
@hypothesis.settings(deadline=None, max_examples=25)
def test_cohorts_are_deterministic_and_duplicate_free(c, w, seed):
    hypothesis.assume(w <= c)
    plan = part.ParticipationPlan(num_clients=c, cohort_size=w, seed=seed)
    again = part.ParticipationPlan(num_clients=c, cohort_size=w, seed=seed)
    stack = plan.stacked_cohorts
    assert stack.shape == (plan.num_rounds, w)
    assert stack.dtype == np.int32
    np.testing.assert_array_equal(stack, again.stacked_cohorts)
    # Exactly W distinct in-range members per round: the scatter back into
    # the (C, ...) resident tables never aliases.
    for row in stack:
        assert len(set(row.tolist())) == w
        assert row.min() >= 0 and row.max() < c


@hypothesis.given(c=st.integers(min_value=2, max_value=64),
                  seed=st.integers(min_value=0, max_value=1_000))
@hypothesis.settings(deadline=None, max_examples=15)
def test_every_client_covered_each_epoch(c, seed):
    w = max(1, c // 3)
    plan = part.ParticipationPlan(num_clients=c, cohort_size=w, seed=seed)
    r = plan.rounds_per_epoch
    stack = plan.stacked_cohorts
    for e in range(plan.epochs):
        epoch_rows = stack[e * r:(e + 1) * r]
        assert set(epoch_rows.ravel().tolist()) == set(range(c)), \
            f"epoch {e} missed clients within its ceil(C/W)={r} rounds"


def test_seed_changes_the_shuffle():
    mk = lambda s: part.ParticipationPlan(24, 6, seed=s).stacked_cohorts
    assert not np.array_equal(mk(0), mk(1))


def test_cohort_at_matches_stack_and_wraps_under_jit():
    plan = part.ParticipationPlan(num_clients=10, cohort_size=3, seed=7)
    at = jax.jit(plan.cohort_at)
    for t in range(2 * plan.num_rounds + 1):
        np.testing.assert_array_equal(
            np.asarray(at(t)), plan.stacked_cohorts[t % plan.num_rounds])


def test_resolve_participation_bypass_and_validation():
    cfg = RobustConfig(aggregator="mean", num_clients=0)
    assert part.resolve_participation(cfg, 8) is None
    cfg = RobustConfig(aggregator="mean", num_clients=8)
    assert part.resolve_participation(cfg, 8) is None   # full participation
    cfg = RobustConfig(aggregator="mean", num_clients=32,
                       participation_seed=3)
    plan = part.resolve_participation(cfg, 8)
    assert plan.num_clients == 32 and plan.cohort_size == 8
    assert plan.seed == 3
    with pytest.raises(ValueError, match="smaller than"):
        part.resolve_participation(
            RobustConfig(aggregator="mean", num_clients=4), 8)
    with pytest.raises(ValueError, match="does not match"):
        part.resolve_participation(
            RobustConfig(aggregator="mean", num_clients=32, cohort_size=6), 8)


def test_gather_scatter_round_trip():
    plan = part.ParticipationPlan(num_clients=12, cohort_size=4, seed=1)
    tree = {"t": jnp.arange(24.0).reshape(12, 2),
            "s": jnp.arange(12, dtype=jnp.int32)}
    cohort = plan.cohort_at(5)
    rows = part.gather_rows(tree, cohort)
    assert rows["t"].shape == (4, 2) and rows["s"].shape == (4,)
    # Writing the gathered rows straight back is the identity (alias-free
    # cohorts), and writing modified rows changes exactly the cohort.
    same = part.scatter_rows(tree, cohort, rows)
    jax.tree_util.tree_map(np.testing.assert_array_equal, tree, same)
    bumped = part.scatter_rows(
        tree, cohort, jax.tree_util.tree_map(lambda r: r + 100, rows))
    mask = np.zeros(12, bool)
    mask[np.asarray(cohort)] = True
    np.testing.assert_array_equal(np.asarray(bumped["s"])[~mask],
                                  np.asarray(tree["s"])[~mask])
    np.testing.assert_array_equal(np.asarray(bumped["s"])[mask],
                                  np.asarray(tree["s"])[mask] + 100)


# ---------------------------------------------------------------------------
# Staleness counters and weights.
# ---------------------------------------------------------------------------

@hypothesis.given(seed=st.integers(min_value=0, max_value=500),
                  rounds=st.integers(min_value=1, max_value=40))
@hypothesis.settings(deadline=None, max_examples=15)
def test_staleness_counters_never_negative_and_reset_on_participation(
        seed, rounds):
    c, w = 13, 4
    plan = part.ParticipationPlan(num_clients=c, cohort_size=w, seed=seed)
    s = part.init_staleness(c)
    tick = jax.jit(part.tick_staleness)
    last_seen = -np.ones(c, int)
    for t in range(rounds):
        cohort = np.asarray(plan.cohort_at(t))
        s = tick(s, cohort)
        last_seen[cohort] = t
        arr = np.asarray(s)
        assert (arr >= 0).all()
        assert (arr[cohort] == 0).all(), "participants must reset to 0"
        # Everyone else's counter is exactly rounds-since-last-seen
        # (t+1 for the never-seen).
        expect = np.where(last_seen >= 0, t - last_seen, t + 1)
        np.testing.assert_array_equal(arr, expect)


def test_staleness_weights_decay_and_cutoff():
    s = jnp.array([0, 1, 2, 7, 8, 100], jnp.int32)
    w = part.staleness_weights(s, decay=0.5, max_staleness=8)
    np.testing.assert_allclose(np.asarray(w),
                               [1.0, 0.5, 0.25, 0.5 ** 7, 0.0, 0.0])
    # decay=1.0 is pure dropout masking: 0/1 weights only.
    w1 = part.staleness_weights(s, decay=1.0, max_staleness=8)
    np.testing.assert_array_equal(np.asarray(w1), [1, 1, 1, 1, 0, 0])


def test_slot_staleness_conventions():
    honest = jnp.array([3, 0, 5, 1], jnp.int32)
    # Sim convention: B byzantine rows APPENDED after the honest cohort.
    out = part.slot_staleness(honest, "straggler", 2, straggler_k=6,
                              max_staleness=64)
    np.testing.assert_array_equal(np.asarray(out), [3, 0, 5, 1, 6, 6])
    # Distributed convention: first B rows of the full-width buffer were
    # mask-replaced by the attack.
    out = part.slot_staleness(honest, "dropout", 2, straggler_k=6,
                              max_staleness=64, byz_first=True)
    np.testing.assert_array_equal(np.asarray(out), [64, 64, 5, 1])
    # Non-staleness attacks report fresh rows; attack "none" is the
    # identity either way.
    out = part.slot_staleness(honest, "sign_flip", 2, straggler_k=6,
                              max_staleness=64, byz_first=True)
    np.testing.assert_array_equal(np.asarray(out), [0, 0, 5, 1])
    out = part.slot_staleness(honest, "none", 2, straggler_k=6,
                              max_staleness=64)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(honest))


def test_uses_staleness_switch():
    mk = lambda **kw: RobustConfig(aggregator="mean", **kw)
    assert not part.uses_staleness(mk(), None)
    assert part.uses_staleness(mk(attack="straggler"), None)
    assert part.uses_staleness(mk(attack="dropout"), None)
    plan = part.ParticipationPlan(16, 4)
    assert part.uses_staleness(mk(), plan)
