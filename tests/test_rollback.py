"""Checkpoint auto-rollback (DESIGN.md Sec. 13): the host-side recovery
layer on top of the in-graph guards -- RunHealth state machine, degradation
ladder, checkpoint integrity/last-good anchoring, and the end-to-end
rollback paths (simulation in-process, distributed driver in a
subprocess)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mesh_harness import run_py
from repro.checkpoint import CheckpointManager
from repro.core import RobustConfig, make_federated_step
from repro.data import ijcnn1_like, logreg_loss, partition
from repro.launch.health import (
    RunHealth,
    apply_rung,
    parse_ladder,
)
from repro.optim import get_optimizer


# ---------------------------------------------------------------------------
# RunHealth state machine
# ---------------------------------------------------------------------------


def test_runhealth_patience_on_rejected_rounds():
    h = RunHealth(patience=3)
    for _ in range(2):
        h.observe({"round_accepted": 0.0, "loss": 1.0})
    assert not h.rollback_pending
    h.observe({"round_accepted": 1.0, "loss": 1.0})   # good round resets
    assert h.healthy
    for _ in range(3):
        h.observe({"round_accepted": 0.0, "loss": 1.0})
    assert h.rollback_pending and not h.healthy


def test_runhealth_nonfinite_and_blowup_losses_are_bad():
    h = RunHealth(patience=2, blowup=10.0)
    h.observe({"loss": 1.0})
    h.observe({"loss": float("nan")})
    h.observe({"loss": float("inf")})
    assert h.rollback_pending
    h2 = RunHealth(patience=2, blowup=10.0)
    h2.observe({"loss": 1.0})
    h2.observe({"loss": 5.0})          # within blowup x best: fine
    assert h2.healthy
    h2.observe({"loss": 11.0})         # > 10 x best(=1.0)
    h2.observe({"loss": 12.0})
    assert h2.rollback_pending


def test_runhealth_rollback_and_dismiss_bookkeeping():
    h = RunHealth(patience=1)
    h.observe({"round_accepted": 0.0})
    assert h.rollback_pending
    h.on_rollback()
    assert h.rollbacks == 1 and not h.rollback_pending and h.healthy
    h.observe({"round_accepted": 0.0})
    assert h.rollback_pending
    h.dismiss()                        # no checkpoint available
    assert h.rollbacks == 1 and not h.rollback_pending
    assert h.summary() == {"rollbacks": 1, "ladder_rungs_used": 0}
    with pytest.raises(ValueError):
        RunHealth(patience=0)


# ---------------------------------------------------------------------------
# Degradation ladder
# ---------------------------------------------------------------------------


def test_parse_ladder_groups_and_errors():
    rungs = parse_ladder("trim=2; aggregator=trimmed_mean , trim=3 ;")
    assert rungs == [{"trim": "2"},
                     {"aggregator": "trimmed_mean", "trim": "3"}]
    assert parse_ladder("") == []
    with pytest.raises(ValueError, match="key=value"):
        parse_ladder("trim")


def test_apply_rung_coerces_to_field_types():
    base = RobustConfig()
    out = apply_rung(base, {"trim": "2", "guard_multiplier": "4.5",
                            "diagnostics": "true", "aggregator": "krum"})
    assert out.trim == 2 and isinstance(out.trim, int)
    assert out.guard_multiplier == 4.5
    assert out.diagnostics is True
    assert out.aggregator == "krum"
    assert base.trim == 1              # frozen original untouched


def test_apply_rung_refuses_unknown_and_structural_fields():
    base = RobustConfig()
    with pytest.raises(ValueError, match="no field"):
        apply_rung(base, {"not_a_field": "1"})
    # Structure-changing fields would invalidate the checkpoint being
    # restored: escalation must refuse them.
    for field in ("vr", "message_dtype", "num_clients", "guards", "comm",
                  "packed", "topology"):
        with pytest.raises(ValueError, match="structure"):
            apply_rung(base, {field: "x"})


def test_escalate_walks_rungs_then_exhausts():
    h = RunHealth(patience=1, ladder="trim=2;trim=3,aggregator=geomed")
    base = RobustConfig(aggregator="trimmed_mean")
    assert h.escalate(base) is base    # no rollback yet
    h.on_rollback()
    r1 = h.escalate(base)
    assert r1.trim == 2 and r1.aggregator == "trimmed_mean"
    h.on_rollback()
    r2 = h.escalate(base)
    assert r2.trim == 3 and r2.aggregator == "geomed"
    h.on_rollback()
    assert h.escalate(base) is base    # ladder exhausted
    assert h.summary() == {"rollbacks": 3, "ladder_rungs_used": 2}


# ---------------------------------------------------------------------------
# Checkpoint integrity + last-good anchor
# ---------------------------------------------------------------------------


def _tree(step):
    return {"w": np.arange(6.0, dtype=np.float32) + step,
            "b": np.float32(step)}


def test_restore_latest_skips_truncated_checkpoint(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, _tree(1))
    p2 = ckpt.save(2, _tree(2))
    blob = open(p2, "rb").read()
    with open(p2, "wb") as f:              # truncate: checksum mismatch
        f.write(blob[: len(blob) // 2])
    with pytest.warns(UserWarning, match="checksum"):
        step, got = ckpt.restore_latest(_tree(0))
    assert step == 1
    np.testing.assert_array_equal(got["w"], _tree(1)["w"])


def test_restore_latest_skips_unreadable_checkpoint(tmp_path):
    """A file whose CONTENT matches the manifest but is not a loadable npz
    (bit rot after the checksum was forged / manifest rebuilt) is skipped
    via the load-exception path, not the checksum path."""
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, _tree(1))
    p2 = ckpt.save(2, _tree(2))
    with open(p2, "wb") as f:
        f.write(b"not an npz at all")
    m = json.load(open(os.path.join(tmp_path, "manifest.json")))
    import hashlib
    m["checksums"][os.path.basename(p2)] = hashlib.sha256(
        b"not an npz at all").hexdigest()
    with open(os.path.join(tmp_path, "manifest.json"), "w") as f:
        json.dump(m, f)
    with pytest.warns(UserWarning, match="unreadable"):
        step, got = ckpt.restore_latest(_tree(0))
    assert step == 1
    np.testing.assert_array_equal(got["w"], _tree(1)["w"])


def test_manifest_checksums_and_legacy_verify(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, _tree(1))
    m = json.load(open(os.path.join(tmp_path, "manifest.json")))
    assert "step_00000001.npz" in m["checksums"]
    assert ckpt.verify(1)
    # Legacy checkpoints (no recorded checksum) must still verify.
    del m["checksums"]["step_00000001.npz"]
    with open(os.path.join(tmp_path, "manifest.json"), "w") as f:
        json.dump(m, f)
    assert ckpt.verify(1)
    assert not ckpt.verify(99)


def test_mark_good_survives_gc_and_restores(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    for s in range(1, 6):
        ckpt.save(s, _tree(s))
        if s == 1:
            ckpt.mark_good(1)
    # keep=2 would normally leave {4, 5}; the last-good anchor survives.
    assert ckpt.all_steps() == [1, 4, 5]
    assert ckpt.last_good_step() == 1
    step, got = ckpt.restore_last_good(_tree(0))
    assert step == 1
    np.testing.assert_array_equal(got["w"], _tree(1)["w"])
    # Stale checksum entries for GC'd files are pruned.
    m = json.load(open(os.path.join(tmp_path, "manifest.json")))
    assert set(m["checksums"]) == {"step_00000001.npz", "step_00000004.npz",
                                   "step_00000005.npz"}
    with pytest.raises(FileNotFoundError):
        ckpt.mark_good(42)


def test_restore_last_good_falls_back_to_latest(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(1, _tree(1))
    step, got = ckpt.restore_last_good(_tree(0))  # no marker yet
    assert step == 1
    np.testing.assert_array_equal(got["b"], _tree(1)["b"])


# ---------------------------------------------------------------------------
# Simulation rollback: bit-exact recovery
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sim():
    key = jax.random.PRNGKey(0)
    data = ijcnn1_like(key, n=600)
    loss = logreg_loss(0.01)
    wd = partition({"a": data.x, "b": data.y}, 10, seed=1)
    return loss, wd


def test_simulation_rollback_recovers_bit_exact(sim, tmp_path):
    """The full recovery loop at simulation scale: honest guarded training,
    last-good checkpoint, a sustained-rejection phase (health vector poisoned
    so the in-graph verdict rejects every round), RunHealth arming the
    rollback, restore_last_good, and a re-descent that matches a straight
    honest run BIT-EXACTLY on every train-state leaf (the state carries its
    own PRNG key, so the seeded schedule replays)."""
    loss, wd = sim
    cfg = RobustConfig(aggregator="geomed", vr="saga", guards=True)
    opt = get_optimizer("momentum", 0.02)
    init_fn, step_fn = make_federated_step(loss, wd, cfg, opt)
    jstep = jax.jit(step_fn)

    def run(st, steps, monitor=None):
        for _ in range(steps):
            st, m = jstep(st)
            if monitor is not None:
                monitor.observe({"round_accepted":
                                 float(m["round_accepted"])})
        return st

    st0 = init_fn({"w": jnp.zeros((22,), jnp.float32)}, jax.random.PRNGKey(3))
    straight = run(st0, 5)                      # the honest reference

    monitor = RunHealth(patience=2)
    st3 = run(st0, 3, monitor)
    assert monitor.healthy                      # warmup rounds all accepted
    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save_train_state(3, st3._asdict())
    ckpt.mark_good(3)

    # Sustained-rejection phase: a collapsed EMA (tiny mean/var, seen past
    # warmup) makes every subsequent aggregate a huge z-score outlier, so
    # the in-graph verdict rejects each round and HOLDS the state.
    poisoned = st3._replace(health=jnp.asarray(
        [1e-8, 1e-16, 0.0, 10.0], jnp.float32))
    bad = run(poisoned, 2, monitor)
    np.testing.assert_array_equal(np.asarray(bad.params["w"]),
                                  np.asarray(st3.params["w"]))
    assert int(bad.step) == 5                   # step counter still advances
    assert monitor.rollback_pending             # 2 rejected rounds = patience

    gstep, restored = ckpt.restore_last_good(st3._asdict())
    assert gstep == 3
    monitor.on_rollback()
    assert monitor.rollbacks == 1
    resumed = run(type(st0)(**restored), 2, monitor)
    assert monitor.healthy                      # re-descent rounds accepted

    paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(
        straight._asdict())[0]]
    for path, a, b in zip(paths,
                          jax.tree_util.tree_leaves(straight._asdict()),
                          jax.tree_util.tree_leaves(resumed._asdict())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(path))


# ---------------------------------------------------------------------------
# Distributed driver rollback (subprocess, 8 forced host devices)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # two full 16-step driver runs in one subprocess
def test_distributed_driver_rollback_is_deterministic(tmp_path):
    """The launch driver end to end: an over-tight verdict gate
    (--reject-zmax 0.02) makes post-warmup rounds reject, RunHealth arms
    after 2, and the loop restores the last-good checkpoint and re-descends
    to completion.  Two identical runs -- each rolling back the same way --
    must land on the bit-identical final loss: the recovery path is as
    deterministic as the trajectory it restores."""
    out = run_py(f"""
        import json, math, os, sys

        def drive(tag):
            ck = os.path.join({str(tmp_path)!r}, tag + "-ckpt")
            lg = os.path.join({str(tmp_path)!r}, tag + "-log")
            sys.argv = ["train", "--arch", "mamba2-130m", "--reduced",
                        "--steps", "16", "--seq", "32", "--mesh", "4x2",
                        "--aggregator", "mean", "--guards",
                        "--reject-zmax", "0.02",
                        "--rollback-patience", "2",
                        "--checkpoint-dir", ck, "--checkpoint-every", "2",
                        "--log-dir", lg, "--log-every", "1"]
            from repro.launch.train import main
            main()
            meta = json.load(open(os.path.join(lg, "meta.json")))
            return meta["resilience"]

        r1 = drive("a")
        r2 = drive("b")
        assert r1["rollbacks"] >= 1, r1
        assert r1["rejected_rounds"] > 0, r1
        assert math.isfinite(r1["final_loss"]), r1
        assert r1 == r2, (r1, r2)
        print("RESILIENCE", json.dumps(r1))
    """, devices=8, timeout=180)
    assert "rollback #1: restored step" in out
    assert "RESILIENCE" in out
