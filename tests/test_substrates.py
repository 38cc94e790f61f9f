"""Data / optimizer / checkpoint substrate tests + repo-level invariants."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load, save
from repro.data import ijcnn1_like, covtype_like, mnist_like, partition, token_stream
from repro.optim import adam, apply_updates, cosine_schedule, get_optimizer, momentum, sgd


# ---------------- data ----------------

def test_dataset_shapes():
    d = ijcnn1_like(jax.random.PRNGKey(0), n=100)
    assert d.x.shape == (100, 22) and d.y.shape == (100,)
    assert set(np.unique(np.asarray(d.y))) <= {-1.0, 1.0}
    d2 = covtype_like(jax.random.PRNGKey(0), n=50)
    assert d2.x.shape == (50, 54)
    m = mnist_like(jax.random.PRNGKey(0), n=40)
    assert m.x.shape == (40, 784) and int(m.y.max()) <= 9


def test_partition_iid_disjoint():
    d = ijcnn1_like(jax.random.PRNGKey(0), n=120)
    wd = partition({"a": d.x, "b": d.y}, 4, seed=0)
    assert wd["a"].shape == (4, 30, 22)
    flat = np.asarray(wd["a"]).reshape(-1, 22)
    assert len(np.unique(flat, axis=0)) == 120  # disjoint samples


def test_partition_replicated():
    d = ijcnn1_like(jax.random.PRNGKey(0), n=60)
    wd = partition({"a": d.x}, 5, mode="replicated", samples_per_worker=20)
    a = np.asarray(wd["a"])
    assert a.shape == (5, 20, 22)
    for w in range(1, 5):
        np.testing.assert_array_equal(a[0], a[w])


def test_token_stream():
    b = token_stream(jax.random.PRNGKey(0), 2, 16, 100)
    assert b["tokens"].shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                  np.asarray(b["labels"][:, :-1]))


# ---------------- optim ----------------

def _quad_loss(p):
    return 0.5 * jnp.sum((p["w"] - 3.0) ** 2)


@pytest.mark.parametrize("name,lr,steps", [("sgd", 0.3, 60), ("momentum", 0.1, 80),
                                           ("adam", 0.3, 120), ("adamw", 0.3, 200)])
def test_optimizers_converge_quadratic(name, lr, steps):
    opt = get_optimizer(name, lr)
    params = {"w": jnp.zeros((4,))}
    state = opt.init(params)
    for i in range(steps):
        g = jax.grad(_quad_loss)(params)
        upd, state = opt.update(g, state, params, i)
        params = apply_updates(params, upd)
    tol = 0.4 if name == "adamw" else 0.05   # decoupled decay biases optimum
    assert float(jnp.max(jnp.abs(params["w"] - 3.0))) < tol


def test_cosine_schedule():
    s = cosine_schedule(1.0, warmup=10, total=100)
    assert float(s(0)) == 0.0
    assert abs(float(s(10)) - 1.0) < 1e-6
    assert float(s(100)) <= 0.11


# ---------------- checkpoint ----------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.asarray(7, jnp.int32)}}
    p = os.path.join(tmp_path, "ck.npz")
    save(p, tree)
    got = load(p, tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_checkpoint_manager_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.zeros((2,))}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4
    got = mgr.restore(4, tree)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.zeros(2))


def test_checkpoint_missing_leaf_raises(tmp_path):
    p = os.path.join(tmp_path, "ck.npz")
    save(p, {"a": jnp.zeros(2)})
    with pytest.raises(KeyError):
        load(p, {"a": jnp.zeros(2), "b": jnp.zeros(3)})


# ---------------- repo invariants ----------------

def test_compat_layer_is_the_only_jax_version_gate():
    """Version-moving jax names must be touched only inside repro.compat
    (DESIGN.md Sec. 3): everything else goes through the compat surface so
    a future move of those names changes one module."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    banned = re.compile(
        r"jax\.shard_map|jax\.set_mesh|jax\.sharding\.AxisType"
        r"|from jax\.sharding import .*AxisType|jax\.experimental\.shard_map"
        r"|jax\.make_mesh|jax\.lax\.axis_size|jax\.profiler")
    offenders = []
    for sub in ("src", "tests", "examples", "benchmarks"):
        for path in glob.glob(os.path.join(repo, sub, "**", "*.py"), recursive=True):
            if os.sep + os.path.join("repro", "compat") + os.sep in path:
                continue
            with open(path) as f:
                for ln, line in enumerate(f, 1):
                    if banned.search(line):
                        offenders.append(f"{os.path.relpath(path, repo)}:{ln}: {line.strip()}")
    assert not offenders, "\n".join(offenders)
