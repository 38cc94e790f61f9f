"""byrd-logreg-ijcnn1: the paper's own experiment (arXiv:1912.12716 Sec. V-A).

l2-regularised logistic regression, f(x) = ln(1 + exp(-b <a, x>)) +
rho/2 ||x||^2, over IJCNN1's shape (49,990 rows x 22 features) split iid
over the honest workers.  Runs on the simulated-federation path
(``bench/lib/sim.py``).  The rows are a seeded surrogate: the LIBSVM file is
not in the repository.

What the harness takes from here: the data generator, the worker split,
the initial parameters, the program's loss (the system under test) and
the plain float32 reference loss.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def make_data(key, cfg: dict) -> dict:
    """Rows ~ N(0, 1), labels in {-1, +1} from a planted logistic model
    with label flips (copied from ``repro.data.synthetic.logreg_dataset``,
    so that no later change to the program moves the data)."""
    n, p = cfg["rows"], cfg["features"]

    @jax.jit
    def gen(key):
        k1, k2, k3 = jax.random.split(key, 3)
        x = jax.random.normal(k1, (n, p), jnp.float32)
        w_true = jax.random.normal(k2, (p,), jnp.float32)
        y = jnp.sign(x @ w_true)
        flip = jax.random.bernoulli(k3, cfg["label_noise"], (n,))
        y = jnp.where(flip, -y, y)
        y = jnp.where(y == 0, 1.0, y)
        return {"a": x, "b": y.astype(jnp.float32)}

    return gen(key)


def split(data: dict, cfg: dict, seed: int) -> dict:
    """iid split over the honest workers: leaves (W, J, ...), J = n // W
    (copied from ``repro.data.federated.partition``'s iid mode)."""
    w = cfg["num_honest"]
    n = data["b"].shape[0]
    j = n // w
    order = np.random.default_rng(seed).permutation(n)[: w * j]
    order = jnp.asarray(order.reshape(w, j))
    return jax.jit(lambda d, o: jax.tree_util.tree_map(lambda x: x[o], d))(
        data, order)


def init_params(cfg: dict) -> dict:
    return {"w": jnp.zeros((cfg["features"],), jnp.float32)}


def program_loss(cfg: dict):
    """The loss the program trains (the system under test)."""
    from repro.data import logreg_loss
    return logreg_loss(cfg["rho"])


def ref_loss(params: dict, batch: dict, cfg: dict, dtype=jnp.float32):
    """Mean of ln(1 + exp(-b <a, w>)) over the batch's rows, plus
    rho/2 ||w||^2, computed in ``dtype``."""
    w = params["w"].astype(dtype)
    a, b = batch["a"].astype(dtype), batch["b"].astype(dtype)
    margin = -b * jnp.sum(a * w, axis=-1)
    rho = jnp.asarray(cfg["rho"], dtype)
    return (jnp.mean(jnp.logaddexp(jnp.zeros((), dtype), margin))
            + 0.5 * rho * jnp.sum(w * w))


# The precision below the one this configuration states (float32, no
# precision flag): the control computes the whole reference in bfloat16.
CONTROL_DTYPE = jnp.bfloat16


def flops_per_round(cfg: dict, traffic: dict):
    """No model FLOP count: a 22-wide problem measures latency, not the
    MXU (no ``step_mfu`` for this configuration)."""
    return None
