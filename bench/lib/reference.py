"""Plain pieces of a Byzantine-robust round, for the references.

Each is the textbook form of what it names (arXiv:1912.12716 Alg. 1 and
eq. (6); AdamW as Loshchilov & Hutter), written with no import of the
program under test.  The configurations' references compose them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DIST_FLOOR = 1e-8   # distances below this count as this (Weiszfeld's 1/d)


def tree_vec(tree) -> jnp.ndarray:
    """All leaves of ``tree`` as one float32 vector (leaf order)."""
    return jnp.concatenate([jnp.ravel(x).astype(jnp.float32)
                            for x in jax.tree_util.tree_leaves(tree)])


def vec_tree(vec: jnp.ndarray, like):
    """Inverse of :func:`tree_vec` onto the shapes of ``like`` (float32)."""
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, at = [], 0
    for x in leaves:
        out.append(vec[at:at + x.size].reshape(x.shape))
        at += x.size
    return jax.tree_util.tree_unflatten(treedef, out)


@jax.jit
def _weiszfeld_iter(points, y):
    d = jnp.sqrt(jnp.sum(jnp.square(points - y[None]), axis=1))
    inv = 1.0 / jnp.maximum(d, DIST_FLOOR)
    y_new = jnp.sum(inv[:, None] * points, axis=0) / jnp.sum(inv)
    return y_new, jnp.sqrt(jnp.sum(jnp.square(y_new - y)))


def geometric_median(points: jnp.ndarray, *, max_iters: int,
                     tol: float) -> jnp.ndarray:
    """Weiszfeld's iteration on the rows of ``points`` (W, D), from their
    mean, until an iterate moves by ``tol`` or less or ``max_iters`` ran."""
    y = jnp.mean(points, axis=0)
    for _ in range(max_iters):
        y, move = _weiszfeld_iter(points, y)
        if float(move) <= tol:
            break
    return y


def aggregate(points: jnp.ndarray, rule: str, *, max_iters: int = 64,
              tol: float = 1e-6) -> jnp.ndarray:
    if rule == "mean":
        return jnp.mean(points, axis=0)
    if rule == "geomed":
        return geometric_median(points, max_iters=max_iters, tol=tol)
    raise ValueError(f"no reference for aggregator {rule!r}")


def sign_flip(honest_mean: jnp.ndarray, magnitude: float = -3.0):
    """The paper's sign-flipping attack: ``magnitude`` x the honest mean."""
    return magnitude * honest_mean


class Adam:
    """AdamW with decoupled weight decay, float32 moments; parameters are
    rounded to their own dtype after each update (bf16 storage)."""

    def __init__(self, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.01):
        self.lr, self.b1, self.b2 = lr, b1, b2
        self.eps, self.wd = eps, weight_decay

    def init(self, params):
        z = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"mu": jax.tree_util.tree_map(z, params),
                "nu": jax.tree_util.tree_map(z, params), "t": 0}

    def update(self, params, grad, state):
        t = state["t"] + 1
        mu = jax.tree_util.tree_map(lambda m, g: self.b1 * m + (1 - self.b1) * g,
                                    state["mu"], grad)
        nu = jax.tree_util.tree_map(
            lambda v, g: self.b2 * v + (1 - self.b2) * g * g, state["nu"], grad)
        bc1, bc2 = 1 - self.b1 ** t, 1 - self.b2 ** t

        def step(p, m, v):
            u = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
            u = u + self.wd * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - self.lr * u).astype(p.dtype)

        new = jax.tree_util.tree_map(step, params, mu, nu)
        return new, {"mu": mu, "nu": nu, "t": t}


class Sgd:
    def __init__(self, lr):
        self.lr = lr

    def init(self, params):
        return {}

    def update(self, params, grad, state):
        new = jax.tree_util.tree_map(
            lambda p, g: (p - self.lr * g.astype(p.dtype)).astype(p.dtype),
            params, grad)
        return new, state


def optimizer(name: str, lr: float):
    if name == "sgd":
        return Sgd(lr)
    if name == "adamw":
        return Adam(lr)
    raise ValueError(f"no reference for optimizer {name!r}")
