"""Keys from ``--seed``, which may exceed 32 bits."""
from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key that depends on every bit of a non-negative ``seed``."""
    import jax
    if seed < 0:
        raise ValueError(f"--seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return key
