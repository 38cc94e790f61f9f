"""Pallas TPU kernel for the decentralized neighborhood hot path.

The decentralized step's coordinate-separable aggregations sweep a dense
``(R, S, d)`` exchange tensor (R receivers x S senders x d coordinates,
:mod:`repro.topology.masked`): per receiver, reduce the sender axis under
its ``(R, S)`` neighbor mask -- a masked mean, or a masked trimmed mean
dropping the ``trim`` most extreme masked entries per coordinate.  Unfused,
that is several HBM passes over R*S*d floats (mask broadcast, fill, sort,
reduce); this kernel tiles d into lane-aligned VMEM blocks with the whole
sender axis resident on-chip and fuses the masking, trimming, and reduction
into ONE HBM sweep.

* :func:`masked_neighbor_reduce_call` -- grid over (receiver, d-tile); each
  grid step loads one receiver's (S, T) slab + its mask row as an (S, 1)
  column and emits the (T,) masked (trimmed) mean.

Trimming avoids sorting (TPU-hostile): ``trim`` rounds of extreme
elimination, each removing exactly ONE occurrence of the current masked
max and min per coordinate (first occurrence by sender index, via a
broadcasted iota -- ties therefore match a stable sort), then a masked sum
over the survivors.  ``trim=0`` degenerates to the fused masked mean.

dtype: f32 or bf16 exchanges (accumulation always f32).  The oracle is
``ref.masked_neighbor_reduce`` (an independent sort-based implementation);
``tests/test_kernels.py`` pins them against each other in both dtypes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_TILE = 512


def _masked_reduce_kernel(e_ref, m_ref, out_ref, *, trim: int):
    z = e_ref[0].astype(jnp.float32)             # (S, T)
    m = m_ref[0].astype(jnp.float32)             # (S, 1)
    s = z.shape[0]
    valid = jnp.broadcast_to(m > 0, z.shape)
    n = jnp.sum(m)

    row_ids = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    work = valid
    for _ in range(trim):
        # Drop one occurrence of the masked max, then of the masked min;
        # "one occurrence" = smallest sender index among the ties, which is
        # what a stable sort-and-slice would drop too.
        vals = jnp.where(work, z, -jnp.inf)
        peak = jnp.max(vals, axis=0, keepdims=True)
        hit = (vals == peak) & work
        first = jnp.min(jnp.where(hit, row_ids, s), axis=0, keepdims=True)
        work = work & (row_ids != first)

        vals = jnp.where(work, z, jnp.inf)
        trough = jnp.min(vals, axis=0, keepdims=True)
        hit = (vals == trough) & work
        first = jnp.min(jnp.where(hit, row_ids, s), axis=0, keepdims=True)
        work = work & (row_ids != first)

    total = jnp.sum(jnp.where(work, z, 0.0), axis=0, keepdims=True)
    out_ref[0] = total / jnp.maximum(n - 2 * trim, 1.0)


def masked_neighbor_reduce_call(exchange: jnp.ndarray, mask: jnp.ndarray, *,
                                trim: int = 0, tile: int = DEFAULT_TILE,
                                interpret: bool = True) -> jnp.ndarray:
    """exchange: (R, S, d), mask: (R, S) -> (R, d) f32 per-receiver masked
    (trimmed) means.  d must be a multiple of ``tile`` (ops.py pads); every
    receiver must have > 2*trim masked senders (the topology validators
    guarantee this upstream)."""
    r, s, d = exchange.shape
    assert mask.shape == (r, s), (mask.shape, (r, s))
    assert d % tile == 0, (d, tile)
    grid = (r, d // tile)
    # Mask and output carry a unit axis so each block's last two dims are
    # either (8, 128)-aligned or the array's own -- the Mosaic block rule:
    # the mask row arrives as an (S, 1) column, the output as a (1, T) row.
    out = pl.pallas_call(
        functools.partial(_masked_reduce_kernel, trim=trim),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s, tile), lambda i, k: (i, 0, k)),
            pl.BlockSpec((1, s, 1), lambda i, k: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile), lambda i, k: (i, 0, k)),
        out_shape=jax.ShapeDtypeStruct((r, 1, d), jnp.float32),
        interpret=interpret,
    )(exchange, mask.reshape(r, s, 1))
    return out.reshape(r, d)
