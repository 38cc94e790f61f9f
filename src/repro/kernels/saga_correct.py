"""Fused SAGA correct+update Pallas kernel.

Per step, SAGA reads the fresh gradient g, the stored row table[idx], and
the running average, then emits

    msg      = g - table[idx] + avg
    new_avg  = avg + (g - table[idx]) / J
    table[idx] <- g            (in-place row update via input/output aliasing)

Unfused that is 5 HBM passes over p floats (+ a J*p scatter); the kernel
does one sweep per p-tile: load three tiles, emit three tiles, with the
table row selected by a scalar-prefetched index (pl.ds dynamic slice on the
J axis) and the table aliased input->output so only the touched row moves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 512


def _saga_kernel(idx_ref, grad_ref, table_ref, avg_ref,
                 msg_ref, avg_out_ref, table_out_ref, *, num_samples: int):
    idx = idx_ref[0]
    g = grad_ref[...].astype(jnp.float32)             # (1, T)
    old = table_ref[pl.ds(idx, 1), :].astype(jnp.float32)
    avg = avg_ref[...].astype(jnp.float32)
    delta = g - old
    msg_ref[...] = (delta + avg).astype(msg_ref.dtype)
    avg_out_ref[...] = (avg + delta / num_samples).astype(avg_out_ref.dtype)
    # Copy-through + row update (aliased, so only the dirty row really moves
    # on TPU; interpret mode materializes the copy which is fine for tests).
    table_out_ref[...] = table_ref[...]
    table_out_ref[pl.ds(idx, 1), :] = g.astype(table_out_ref.dtype)


def saga_correct_call(grad: jnp.ndarray, table: jnp.ndarray, avg: jnp.ndarray,
                      idx: jnp.ndarray, *, tile: int = DEFAULT_TILE,
                      interpret: bool = True):
    """grad: (p,), table: (J, p), avg: (p,), idx: () int32.
    Returns (msg (p,), new_avg (p,), new_table (J, p))."""
    j, p = table.shape
    assert grad.shape == (p,) and avg.shape == (p,)
    assert p % tile == 0
    grid = (p // tile,)
    kernel = functools.partial(_saga_kernel, num_samples=j)
    msg, new_avg, new_table = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),         # idx (scalar)
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # grad
            pl.BlockSpec((j, tile), lambda i: (0, i)),     # table
            pl.BlockSpec((1, tile), lambda i: (0, i)),     # avg
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((j, tile), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, p), grad.dtype),
            jax.ShapeDtypeStruct((1, p), avg.dtype),
            jax.ShapeDtypeStruct((j, p), table.dtype),
        ],
        input_output_aliases={2: 2},
        interpret=interpret,
    )(idx.reshape(1), grad.reshape(1, p), table, avg.reshape(1, p))
    return msg[0], new_avg[0], new_table
