"""Public jit'd wrappers over the Pallas kernels (padding, full geomed loop).

On a TPU the kernels compile to Mosaic.  On the CPU backend they run with
``interpret=True`` (the kernel bodies run as XLA-CPU ops, numerically
identical) -- a correctness harness, not a fast path.  The mode is resolved
when a wrapper is traced, from the default backend (:func:`_interpret`);
importing this module touches no backend.  Any other backend compiles the
kernel as for the chip and fails loudly if it cannot: nothing falls back
to interpret mode or to a jnp reference in silence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import robust_stats as rs
from repro.kernels import saga_correct as sc
from repro.kernels import topology as tp
from repro.kernels import weiszfeld as wz

_TILE = wz.DEFAULT_TILE


def _interpret(interpret: Optional[bool]) -> bool:
    """An explicit ``interpret`` wins; else interpret only on the CPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def _pad_p(x: jnp.ndarray, tile: int, axis: int = -1):
    p = x.shape[axis]
    pad = (-p) % tile
    if pad == 0:
        return x, p
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), p


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def weiszfeld_step(z: jnp.ndarray, y: jnp.ndarray, *, tile: int = _TILE,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """One fused Weiszfeld iteration on (W, p) messages."""
    interp = _interpret(interpret)
    zp, p = _pad_p(z, tile)
    yp, _ = _pad_p(y, tile)
    sq = wz.partial_sqdist_call(zp, yp, tile=tile, interpret=interp)
    inv = 1.0 / jnp.maximum(jnp.sqrt(sq), 1e-8)
    num = wz.weighted_sum_call(zp, inv, tile=tile, interpret=interp)
    return (num / jnp.sum(inv))[:p].astype(z.dtype)


@functools.partial(jax.jit, static_argnames=("iters", "tile", "interpret"))
def geomed(z: jnp.ndarray, *, iters: int = 32, tile: int = _TILE,
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Kernel-backed geometric median (fixed iteration count)."""
    y0 = jnp.mean(z.astype(jnp.float32), axis=0)

    def body(_, y):
        return weiszfeld_step(z, y, tile=tile, interpret=interpret).astype(jnp.float32)

    y = jax.lax.fori_loop(0, iters, body, y0)
    return y.astype(z.dtype)


@functools.partial(jax.jit, static_argnames=("num_segments", "tile", "interpret"))
def partial_sqdist_segments(z: jnp.ndarray, y: jnp.ndarray,
                            seg_ids: jnp.ndarray, *, num_segments: int,
                            tile: int = _TILE,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """Per-(worker, block) squared distances: z (W, p), y (p,), seg_ids (p,)
    int block id per coordinate -> (W, num_segments).  One fused sweep
    instead of num_segments sqdist passes -- the TPU form of the segment sum
    in ``core/geomed.weiszfeld_blockwise_sharded`` (not yet wired into that
    shard_map path); padding introduced here contributes to no block."""
    interp = _interpret(interpret)
    zp, p = _pad_p(z, tile)
    yp, _ = _pad_p(y, tile)
    onehot = (seg_ids[None, :] == jnp.arange(num_segments)[:, None]).astype(
        jnp.float32)
    ohp, _ = _pad_p(onehot, tile)  # padded coordinates: all-zero columns
    return wz.partial_sqdist_segments_call(zp, yp, ohp, tile=tile,
                                           interpret=interp)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def saga_correct(grad: jnp.ndarray, table: jnp.ndarray, avg: jnp.ndarray,
                 idx: jnp.ndarray, *, tile: int = _TILE,
                 interpret: Optional[bool] = None):
    """Fused SAGA correct+update on a raveled (p,) gradient."""
    interp = _interpret(interpret)
    gp, p = _pad_p(grad, tile)
    tp, _ = _pad_p(table, tile)
    ap, _ = _pad_p(avg, tile)
    msg, new_avg, new_table = sc.saga_correct_call(
        gp, tp, ap, idx.astype(jnp.int32), tile=tile, interpret=interp)
    return msg[:p], new_avg[:p], new_table[:, :p]


@functools.partial(jax.jit, static_argnames=("causal", "q_block", "kv_block", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, q_block: int = 128,
                    kv_block: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention on (B, S, H, hd) tensors with GQA (KV <= H heads,
    repeated on entry).  Output dtype follows q."""
    interp = _interpret(interpret)
    b, s, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    o = fa.flash_attention_call(to_bh(q), to_bh(k), to_bh(v), causal=causal,
                                q_block=q_block, kv_block=kv_block,
                                interpret=interp)
    return o.reshape(b, h, s, hd).transpose(0, 2, 1, 3).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("trim", "tile", "interpret"))
def masked_neighbor_reduce(exchange: jnp.ndarray, mask: jnp.ndarray, *,
                           trim: int = 0, tile: int = _TILE,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Fused per-receiver masked (trimmed) neighborhood mean on a dense
    (R, S, d) exchange tensor + (R, S) neighbor mask -> (R, d) f32.  The
    decentralized hot path (DESIGN.md Sec. 6); the jnp shard_map path in
    ``topology/masked.py`` is the oracle-checked reference, this is the
    TPU form (one HBM sweep, no sort).  Padding coordinates introduced
    here average masked zeros and are stripped before returning."""
    interp = _interpret(interpret)
    ep, d = _pad_p(exchange, tile)
    return tp.masked_neighbor_reduce_call(ep, mask, trim=trim, tile=tile,
                                          interpret=interp)[:, :d]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def coordinate_median(z: jnp.ndarray, *, tile: int = _TILE,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    interp = _interpret(interpret)
    zp, p = _pad_p(z, tile)
    return rs.coordinate_median_call(zp, tile=tile, interpret=interp)[:p]


@functools.partial(jax.jit, static_argnames=("trim", "tile", "interpret"))
def trimmed_mean(z: jnp.ndarray, *, trim: int = 1, tile: int = _TILE,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    interp = _interpret(interpret)
    zp, p = _pad_p(z, tile)
    return rs.trimmed_mean_call(zp, trim, tile=tile, interpret=interp)[:p]
