"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels execute in interpret mode, so
wall-clock numbers characterize the *oracle-equivalence harness*, not TPU
performance; the derived metric therefore reports the structural quantity
that matters on TPU -- the arithmetic intensity (FLOPs per HBM byte) of the
fused kernel vs its unfused reference, which determines the roofline
position of the aggregation step.  The intensity report is parametrized
over the message element size (f32 and bf16 wires -- the
``message_dtype="bfloat16"`` packing mode of DESIGN.md Sec. 8 halves every
byte term while the FLOPs stay f32-accumulated, doubling intensity).

Timing uses ``time.perf_counter`` (monotonic, ns resolution) -- never
``time.time``, whose wall-clock can step under NTP and only guarantees
~µs-scale resolution, the same magnitude as one fused kernel call.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache

# Wire element sizes the intensity report is parametrized over: the f32
# baseline and the bf16 packed-message mode (DESIGN.md Sec. 8).
ELEMENT_SIZES = {"f32": 4, "bf16": 2}


def _time(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _emit_intensity(name: str, us: float, flops: float,
                    elems_moved: float, fixed_f32_elems: float = 0.0) -> None:
    """One CSV row per wire dtype: ``elems_moved`` scale with the message
    element size; ``fixed_f32_elems`` (accumulators, f32 outputs) do not."""
    for tag, esize in ELEMENT_SIZES.items():
        bytes_moved = elems_moved * esize + fixed_f32_elems * 4
        print(f"kernel/{name}/{tag},{us:.1f},{flops/bytes_moved:.4f}")


def main() -> None:
    enable_compile_cache()
    key = jax.random.PRNGKey(0)
    w, p = 32, 65536
    z = jax.random.normal(key, (w, p))
    y = jnp.mean(z, axis=0)

    us = _time(ops.weiszfeld_step, z, y)
    # Fused Weiszfeld pass: reads W*p once per sub-kernel (2 sweeps of the
    # message matrix), writes the p-dim f32 iterate.
    flops = 4 * w * p          # sub, mul, add (dist) + weighted sum
    _emit_intensity(f"weiszfeld_step/W{w}xP{p}", us,
                    flops, elems_moved=2 * w * p, fixed_f32_elems=2 * p)
    us_ref = _time(jax.jit(ref.weiszfeld_step), z, y)
    # Unfused reference: residual matrix materialized (3 extra W*p sweeps).
    _emit_intensity(f"weiszfeld_step_ref/W{w}xP{p}", us_ref,
                    flops, elems_moved=5 * w * p, fixed_f32_elems=2 * p)

    j = 16
    table = jax.random.normal(key, (j, p))
    grad = jax.random.normal(key, (p,))
    avg = jnp.mean(table, axis=0)
    idx = jnp.asarray(3, jnp.int32)
    us = _time(ops.saga_correct, grad, table, avg, idx)
    flops = 4 * p
    # read g, row, avg; write msg, avg, row
    _emit_intensity(f"saga_correct/J{j}xP{p}", us, flops, elems_moved=6 * p)
    us_ref = _time(jax.jit(lambda *a: ref.saga_correct(*a)), grad, table, avg, idx)
    # + full-table scatter copy
    _emit_intensity(f"saga_correct_ref/J{j}xP{p}", us_ref, flops,
                    elems_moved=6 * p + 2 * j * p)

    us = _time(ops.coordinate_median, z)
    _emit_intensity(f"coordinate_median/W{w}xP{p}", us,
                    flops=float(w * jnp.log2(w) * p),
                    elems_moved=w * p, fixed_f32_elems=p)


if __name__ == "__main__":
    main()
