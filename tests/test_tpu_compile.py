"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Nothing runs here: each kernel is lowered and compiled for a v5e chip that
is described, not attached (``jax.experimental.topologies``), which is where
Mosaic refuses block shapes, slices and memory the interpret mode accepts.
The topology is described inside a fixture, so collecting this file touches
no TPU library; where it cannot be described the tests skip.
"""
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

W, D = 16, 2 ** 20          # workers x coordinates of one message buffer


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # Loading the TPU library installs a handler that prints a stack
        # trace on SIGTERM; keep this process's own disposition, so a run
        # stopped by its time limit ends with its output intact.
        signal.signal(signal.SIGTERM, on_term)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _cases(sds):
    f32 = lambda *shape: sds(shape, jnp.float32)
    return {
        "weiszfeld_step": (
            lambda z, y: ops.weiszfeld_step(z, y, interpret=False),
            (f32(W, D), f32(D))),
        "geomed": (
            lambda z: ops.geomed(z, iters=4, interpret=False), (f32(W, D),)),
        "partial_sqdist_segments": (
            lambda z, y, s: ops.partial_sqdist_segments(
                z, y, s, num_segments=7, interpret=False),
            (f32(W, D), f32(D), sds((D,), jnp.int32))),
        "masked_neighbor_reduce": (
            lambda e, m: ops.masked_neighbor_reduce(e, m, trim=1,
                                                    interpret=False),
            (f32(4, 4, D), f32(4, 4))),
        "saga_correct": (
            lambda g, t, a, i: ops.saga_correct(g, t, a, i, interpret=False),
            (f32(D), f32(4, D), f32(D), sds((), jnp.int32))),
    }


@pytest.mark.parametrize("name", ["weiszfeld_step", "geomed",
                                  "partial_sqdist_segments",
                                  "masked_neighbor_reduce", "saga_correct"])
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn, args = _cases(sds)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * D, mem
