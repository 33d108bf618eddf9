"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e,
at the widths ``chip_smoke.py`` runs (a 6M-tuple column).

Nothing runs: the TPU compiler that ships with libtpu compiles for a
described chip and refuses what the chip would refuse -- blocks off the
(8, 128) tiling, primitives Mosaic cannot lower, too much SMEM or VMEM --
all of which interpret mode accepts.  Each compiled program must hold the
kernel (``tpu_custom_call``).  ``kernels/polymul.py`` is off the engine's
path and is not compiled here (its ``dynamic_slice`` does not lower).

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and the test workers all import this
file.
"""
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import cumulants, group_cf, group_cumulants, pb_cf

N = 6_000_000            # lineitem rows at TPC-H SF1


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or it is held elsewhere
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # Loading libtpu installs a failure-signal handler that dumps a
        # stack trace on SIGTERM; give the worker back the default, so a
        # worker stopped by a time limit exits quietly as before.
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        # The chip path runs with x64 off (the suite turns it on), and a
        # compile for a described chip can be written to the persistent
        # cache but not read back without one: keep the cache out of it.
        prev = (jax.config.jax_enable_x64,
                jax.config.jax_enable_compilation_cache)
        jax.config.update("jax_enable_x64", False)
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_x64", prev[0])
            jax.config.update("jax_enable_compilation_cache", prev[1])


# The grouped kernels take pre-sorted operands (``operands=``), as the
# engine hoists the sort: compiling a 6M-element argsort for v5e takes
# ~25 s on its own and holds no kernel.  The grouped-cumulants layout
# gives each 512-tuple stream tile a super tile of 1024 lanes.
SUPER = 2 * 512 * -(-N // 512)
KERNELS = {
    "cumulants": (
        lambda p, v, g: cumulants.cumulant_sums(p, v, orders=8,
                                                interpret=False),
        jnp.float32, N),
    "group_cumulants": (         # (ob, gb, tb) = (8, 128, 512)
        lambda p, v, g: group_cumulants.group_cumulant_sums(
            p, v, g, num_groups=1024, orders=8, ob=8, gb=128, tb=512,
            interpret=False, operands=(p, v, g)),
        jnp.float32, SUPER),
    "pb_cf": (                   # the smoke's COUNT grid
        lambda p, v, g: pb_cf.logcf(p, v, num_freq=8192, interpret=False),
        jnp.int32, N),
    "group_cf": (                # (gb, fb, tb) = (8, 256, 512)
        lambda p, v, g: group_cf.group_logcf(
            p, v, g, num_groups=4096, num_freq=256, gb=8, fb=256, tb=512,
            interpret=False, operands=(p, v, v, g)),
        jnp.int32, N),
}


@pytest.mark.kernels
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, vdtype, n = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
              for dt in (jnp.float32, vdtype, jnp.int32)]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
