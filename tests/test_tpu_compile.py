"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret-mode tests run the kernel bodies in Python and never meet the
TPU compiler's tiling, alignment and VMEM checks.  Here each kernel is
lowered with ``interpret=False`` at a real chunk width for a described (not
attached) v5e and compiled; the compiled program must hold the kernel as a
``tpu_custom_call``.  Nothing runs, so these say nothing about results or
times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every xdist worker imports
this file.
"""

import os
import signal
import threading

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.seg_scan.kernel import (
    seg_prefix_scan_pallas,
    seg_suffix_scan_pallas,
)
from repro.kernels.sliding_window.kernel import sliding_window_pallas
from repro.kernels.suffix_scan.kernel import suffix_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with JAX's persistent
    compilation cache off (a TPU executable cannot be read back without a
    chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # loading the TPU library installed a crash reporter for SIGTERM;
        # a worker the test runner stops must exit as quietly as the others
        if threading.current_thread() is threading.main_thread():
            signal.signal(signal.SIGTERM, signal.SIG_DFL)
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("kernel", [seg_suffix_scan_pallas,
                                    seg_prefix_scan_pallas],
                         ids=["suffix", "prefix"])
def test_seg_scan_compiles_for_v5e(one_chip, kernel, op):
    """The keyed flip sweep's segmented scans at one keyed chunk (C=4096)."""
    x = _spec((1, 4096), jnp.int32, one_chip)
    flags = _spec((1, 4096), jnp.bool_, one_chip)
    _assert_kernel(kernel.lower(x, flags, op=op, interpret=False))


@pytest.mark.parametrize("op", ["sum", "max"])
def test_sliding_window_compiles_for_v5e(one_chip, op):
    """The chunked engine's VHGW kernel: 8 lanes, window 1024."""
    x = _spec((8, 8192), jnp.float32, one_chip)
    _assert_kernel(
        sliding_window_pallas.lower(x, window=1024, op=op, interpret=False)
    )


@pytest.mark.parametrize("op", ["sum", "max"])
def test_suffix_scan_compiles_for_v5e(one_chip, op):
    """The chunked engine's carry refresh (suffix scan over the chunk)."""
    x = _spec((8, 4096), jnp.float32, one_chip)
    _assert_kernel(suffix_scan_pallas.lower(x, op=op, interpret=False))
