"""Compile the solver's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles for a `v5e:2x2` topology
that is described, not attached, so the TPU compiler refuses here what
interpret mode cannot show (tiling, VMEM, Mosaic layout checks). The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one given this file loads
the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.solver import (
    CircuitParams,
    SolveOptions,
    solve_crossbar,
    suggest_iters,
)
from repro.kernels.gs_fused.kernel import gs_fused_nb
from repro.kernels.gs_fused.ops import fused_lane_block
from repro.kernels.imac_mvm.kernel import imac_mvm_padded
from repro.kernels.tridiag.kernel import LANES, tridiag_nb


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent
    compilation cache off (a TPU executable written here could not be
    read back without the chip)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _shape(sharding, *dims):
    return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("size", [32, 128, 256])
def test_gs_fused_compiles(one_chip, size):
    lb = fused_lane_block(size, size)
    assert lb >= 1
    batch = 2 * lb
    mn = _shape(one_chip, batch, size, size)
    scalar = _shape(one_chip, batch, 1, 1)
    compiled = gs_fused_nb.lower(
        mn, mn, mn, mn, mn, mn, mn, scalar, scalar, scalar, mn,
        m=size, n=size, sweeps=suggest_iters(size, size), lane_block=lb,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [32, 512])
def test_tridiag_compiles(one_chip, n):
    x = _shape(one_chip, n, 2 * LANES)
    compiled = tridiag_nb.lower(x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_imac_mvm_compiles(one_chip):
    compiled = imac_mvm_padded.lower(
        _shape(one_chip, 256, 512), _shape(one_chip, 512, 256),
        dac_bits=8, levels=16, bm=128, bn=128, bk=128,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["pallas", "fused"])
def test_batched_tiles_solve_compiles(one_chip, backend):
    """The whole jitted solve at the paper's batched-tiles shape
    (104 tiles x 64 samples of 32x32), kernels compiled, not interpreted."""
    tiles, size, batch = 104, 32, 64
    cp = CircuitParams(gs_iters=suggest_iters(size, size))
    options = SolveOptions(backend=backend, interpret=False)
    fn = jax.jit(
        lambda g, v: solve_crossbar(g[None], v, cp, options=options).i_out
    )
    compiled = fn.lower(
        _shape(one_chip, tiles, size, size),
        _shape(one_chip, batch, tiles, size),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
