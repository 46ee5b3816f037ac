"""The served codec kernels compile for a TPU v5e at smollm-360m widths.

Nothing runs: each test lowers and compiles for a *described* v5e chip, so
the TPU compiler refuses here what it would refuse on the chip (block
shapes off the (8, 128) tiling, casts Mosaic lacks, VMEM overruns).  Widths
are smollm-360m's: 32 layers x (K, V) lanes of 5 KV heads x 64 channels,
256-token chunks in groups of 10, so G = 26 anchor groups per chunk; G = 32
covers a group count that is a multiple of 8.

The topology is described inside a module fixture (one worker loads the
TPU compiler library and keeps it), and the persistent compilation cache is
off around these compiles: an entry written for a described chip cannot be
read back without one.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import codec as kvcodec
from repro.core import gop
from repro.kernels.kvquant import kv_dequant_tokens_pallas, kv_lossless_tokens_pallas

L, C, GROUP, QMAX = 32, 5 * 64, 10, 127
CHUNK = 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("groups", [26, 32])
def test_dequant_tokens_kernel_compiles(one_chip, groups):
    rows = 3 * L * 2  # three chunks' (layer, K/V) rows
    compiled = jax.jit(
        lambda d, a, b: kv_dequant_tokens_pallas(d, a, b, qmax=QMAX)
    ).lower(
        _spec(one_chip, (rows, groups, GROUP - 1, C), jnp.uint16),
        _spec(one_chip, (rows, groups, C), jnp.float32),
        _spec(one_chip, (rows,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("groups", [26, 32])
def test_lossless_tokens_kernel_compiles(one_chip, groups):
    rows = 3 * L * 2
    compiled = jax.jit(kv_lossless_tokens_pallas).lower(
        _spec(one_chip, (rows, groups, GROUP - 1, C), jnp.uint16),
        _spec(one_chip, (rows, groups, C), jnp.uint16),
        _spec(one_chip, (rows, groups), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_assemble_chunks_runs_the_kernels(one_chip):
    """The served assemble step over a mixed run (level 0, a lossy level,
    level 0) compiles with the Pallas kernels in it."""
    layout = gop.make_layout(CHUNK, GROUP)
    G, D = layout.n_anchors, layout.n_deltas
    assert G == 26
    lossless = (True, False, True)
    n, n_lossy, lanes = len(lossless), lossless.count(False), L * 2 * C
    compiled = kvcodec._assemble_chunks.lower(
        _spec(one_chip, (n * lanes, G), jnp.uint16),
        _spec(one_chip, (n * lanes, D), jnp.uint16),
        _spec(one_chip, (n, L, 2, G), jnp.float32),
        _spec(one_chip, (n_lossy, L, 2), jnp.float32),
        shape_meta=(L, C, GROUP, QMAX, tuple((CHUNK, G, D, ll) for ll in lossless)),
        out_dtype=np.dtype(jnp.bfloat16),
        use_pallas=True,
        interpret=False,
        block_groups=8,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
