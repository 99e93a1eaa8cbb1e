"""Compile the served Pallas kernels and graph halves for a TPU v5e.

Interpret mode (every other kernel test) cannot vet what Mosaic, the TPU
compiler behind Pallas, refuses: casts, primitives without a lowering, 3-D
dot shapes, narrow integer vectors, or more fast memory than a kernel may
use.  Here the installed TPU compiler compiles each served kernel with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology --
no chip attached, nothing runs -- at the widths the engines dispatch.  A
kernel over its VMEM limit fails to compile; ``memory_analysis()`` bounds
the program's HBM footprint by the chip's 16 GiB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers each
import every test file.  The persistent compile cache is off around these
compiles -- their entries could not be read back without a chip.
"""

from __future__ import annotations

import functools
import os

import numpy as np
import pytest

ROWS = 16_384  # TopKEngine.MAX_BUCKET: the largest gathered-row bucket
ARENA_ROWS = 1 << 20  # resident blocks of a ~130M-posting arena
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler, or its library is held
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


def _kernel_cases():
    """name -> (fn, [(shape, dtype)]) for the seven served pallas_calls."""
    import jax.numpy as jnp

    from repro.kernels.blockmax_pivot.kernel import pivot_select_blocks
    from repro.kernels.bm25_score.kernel import (
        NORM_LEVELS,
        bm25_score_blocks,
        bm25_score_probe_blocks,
    )
    from repro.kernels.ef_search.kernel import ef_search_blocks
    from repro.kernels.pivot_score.kernel import pivot_score_blocks
    from repro.kernels.vbyte_decode.kernel import (
        BLOCK_BYTES,
        BLOCK_VALS,
        BM,
        decode_blocks,
        decode_search_blocks,
    )

    i32, u8, f32 = jnp.int32, jnp.uint8, jnp.float32
    v = ((ROWS, BLOCK_VALS), i32)
    b = ((ROWS, BLOCK_BYTES), u8)
    fv = ((ROWS, BLOCK_VALS), f32)
    tile = ((BM, NORM_LEVELS), f32)

    def compiled(fn):
        return functools.partial(fn, interpret=False)

    def pivot_score(qb, qmin, meta, fl, fd, nq, idf, table):
        return pivot_score_blocks(
            qb, qmin, meta, fl, fd, nq, idf, table, 2.2, interpret=False
        )

    return {
        "decode_blocks": (compiled(decode_blocks), [v, b]),
        "decode_search_blocks": (compiled(decode_search_blocks), [v, b, v]),
        "bm25_score_blocks": (compiled(bm25_score_blocks), [v, b, v, tile, fv]),
        "bm25_score_probe_blocks": (
            compiled(bm25_score_probe_blocks),
            [v, b, v, b, v, tile, v, fv],
        ),
        "ef_search_blocks": (compiled(ef_search_blocks), [v, v]),
        "pivot_select_blocks": (compiled(pivot_select_blocks), [v, v, v]),
        "pivot_score_blocks": (
            pivot_score,
            [
                v, v, v,
                ((ARENA_ROWS, BLOCK_VALS), i32),
                ((ARENA_ROWS, BLOCK_BYTES), u8),
                ((ARENA_ROWS, BLOCK_VALS), u8),
                ((ARENA_ROWS,), f32),
                ((NORM_LEVELS,), f32),
            ],
        ),
    }


KERNELS = (
    "decode_blocks",
    "decode_search_blocks",
    "bm25_score_blocks",
    "bm25_score_probe_blocks",
    "ef_search_blocks",
    "pivot_select_blocks",
    "pivot_score_blocks",
)
GRAPHS = (
    "locate_graph",
    "decode_search_graph",
    "ef_search_graph",
    "pivot_graph",
    "score_probe_graph",
    "score_rows_graph",
    "pivot_score_graph",
)


def _compile(fn, specs, sharding):
    import jax

    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in specs]
    return jax.jit(fn).lower(*args).compile()


def _check_fits(compiled):
    ma = compiled.memory_analysis()
    used = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
    )
    assert 0 < used < V5E_HBM_BYTES, used


def test_kernel_and_graph_lists_cover_the_registries():
    from repro.core.engine_core import GRAPH_CONTRACTS

    assert set(GRAPHS) == set(GRAPH_CONTRACTS)
    assert set(KERNELS) == set(_kernel_cases())


@pytest.mark.parametrize("name", KERNELS)
def test_served_kernel_compiles_for_v5e(one_chip, name):
    fn, specs = _kernel_cases()[name]
    compiled = _compile(fn, specs, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    _check_fits(compiled)


@pytest.mark.parametrize("name", GRAPHS)
def test_graph_half_compiles_for_v5e(one_chip, name):
    from repro.analyze.hlo_check import graph_specs

    fn, args = graph_specs("pallas", nr=ROWS, nb=ARENA_ROWS)[name]
    specs = [(np.shape(a), a.dtype) for a in args]
    compiled = _compile(fn, specs, one_chip)
    # locate is plain XLA; every other half dispatches a Pallas kernel
    assert ("tpu_custom_call" in compiled.as_text()) == (name != "locate_graph")
    _check_fits(compiled)
