"""Compile the main path for a described TPU v5e (no chip attached).

The TPU compiler is installed next to the CPU runtime, so the kernels and
the fused chunk round compile here for ``v5e:2x2`` exactly as they would on
the chip: a slice not aligned to the tiling, a kernel that asks for too much
VMEM or a program that does not fit the device is refused here, at no chip
time.  Nothing runs, so nothing here says anything about results or times.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.chunked_jit import _chunk_round
from repro.core.dualtree import PAIR_RUNGS, _pair_hist_kernel
from repro.core.toptree import slab_len, suggest_height
from repro.kernels.knn_scan import leaf_scan_pallas

# chip_smoke.py phase 1: 10M x 10-D catalog, 1,048,576 queries, k = 10
SMOKE_N, SMOKE_D_PAD, SMOKE_M, K, TQ = 10_000_000, 16, 1_048_576, 10, 128
SMOKE_H = suggest_height(SMOKE_N)
SMOKE_L_PAD = slab_len(-(-SMOKE_N // (1 << SMOKE_H)))
# chip_smoke.py phase 4: 50k 3-D positions, height 8, 9 edges
PAIR_N, PAIR_H, PAIR_D_PAD, PAIR_EDGES = 50_000, 8, 8, 9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_smoke_slab_is_whole_kernel_tiles():
    assert SMOKE_L_PAD == 5120 and SMOKE_L_PAD % 512 == 0


@pytest.mark.parametrize("l_pad", [4096, SMOKE_L_PAD])
def test_leaf_scan_compiles(one_chip, l_pad):
    fn = jax.jit(functools.partial(
        leaf_scan_pallas, k=K, tq=TQ, selection="two_phase"
    ))
    compiled = fn.lower(
        _sds((8, TQ, SMOKE_D_PAD), jnp.float32, one_chip),
        _sds((8, l_pad, SMOKE_D_PAD), jnp.float32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chunk_round_compiles_at_smoke_shapes(one_chip):
    m, c, nl = SMOKE_M, 1 << SMOKE_H, 1 << SMOKE_H
    i32, f32 = jnp.int32, jnp.float32
    args = (
        _sds((m,), i32, one_chip),                       # node
        _sds((m,), i32, one_chip),                       # fromc
        _sds((m,), i32, one_chip),                       # leaf
        _sds((m + 1, K), f32, one_chip),                 # knn_d
        _sds((m + 1, K), i32, one_chip),                 # knn_i
        _sds((2,), i32, one_chip),                       # counts
        _sds((m, SMOKE_D_PAD), f32, one_chip),           # qpad
        _sds((c, SMOKE_L_PAD, SMOKE_D_PAD), f32, one_chip),  # dev_slab
        _sds((), i32, one_chip),                         # lo
        _sds((nl,), i32, one_chip),                      # leaf_start
        _sds((nl,), i32, one_chip),                      # leaf_size
        _sds((nl,), i32, one_chip),                      # split_dim
        _sds((nl,), f32, one_chip),                      # split_val
        _sds((1, 1), f32, one_chip),                     # q_scale
        _sds((1, 1), f32, one_chip),                     # q_offset
        _sds((1, 1), jnp.uint8, one_chip),               # q_dead
        _sds((), f32, one_chip),                         # qeps
    )
    compiled = _chunk_round.lower(
        *args, k=K, tq=TQ, first_leaf_heap=nl, ub=8, backend="pallas",
        quant=False, affine=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pair_hist_kernel_compiles_at_top_rung(one_chip):
    nl = 1 << PAIR_H
    lp = slab_len(-(-PAIR_N // nl))
    rung = PAIR_RUNGS[-1]
    slab = _sds((nl, lp, PAIR_D_PAD), jnp.float32, one_chip)
    ids = _sds((rung,), jnp.int32, one_chip)
    compiled = _pair_hist_kernel.lower(
        slab, slab, ids, ids, ids, ids,
        _sds((PAIR_EDGES,), jnp.float32, one_chip),
    ).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("n_edges", [PAIR_EDGES, 65])
def test_pair_hist_kernel_counts_without_scatter(one_chip, n_edges):
    """Bins are counted by comparison at the 2PCF cell's batch shape (256-row
    leaves): no scatter, and no temporary beyond one batch's distances at any
    edge count."""
    rung, lp = PAIR_RUNGS[-1], 256
    slab = _sds((512, lp, PAIR_D_PAD), jnp.float32, one_chip)
    ids = _sds((rung,), jnp.int32, one_chip)
    compiled = _pair_hist_kernel.lower(
        slab, slab, ids, ids, ids, ids,
        _sds((n_edges,), jnp.float32, one_chip),
    ).compile()
    # an op, not this test's name in the source locations
    assert not re.search(r"\bscatter", compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes <= rung * lp * lp * 4
