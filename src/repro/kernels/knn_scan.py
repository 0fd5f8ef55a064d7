"""Pallas TPU kernel: buffered brute-force kNN leaf scan (ProcessAllBuffers).

This is the paper's compute hot spot (§2.4, §3.2): every query buffered at a
leaf is compared against the leaf's contiguous reference slab, brute force.
On the GPU the win comes from coalesced/cached global loads; the TPU-native
re-think is:

  * the cross term of ||q - x||^2 = ||q||^2 - 2 q.x + ||x||^2 is a
    [TQ, d] x [d, TX] matmul -> MXU systolic work instead of VPU subtract/
    square loops;
  * BlockSpec tiling keeps a [TQ, d] query tile resident in VMEM while the
    leaf slab streams through in [TX, d] tiles (HBM -> VMEM), the exact
    analogue of the paper's chunked leaf streaming one level down the memory
    hierarchy;
  * the running top-k lives in VMEM scratch across the slab-tile grid
    dimension, so distance tiles are never written back to HBM.

Grid: (W work units, L_pad // TX slab tiles); the slab-tile dimension is the
inner ("arbitrary") one so scratch carries across it.

k-selection comes in two forms (``selection=``):

  * ``two_phase`` (default on compiled TPU): per slab tile, (1) a partial
    top-k over the fresh [TQ, TX] distance tile via k min-extraction passes,
    then (2) a SINGLE-PASS merge of the two sorted k-lists (tile top-k vs
    VMEM scratch) by rank arithmetic — each element's merged rank is its own
    position plus the count of smaller elements in the other list, so the
    merge is O(k^2) data-parallel compare/accumulate ops with no sequential
    min-extraction over the carried scratch.  Per-tile VPU work drops from
    the min-trick's k passes over width (k + TX) to k passes over TX plus an
    O(k^2) merge, and the scratch list is never re-scanned.
  * ``min_trick`` (interpret-mode fallback): the original k min-extraction
    passes over the concatenated [TQ, k + TX] candidates.  Uses only min
    reductions + masking, the most conservative lowering.

Both forms move values around without re-deriving them and break ties toward
the lower slab index (``lax.top_k`` order), so they are bit-identical to each
other.  Against ``kernels/ref.py::leaf_scan_ref``, an XLA lowering of the same
arithmetic, the contract is a tolerance: distances agree within
``rtol = atol = 1e-5`` (two lowerings of one matmul may round differently,
and by more on a chip than in interpret mode), and the selected neighbours
agree as sets up to ties within that tolerance.  The cross term runs at
``Precision.HIGHEST``: a default-precision f32 dot may run as one bf16 pass
on a TPU, an error far larger than the gap between neighbours.

Work-unit contract (shared with kernels/ref.py::leaf_scan_ref):
  q         f32[W, TQ, d_pad]   padded query tiles (pad rows = 0.0)
  leaf_pts  f32[W, L_pad, d_pad] padded slabs (pad rows = PAD_COORD)
  ->        (f32[W, TQ, k] ascending sq-dists, i32[W, TQ, k] local indices)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import INVALID_DIST

__all__ = ["leaf_scan_pallas", "DEFAULT_TQ", "DEFAULT_TX", "SELECTIONS"]

DEFAULT_TQ = 128   # queries per tile (MXU sublane-friendly)
DEFAULT_TX = 512   # slab points per tile (VMEM: 128x512 f32 dist tile = 256KB)
_BIG_I = 2**30  # python int: avoids captured-constant arrays in the kernel

SELECTIONS = ("auto", "two_phase", "min_trick")


def _dist_tile(q, x):
    """[TQ, d] x [TX, d] -> [TQ, TX] squared distances (MXU decomposition)."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)                    # [TQ, 1]
    xn = jnp.sum(x * x, axis=-1)[None, :]                          # [1, TX]
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                              # [TQ, TX]
    return jnp.maximum(qn - 2.0 * cross + xn, 0.0)


def _extract_topk(cand_d, cand_i, k):
    """k min-extraction passes (min reductions + one-hot masking only).

    cand_d/cand_i: [TQ, width].  Returns sorted-ascending ([TQ, k], [TQ, k]);
    ties resolve to the first (lowest-index) position.
    """
    tq, width = cand_d.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)
    out_d, out_i = [], []
    for _ in range(k):
        mn = jnp.min(cand_d, axis=1)                               # [TQ]
        # first position attaining the min (min-trick, no argmin reduce)
        am = jnp.min(jnp.where(cand_d == mn[:, None], pos, _BIG_I), axis=1)
        hit = pos == am[:, None]
        iv = jnp.min(jnp.where(hit, cand_i, _BIG_I), axis=1)
        out_d.append(mn[:, None])
        out_i.append(iv[:, None])
        cand_d = jnp.where(hit, jnp.float32(INVALID_DIST * 100.0), cand_d)
    return jnp.concatenate(out_d, axis=1), jnp.concatenate(out_i, axis=1)


def _rank_merge(a_d, a_i, b_d, b_i, k):
    """Single-pass merge of two sorted-ascending k-lists, keeping the k
    smallest.  a wins ties (carries lower global indices: earlier tiles).

    Merged rank of a[i] = i + |{j : b[j] <  a[i]}|;
    merged rank of b[j] = j + |{i : a[i] <= b[j]}| — a permutation of
    0..2k-1, computed with 2D ops only (k unrolled [TQ, k] compares).
    """
    tq = a_d.shape[0]
    pos_k = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    ra = pos_k
    rb = pos_k
    for j in range(k):
        ra = ra + (b_d[:, j : j + 1] < a_d).astype(jnp.int32)
        rb = rb + (a_d[:, j : j + 1] <= b_d).astype(jnp.int32)
    out_d = jnp.full((tq, k), jnp.float32(INVALID_DIST * 10.0))
    out_i = jnp.full((tq, k), _BIG_I, jnp.int32)
    for j in range(k):
        hit_a = ra[:, j : j + 1] == pos_k                          # [TQ, k]
        out_d = jnp.where(hit_a, a_d[:, j : j + 1], out_d)
        out_i = jnp.where(hit_a, a_i[:, j : j + 1], out_i)
        hit_b = rb[:, j : j + 1] == pos_k
        out_d = jnp.where(hit_b, b_d[:, j : j + 1], out_d)
        out_i = jnp.where(hit_b, b_i[:, j : j + 1], out_i)
    return out_d, out_i


def _kernel(q_ref, x_ref, out_d_ref, out_i_ref, best_d, best_i, *,
            k, tx, n_tx, selection):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        best_d[...] = jnp.full(best_d.shape, INVALID_DIST * 10.0, jnp.float32)
        best_i[...] = jnp.full(best_i.shape, _BIG_I, jnp.int32)

    q = q_ref[0]                     # [TQ, d_pad]
    x = x_ref[0]                     # [TX, d_pad]
    dist = _dist_tile(q, x)

    tq = q.shape[0]
    local_base = t * tx
    col_idx = jax.lax.broadcasted_iota(jnp.int32, (tq, tx), 1) + local_base

    if selection == "two_phase":
        # phase 1: partial top-k of the fresh tile only (k passes over TX)
        tile_d, tile_i = _extract_topk(dist, col_idx, k)
        # phase 2: single-pass rank merge against the carried scratch;
        # scratch first => ties keep the earlier (lower-index) tile's entry
        new_d, new_i = _rank_merge(best_d[...], best_i[...], tile_d, tile_i, k)
        best_d[...] = new_d
        best_i[...] = new_i
    else:
        # min_trick: k min-extractions over the full [TQ, k + TX] candidates
        cand_d = jnp.concatenate([best_d[...], dist], axis=1)
        cand_i = jnp.concatenate([best_i[...], col_idx], axis=1)
        new_d, new_i = _extract_topk(cand_d, cand_i, k)
        best_d[...] = new_d
        best_i[...] = new_i

    @pl.when(t == n_tx - 1)
    def _emit():
        out_d_ref[0] = best_d[...]
        out_i_ref[0] = best_i[...]


@functools.partial(
    jax.jit, static_argnames=("k", "tq", "tx", "interpret", "selection")
)
def leaf_scan_pallas(
    q: jnp.ndarray,
    leaf_pts: jnp.ndarray,
    *,
    k: int,
    tq: int = DEFAULT_TQ,
    tx: int = DEFAULT_TX,
    interpret: bool = False,
    selection: str = "auto",
):
    """Tiled Pallas leaf scan.  See module docstring for the contract."""
    w, tq_in, d_pad = q.shape
    w2, l_pad, d_pad2 = leaf_pts.shape
    if w != w2 or d_pad != d_pad2:
        raise ValueError(f"shape mismatch q={q.shape} leaf_pts={leaf_pts.shape}")
    if tq_in % tq != 0 and tq_in != tq:
        # allow a single smaller query tile
        tq = tq_in
    if tq_in != tq:
        raise ValueError(f"TQ dim {tq_in} must equal tile {tq}")
    if l_pad % tx != 0:
        # shrink the slab tile to the padded slab if it is smaller
        if l_pad < tx:
            tx = l_pad
        else:
            raise ValueError(f"L_pad={l_pad} not a multiple of tx={tx}")
    n_tx = l_pad // tx
    if selection not in SELECTIONS:
        raise ValueError(f"selection={selection!r} not in {SELECTIONS}")
    if selection == "auto":
        # two-phase on the compiled path; the min-trick form is the most
        # conservative lowering and stays the interpret-mode fallback
        selection = "min_trick" if interpret else "two_phase"

    kernel = functools.partial(_kernel, k=k, tx=tx, n_tx=n_tx,
                               selection=selection)
    out_shape = (
        jax.ShapeDtypeStruct((w, tq, k), jnp.float32),
        jax.ShapeDtypeStruct((w, tq, k), jnp.int32),
    )
    grid = (w, n_tx)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, d_pad), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, tx, d_pad), lambda i, t: (i, t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, k), lambda i, t: (i, 0, 0)),
            pl.BlockSpec((1, tq, k), lambda i, t: (i, 0, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tq, k), jnp.float32),
            pltpu.VMEM((tq, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, leaf_pts)
