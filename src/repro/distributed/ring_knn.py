"""Ring kNN: the paper's compute/copy overlap, mapped onto the ICI.

Paper §3.2 hides host->device chunk copies behind brute-force compute using
two chunk buffers and two command queues.  On a TPU mesh the analogous
resource is the inter-chip interconnect: reference shards stay resident
(HBM is the new "host memory", sharded), and it is the *query blocks* —
orders of magnitude smaller — that rotate around the ring with
``lax.ppermute`` while each chip scans its resident shard.  Each ring step
is exactly the paper's 3-phase pipeline:

  (1) Brute: scan resident reference shard against the in-flight query block
  (2) Copy : ppermute the (query block, running top-k) to the next chip
  (3) Wait : implicit — XLA overlaps (1) and (2) per step

After P steps every query block has met every reference shard and is back
home.  Transfer per step per chip = |q block| + |top-k| bytes, independent
of n — the property that lets the reference set scale to "hundreds of
billions of points" (paper §5, future work).

This module is the *brute* ring (baseline + roofline cell for the kNN
service); ``distributed/forest.py`` composes the same idea with per-shard
buffer k-d trees.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.kernels.ref import INVALID_DIST

__all__ = ["ring_knn_brute", "ring_knn_shardmap_fn"]


REF_TILE = 65536  # distance tile = q_block x REF_TILE (VMEM/HBM-bounded)


def _tile_merge(q, x, base, best_d, best_i, k):
    """One distance tile + running top-k merge."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    xn = jnp.sum(x * x, axis=-1)[None, :]
    cross = jax.lax.dot_general(
        q, x, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dist = jnp.maximum(qn - 2.0 * cross + xn, 0.0)
    idx = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1) + base
    cd = jnp.concatenate([best_d, dist], axis=1)
    ci = jnp.concatenate([best_i, idx], axis=1)
    neg, sel = jax.lax.top_k(-cd, k)
    return -neg, jnp.take_along_axis(ci, sel, axis=1)


def _scan_merge(q, x, base, best_d, best_i, k, ref_tile: int = REF_TILE):
    """Brute scan of local refs vs in-flight query block + top-k merge,
    tiled over the reference shard so the [mb, nb] distance matrix is never
    materialized (paper's chunk streaming, HBM->VMEM edition)."""
    nb = x.shape[0]
    if nb <= ref_tile:
        return _tile_merge(q, x, base, best_d, best_i, k)
    n_tiles = (nb + ref_tile - 1) // ref_tile
    pad = n_tiles * ref_tile - nb
    if pad:
        from repro.kernels.ref import PAD_COORD

        x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=PAD_COORD)

    def body(t, carry):
        bd, bi = carry
        xt = jax.lax.dynamic_slice_in_dim(x, t * ref_tile, ref_tile, 0)
        return _tile_merge(q, xt, base + t * ref_tile, bd, bi, k)

    best_d, best_i = jax.lax.fori_loop(0, n_tiles, body, (best_d, best_i))
    return best_d, best_i


def ring_knn_shardmap_fn(k: int, axis: str, pad_coord_guard: bool = True):
    """Returns the per-device shard_map body for the query-rotation ring.

    Body signature: (q_local f32[mb, d], refs_local f32[nb, d]) ->
    (sq_dists f32[mb, k], global idx i32[mb, k]).
    """

    def body(q_local: jnp.ndarray, refs_local: jnp.ndarray):
        p = jax.lax.axis_size(axis)
        me = jax.lax.axis_index(axis)
        nb = refs_local.shape[0]
        mb = q_local.shape[0]

        best_d = jnp.full((mb, k), INVALID_DIST, jnp.float32)
        best_i = jnp.full((mb, k), -1, jnp.int32)

        def step(s, carry):
            q, bd, bi = carry
            # Indices are global offsets of the refs resident on THIS chip.
            base = me * nb
            bd, bi = _scan_merge(q, refs_local, base, bd, bi, k)
            # Phase (2): rotate block + running top-k to the next chip.
            perm = [(i, (i + 1) % p) for i in range(p)]
            q = jax.lax.ppermute(q, axis, perm)
            bd = jax.lax.ppermute(bd, axis, perm)
            bi = jax.lax.ppermute(bi, axis, perm)
            return q, bd, bi

        q, best_d, best_i = jax.lax.fori_loop(
            0, p, step, (q_local, best_d, best_i)
        )
        # After p rotations every block is home again.
        return best_d, best_i

    return body


@functools.partial(jax.jit, static_argnames=("k", "axis", "mesh"))
def ring_knn_brute(
    queries: jnp.ndarray,     # f32[m, d] (global)
    refs: jnp.ndarray,        # f32[n, d] (global)
    *,
    k: int,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-device exact kNN with reference shards resident, queries ringed.

    ``queries`` and ``refs`` are sharded on ``axis`` along dim 0 (m and n
    must divide the axis size).  Other mesh axes replicate (callers shard
    the query set over data/pod axes outside, paper-style).
    """
    body = ring_knn_shardmap_fn(k, axis)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None)),
    )
    return fn(queries, refs)
