"""Pure-jnp oracles for the kNN leaf-scan kernel.

Two references:

* ``leaf_scan_ref`` — same work-unit contract as the Pallas kernel
  (``kernels/knn_scan.py``): per work unit, scan a padded leaf slab against a
  padded query tile and return the k smallest squared distances + *local*
  slab indices.  Uses the same ||q||^2 - 2 q.x + ||x||^2 decomposition so the
  kernel can be compared with tight tolerances.
* ``knn_brute_ref`` — exact full brute-force kNN (direct squared differences)
  used as the end-to-end ground truth for the whole tree engine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["leaf_scan_ref", "knn_brute_ref", "PAD_COORD", "INVALID_DIST"]

# Padding coordinate for slab rows that do not hold a real point.  Large but
# finite so the distance decomposition stays NaN-free (see kernel docstring);
# any distance >= INVALID_DIST is treated as "no candidate" by callers.
PAD_COORD = 1.0e18
INVALID_DIST = 1.0e30


def _decomposed_sq_dists(q: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """[TQ, d] x [L, d] -> [TQ, L] squared distances via the MXU-friendly
    decomposition (matches the kernel's arithmetic)."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)            # [TQ, 1]
    xn = jnp.sum(x * x, axis=-1)[None, :]                  # [1, L]
    cross = jax.lax.dot_general(
        q, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return jnp.maximum(qn - 2.0 * cross + xn, 0.0)


@functools.partial(jax.jit, static_argnames=("k",))
def leaf_scan_ref(q: jnp.ndarray, leaf_pts: jnp.ndarray, *, k: int):
    """Oracle for the leaf-scan work-unit kernel.

    Args:
      q:        f32[W, TQ, d_pad] padded query tiles.
      leaf_pts: f32[W, L_pad, d_pad] padded leaf slabs (PAD_COORD rows).
      k:        neighbors per query.

    Returns:
      (dists f32[W, TQ, k] ascending squared distances,
       idx   i32[W, TQ, k] local slab indices)
    """
    def per_unit(qu, xu):
        d2 = _decomposed_sq_dists(qu, xu)                   # [TQ, L]
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx.astype(jnp.int32)

    return jax.vmap(per_unit)(q, leaf_pts)


@functools.partial(jax.jit, static_argnames=("k",))
def knn_brute_ref(queries: jnp.ndarray, points: jnp.ndarray, *, k: int):
    """Exact brute-force kNN: direct (q - x)^2 accumulation.

    Returns (sq_dists f32[m, k], idx i32[m, k]) ascending.
    """
    d2 = jnp.sum(
        (queries[:, None, :] - points[None, :, :]) ** 2, axis=-1
    )                                                        # [m, n]
    neg, idx = jax.lax.top_k(-d2, k)
    return -neg, idx.astype(jnp.int32)
