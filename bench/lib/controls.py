"""Controls of the comparison that decides ``correct``: the plain reference
put in the program's place and computed one precision below the one the
configuration states.

Both configurations state float32 with the distance cross term at
``Precision.HIGHEST``.  The step below is ``Precision.HIGH``: three bf16
passes, hi*hi + hi*lo + lo*hi of each operand split as hi = bf16(x),
lo = bf16(x - hi).  A control has to come out as not correct.

Shared arithmetic: later benchmarks add functions and never edit these.
"""

from __future__ import annotations

import functools

import numpy as np

__all__ = ["dot_high", "knn_control", "pair_count_control"]


def _split(x):
    import jax.numpy as jnp

    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def dot_high(a, b):
    """[m, d] x [n, d] -> [m, n] float32 inner products in three bf16
    passes.  On a TPU that is ``Precision.HIGH`` itself: written out in
    bf16 there, XLA's bf16 propagation folds the low halves away and
    leaves one pass.  Elsewhere (the CPU of a test, whose dots ignore the
    precision flag) the three passes are written out."""
    import jax
    import jax.numpy as jnp

    dims = (((1,), (1,)), ((), ()))
    if jax.default_backend() == "tpu":
        return jax.lax.dot_general(a, b, dims,
                                   precision=jax.lax.Precision.HIGH,
                                   preferred_element_type=jnp.float32)
    ah, al = _split(a)
    bh, bl = _split(b)

    def f(x, y):
        return jax.lax.dot_general(x, y, dims,
                                   preferred_element_type=jnp.float32)

    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@functools.lru_cache(maxsize=None)
def _knn_block(k: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(q, x, base):
        qn = jnp.sum(q * q, axis=1)
        xn = jnp.sum(x * x, axis=1)
        d2 = qn[:, None] - 2.0 * dot_high(q, x) + xn[None, :]
        neg, idx = jax.lax.top_k(-d2, k)
        return -neg, idx + base

    return block


def knn_control(points: np.ndarray, queries: np.ndarray, k: int,
                block: int = 1 << 20):
    """Brute-force kNN by the expansion |q|^2 - 2 q.x + |x|^2 with the
    cross term at ``dot_high``; (dists f32[m, k], ids i64[m, k])."""
    import jax.numpy as jnp

    n = points.shape[0]
    block = min(block, n)
    fn = _knn_block(k)
    q = jnp.asarray(queries, jnp.float32)
    best_d, best_i = [], []
    for lo in range(0, n, block):
        x = np.asarray(points[lo:lo + block], np.float32)
        if x.shape[0] < block:       # one shape: pad with far-away rows
            pad = np.full((block - x.shape[0], x.shape[1]), 1e6, np.float32)
            x = np.concatenate([x, pad])
        d2, ids = fn(q, jnp.asarray(x), lo)
        best_d.append(np.asarray(d2))
        best_i.append(np.asarray(ids))
    d2 = np.concatenate(best_d, axis=1)
    ids = np.concatenate(best_i, axis=1).astype(np.int64)
    order = np.argsort(d2, axis=1, kind="stable")[:, :k]
    d2 = np.take_along_axis(d2, order, 1)
    ids = np.take_along_axis(ids, order, 1)
    return np.sqrt(np.maximum(d2, 0.0)).astype(np.float32), ids


@functools.lru_cache(maxsize=None)
def _pc_block():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(a, p, e2):
        an = jnp.sum(a * a, axis=1)
        pn = jnp.sum(p * p, axis=1)
        d2 = (an[:, None] - 2.0 * dot_high(a, p)) + pn[None, :]
        return jnp.stack([jnp.sum(d2 < e, dtype=jnp.int32) for e in e2])

    return block


def pair_count_control(pos: np.ndarray, edge_sq, block: int = 512):
    """All-pairs histogram over the edges sqrt(edge_sq) from float32
    squared distances whose cross term is ``dot_high``; self pairs fall
    below the first edge and drop out of the differences."""
    import jax.numpy as jnp

    n = pos.shape[0]
    block = min(block, n)
    e2 = tuple(float(e) for e in edge_sq)
    fn = _pc_block()
    p = jnp.asarray(pos, jnp.float32)
    cum = np.zeros(len(e2), np.int64)
    for lo in range(0, n, block):
        a = np.asarray(pos[lo:lo + block], np.float32)
        rows = a.shape[0]
        if rows < block:             # one shape: pad rows far outside
            a = np.concatenate(
                [a, np.full((block - rows, a.shape[1]), 1e5, np.float32)])
        c = np.asarray(fn(jnp.asarray(a), p, jnp.asarray(e2, jnp.float32)),
                       np.int64)
        cum += c
    # padded rows are 1e5 away from every real point: beyond every edge
    return np.diff(cum)
