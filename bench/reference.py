"""The plain reference: exact filtered top-k by brute force.

It imports nothing of the program.  A row passes a DNF interval predicate
``(T, A)`` when, for some term, every attribute lies in ``[lo, hi]`` (the
semantics of ``core/predicate.evaluate``).  The device scan ranks every row
of the corpus in blocks, with its one matrix product at an explicit
precision, and keeps ``depth`` candidates per query; :func:`truth` then
re-scores those candidates in float64 on the host and keeps the best ``k``.

``precision`` is ``"highest"`` (float32, what the configurations state) or
``"high"``: the three bfloat16 passes ``hi*hi + hi*lo + lo*hi`` of a
float32 product, written out so that it computes the same on every
platform.  ``"high"`` is the control's precision, the step below float32
at ``highest``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: rows of the corpus scored per block of the scan
BLOCK = 32768


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def matmul(a, b, precision: str):
    """``a @ b.T`` in float32 at ``precision`` (``"highest"`` or ``"high"``)."""
    if precision == "highest":
        return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)
    dot = lambda u, v: jnp.dot(u, v.T, preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def passes(attrs, lo, hi):
    """``attrs (R, A)`` against ``lo, hi (T, A)`` -> ``(R,)`` bool."""
    a = attrs[:, None, :]
    return jnp.any(jnp.all((a >= lo) & (a <= hi), axis=-1), axis=-1)


@functools.partial(jax.jit, static_argnames=("depth", "metric", "precision"))
def scan(vectors, attrs, queries, lo, hi, *, depth: int, metric: str, precision: str):
    """Best ``depth`` passing rows per query: ``(ids (B, depth) int32,
    dists (B, depth) float32)``, ``+inf`` / ``-1`` where fewer pass."""
    n, d = vectors.shape
    b = queries.shape[0]
    pad = (-n) % BLOCK
    vp = jnp.pad(vectors, ((0, pad), (0, 0))).reshape(-1, BLOCK, d)
    ap = jnp.pad(attrs, ((0, pad), (0, 0))).reshape(-1, BLOCK, attrs.shape[1])
    q2 = jnp.sum(queries * queries, axis=-1, keepdims=True)

    def block(carry, blk):
        best_d, best_i = carry
        vb, ab, base = blk
        qx = matmul(queries, vb, precision)
        dist = q2 + jnp.sum(vb * vb, axis=-1)[None, :] - 2.0 * qx if metric == "l2" else -qx
        row = base + jnp.arange(BLOCK, dtype=jnp.int32)
        ok = jax.vmap(passes, in_axes=(None, 0, 0))(ab, lo, hi) & (row < n)[None, :]
        dist = jnp.where(ok, dist, jnp.inf)
        cat_d = jnp.concatenate([best_d, dist], axis=1)
        cat_i = jnp.concatenate([best_i, jnp.broadcast_to(row, (b, BLOCK))], axis=1)
        neg, sel = jax.lax.top_k(-cat_d, depth)
        return (-neg, jnp.take_along_axis(cat_i, sel, axis=1)), None

    init = (jnp.full((b, depth), jnp.inf, jnp.float32), jnp.full((b, depth), -1, jnp.int32))
    bases = jnp.arange(vp.shape[0], dtype=jnp.int32) * BLOCK
    (best_d, best_i), _ = jax.lax.scan(block, init, (vp, ap, bases))
    best_i = jnp.where(jnp.isfinite(best_d), best_i, -1)
    return best_i, best_d


def distances64(x_rows: np.ndarray, q: np.ndarray, metric: str) -> np.ndarray:
    """Float64 distances of ``x_rows (R, d)`` to ``q (d,)``."""
    x64, q64 = x_rows.astype(np.float64), q.astype(np.float64)
    if metric == "l2":
        return ((x64 - q64) ** 2).sum(axis=1)
    return -(x64 @ q64)


def truth(x: np.ndarray, cand_ids: np.ndarray, q: np.ndarray, k: int, metric: str):
    """The exact top-``k`` of one query from its scan candidates, re-scored
    in float64: ``(ids (<= k,), dists float64 (<= k,))``."""
    ids = cand_ids[cand_ids >= 0]
    d64 = distances64(x[ids], q, metric)
    order = np.argsort(d64, kind="stable")[:k]
    return ids[order], d64[order]
