"""Fused gather + distance + predicate Pallas TPU kernel — the Compass
query hot-spot (Algorithm 4's VISIT over a batch of candidate ids), and
its batched form, the planner's PREFILTER run scan.

Both entry points run the shared row-gather pipeline
(kernels/row_gather.py): candidate ids are scalar-prefetched, the rows of
``vectors`` are DMA'd from HBM into a double-buffered VMEM scratch ``RB``
rows per grid step (gathered by XLA where ``d`` is not a multiple of
128), the distance (squared L2 or negated inner product —
static ``metric``, shared expression ``ref.row_distance``) reduces on the
VPU against the VMEM-resident query, and the DNF interval predicate
evaluates on the candidates' attribute rows (gathered by XLA, ``RB`` rows
per block).  Masked slots point at the sentinel row N, yielding +inf
distance and pass=false — exactly the reference semantics in
kernels/ref.py.

VMEM working set per step: 2·RB·(d + A) + d + 2·T·A floats — e.g. RB=8,
d=128, A=4: ~8.5 KB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .interpret import default_interpret
from .row_gather import gather_score

#: rows gathered per grid step by the scan kernels
ROWS_PER_STEP = 8


def filter_distance(
    vectors: jax.Array,  # (N + 1, d) padded corpus (row N = sentinel)
    attrs: jax.Array,  # (N + 1, A)
    idx: jax.Array,  # (V,) int32 candidate ids (may repeat / sentinel)
    mask: jax.Array,  # (V,) bool visit mask
    q: jax.Array,  # (d,) query
    lo: jax.Array,  # (T, A)
    hi: jax.Array,  # (T, A)
    *,
    metric: str = "l2",
    interpret: bool | None = None,
):
    """Returns (dists (V,) f32, +inf where masked; passed (V,) bool) — the
    one-lane case of :func:`filter_distance_batch`.

    ``metric``: "l2" (squared L2) or "ip" (negated inner product).  The
    interpret default comes from kernels/interpret.py.
    """
    dists, passed = filter_distance_batch(
        vectors, attrs, idx[None], mask[None], q[None], lo[None], hi[None],
        metric=metric, interpret=interpret,
    )
    return dists[0], passed[0]


def filter_distance_batch(
    vectors: jax.Array,  # (N + 1, d) padded corpus (row N = sentinel)
    attrs: jax.Array,  # (N + 1, A)
    idx: jax.Array,  # (B, V) int32 candidate ids (may repeat / sentinel)
    mask: jax.Array,  # (B, V) bool valid-slot mask
    queries: jax.Array,  # (B, d) per-lane queries
    lo: jax.Array,  # (B, T, A) per-lane DNF bounds
    hi: jax.Array,  # (B, T, A)
    *,
    metric: str = "l2",
    interpret: bool | None = None,
):
    """Batched :func:`filter_distance` for the planner's PREFILTER run
    scan: one ``pallas_call`` over grid (B, V / RB) for the whole
    micro-batch instead of a vmapped per-query call; the per-lane query
    and bounds blocks re-DMA only when the lane changes.

    Returns (dists (B, V) f32, +inf where masked; passed (B, V) bool).
    """
    if interpret is None:
        interpret = default_interpret()
    n = vectors.shape[0] - 1
    safe = jnp.where(mask, jnp.clip(idx, 0, n), n)
    return gather_score(
        safe, vectors, attrs, queries[:, None, :], lo, hi,
        score=metric, emit="pass", rb=ROWS_PER_STEP, interpret=interpret,
    )
