"""Fused ADC scoring Pallas TPU kernel — the quantized tier's query
hot-spot (Algorithm 4's VISIT, and the PREFILTER / delta brute scans, over
uint8 PQ codes instead of float32 rows).

Asymmetric distance computation turns one d-dim distance into ``m`` table
lookups.  The per-query (m, ks) table is built once per query outside the
kernel (``quant/encode.build_luts`` over ``ref.adc_lut``, which carries
the metric: squared-L2 tables, or negated inner product over raw codes —
see quant/params.py), so the kernel and the jnp oracle look up the very
same table.  The kernel runs the shared row-gather pipeline
(kernels/row_gather.py):

  * **blocked code rows** — the candidates' uint8 code rows (m bytes
    each instead of 4·d) are gathered by XLA and enter as (RB, m) row
    blocks: Mosaic refuses a one-row DMA slice of a narrow uint8 table.
  * **table lookups on the VPU** — the dynamic per-code gather is a
    one-hot select over each (1, ks) table row (TPU vector units have no
    arbitrary-index VMEM gather).  Adding the masked-out zeros is exact in
    f32 and the m partial values fold through ``ref.chain_sum_m``, so the
    result is bitwise identical to the oracle's take-then-sum.
  * **predicate masking** — the candidates' attribute rows evaluate the
    DNF bounds exactly as kernels/filter_distance.py; masked slots point
    at the sentinel row N and yield +inf / false.

VMEM working set per step: m·ks (table) + 2·RB·(m + A) + 2·T·A — e.g.
m=16, ks=256: 16 KB of table.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .filter_distance import ROWS_PER_STEP
from .interpret import default_interpret
from .row_gather import gather_score


def pq_score(
    codes: jax.Array,  # (N + 1, m) uint8 PQ codes (row N = sentinel)
    attrs: jax.Array,  # (N + 1, A)
    idx: jax.Array,  # (V,) int32 candidate ids (may repeat / sentinel)
    mask: jax.Array,  # (V,) bool visit mask
    lut: jax.Array,  # (m, ks) the query's ADC table
    lo: jax.Array,  # (T, A)
    hi: jax.Array,  # (T, A)
    *,
    interpret: bool | None = None,
):
    """Returns (dists (V,) f32, +inf where masked; passed (V,) bool) — the
    one-lane case of :func:`pq_score_batch`."""
    dists, passed = pq_score_batch(
        codes, attrs, idx[None], mask[None], lut[None], lo[None], hi[None],
        interpret=interpret,
    )
    return dists[0], passed[0]


def pq_score_batch(
    codes: jax.Array,  # (N + 1, m) uint8 PQ codes (row N = sentinel)
    attrs: jax.Array,  # (N + 1, A)
    idx: jax.Array,  # (B, V) int32 candidate ids
    mask: jax.Array,  # (B, V) bool valid-slot mask
    luts: jax.Array,  # (B, m, ks) per-lane ADC tables
    lo: jax.Array,  # (B, T, A) per-lane DNF bounds
    hi: jax.Array,  # (B, T, A)
    *,
    interpret: bool | None = None,
):
    """Batched :func:`pq_score`: one grid-(B, V / RB) call for a whole
    micro-batch; each lane's table block is fetched once per lane.

    Returns (dists (B, V) f32, +inf where masked; passed (B, V) bool).
    """
    if interpret is None:
        interpret = default_interpret()
    n = codes.shape[0] - 1
    safe = jnp.where(mask, jnp.clip(idx, 0, n), n)
    return gather_score(
        safe, codes, attrs, luts, lo, hi,
        score="adc", emit="pass", rb=ROWS_PER_STEP, interpret=interpret,
    )
