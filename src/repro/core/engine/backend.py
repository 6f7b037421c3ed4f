"""Scoring backends for the Compass execution engine.

A :class:`VisitBackend` answers the score queries the engine makes on
its hot path, and nothing else:

  * ``visit_step``      — Algorithm 4's whole per-step scoring: distance +
    DNF predicate + tombstone mask + queue-admission candidates over a
    fixed-size visit list (the per-step hot spot).  The pallas backend
    runs it as ONE fused kernel (kernels/visit_step.py); ref composes
    ``visit_scores`` + the live gather + the admission select — the exact
    pre-fusion engine sequence, so ``backend="ref"`` stays bitwise
    identical across engine versions.
  * ``visit_scores``    — the unfused distance + predicate evaluation
    (kept public: the planner's probes and the unfused visit path use it).
  * ``centroid_scores`` — B.OPEN / G.OPEN's exact centroid ranking input
    (one blocked scan per query *batch*, hoisted out of the per-query vmap
    so the pallas path gets the cross-query MXU blocking ``ivf_score`` is
    built for; see index.py for why this replaces the paper's cluster
    graph G').

Candidate *generation* (queue management, graph expansion, B+-tree cursors)
stays in the iterators — the NaviX/CHASE lesson that hybrid-query engines
need generation and scoring separable.  Backends agree exactly on
semantics (masked entries score ``+inf`` / ``False``; the same records
pass, the same distances are returned for VISIT), and the parity suite in
tests/test_compass_search.py asserts end-to-end identical ids/dists on its
fixed workloads.  One caveat keeps this short of a universal bit-for-bit
guarantee: ``ivf_score`` computes centroid distances via the
``||q||² - 2q·c + ||c||²`` MXU expansion while the ref path computes
``Σ(c-q)²``, so two *near-equidistant* clusters can swap rank order under
float32 rounding, which may reorder cluster visits on adversarial data.
Result-queue contents are distance-sorted either way; only tie-adjacent
candidate sets can differ, and never for VISIT scoring itself (the
filter_distance kernel evaluates the same f32 ``Σ(v-q)²`` as the ref
gather).

``"ref"`` is the plain-jnp gather path (the original core/search.py math,
moved verbatim).  ``"pallas"`` routes VISIT through the fused
``kernels.visit_step`` TPU kernel (``kernels.filter_distance`` when
``fused_visit=False``) and centroid ranking through ``kernels.ivf_score``;
on CPU the kernels run in Pallas interpret mode (see kernels/ops.py) so
tests exercise the kernel path.  ``"auto"`` resolves to ``"pallas"`` on
TPU and ``"ref"`` elsewhere.

Metrics: every scoring surface takes ``metric`` — "l2" (squared L2) and
"ip" (negated inner product) both run on the kernels; cosine is rewritten
to ip over normalized rows by the driver and never reaches this layer.
The shared per-row expression is ``kernels.ref.row_distance``, so ref and
pallas agree bitwise on VISIT for both metrics.
"""
from __future__ import annotations

from typing import Protocol

import jax
import jax.numpy as jnp

from .. import predicate as P


class VisitBackend(Protocol):
    """Scoring interface consumed by :func:`engine.state.visit`, the
    driver's OPEN step, and the planner's PREFILTER run scan."""

    name: str

    def visit_scores(self, index, q, pred, safe_ids, mask, metric):
        """(dist (V,) f32 with +inf where masked; passing (V,) bool)."""
        ...

    def visit_step(
        self, index, q, pred, safe_ids, mask, metric, fused=True, rows_per_step=None
    ):
        """The fused per-step scoring surface consumed by ``state.visit``:
        returns ``(dist (V,) f32, admit (V,) f32)`` where ``dist`` feeds
        the traversal queues (+inf where masked/sentinel) and ``admit``
        equals ``dist`` for valid, predicate-passing AND live rows, +inf
        otherwise (what the filtered result queue merges).  ``fused=False``
        forces the unfused visit_scores + live + select composition on
        every backend (CompassParams.fused_visit).  ``rows_per_step`` pins
        the fused kernel's block size (ShapePolicy.visit_rb; None =
        autotune); non-kernel backends ignore it — block choice never
        affects results."""
        ...

    def centroid_scores(self, index, queries, metric):
        """Per-cluster distance scores for a query batch: (B, nlist) f32."""
        ...

    def scan_scores(self, index, queries, pred, ids, mask, metric):
        """Batched run-scan scoring for the planner's PREFILTER mode:
        (B, V) candidate ids against (B, d) queries and (B, T, A) predicate
        tensors -> (dist (B, V) f32 with +inf where masked, passing (B, V)
        bool).  Same per-row semantics as visit_scores, hoisted out of the
        per-query vmap so the pallas path gets one blocked problem."""
        ...

    def adc_scores(self, index, lut, pred, safe_ids, mask, metric):
        """Quantized visit scoring: distances come from the query's (m, ks)
        ADC table (quant/encode.build_luts, which carries the metric) over
        ``index.qvecs`` codes instead of the float32 rows — one table for
        both backends.  Sentinel ids are masked-out slots even under a
        true mask.  Returns (dist (V,), passing (V,))."""
        ...

    def scan_scores_quantized(self, index, luts, pred, ids, mask, metric):
        """Batched quantized scan — scan_scores over PQ codes: (B, V) ids,
        (B, m, ks) tables.  Serves the planner's PREFILTER materialization
        and the mutable delta brute scan when the quantized tier is
        active."""
        ...


class RefBackend:
    """Plain jnp gathers — the original search hot path, moved verbatim."""

    name = "ref"

    def visit_scores(self, index, q, pred, safe_ids, mask, metric):
        from ...kernels.ref import row_distance

        vecs = index.vectors[safe_ids]  # (V, d)
        # the one expression the pallas kernels also evaluate per row
        # (kernels/ref.row_distance) — parity is bitwise for l2 and ip
        dist = row_distance(vecs, q[None, :], metric)
        dist = jnp.where(mask, dist, jnp.inf)
        attrs = index.attrs[safe_ids]
        passing = P.evaluate(pred, attrs) & mask
        return dist, passing

    def visit_step(
        self, index, q, pred, safe_ids, mask, metric, fused=True, rows_per_step=None
    ):
        # the pre-fusion engine sequence, verbatim: unfused scoring, then
        # the tombstone AND, then the admission select (state.visit's old
        # body) — the parity oracle for the fused kernel
        dist, passing = self.visit_scores(index, q, pred, safe_ids, mask, metric)
        if index.live is not None:
            passing = passing & index.live[safe_ids]
        return dist, jnp.where(passing, dist, jnp.inf)

    def centroid_scores(self, index, queries, metric):
        if metric == "l2":
            cdiff = index.centroids[None, :, :] - queries[:, None, :]
            return jnp.sum(cdiff * cdiff, axis=-1)
        return -(queries @ index.centroids.T)

    def scan_scores(self, index, queries, pred, ids, mask, metric):
        n = index.n_records
        safe = jnp.where(mask, jnp.clip(ids, 0, n), n).astype(jnp.int32)
        # sentinel ids are masked-out slots even under a true mask (same
        # validity rule as the filter_distance kernels)
        valid = mask & (safe < n)
        from ...kernels.ref import row_distance

        vecs = index.vectors[safe]  # (B, V, d)
        dist = row_distance(vecs, queries[:, None, :], metric)
        dist = jnp.where(valid, dist, jnp.inf)
        attrs = index.attrs[safe]  # (B, V, A)
        passing = jax.vmap(
            lambda lo, hi, at: P.evaluate(P.Predicate(lo, hi), at)
        )(pred.lo, pred.hi, attrs)
        return dist, passing & valid

    def adc_scores(self, index, lut, pred, safe_ids, mask, metric):
        from ...kernels.ref import chain_sum_m

        qv = index.qvecs
        n = index.n_records
        valid = mask & (safe_ids < n)
        cd = qv.codes[safe_ids].astype(jnp.int32)  # (V, m)
        vals = lut[jnp.arange(qv.m)[None, :], cd]  # (V, m)
        dist = chain_sum_m([vals[:, mi] for mi in range(qv.m)])
        dist = jnp.where(valid, dist, jnp.inf)
        attrs = index.attrs[safe_ids]
        passing = P.evaluate(pred, attrs) & valid
        return dist, passing

    def scan_scores_quantized(self, index, luts, pred, ids, mask, metric):
        from ...kernels.ref import chain_sum_m

        qv = index.qvecs
        n = index.n_records
        safe = jnp.where(mask, jnp.clip(ids, 0, n), n).astype(jnp.int32)
        valid = mask & (safe < n)
        cd = qv.codes[safe].astype(jnp.int32)  # (B, V, m)
        # per-subspace take_along_axis over the (B, ks) LUT rows — bitwise
        # identical to vmapping adc_scores but ~5x faster on CPU XLA, which
        # lowers the (V, m) two-axis fancy gather to a scalar loop while
        # this shape stays a vectorized single-axis gather; the m partial
        # sums fold through the same chain as the kernel (ref.chain_sum_m)
        parts = [
            jnp.take_along_axis(luts[:, mi, :], cd[:, :, mi], axis=1)
            for mi in range(qv.m)
        ]
        dist = jnp.where(valid, chain_sum_m(parts), jnp.inf)
        attrs = index.attrs[safe]
        passing = jax.vmap(
            lambda lo, hi, at: P.evaluate(P.Predicate(lo, hi), at)
        )(pred.lo, pred.hi, attrs)
        return dist, passing & valid


class PallasBackend:
    """Fused Pallas kernels on the hot path.

    VISIT goes through ``kernels.visit_step`` — one kernel for the whole
    per-step hot spot: scalar-prefetched row gather + VPU distance + DNF
    predicate + tombstone mask + queue-admission candidates (the unfused
    ``kernels.filter_distance`` stays behind ``fused_visit=False``) — and
    the centroid ranking through ``kernels.ivf_score`` (blocked MXU
    distance matrix).  Every kernel implements squared L2 and negated
    inner product (static ``metric``); only genuinely unknown metrics fall
    back to the reference math, and each such fallback bumps
    ``compass_kernel_fallback_total{kernel,reason="metric:<m>"}`` so a
    silently-ref-routed deployment is visible in the registry.
    """

    name = "pallas"

    _KERNEL_METRICS = ("l2", "ip")

    @staticmethod
    def _metric_fallback(kernel: str, metric: str) -> None:
        from repro.obs import profiling as prof

        prof.count_fallback(kernel, f"metric:{metric}")

    def visit_scores(self, index, q, pred, safe_ids, mask, metric):
        if metric not in self._KERNEL_METRICS:
            self._metric_fallback("filter_distance", metric)
            return RefBackend().visit_scores(index, q, pred, safe_ids, mask, metric)
        from ...kernels import ops

        dist, passing = ops.filter_distance(
            index.vectors, index.attrs, safe_ids, mask, q, pred.lo, pred.hi,
            metric=metric,
        )
        return dist, passing & mask

    def visit_step(
        self, index, q, pred, safe_ids, mask, metric, fused=True, rows_per_step=None
    ):
        if not fused or metric not in self._KERNEL_METRICS:
            if metric not in self._KERNEL_METRICS:
                self._metric_fallback("visit_step", metric)
            else:
                self._metric_fallback("visit_step", "fused_visit=False")
            # unfused: the pre-fusion kernel sequence (filter_distance
            # kernel + jnp live gather + admission select)
            dist, passing = self.visit_scores(index, q, pred, safe_ids, mask, metric)
            if index.live is not None:
                passing = passing & index.live[safe_ids]
            return dist, jnp.where(passing, dist, jnp.inf)
        from ...kernels import ops

        return ops.visit_step(
            index.vectors, index.attrs, index.live, safe_ids, mask, q,
            pred.lo, pred.hi, metric=metric, rows_per_step=rows_per_step,
        )

    def centroid_scores(self, index, queries, metric):
        if metric not in self._KERNEL_METRICS:
            self._metric_fallback("ivf_score", metric)
            return RefBackend().centroid_scores(index, queries, metric)
        from ...kernels import ops

        return ops.ivf_score(queries, index.centroids, metric=metric)

    def scan_scores(self, index, queries, pred, ids, mask, metric):
        if metric not in self._KERNEL_METRICS:
            self._metric_fallback("filter_distance", metric)
            return RefBackend().scan_scores(index, queries, pred, ids, mask, metric)
        from ...kernels import ops

        dist, passing = ops.filter_distance_batch(
            index.vectors, index.attrs, ids, mask, queries, pred.lo, pred.hi,
            metric=metric,
        )
        return dist, passing & mask

    def adc_scores(self, index, lut, pred, safe_ids, mask, metric):
        # the table already carries the metric, so every metric the table
        # builder accepts runs on the kernel
        from ...kernels import ops

        dist, passing = ops.pq_score(
            index.qvecs.codes, index.attrs, safe_ids, mask, lut, pred.lo, pred.hi
        )
        return dist, passing & mask

    def scan_scores_quantized(self, index, luts, pred, ids, mask, metric):
        from ...kernels import ops

        dist, passing = ops.pq_score_batch(
            index.qvecs.codes, index.attrs, ids, mask, luts, pred.lo, pred.hi
        )
        return dist, passing & mask


class QuantAdapter:
    """Per-query scoring view over a base backend: VISIT goes through the
    ADC tables, everything else passes through.

    The driver instantiates one per query (inside the vmap) when
    ``CompassParams.quant`` is active, capturing that query's precomputed
    (m, ks) table; the iterators and ``state.visit``
    keep calling the ordinary ``visit_scores`` surface, so candidate
    generation is untouched — exactly the generation/scoring split the
    backend layer exists for.  ``counts_as`` routes the work into
    ``SearchStats.n_adc`` (see state.visit).
    """

    counts_as = "adc"

    def __init__(self, inner: VisitBackend, lut):
        self.inner = inner
        self.name = inner.name
        self.lut = lut

    def visit_scores(self, index, q, pred, safe_ids, mask, metric):
        return self.inner.adc_scores(index, self.lut, pred, safe_ids, mask, metric)

    def visit_step(
        self, index, q, pred, safe_ids, mask, metric, fused=True, rows_per_step=None
    ):
        # ADC scoring stays a separate kernel (pq_score); the tombstone
        # AND + admission select compose here —
        # both inner backends produce parity-tested (dist, passing), so the
        # composed admit inherits the parity
        dist, passing = self.visit_scores(index, q, pred, safe_ids, mask, metric)
        if index.live is not None:
            passing = passing & index.live[safe_ids]
        return dist, jnp.where(passing, dist, jnp.inf)

    def centroid_scores(self, index, queries, metric):
        # the coarse layer stays full-precision (standard IVF-PQ: centroid
        # ranking is (B, C) small and drives candidate generation)
        return self.inner.centroid_scores(index, queries, metric)

    def scan_scores(self, index, queries, pred, ids, mask, metric):
        return self.inner.scan_scores(index, queries, pred, ids, mask, metric)


_BACKENDS = {"ref": RefBackend(), "pallas": PallasBackend()}


def resolve_backend(name: str) -> VisitBackend:
    """Map a CompassParams.backend value to a backend instance.

    ``"auto"`` picks the Pallas kernels when running natively on TPU and the
    reference path elsewhere (interpret-mode kernels are correct on CPU but
    slower than XLA's fused gathers; tests opt in explicitly).
    """
    if name == "auto":
        name = "pallas" if jax.default_backend() == "tpu" else "ref"
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {sorted(_BACKENDS)} or 'auto'"
        ) from None
