"""jit'd public wrappers around the Pallas kernels.

Interpret mode follows the platform (see ``kernels/interpret.py``:
native TPU lowers to Mosaic, everywhere else the Pallas interpreter
executes the kernel body for correctness, so the engine's ``"pallas"``
backend is testable on CPU; ``REPRO_PALLAS_BLOCK_*`` pins kernel block
sizes past the autotuner).  ``use_pallas=False``
falls back to the jnp oracle — search code paths stay identical either
way.

Observability (obs/profiling.py): every kernel launch is wrapped in
``obs.kernel_scope`` — the ``compass/<kernel>`` named scope (pure HLO
metadata; the compiled program is identical) plus a per-kernel wrapper
counter, and every reference-path fallback (``use_pallas=False``)
bumps ``compass_kernel_fallback_total{kernel,reason}``.  Both record at
wrapper-call time — inside a jit that is *trace time*, once per compile,
the same semantics as the ``visit_step.TRACE_COUNT`` CI tripwire.

Full-precision scoring kernels take ``metric`` ("l2" squared L2 / "ip"
negated inner product; the ADC kernels take tables that already carry
it); cosine runs as ip over normalized rows and never reaches this
layer (the engine rewrites it — see core/engine/driver.py).
"""
from __future__ import annotations

from repro.obs import profiling as prof

from . import ref
from .filter_distance import filter_distance as _filter_distance_kernel
from .filter_distance import filter_distance_batch as _filter_distance_batch_kernel
from .flash_attention import flash_attention as _flash_kernel
from .ivf_score import ivf_score as _ivf_kernel
from .pq_score import pq_score as _pq_score_kernel
from .pq_score import pq_score_batch as _pq_score_batch_kernel
from .visit_step import visit_step as _visit_step_kernel


def filter_distance(vectors, attrs, idx, mask, q, lo, hi, *,
                    metric: str = "l2", use_pallas: bool = True):
    if not use_pallas:
        prof.count_fallback("filter_distance", "use_pallas=False")
        return ref.filter_distance_ref(vectors, attrs, idx, mask, q, lo, hi, metric)
    with prof.kernel_scope("filter_distance"):
        return _filter_distance_kernel(
            vectors, attrs, idx, mask, q, lo, hi, metric=metric
        )


def filter_distance_batch(
    vectors, attrs, idx, mask, queries, lo, hi, *,
    metric: str = "l2", use_pallas: bool = True
):
    if not use_pallas:
        prof.count_fallback("filter_distance", "use_pallas=False")
        return ref.filter_distance_batch_ref(
            vectors, attrs, idx, mask, queries, lo, hi, metric
        )
    with prof.kernel_scope("filter_distance"):
        return _filter_distance_batch_kernel(
            vectors, attrs, idx, mask, queries, lo, hi, metric=metric
        )


def visit_step(vectors, attrs, live, idx, mask, q, lo, hi, *,
               metric: str = "l2", use_pallas: bool = True, **kw):
    """Fused visit step (gather + distance + predicate + tombstone +
    admission) — returns (dist (V,), admit (V,)); see kernels/visit_step.py."""
    if not use_pallas:
        prof.count_fallback("visit_step", "use_pallas=False")
        return ref.visit_step_ref(vectors, attrs, live, idx, mask, q, lo, hi, metric)
    with prof.kernel_scope("visit_step"):
        return _visit_step_kernel(vectors, attrs, live, idx, mask, q, lo, hi,
                                  metric=metric, **kw)


def pq_score(codes, attrs, idx, mask, lut, lo, hi, *, use_pallas: bool = True):
    """ADC scoring of one query's visit list against its (m, ks) table."""
    if not use_pallas:
        prof.count_fallback("pq_score", "use_pallas=False")
        return ref.pq_score_ref(codes, attrs, idx, mask, lut, lo, hi)
    with prof.kernel_scope("pq_score"):
        return _pq_score_kernel(codes, attrs, idx, mask, lut, lo, hi)


def pq_score_batch(codes, attrs, idx, mask, luts, lo, hi, *, use_pallas: bool = True):
    if not use_pallas:
        prof.count_fallback("pq_score", "use_pallas=False")
        return ref.pq_score_batch_ref(codes, attrs, idx, mask, luts, lo, hi)
    with prof.kernel_scope("pq_score"):
        return _pq_score_batch_kernel(codes, attrs, idx, mask, luts, lo, hi)


def ivf_score(queries, centroids, *, metric: str = "l2", use_pallas: bool = True, **kw):
    if not use_pallas:
        prof.count_fallback("ivf_score", "use_pallas=False")
        return ref.ivf_score_ref(queries, centroids, metric)
    with prof.kernel_scope("ivf_score"):
        return _ivf_kernel(queries, centroids, metric=metric, **kw)


def flash_attention(q, k, v, *, use_pallas: bool = True, **kw):
    if not use_pallas:
        prof.count_fallback("flash_attention", "use_pallas=False")
        return ref.flash_attention_ref(q, k, v)
    with prof.kernel_scope("flash_attention"):
        return _flash_kernel(q, k, v, **kw)
