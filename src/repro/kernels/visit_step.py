"""Fused visit-step Pallas TPU kernel — Algorithm 4's whole per-step hot
spot (gather → distance → DNF predicate → tombstone mask → queue-admission
candidates) in one ``pallas_call``.

``filter_distance`` fuses the gather + distance + predicate; the engine
would then still pay the admission select ``where(passing & live, dist,
+inf)`` that feeds the result queue on the jnp side.  This kernel folds it
in and emits exactly what ``engine/state.visit`` merges:

  * **dist**  — the raw visit distance (+inf where masked/sentinel), fed
    to the traversal queues (CandQ / graph-top) so dead records keep
    routing (DESIGN.md §Mutability).
  * **admit** — ``dist`` where the row is valid, predicate-passing AND
    alive, else +inf — merged into the filtered result queue directly.

It runs the shared row-gather pipeline (kernels/row_gather.py): candidate
ids are scalar-prefetched, each grid step DMAs a *block of RB rows* of
``vectors`` from HBM into a double-buffered VMEM scratch (the next
block's DMAs are in flight while this block computes; at a width ``d``
that is not a multiple of 128 the rows are gathered by XLA instead), and
the distance
(squared L2 or negated inner product, static ``metric``) reduces on the
VPU through the same ``ref.row_distance`` expression the oracle uses —
bitwise parity in interpret mode.  The attribute rows and tombstone flags
of the V visited rows (a few bytes each) are gathered by XLA and ride in
as RB-row blocks; immutable indices (``live is None``) compile a variant
without the tombstone operand (trace-time branch, zero cost).

VMEM working set per step: 2·RB·(d + A) + RB + d + 2·T·A floats — e.g.
RB=8, d=128, A=4, T=4: ~8.6 KB, far under the ~16 MB budget.

Block-size resolution (``rows_per_step=None``) goes through
``kernels/autotune.py``: pin with ``REPRO_PALLAS_BLOCK_VISIT_STEP="rb=8"``,
else the measured per-shape table, else RB=8.  Measurement needs concrete
arrays: a call with concrete arguments times each candidate on those very
arguments, while a call being traced (the engine hot path inside
``compass_search``) never measures and takes the table entry or the
default.  RB never changes results — every row is computed independently
by the same expressions — so tests assert bitwise equality across RB
values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import autotune
from .interpret import default_interpret
from .row_gather import gather_score

#: wrapper entries (trace-time inside jit) — benchmarks/bench_kernels.py's
#: selfcheck and chip_smoke.py assert this advances when the engine claims
#: the fused path, catching silent fallbacks to ref on any platform.
TRACE_COUNT = 0

_RB_CANDIDATES = (8, 4, 16, 32)


def _tuned_rb(args, metric: str, interpret: bool) -> int:
    ids, vectors, attrs, _, lo, _, live = args
    v = ids.shape[1]
    candidates = [{"rb": r} for r in _RB_CANDIDATES if r <= max(v, _RB_CANDIDATES[0])]
    concrete = not any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(args))

    def measure(cfg):
        return gather_score(*args, score=metric, emit="admit", rb=cfg["rb"],
                            interpret=interpret)

    cfg = autotune.choose(
        "visit_step",
        (vectors.shape[0], vectors.shape[1], attrs.shape[1], lo.shape[1], v,
         metric, live is not None, interpret),
        candidates,
        measure if concrete else None,
    )
    return cfg["rb"]


def visit_step(
    vectors: jax.Array,  # (N + 1, d) padded corpus (row N = sentinel)
    attrs: jax.Array,  # (N + 1, A)
    live: jax.Array | None,  # (N + 1,) bool tombstones, or None (immutable)
    idx: jax.Array,  # (V,) int32 candidate ids (may repeat / sentinel)
    mask: jax.Array,  # (V,) bool visit mask
    q: jax.Array,  # (d,) query
    lo: jax.Array,  # (T, A)
    hi: jax.Array,  # (T, A)
    *,
    metric: str = "l2",
    rows_per_step: int | None = None,
    interpret: bool | None = None,
):
    """Returns ``(dist (V,) f32, admit (V,) f32)`` — see module docstring.

    ``rows_per_step=None`` resolves the block size through the autotuner;
    an explicit value always wins.  The interpret default comes from
    kernels/interpret.py."""
    global TRACE_COUNT
    if interpret is None:
        interpret = default_interpret()
    n = vectors.shape[0] - 1
    safe = jnp.where(mask, jnp.clip(idx, 0, n), n).astype(jnp.int32)
    args = (safe[None], vectors, attrs, q[None, None, :], lo[None], hi[None], live)
    if rows_per_step is None:
        rows_per_step = _tuned_rb(args, metric, interpret)
    TRACE_COUNT += 1
    dist, admit = gather_score(*args, score=metric, emit="admit", rb=rows_per_step,
                               interpret=interpret)
    return dist[0], admit[0]
