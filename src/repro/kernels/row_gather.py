"""Shared Pallas TPU pipeline of the row-gather scoring kernels.

``visit_step``, ``filter_distance(_batch)`` and ``pq_score(_batch)`` all
do the same thing per candidate row: fetch the row of a table (float32
vectors or uint8 PQ codes) and the row of ``attrs`` by id, score it
against the lane's query operand, and test the DNF interval predicate.
This module is that pipeline, written once in the form Mosaic accepts:

  * **lane-aligned float32 rows stay in HBM** (``memory_space=pl.ANY``)
    when ``d`` is a multiple of 128.  The ids are scalar-prefetched into
    SMEM (flattened to ``(B * Vp,)``) and each grid step DMAs its ``rb``
    rows (``make_async_copy`` of a ``(1, d)`` slice) into a two-slot VMEM
    scratch; the DMAs of step ``s + 1`` start before step ``s`` waits on
    its own, so the gather of the next block overlaps the scoring of this
    one.  Index-mapped ``(1, d)`` BlockSpecs are not an option: the TPU
    lowering requires the last two block dims to be multiples of (8, 128)
    or the full array dims.  A one-row DMA slice must be lane-aligned too,
    which is why the route depends on ``d``.
  * **every other table is gathered by XLA.**  Mosaic lays a table whose
    rows are not a whole number of 128-lane tiles (``(n + 1, 4)`` f32
    attributes, ``(n + 1, 16)`` uint8 PQ codes, float32 vectors at
    ``d`` = 48, 96, 100, ...) out in HBM as padded tiles and refuses a
    one-row slice of it.  So those rows of the V candidates
    (``table[ids]``) ride in as ordinary ``(rb, W)`` row blocks, whose last
    two dims are the array's own; the scoring, the predicate, the
    tombstone AND and the admission select stay in the kernel.  Both
    routes score the same rows with the same expressions.  The float32
    rows' gather runs in the stage scope ``compass/row_gather/xla``.
  * **outputs are written in (rb, 1) blocks** of a ``(B, G, rb, 1)`` array
    (the block's last two dims equal the array's, so any ``rb`` lowers),
    and reshaped to ``(B, V)`` outside.

The grid is ``(B, G)`` with ``G = Vp / rb``: lanes outer, row blocks
inner, both sequential ("arbitrary"), because the cross-step prefetch
relies on the order.  Per-lane operands (the query row or the ADC table,
the ``(T, A)`` bounds) are ordinary blocks indexed by the lane and are
re-fetched only when the lane changes.

Row semantics match ``kernels/ref.py``: masked slots point at the
sentinel row ``n`` and yield ``+inf`` / false.  ``emit="pass"`` returns
``(dist, passed)``; ``emit="admit"`` returns ``(dist, admit)`` with
``admit = dist`` where the row is valid, passes the predicate AND is
alive (``live``), else ``+inf``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.obs.profiling import stage_scope

from .ref import chain_sum_m, row_distance

#: row width (elements) a one-row DMA slice of a float32 table must be a
#: multiple of: one lane tile
LANES = 128


def dma_rows(score: str, width: int) -> bool:
    """Whether ``gather_score`` DMAs the scored rows from HBM (lane-aligned
    float32 vectors) rather than taking them gathered by XLA."""
    return score != "adc" and width % LANES == 0


def dnf_pass(attrs, lo, hi):
    """``(R, A)`` rows against ``(T, A)`` bounds -> ``(R, 1)`` bool: does
    any term hold on every attribute.  Terms unroll statically (T is a
    compiled shape); a term holds where no attribute falls outside it."""
    ok = None
    for t in range(lo.shape[0]):
        inside = (attrs >= lo[t : t + 1, :]) & (attrs <= hi[t : t + 1, :])
        term = jnp.min(jnp.where(inside, 1.0, 0.0), axis=-1, keepdims=True) > 0.0
        ok = term if ok is None else ok | term
    return ok


def _score(score: str, rows, lane_ref):
    """``(rb, 1)`` scores of one row block.  ``"l2"`` / ``"ip"``: float32
    rows vs the lane's ``(1, d)`` query through the oracle's own expression
    (``ref.row_distance``, bitwise in interpret mode); ``"adc"``: PQ codes
    vs the lane's table (:func:`_score_adc`)."""
    if score == "adc":
        return _score_adc(rows, lane_ref)
    return row_distance(rows, lane_ref[...], score, keepdims=True)


def _score_adc(rows, lane_ref):
    """ADC scorer: ``(rb, m)`` uint8 codes vs the lane's ``(m, ks)`` table.

    The per-code table lookup is a one-hot select over the table row (the
    vector units have no arbitrary-index gather); adding the masked zeros
    is exact in f32, and the ``m`` partial values fold through
    ``ref.chain_sum_m`` like the oracle's, so parity is bitwise."""
    codes = rows.astype(jnp.int32)
    lut = lane_ref[...]
    m, ks = lut.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], ks), 1)
    parts = [
        jnp.sum(
            jnp.where(codes[:, mi : mi + 1] == lanes, lut[mi : mi + 1, :], 0.0),
            axis=-1,
            keepdims=True,
        )
        for mi in range(m)
    ]
    return chain_sum_m(parts)


def _kernel(*refs, n, rb, n_blocks, score, emit, has_live, dma):
    if dma:
        ids_smem, rows_hbm, ids_blk, attrs_blk, *refs = refs
    else:
        ids_blk, rows_blk, attrs_blk, *refs = refs
    if has_live:
        live_blk, *refs = refs
    lane_ref, lo_ref, hi_ref, out0_ref, out1_ref, *scratch = refs

    if not dma:
        rows = rows_blk[...]
    else:
        rbuf, sem = scratch
        step = pl.program_id(0) * n_blocks + pl.program_id(1)
        slot = jax.lax.rem(step, 2)

        def copies(s, sl):
            return [
                pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(ids_smem[s * rb + j], 1)],
                    rbuf.at[sl, pl.ds(j, 1)],
                    sem.at[sl],
                )
                for j in range(rb)
            ]

        @pl.when(step == 0)
        def _first():
            for c in copies(step, slot):
                c.start()

        @pl.when(step + 1 < pl.num_programs(0) * n_blocks)
        def _prefetch_next():
            for c in copies(step + 1, 1 - slot):
                c.start()

        for c in copies(step, slot):
            c.wait()
        rows = rbuf[slot]

    dist = _score(score, rows, lane_ref)  # (rb, 1)
    valid = ids_blk[...] < n  # sentinel row == masked-out slot
    ok = valid & dnf_pass(attrs_blk[...], lo_ref[...], hi_ref[...])
    out0_ref[...] = jnp.where(valid, dist, jnp.inf)
    if emit == "admit":
        if has_live:
            ok = ok & (live_blk[...] > 0)
        out1_ref[...] = jnp.where(ok, dist, jnp.inf)
    else:
        out1_ref[...] = ok.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("score", "emit", "rb", "interpret"))
def gather_score(ids, rows, attrs, lane, lo, hi, live=None, *, score: str, emit: str,
                 rb: int, interpret: bool):
    """Score ``(B, V)`` candidate ids against per-lane operands.

    ``ids``: candidate ids, masked slots already pointing at the sentinel
    ``n``; ``rows``: ``(n + 1, W)`` table — float32 vectors for ``score``
    ``"l2"`` / ``"ip"`` (``lane``: ``(B, 1, d)`` queries), uint8 PQ codes
    for ``"adc"`` (``lane``: ``(B, m, ks)`` tables); ``attrs``:
    ``(n + 1, A)``; ``lo``/``hi``: ``(B, T, A)``; ``live``: ``(n + 1,)``
    tombstones or None.  Returns two ``(B, V)`` arrays: ``dist`` and, by
    ``emit``, ``passed`` (bool) or ``admit`` (f32).
    """
    b, v = ids.shape
    n = rows.shape[0] - 1
    t, a = lo.shape[1:]
    pad = (-v) % rb
    ids = jnp.pad(ids, ((0, 0), (0, pad)), constant_values=n).astype(jnp.int32)
    g = (v + pad) // rb

    def per_row(x):  # (B, Vp, ...) -> (B, G, rb, W) row blocks
        return x.reshape(b, g, rb, -1)

    row_block = lambda w: pl.BlockSpec(
        (None, None, rb, w), lambda bi, gi, *_: (bi, gi, 0, 0)
    )
    lane_block = lambda shape: pl.BlockSpec(
        (None, *shape), lambda bi, gi, *_: (bi, 0, 0)
    )
    # only lane-aligned float32 rows are DMA'd; the rest (attrs, codes,
    # tombstones, vectors of other widths) are gathered by XLA and ride in
    # as row blocks
    dma = dma_rows(score, rows.shape[1])
    if not dma:
        if score == "adc":
            gathered = rows[ids].astype(jnp.int32)
        else:  # float32 rows that cannot be DMA'd: their own stage on the trace
            with stage_scope("row_gather/xla"):
                gathered = rows[ids]
        in_specs = [row_block(1), row_block(rows.shape[1])]
        operands = [per_row(ids), per_row(gathered)]
        scratch = []
    else:
        in_specs = [pl.BlockSpec(memory_space=pl.ANY), row_block(1)]
        operands = [rows, per_row(ids)]
        scratch = [
            pltpu.VMEM((2, rb, rows.shape[1]), rows.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    in_specs.append(row_block(a))
    operands.append(per_row(attrs[ids]))
    if live is not None:
        in_specs.append(row_block(1))
        operands.append(per_row(live[ids].astype(jnp.int32)))
    in_specs += [lane_block(lane.shape[1:]), lane_block((t, a)), lane_block((t, a))]
    operands += [lane, lo, hi]
    out_dtype = jnp.float32 if emit == "admit" else jnp.int32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if dma else 0,
        grid=(b, g),
        in_specs=in_specs,
        out_specs=[row_block(1), row_block(1)],
        scratch_shapes=scratch,
    )
    prefetch = [ids.reshape(-1)] if dma else []
    out0, out1 = pl.pallas_call(
        functools.partial(_kernel, n=n, rb=rb, n_blocks=g, score=score, emit=emit,
                          has_live=live is not None, dma=dma),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, g, rb, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, g, rb, 1), out_dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(*prefetch, *operands)
    out0 = out0.reshape(b, -1)[:, :v]
    out1 = out1.reshape(b, -1)[:, :v]
    return out0, (out1 if emit == "admit" else out1 > 0)
