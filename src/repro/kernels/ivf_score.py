"""IVF centroid scoring Pallas kernel: blocked (B, C) distance matrix on
the MXU — squared L2 or negated inner product (static ``metric``).

This is Compass's B.OPEN step (exact centroid ranking; see index.py for why
the TPU replaces the paper's cluster graph with a scan).  Tiling:

  grid = (B/BB, C/BC, d/BD)   —  classic three-loop matmul blocking
  VMEM per step: BB*BD (queries) + BC*BD (centroids) + BB*BC f32 (acc)

with hardware-aligned tiles (128-multiples) so the -2*q@c^T (l2) / -q@c^T
(ip) term lands on the MXU; the l2 ||q||^2 / ||c||^2 norms fold in per
d-block.  The accumulator lives in the output block across the d-grid
(revisited dimension).

Block sizes (``bb``/``bc``/``bd``) resolve through ``kernels/autotune.py``
when not passed explicitly: pin with
``REPRO_PALLAS_BLOCK_IVF_SCORE="bb=8,bc=128,bd=128"``, else the measured
per-shape table (measured only on concrete arguments, never while
traced), else the 8/128/128 default.  Tile choice only re-blocks
the same f32 accumulation order per (query, centroid) pair along d, so
results are tile-independent up to the documented MXU-vs-ref ULP caveat
(engine/backend.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune
from .interpret import default_interpret

_BLOCK_CANDIDATES = (
    {"bb": 8, "bc": 128, "bd": 128},
    {"bb": 16, "bc": 128, "bd": 128},
    {"bb": 8, "bc": 256, "bd": 128},
    {"bb": 8, "bc": 128, "bd": 256},
)


def _kernel(q_ref, c_ref, out_ref, *, nd_blocks, metric):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    qb = q_ref[...].astype(jnp.float32)  # (BB, BD)
    cb = c_ref[...].astype(jnp.float32)  # (BC, BD)
    acc = out_ref[...]
    dot = jax.lax.dot_general(
        qb, cb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if metric == "l2":
        acc += -2.0 * dot
        acc += jnp.sum(qb * qb, axis=1, keepdims=True)
        acc += jnp.sum(cb * cb, axis=1)[None, :]
    else:  # ip: negated inner product (zero-padded d-tail adds exact zeros)
        acc += -dot
    out_ref[...] = acc


def _tuned_blocks(queries, centroids, metric, interpret) -> dict[str, int]:
    concrete = not any(isinstance(x, jax.core.Tracer) for x in (queries, centroids))

    def measure(cfg):
        return _ivf_score(queries, centroids, metric=metric, interpret=interpret, **cfg)

    b, d = queries.shape
    return autotune.choose(
        "ivf_score", (b, centroids.shape[0], d, str(queries.dtype), metric, interpret),
        _BLOCK_CANDIDATES, measure if concrete else None,
    )


def ivf_score(
    queries: jax.Array,  # (B, d)
    centroids: jax.Array,  # (C, d)
    *,
    metric: str = "l2",
    bb: int | None = None,
    bc: int | None = None,
    bd: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Centroid distance scores (B, C): squared L2 or negated inner product.

    Unset block sizes resolve through the autotuner; explicit values always
    win.  The interpret default comes from kernels/interpret.py.
    """
    if interpret is None:
        interpret = default_interpret()
    if bb is None or bc is None or bd is None:
        tuned = _tuned_blocks(queries, centroids, metric, interpret)
        bb, bc, bd = bb or tuned["bb"], bc or tuned["bc"], bd or tuned["bd"]
    return _ivf_score(queries, centroids, metric=metric, bb=bb, bc=bc, bd=bd,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("metric", "bb", "bc", "bd", "interpret"))
def _ivf_score(queries, centroids, *, metric: str, bb: int, bc: int, bd: int,
               interpret: bool):
    b, d = queries.shape
    c = centroids.shape[0]
    pb, pc, pd = (-b) % bb, (-c) % bc, (-d) % bd
    qp = jnp.pad(queries, ((0, pb), (0, pd)))
    cp = jnp.pad(centroids, ((0, pc), (0, pd)))
    grid = (qp.shape[0] // bb, cp.shape[0] // bc, qp.shape[1] // bd)
    out = pl.pallas_call(
        functools.partial(_kernel, nd_blocks=grid[2], metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bd), lambda i, j, k: (i, k)),
            pl.BlockSpec((bc, bd), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bb, bc), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], cp.shape[0]), jnp.float32),
        interpret=interpret,
    )(qp, cp)
    return out[:b, :c]
