"""Vectorized PQ encode/decode and the :class:`QuantizedVectors` pytree.

``QuantizedVectors`` is the device-resident quantized tier: uint8 codes
(one byte per subspace per row, sentinel-padded like ``CompassIndex``'s
row arrays), the frozen per-subspace codebooks, and the centering mean.
It rides on ``CompassIndex.qvecs`` alongside — or, for deployments that
drop the float32 table and rerank by decoding, instead of — the
full-precision rows; ``None`` (the default) keeps every pre-quantization
index bitwise identical.

Everything search needs at query time is a pure function of these arrays:

  * :func:`residual_queries` — center + zero-pad the query batch.
  * :func:`build_luts` — the per-query ``(m, ks)`` subspace distance
    tables (ADC's whole trick: a distance becomes ``m`` table lookups).
    The ref and pallas scoring paths both look up these tables, so they
    agree bitwise.
  * :func:`decode` — codebook gather, for on-demand exact rerank without
    the full-precision rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.ref import adc_lut
from .codebook import pad_dim, train_codebooks
from .params import QuantConfig


class QuantizedVectors(NamedTuple):
    """Quantized row storage (a JAX pytree; every field is an array)."""

    codes: jax.Array  # (N + 1, m) uint8 — row N is the sentinel (all-zero)
    codebooks: jax.Array  # (m, ks, dsub) f32 frozen per-subspace centroids
    mean: jax.Array  # (d,) f32 centering offset (all-zero for raw encoding)
    train_mse: jax.Array  # () f32 quantization MSE at train time (drift anchor)

    @property
    def n_records(self) -> int:
        return self.codes.shape[0] - 1

    @property
    def m(self) -> int:
        return self.codebooks.shape[0]

    @property
    def ks(self) -> int:
        return self.codebooks.shape[1]

    @property
    def dsub(self) -> int:
        return self.codebooks.shape[2]

    @property
    def dim(self) -> int:
        return self.mean.shape[0]

    @property
    def bytes_per_vector(self) -> float:
        """Per-row storage of the quantized tier: codes plus the codebook
        amortized over the rows (the honest figure for small corpora)."""
        n = max(self.n_records, 1)
        codebook_bytes = self.m * self.ks * self.dsub * 4 + self.dim * 4
        return self.m * 1.0 + codebook_bytes / n


def _center_pad(vectors: jax.Array, mean: jax.Array, m: int) -> jax.Array:
    """(N, d) -> (N, d_pad) centered rows, zero-padded to ``m`` subspaces."""
    d = vectors.shape[-1]
    x = vectors - mean
    dp = pad_dim(d, m)
    if dp != d:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (dp - d,), jnp.float32)], axis=-1
        )
    return x


@functools.partial(jax.jit, static_argnames=("block",))
def _encode_padded(xp: jax.Array, codebooks: jax.Array, *, block: int = 4096) -> jax.Array:
    """Nearest-centroid code per subspace, blocked over rows to bound the
    (block, m, ks) distance tensor (same trick as kmeans._assign_blocked)."""
    n = xp.shape[0]
    m, _, dsub = codebooks.shape
    pad = (-n) % block
    xpp = jnp.pad(xp, ((0, pad), (0, 0)))
    nb = xpp.shape[0] // block
    c2 = jnp.sum(codebooks * codebooks, axis=-1)  # (m, ks)

    def body(carry, xb):
        xs = xb.reshape(block, m, dsub)
        # ||x - c||^2 up to the row-constant ||x||^2, which cannot move argmin
        dist = c2[None, :, :] - 2.0 * jnp.einsum("nmd,mkd->nmk", xs, codebooks)
        return carry, jnp.argmin(dist, axis=-1).astype(jnp.uint8)

    _, codes = jax.lax.scan(body, 0, xpp.reshape(nb, block, -1))
    return codes.reshape(-1, m)[:n]


def encode_rows(codebooks: jax.Array, mean: jax.Array, vectors) -> jax.Array:
    """Encode (N, d) rows against frozen codebooks -> (N, m) uint8."""
    m = codebooks.shape[0]
    xp = _center_pad(jnp.asarray(vectors, jnp.float32), jnp.asarray(mean), m)
    return _encode_padded(xp, jnp.asarray(codebooks))


def decode(qv: QuantizedVectors, ids: jax.Array) -> jax.Array:
    """Decode rows by id -> (..., d) float32 approximations."""
    codes = qv.codes[ids].astype(jnp.int32)  # (..., m)
    m = qv.m
    sub = qv.codebooks[jnp.arange(m), codes]  # (..., m, dsub)
    flat = sub.reshape(sub.shape[:-2] + (m * qv.dsub,))[..., : qv.dim]
    return flat + qv.mean


def decode_all(qv: QuantizedVectors) -> jax.Array:
    """Decode the whole table (without the sentinel row) -> (N, d)."""
    return decode(qv, jnp.arange(qv.n_records))


def quant_mse(qv: QuantizedVectors, vectors) -> float:
    """Mean squared decode error over ``vectors`` (rows in table order) —
    the drift metric compaction tracks against ``train_mse``."""
    x = jnp.asarray(vectors, jnp.float32)
    err = decode(qv, jnp.arange(x.shape[0])) - x
    return float(jnp.mean(err * err))


def quantize_vectors(
    vectors, cfg: QuantConfig = QuantConfig(), metric: str = "l2"
) -> QuantizedVectors:
    """Train codebooks on ``vectors`` and encode them: the build entry point."""
    vectors = np.asarray(vectors, np.float32)
    codebooks, mean = train_codebooks(vectors, cfg, metric)
    codes = np.asarray(encode_rows(jnp.asarray(codebooks), jnp.asarray(mean), vectors))
    codes = np.concatenate([codes, np.zeros((1, cfg.m), np.uint8)], axis=0)
    qv = QuantizedVectors(
        jnp.asarray(codes),
        jnp.asarray(codebooks),
        jnp.asarray(mean),
        jnp.float32(0.0),
    )
    return qv._replace(train_mse=jnp.float32(quant_mse(qv, vectors)))


def quantize_index(index, cfg: QuantConfig = QuantConfig(), metric: str = "l2"):
    """Attach a quantized tier to a built CompassIndex (new index returned;
    pass the result anywhere the original was accepted — ``qvecs`` is an
    optional field, exact search paths ignore it)."""
    n = index.n_records
    qv = quantize_vectors(np.asarray(index.vectors)[:n], cfg, metric)
    return index._replace(qvecs=qv)


def residual_queries(qv: QuantizedVectors, queries: jax.Array) -> jax.Array:
    """Center + pad a query batch: (B, d) -> (B, d_pad) f32."""
    return _center_pad(jnp.asarray(queries, jnp.float32), qv.mean, qv.m)


def build_luts(qv: QuantizedVectors, queries: jax.Array, metric: str) -> jax.Array:
    """Per-query ADC tables: (B, m, ks).

    l2: ``lut[m, k] = ||q'_m - cb[m, k]||^2`` over centered-padded queries,
    summing to the exact decoded-row distance.  ip: ``lut[m, k] =
    -(q_m . cb[m, k])`` (raw encoding only; residual-ip is rejected at
    train time because it would need a per-query bias).  The ref and
    pallas scoring paths both look up these tables, so they agree bitwise.
    """
    qr = residual_queries(qv, queries)  # (B, d_pad)
    return jax.vmap(lambda q: adc_lut(qv.codebooks, q, metric))(qr)
