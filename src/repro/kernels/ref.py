"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def row_distance(vec, q, metric, keepdims=False):
    """The one distance expression every visit-path oracle and kernel body
    shares: rows-vs-query over the trailing axis, f32.  ``metric``:
    ``"l2"`` squared L2, ``"ip"`` negated inner product (so smaller is
    better for both).  Keeping it a single expression — an elementwise map
    followed by one trailing-axis reduce — is what makes the (V, d) oracle
    and the (rb, d) kernel row-block reductions bitwise identical."""
    if metric == "l2":
        diff = (vec - q).astype(jnp.float32)
        return jnp.sum(diff * diff, axis=-1, keepdims=keepdims)
    if metric == "ip":
        return jnp.sum(
            -(vec.astype(jnp.float32) * q.astype(jnp.float32)), axis=-1, keepdims=keepdims
        )
    raise ValueError(f"unknown kernel metric {metric!r}; expected 'l2' or 'ip'")


def filter_distance_ref(vectors, attrs, idx, mask, q, lo, hi, metric="l2"):
    n = vectors.shape[0] - 1
    safe = jnp.where(mask, jnp.clip(idx, 0, n), n)
    # ids pointing at the sentinel row are masked-out visits even under a
    # true mask — identical to the kernel's `idx < n` validity check
    valid = mask & (safe < n)
    vec = vectors[safe]
    dist = row_distance(vec, q[None, :], metric)
    a = attrs[safe]
    term_ok = jnp.all((a[:, None, :] >= lo[None]) & (a[:, None, :] <= hi[None]), axis=-1)
    passed = jnp.any(term_ok, axis=-1) & valid
    return jnp.where(valid, dist, jnp.inf), passed


def filter_distance_batch_ref(vectors, attrs, idx, mask, queries, lo, hi, metric="l2"):
    """Batched (B, V) oracle: per-lane query/bounds, same row semantics."""
    return jax.vmap(
        lambda i, m, q, l, h: filter_distance_ref(vectors, attrs, i, m, q, l, h, metric)
    )(idx, mask, queries, lo, hi)


def visit_step_ref(vectors, attrs, live, idx, mask, q, lo, hi, metric="l2"):
    """Oracle for the fused visit step: distance + DNF predicate + tombstone
    mask + queue-admission candidates in one call.

    ``live`` is the (N + 1,) bool tombstone vector or None (immutable
    index).  Returns ``(dist (V,) f32, admit (V,) f32)``: ``dist`` is the
    raw visit distance (+inf where masked/sentinel) that feeds the
    traversal queues, ``admit`` equals ``dist`` where the row is valid,
    predicate-passing AND alive, else +inf — exactly what the result queue
    merges.  Composes the pre-fusion engine sequence
    (backend.visit_scores → live AND → where) verbatim, so the ref engine
    path stays bitwise identical to earlier engine versions."""
    dist, passed = filter_distance_ref(vectors, attrs, idx, mask, q, lo, hi, metric)
    if live is not None:
        n = vectors.shape[0] - 1
        safe = jnp.where(mask, jnp.clip(idx, 0, n), n)
        passed = passed & live[safe]
    return dist, jnp.where(passed, dist, jnp.inf)


def chain_sum_m(parts):
    """Fold per-subspace partial distances left-to-right.

    ADC distances are a sum of ``m`` table values; XLA's reduce is free to
    pick different association trees for a (m,)->() reduce (kernel) and a
    (V, m)->(V,) reduce (oracle), which costs a ULP.  ``m`` is small and
    static, so both sides fold an explicit sequential chain instead —
    order-deterministic, hence bitwise-identical across paths.
    """
    acc = parts[0]
    for p in parts[1:]:
        acc = acc + p
    return acc


def subspace_lut(codebooks, q_resid):
    """Per-subspace squared-L2 ADC table: (m, ks, dsub), (d_pad,) -> (m, ks).

    Built once per query (vmapped in quant/encode.build_luts); the jnp
    scoring path and the pq_score kernel both look up that one table.
    """
    m, _, dsub = codebooks.shape
    qs = q_resid.reshape(m, 1, dsub)
    diff = codebooks - qs
    # explicit left-to-right fold over the (small, static) subspace dim:
    # an axis reduce may lower to different association/FMA choices inside
    # the kernel body vs the outer jit, which costs a ULP (see chain_sum_m)
    return chain_sum_m([diff[..., j] * diff[..., j] for j in range(dsub)])


def subspace_lut_ip(codebooks, q_resid):
    """Per-subspace negated-inner-product ADC table: (m, ks, dsub),
    (d_pad,) -> (m, ks).  Summing the m tables reconstructs
    ``-(q · decode(code))`` (codes are raw for ip — quant/params.py rejects
    residual centering off-l2, and the zero-padded tail contributes exact
    zeros).  Same explicit fold and sharing contract as
    :func:`subspace_lut`."""
    m, _, dsub = codebooks.shape
    qs = q_resid.reshape(m, 1, dsub)
    prod = codebooks * qs
    return chain_sum_m([-prod[..., j] for j in range(dsub)])


def adc_lut(codebooks, q_resid, metric="l2"):
    """Metric dispatch for the shared ADC table expressions."""
    if metric == "l2":
        return subspace_lut(codebooks, q_resid)
    if metric == "ip":
        return subspace_lut_ip(codebooks, q_resid)
    raise ValueError(f"unknown kernel metric {metric!r}; expected 'l2' or 'ip'")


def pq_score_ref(codes, attrs, idx, mask, lut, lo, hi):
    """ADC oracle: code-gather table lookups + DNF predicate.

    ``codes``: (N + 1, m) uint8 (sentinel row N); ``lut``: the query's
    (m, ks) table (:func:`adc_lut`, which carries the metric).  Sentinel
    ids are masked-out visits even under a true mask, exactly like
    filter_distance_ref.  Returns (dists (V,) f32 +inf where masked,
    passed (V,) bool).
    """
    n = codes.shape[0] - 1
    m = lut.shape[0]
    safe = jnp.where(mask, jnp.clip(idx, 0, n), n)
    valid = mask & (safe < n)
    cd = codes[safe].astype(jnp.int32)  # (V, m)
    vals = lut[jnp.arange(m)[None, :], cd]  # (V, m)
    dist = chain_sum_m([vals[:, mi] for mi in range(m)])
    a = attrs[safe]
    term_ok = jnp.all((a[:, None, :] >= lo[None]) & (a[:, None, :] <= hi[None]), axis=-1)
    passed = jnp.any(term_ok, axis=-1) & valid
    return jnp.where(valid, dist, jnp.inf), passed


def pq_score_batch_ref(codes, attrs, idx, mask, luts, lo, hi):
    """Batched (B, V) ADC oracle: per-lane (m, ks) tables and bounds."""
    return jax.vmap(
        lambda i, m, t, l, h: pq_score_ref(codes, attrs, i, m, t, l, h)
    )(idx, mask, luts, lo, hi)


def ivf_score_ref(queries, centroids, metric="l2"):
    qc = queries.astype(jnp.float32) @ centroids.astype(jnp.float32).T
    if metric == "ip":
        return -qc
    q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=1, keepdims=True)
    c2 = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=1)
    return q2 + c2[None, :] - 2.0 * qc


def flash_attention_ref(q, k, v):
    """Dense causal GQA attention in f32."""
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, kf) / math.sqrt(d)
    mask = jnp.arange(t)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)
