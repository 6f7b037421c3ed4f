"""Corpus, query pool and predicates of a cell, made from ``--seed``.

The corpus follows ``chip_smoke.make_corpus``: rows near ``intrinsic``-
dimensional affine patches around ``modes`` centres (descriptor sets such as
SIFT have a low intrinsic dimension), plus ``attrs`` uniform [0, 1)
attributes.  Queries are held-out rows of the same distribution.  It is
generated on the device in one jitted call and then copied to the host,
because the index build takes host arrays.

Predicates are DNF interval tensors ``(T, A)`` with the semantics of
``core/predicate.evaluate``: a row passes when, for some term, every
attribute lies in ``[lo, hi]``.  Unconstrained attributes span the float32
range.  Each shape of the traffic file's ``mix`` is drawn by one general
rule (see :func:`make_predicates`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32_MIN = float(np.finfo(np.float32).min)
F32_MAX = float(np.finfo(np.float32).max)


def prng_key(seed: int):
    """A JAX key from any whole ``seed``, including seeds above 32 bits."""
    s = int(seed) % (1 << 64)
    key = jax.random.key(np.uint32(s & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(s >> 32))


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream ``stream`` of ``seed``."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@functools.partial(jax.jit,
                   static_argnames=("n", "n_queries", "dim", "n_attrs", "modes", "intrinsic"))
def _corpus(key, *, n, n_queries, dim, n_attrs, modes, intrinsic):
    kc, kb, km, kz, kn, ka = jax.random.split(key, 6)
    total = n + n_queries
    centers = jax.random.normal(kc, (modes, dim), jnp.float32) * 4.0
    basis = jax.random.normal(kb, (modes, intrinsic, dim), jnp.float32) * 0.5
    mode = jax.random.randint(km, (total,), 0, modes)
    z = jax.random.normal(kz, (total, intrinsic), jnp.float32)
    x = centers[mode] + 0.05 * jax.random.normal(kn, (total, dim), jnp.float32)

    def add_axis(i, acc):  # one patch axis at a time keeps the gather (total, dim)
        return acc + z[:, i][:, None] * basis[mode, i]

    x = jax.lax.fori_loop(0, intrinsic, add_axis, x)
    attrs = jax.random.uniform(ka, (n, n_attrs), jnp.float32)
    return x[:n], attrs, x[n:]


def make_corpus(seed: int, corpus: dict, n_queries: int):
    """``(vectors (n, d), attrs (n, A), queries (n_queries, d))`` as float32
    numpy arrays, from ``corpus`` = the configuration's ``corpus`` block."""
    out = _corpus(prng_key(seed), n=int(corpus["rows"]), n_queries=int(n_queries),
                  dim=int(corpus["dim"]), n_attrs=int(corpus["attrs"]),
                  modes=int(corpus["modes"]), intrinsic=int(corpus["intrinsic"]))
    x, attrs, queries = (np.asarray(a) for a in jax.device_get(out))
    del out
    return x, attrs, queries


class Predicates:
    """A pool of predicates drawn from a traffic ``mix``: per shape ``s`` of
    the mix, ``lo[s]`` / ``hi[s]`` of shape ``(count_s, T_s, A)``; query
    ``i`` of the pool is row ``row[i]`` of shape ``kind[i]``."""

    def __init__(self, kind, row, lo, hi):
        self.kind, self.row, self.lo, self.hi = kind, row, lo, hi

    def __len__(self):
        return len(self.kind)

    def __getitem__(self, i):
        """``(lo (T, A), hi (T, A))`` of query ``i``."""
        s, r = self.kind[i], self.row[i]
        return self.lo[s][r], self.hi[s][r]


def shape_sequence(weights: list, count: int) -> np.ndarray:
    """Shape index of each of ``count`` queries: smooth weighted round-robin,
    so every prefix of the sequence holds each shape in proportion to its
    weight (``[1, 1]`` alternates).  It is the same for every seed, so the
    micro-batches a window dispatches do not change their mix of predicate
    shapes from seed to seed."""
    w = np.asarray(weights, np.float64)
    credit = np.zeros_like(w)
    out = np.empty(count, np.int64)
    for i in range(count):
        credit += w
        k = int(np.argmax(credit))
        credit[k] -= w.sum()
        out[i] = k
    return out


def make_predicates(rng: np.random.Generator, count: int, mix: list,
                    n_attrs: int) -> Predicates:
    """``count`` predicates drawn from ``mix``.

    Each shape of ``mix`` has a ``weight``, ``terms`` T, ``attrs_per_term``
    and ``width`` (a fraction of the [0, 1) attribute range).  With
    ``distinct_attrs`` the T terms constrain disjoint attributes (a
    disjunction over different attributes); otherwise each term draws its
    own.  Each range starts uniformly in
    ``[0, 1 - width]``.  Which shape each query has does not depend on the
    seed (:func:`shape_sequence`): every seed sends the same mix of shapes
    in the same order, and only what each query asks for is drawn.
    """
    kind = shape_sequence([float(s["weight"]) for s in mix], count)
    row = np.zeros(count, np.int64)
    los, his = [], []
    for k, s in enumerate(mix):
        sel = np.flatnonzero(kind == k)
        row[sel] = np.arange(len(sel))
        c, t, per = len(sel), int(s["terms"]), int(s["attrs_per_term"])
        if s.get("distinct_attrs"):
            order = np.argsort(rng.random((c, n_attrs)), axis=1)[:, : t * per]
            attrs = order.reshape(c, t, per)
        else:
            attrs = np.argsort(rng.random((c, t, n_attrs)), axis=2)[:, :, :per]
        width = np.full((c, t, per), float(s["width"]))
        start = rng.uniform(0.0, 1.0, (c, t, per)) * (1.0 - width)
        lo = np.full((c, t, n_attrs), F32_MIN, np.float32)
        hi = np.full((c, t, n_attrs), F32_MAX, np.float32)
        np.put_along_axis(lo, attrs, start.astype(np.float32), axis=2)
        np.put_along_axis(hi, attrs, (start + width).astype(np.float32), axis=2)
        los.append(lo)
        his.append(hi)
    return Predicates(kind, row, los, his)
