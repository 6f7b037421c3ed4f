"""The system under test, built from a configuration file.

The configuration's ``index``, ``quant``, ``tier``, ``search`` and
``service`` blocks are handed to the program's own entry points
(``build_index``, ``quantize_index``, ``MutableIndex``,
``CollectionService``) unchanged; nothing here scores a row.
:class:`ControlService` is the control of the comparison: the plain
reference put in the service's place, computed one precision step below
what the configurations state.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

COLLECTION = "bench"
#: the keys of a configuration's ``quant`` block, each required
QUANT_KEYS = ("m", "ks", "iters", "refine_factor", "rerank")


def build(config: dict, x: np.ndarray, attrs: np.ndarray, seed: int, phases: dict):
    """``(service, collection name)`` serving ``x``/``attrs`` as ``config``
    says.  Wall times of the build go into ``phases``.  A ``quant`` block
    quantizes the built index (``phases["quantize_index"]``) and serves the
    collection with its two-stage search."""
    from repro.compass import CompassParams
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex
    from repro.core.quant import QuantConfig, QuantParams, quantize_index
    from repro.serving.tenancy import CollectionService

    ix, quant, tier, search, svc_cfg = config["index"], config.get("quant"), \
        config["tier"], config["search"], config["service"]
    if quant is not None and sorted(quant) != sorted(QUANT_KEYS):
        raise ValueError(f"a quant block has exactly the keys {QUANT_KEYS}, not {sorted(quant)}")
    if tier["kind"] not in ("mutable", "immutable"):
        raise ValueError(f"unknown tier kind {tier['kind']!r}")
    build_seed = int(seed) % (1 << 31)
    t0 = time.perf_counter()
    index = build_index(x, attrs, BuildConfig(
        m=int(ix["graph_degree"]), nlist=int(ix["nlist"]), metric=ix["metric"],
        seed=build_seed))
    jax.block_until_ready(index)
    phases["build_index"] = time.perf_counter() - t0
    if quant is not None:
        t0 = time.perf_counter()
        index = quantize_index(index, QuantConfig(
            m=int(quant["m"]), ks=int(quant["ks"]), iters=int(quant["iters"]),
            seed=build_seed), metric=ix["metric"])
        jax.block_until_ready(index)
        phases["quantize_index"] = time.perf_counter() - t0
    if tier["kind"] == "mutable":
        t0 = time.perf_counter()
        index = MutableIndex(index, delta_cap=int(tier["delta_cap"]))
        jax.block_until_ready(index.snapshot().index)
        phases["mutable_wrap"] = time.perf_counter() - t0
    params = CompassParams(k=int(search["k"]), ef=int(search["ef"]),
                           planner=bool(search["planner"]), backend=search["backend"],
                           metric=ix["metric"])
    svc = CollectionService(params, batch_size=int(svc_cfg["batch_size"]),
                            max_wait_s=float(svc_cfg["max_wait_s"]),
                            max_batches_per_step=int(svc_cfg["max_batches_per_step"]))
    svc.create(COLLECTION, index, cache_capacity=int(svc_cfg["cache_capacity"]),
               quant=None if quant is None else QuantParams(
                   refine_factor=int(quant["refine_factor"]), rerank=quant["rerank"]))
    return svc, COLLECTION


@dataclasses.dataclass
class _Answer:
    rid: int
    ids: np.ndarray
    dists: np.ndarray


class ControlService:
    """The reference in the service's place, at ``precision`` ``"high"``.

    It answers the calls the window makes (``submit``, ``step``,
    ``pending``) in micro-batches of ``batch_size`` grouped by predicate
    shape, like the service, and returns the reference's own top-k with its
    float32 distances."""

    def __init__(self, x, attrs, k: int, metric: str, batch_size: int,
                 precision: str = "high"):
        self.x, self.attrs = jnp.asarray(x), jnp.asarray(attrs)
        self.k, self.metric, self.batch = k, metric, batch_size
        self.precision = precision
        self.queues: dict[int, list] = {}
        self._rid = 0

    def submit(self, name, query, pred):
        rid, self._rid = self._rid, self._rid + 1
        self.queues.setdefault(pred.lo.shape[0], []).append((rid, query, pred))
        return rid

    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def step(self):
        ready = [t for t, q in self.queues.items() if q]
        if not ready:
            return []
        t = max(ready, key=lambda t: (len(self.queues[t]), -t))
        jobs, self.queues[t] = self.queues[t][: self.batch], self.queues[t][self.batch :]
        pad = self.batch - len(jobs)
        qs = np.stack([j[1] for j in jobs] + [jobs[0][1]] * pad)
        lo = np.stack([np.asarray(j[2].lo) for j in jobs] + [np.asarray(jobs[0][2].lo)] * pad)
        hi = np.stack([np.asarray(j[2].hi) for j in jobs] + [np.asarray(jobs[0][2].hi)] * pad)
        ids, dists = reference.scan(self.x, self.attrs, jnp.asarray(qs), jnp.asarray(lo),
                                    jnp.asarray(hi), depth=self.k, metric=self.metric,
                                    precision=self.precision)
        ids, dists = np.asarray(ids), np.asarray(dists)
        return [_Answer(j[0], ids[i], dists[i]) for i, j in enumerate(jobs)]
