"""Multi-tenant collection service: one front door over many indexes
(DESIGN.md §Tenancy).

A :class:`CollectionService` manages *named collections* — each its own
index (:class:`MutableIndex` or immutable :class:`CompassIndex`), quant
configuration and result cache — behind a single scheduler:

* **Per-tenant admission queues + weighted-fair scheduling.**  Every
  collection keeps its own per-``t_bucket`` queues; dispatch order
  follows start-time-fair virtual time (SCFQ): a collection is charged
  ``1/weight`` of virtual time per micro-batch, and the ready collection
  with the smallest virtual time dispatches next.  A weight-3 tenant
  therefore gets ~3x the batch slots of a weight-1 tenant under
  contention, while an idle tenant's unused share flows to the others
  (its virtual time is clamped forward on its next dispatch, so no
  tenant banks credit while idle).
* **Queue-depth load shedding, never silent.**  When a collection's
  total queued depth reaches ``CollectionSpec.max_queue_depth``,
  ``submit`` returns a typed :class:`Rejected` (synchronously — the
  caller always learns the fate of the request) and increments
  ``compass_shed_total{tenant=...}``.
* **Executable-cache sharing across tenants.**  Compiled programs are
  keyed by shape, not by collection: mutable collections share one
  shape-key set (the underlying ``mutable_search`` jit cache is global,
  so N tenants whose ``(B, T, A, params, rows, delta_cap)`` keys
  collapse run one compiled program), and immutable collections share
  AOT executables keyed on ``(B, T, A, params, index-signature)`` — the
  index is an *argument* of the compiled program, so any same-shaped
  index reuses it.  ``compile_count`` == occupied shape keys, not
  tenants x buckets (the bench_tenancy ``--selfcheck`` tripwire).
* **Two-tier semantic result cache** per collection
  (:mod:`.cache`): exact request-byte hits (bitwise-identical replay)
  plus an opt-in near-duplicate tier keyed on the collection's own PQ
  codes; invalidated on every applied write and every epoch swap of the
  owning collection only.

Observability rides the PR-8/9 stack: every serving family carries a
``tenant`` label (``""`` for the single-index :class:`SearchService`),
so per-tenant p50/p99, shed rate and cache hit rate land in the existing
``compass_*`` series, `obs.health`'s ``admission_pressure`` watchdog
grades shed rate + queue fill, and :func:`repro.obs.slo.tenant_slos`
builds per-tenant burn-rate objectives from the same labels.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import time
from collections import OrderedDict, deque
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predicate as P
from repro.core.engine import CompassParams, compass_search_jit
from repro.core.index import CompassIndex
from repro.core.mutable import MutableIndex, mutable_search
from repro.core.planner import plan as plan_mod
from repro.core.quant.encode import encode_rows
from repro.obs import events as obs_events
from repro.obs import health as obs_health
from repro.obs import profiling as obs_prof
from repro.obs import registry as obs_reg
from repro.serving.search_service import BucketStats, WriteJob

from .cache import CollectionCache


@dataclasses.dataclass(frozen=True)
class CollectionSpec:
    """Per-collection policy: QoS weight, admission bound, cache sizing.

    ``weight`` is the fair-share ratio (a weight-3 collection gets 3x
    the micro-batch slots of a weight-1 collection under contention).
    ``max_queue_depth`` is the shed threshold over the collection's
    total queued requests.  ``cache_capacity`` bounds the exact result
    tier (0 disables caching); ``near_cache`` opts into the PQ-code
    near-duplicate tier (requires a quantized index).  ``quant``
    overrides the service-level search-time quant params for this
    collection only.
    """

    name: str
    weight: float = 1.0
    max_queue_depth: int = 1024
    cache_capacity: int = 256
    near_cache: bool = False
    quant: Optional[object] = None  # QuantParams | None

    def __post_init__(self):
        if not self.name:
            raise ValueError("collection name must be non-empty")
        if not self.weight > 0:
            raise ValueError(f"{self.name}: weight must be > 0, got {self.weight}")
        if self.max_queue_depth <= 0:
            raise ValueError(f"{self.name}: max_queue_depth must be > 0")
        if self.cache_capacity < 0:
            raise ValueError(f"{self.name}: cache_capacity must be >= 0")


@dataclasses.dataclass(frozen=True)
class Rejected:
    """Typed load-shed verdict — the *result* of an over-limit submit.

    Returned synchronously from :meth:`CollectionService.submit` instead
    of a request id; the request was never queued.  ``queue_depth`` is
    the depth observed at admission, ``limit`` the spec's threshold.
    """

    rid: int
    collection: str
    reason: str  # currently always "queue_depth"
    queue_depth: int
    limit: int


@dataclasses.dataclass
class TenantResult:
    """A :class:`~repro.serving.search_service.ServiceResult` plus
    tenancy provenance: the owning collection and, for cache-served
    responses, which tier answered (``"exact"`` hits are bitwise
    identical to an uncached search; ``"near"`` hits are approximate by
    contract and flagged so callers can ignore them per request)."""

    rid: int
    collection: str
    ids: np.ndarray  # (k,) int32
    dists: np.ndarray  # (k,) float32
    bucket: Optional[tuple]  # (B, T) shape bucket; None for cache hits
    queue_wait_s: float
    batch_exec_s: float
    epoch: Optional[int] = None
    cache_tier: Optional[str] = None  # None | "exact" | "near"


@dataclasses.dataclass
class _Job:
    """One admitted request inside a collection's ``t_bucket`` queue."""

    rid: int
    query: np.ndarray  # (d,) float32
    pred: P.Predicate  # (T, A) natural shape
    k: int
    t_submit: float
    t_bucket: int
    exact_key: Optional[tuple] = None
    near_key: Optional[tuple] = None


class _Collection:
    """Internal per-collection state: index, params, queues, cache,
    counters.  The public face is :class:`CollectionClient`."""

    def __init__(self, spec: CollectionSpec, index, params: CompassParams):
        self.spec = spec
        self.mutable = index if isinstance(index, MutableIndex) else None
        self._index = index if self.mutable is None else None
        self.params = params
        self.queues: dict[int, deque[_Job]] = {}
        self.writes: deque[WriteJob] = deque()
        self.vtime = 0.0
        self.cache = CollectionCache(
            spec.cache_capacity,
            near_capacity=spec.cache_capacity if spec.near_cache else 0,
        )
        self.cached_epoch = None if self.mutable is None else self.mutable.epoch
        self.stats: dict[tuple, BucketStats] = {}
        self.n_submitted = 0
        self.n_shed = 0
        self.n_cache_served = 0
        self.n_upserts = 0
        self.n_deletes = 0
        self.n_write_errors = 0

    @property
    def index(self) -> CompassIndex:
        return self._index if self.mutable is None else self.mutable.base

    def depth(self) -> int:
        return sum(len(q) for q in self.queues.values())


def _abstract(tree):
    """The shapes, dtypes and placements of ``tree``'s arrays, no data: what
    ``jit`` keys its compiled programs on (an uncommitted array has no
    placement of its own)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if getattr(a, "committed", False) else None),
        tree)


def _compiled_text(fn, args, params) -> str:
    return fn.lower(*args, params).compile().as_text()


def _index_sig(index: CompassIndex) -> tuple:
    """Hashable shape/dtype signature of an index pytree — the part of
    the AOT executable key that makes cross-tenant sharing safe: two
    indexes with the same signature are interchangeable arguments of one
    compiled program."""
    leaves, treedef = jax.tree_util.tree_flatten(index)
    return (
        str(treedef),
        tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves),
    )


class CollectionClient:
    """Handle to one named collection — the per-tenant API surface.

    Duck-type compatible with :class:`SearchService` for read traffic
    (``submit`` / ``step`` / ``flush`` / ``run_until_idle`` / ``poll`` /
    ``stats``), which is how ``RagIndex.make_service`` hands existing
    callers tenancy without an interface change.  ``run_until_idle`` and
    ``step`` drive the *whole* service (batches of other collections may
    execute) but return only this collection's results; other tenants'
    results stay pollable by rid.
    """

    def __init__(self, service: "CollectionService", name: str):
        self.service = service
        self.name = name

    def submit(self, query, pred, k: Optional[int] = None) -> Union[int, Rejected]:
        return self.service.submit(self.name, query, pred, k=k)

    def submit_upsert(self, gid: int, vector, attrs) -> None:
        self.service.submit_upsert(self.name, gid, vector, attrs)

    def submit_delete(self, gid: int) -> None:
        self.service.submit_delete(self.name, gid)

    def _mine(self, results: list[TenantResult]) -> list[TenantResult]:
        return [r for r in results if r.collection == self.name]

    def step(self) -> list[TenantResult]:
        return self._mine(self.service.step())

    def flush(self) -> list[TenantResult]:
        return self._mine(self.service.flush())

    def run_until_idle(self) -> list[TenantResult]:
        return self._mine(self.service.run_until_idle())

    def poll(self, rid: int) -> Optional[TenantResult]:
        return self.service.poll(rid)

    def pending(self) -> int:
        return self.service._col(self.name).depth()

    def compact(self, retrain_codebooks: bool = False) -> None:
        self.service.compact(self.name, retrain_codebooks=retrain_codebooks)

    def health(self):
        return self.service.health()

    @property
    def mutable(self) -> Optional[MutableIndex]:
        return self.service._col(self.name).mutable

    @property
    def index(self) -> CompassIndex:
        return self.service._col(self.name).index

    def stats(self) -> dict:
        return self.service.collection_stats(self.name)


class CollectionService:
    """Weighted-fair, load-shedding, result-caching front door over many
    named collections (module docstring has the design contract).

    Parameters mirror :class:`SearchService` where they overlap;
    ``max_batches_per_step`` bounds how many micro-batches one
    :meth:`step` may dispatch (0 = drain everything ready), which makes
    fair-share ratios observable per round and lets queues actually
    build toward the shed threshold under synthetic overload.
    """

    def __init__(
        self,
        params: CompassParams = CompassParams(),
        *,
        batch_size: int = 8,
        max_wait_s: float = 0.01,
        max_terms: int = 64,
        max_batches_per_step: int = 0,
        result_buffer: int = 4096,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.params = params
        self.batch_size = int(batch_size)
        self.max_wait_s = float(max_wait_s)
        self.max_terms = int(max_terms)
        self.max_batches_per_step = int(max_batches_per_step)
        self.result_buffer = int(result_buffer)
        self.clock = clock
        self._collections: dict[str, _Collection] = {}
        self._executables: dict[tuple, Callable] = {}  # immutable AOT, shared
        self._mutable_shapes: set[tuple] = set()  # mutable jit shapes, shared
        # serve_batch label -> how to get the compiled HLO text of its program
        self._programs: dict[str, Callable[[], str]] = {}
        self._results: OrderedDict[int, TenantResult] = OrderedDict()
        self._cache_served: list[TenantResult] = []
        self._rid = itertools.count()
        self._vtime = 0.0
        self.monitor: Optional[obs_health.Monitor] = None

    # -- collection lifecycle ------------------------------------------------

    def create(
        self,
        name: str,
        index: "CompassIndex | MutableIndex",
        *,
        spec: Optional[CollectionSpec] = None,
        **spec_kw,
    ) -> CollectionClient:
        """Register ``index`` under ``name``; returns the tenant handle.

        ``spec_kw`` (weight, max_queue_depth, cache_capacity, near_cache,
        quant) builds a :class:`CollectionSpec` when ``spec`` is not
        given.  Fails loudly at registration for every misconfiguration
        that would otherwise surface at first dispatch: duplicate names,
        quant params over an unquantized index, near-cache without PQ
        codes, and (mutable) a ShapePolicy that disagrees with the
        service params — the same cache-accounting guard
        :class:`SearchService` enforces.
        """
        if name in self._collections:
            raise ValueError(f"collection {name!r} already exists")
        spec = CollectionSpec(name=name, **spec_kw) if spec is None else spec
        if spec.name != name:
            raise ValueError(f"spec.name {spec.name!r} != collection name {name!r}")
        params = (
            self.params
            if spec.quant is None
            else dataclasses.replace(self.params, quant=spec.quant)
        )
        base = index.base if isinstance(index, MutableIndex) else index
        if params.quant is not None and base.qvecs is None:
            raise ValueError(
                f"collection {name!r}: quant params require a quantized index"
            )
        if spec.near_cache and base.qvecs is None:
            raise ValueError(
                f"collection {name!r}: near_cache keys on the index's PQ "
                "codes — quantize_index first"
            )
        if isinstance(index, MutableIndex):
            mine = dataclasses.replace(params.shape, ef=0, refine_factor=0)
            theirs = dataclasses.replace(index.shape, ef=0, refine_factor=0)
            if mine != theirs:
                raise ValueError(
                    f"collection {name!r}: params.shape != index ShapePolicy "
                    f"({mine} vs {theirs}); shared shape keys need one policy"
                )
        self._collections[name] = _Collection(spec, index, params)
        obs_events.emit(
            "collection_create",
            collection=name,
            weight=spec.weight,
            max_queue_depth=spec.max_queue_depth,
            mutable=isinstance(index, MutableIndex),
        )
        return CollectionClient(self, name)

    def drop(self, name: str) -> None:
        """Unregister a collection (queued work is discarded; shared
        executables stay — other tenants may hold the same shapes)."""
        col = self._col(name)
        dropped = col.depth() + len(col.writes)
        del self._collections[name]
        obs_events.emit("collection_drop", collection=name, dropped_queued=dropped)

    def collection(self, name: str) -> CollectionClient:
        self._col(name)  # raise on unknown
        return CollectionClient(self, name)

    def collections(self) -> tuple[str, ...]:
        return tuple(sorted(self._collections))

    def _col(self, name: str) -> _Collection:
        try:
            return self._collections[name]
        except KeyError:
            raise KeyError(f"unknown collection {name!r}") from None

    # -- admission -----------------------------------------------------------

    def submit(
        self,
        name: str,
        query: np.ndarray,
        pred: "P.Pred | P.Predicate",
        k: Optional[int] = None,
    ) -> Union[int, Rejected]:
        """Admit one request to collection ``name``.

        Returns a request id, or a typed :class:`Rejected` when the
        collection's queue is at its shed threshold (the request was
        never queued — the caller learns synchronously, nothing is
        dropped silently).  Cache hits are admitted as already-complete:
        the id is returned and the result is delivered by the next
        ``step()``/``flush()`` (and via :meth:`poll` immediately).
        """
        col = self._col(name)
        index = col.index
        if isinstance(pred, P.Pred):
            pred = pred.tensor(index.n_attrs)
        if pred.lo.ndim != 2:
            raise ValueError(f"expected (T, A) predicate, got shape {pred.lo.shape}")
        if pred.n_attrs != index.n_attrs:
            raise ValueError(
                f"predicate has {pred.n_attrs} attrs, collection {name!r} "
                f"has {index.n_attrs}"
            )
        k = col.params.k if k is None else int(k)
        if not 0 < k <= col.params.k:
            raise ValueError(f"k={k} outside (0, params.k={col.params.k}]")
        if pred.n_terms > self.max_terms:
            raise ValueError(
                f"predicate has {pred.n_terms} terms > max_terms={self.max_terms}"
            )
        query = np.asarray(query, np.float32)
        if query.shape != (index.dim,):
            raise ValueError(f"query shape {query.shape} != ({index.dim},)")

        rid = next(self._rid)
        col.n_submitted += 1
        if obs_reg.enabled():
            obs_reg.registry().counter(
                "compass_submitted_total",
                "Requests offered for admission",
                labelnames=("tenant",),
            ).inc(tenant=name)

        depth = col.depth()
        if depth >= col.spec.max_queue_depth:
            col.n_shed += 1
            if obs_reg.enabled():
                obs_reg.registry().counter(
                    "compass_shed_total",
                    "Requests shed at admission (typed Rejected)",
                    labelnames=("tenant",),
                ).inc(tenant=name)
            obs_events.emit(
                "shed",
                collection=name,
                queue_depth=depth,
                limit=col.spec.max_queue_depth,
            )
            return Rejected(
                rid=rid,
                collection=name,
                reason="queue_depth",
                queue_depth=depth,
                limit=col.spec.max_queue_depth,
            )

        # an epoch swap done directly on the MutableIndex (not via this
        # service) must not let stale entries serve — reconcile before lookup
        self._check_epoch(col)
        exact_key = near_key = None
        if col.cache.enabled:
            exact_key = (
                query.tobytes(),
                np.asarray(pred.lo, np.float32).tobytes(),
                np.asarray(pred.hi, np.float32).tobytes(),
                k,
            )
            if col.cache.near_capacity > 0:
                near_key = (
                    self._query_codes(col, query),
                    exact_key[1],
                    exact_key[2],
                    k,
                )
            entry, tier = col.cache.lookup(exact_key, near_key)
            if entry is not None:
                res = TenantResult(
                    rid=rid,
                    collection=name,
                    ids=entry.ids[:k].copy(),
                    dists=entry.dists[:k].copy(),
                    bucket=None,
                    queue_wait_s=0.0,
                    batch_exec_s=0.0,
                    epoch=entry.epoch,
                    cache_tier=tier,
                )
                col.n_cache_served += 1
                self._store(res)
                self._cache_served.append(res)
                if obs_reg.enabled():
                    obs_reg.registry().counter(
                        "compass_result_cache_hits_total",
                        "Requests answered from the semantic result cache",
                        labelnames=("tenant", "tier"),
                    ).inc(tenant=name, tier=tier)
                return rid
            if obs_reg.enabled():
                obs_reg.registry().counter(
                    "compass_result_cache_misses_total",
                    "Cache-enabled requests that required a live search",
                    labelnames=("tenant",),
                ).inc(tenant=name)

        job = _Job(
            rid=rid,
            query=query,
            pred=pred,
            k=k,
            t_submit=self.clock(),
            t_bucket=P.term_bucket(pred.n_terms),
            exact_key=exact_key,
            near_key=near_key,
        )
        col.queues.setdefault(job.t_bucket, deque()).append(job)
        return rid

    def _query_codes(self, col: _Collection, query: np.ndarray) -> bytes:
        """The query's PQ code word under this collection's codebooks —
        the near-duplicate cache key (ISSUE: keyed on the collection's
        *own* codes, so a word can never mean the same thing in another
        collection)."""
        qv = col.index.qvecs
        codes = np.asarray(encode_rows(qv.codebooks, qv.mean, query[None]))
        return codes[0].tobytes()

    # -- write admission -----------------------------------------------------

    def _require_mutable(self, col: _Collection) -> MutableIndex:
        if col.mutable is None:
            raise ValueError(
                f"writes require collection {col.spec.name!r} to wrap a MutableIndex"
            )
        return col.mutable

    def submit_upsert(self, name: str, gid: int, vector, attrs) -> None:
        col = self._col(name)
        self._require_mutable(col)
        vector = np.asarray(vector, np.float32)
        attrs = np.asarray(attrs, np.float32)
        if vector.shape != (col.index.dim,):
            raise ValueError(f"vector shape {vector.shape} != ({col.index.dim},)")
        if attrs.shape != (col.index.n_attrs,):
            raise ValueError(f"attrs shape {attrs.shape} != ({col.index.n_attrs},)")
        col.writes.append(WriteJob("upsert", int(gid), vector, attrs))

    def submit_delete(self, name: str, gid: int) -> None:
        col = self._col(name)
        mut = self._require_mutable(col)
        gid = int(gid)
        if gid not in mut and not any(
            w.kind == "upsert" and w.gid == gid for w in col.writes
        ):
            raise KeyError(f"unknown id {gid} in collection {name!r}")
        col.writes.append(WriteJob("delete", gid))

    def _apply_writes(self, col: _Collection) -> int:
        """Drain one collection's write queue (round boundary only —
        batches stay pinned to a single epoch).  Any applied write
        invalidates *this collection's* result cache (upserts can
        auto-compact on delta overflow, so this also covers implicit
        epoch swaps)."""
        applied = 0
        while col.writes:
            w = col.writes.popleft()
            if w.kind == "upsert":
                col.mutable.upsert(w.gid, w.vector, w.attrs)
                col.n_upserts += 1
            else:
                try:
                    col.mutable.delete(w.gid)
                    col.n_deletes += 1
                except KeyError:  # raced by a queued delete of the same gid
                    col.n_write_errors += 1
                    obs_events.emit(
                        "write_error",
                        kind_detail="delete_missing",
                        gid=w.gid,
                        collection=col.spec.name,
                    )
                    if obs_reg.enabled():
                        obs_reg.registry().counter(
                            "compass_write_errors_total",
                            "Rejected/raced write operations",
                            labelnames=("tenant",),
                        ).inc(tenant=col.spec.name)
            applied += 1
        if applied:
            col.cache.invalidate()
            col.cached_epoch = col.mutable.epoch
        return applied

    def _check_epoch(self, col: _Collection) -> None:
        """Invalidate the collection's cache if its index epoch moved
        outside this service's write path (direct ``compact()`` on the
        operator's MutableIndex handle)."""
        if col.mutable is not None and col.mutable.epoch != col.cached_epoch:
            col.cache.invalidate()
            col.cached_epoch = col.mutable.epoch

    def compact(self, name: str, retrain_codebooks: bool = False) -> None:
        """Epoch-swap one collection; its cache (and only its cache) is
        invalidated."""
        col = self._col(name)
        self._require_mutable(col).compact(retrain_codebooks=retrain_codebooks)
        self._check_epoch(col)

    def invalidate(self, name: str) -> int:
        """Manually clear one collection's result cache."""
        return self._col(name).cache.invalidate()

    # -- scheduling ----------------------------------------------------------

    def _charge(self, col: _Collection) -> None:
        """SCFQ virtual-time accounting: one micro-batch costs
        ``1/weight``; clamping the start to the service virtual time is
        what stops an idle tenant banking credit."""
        start = max(col.vtime, self._vtime)
        col.vtime = start + 1.0 / col.spec.weight
        self._vtime = start

    def _pick_ready(self, now: float):
        """The next (collection, t_bucket, full) to dispatch: among
        collections with a ready bucket (full batch, or oldest request
        past the deadline), the one with the smallest virtual time; full
        buckets beat deadline flushes within a collection."""
        best = None
        for col in self._collections.values():
            cands = []
            for tb, q in col.queues.items():
                if len(q) >= self.batch_size:
                    cands.append((True, len(q), -tb, tb))
                elif q and now - q[0].t_submit >= self.max_wait_s:
                    cands.append((False, len(q), -tb, tb))
            if not cands:
                continue
            full, _, _, tb = max(cands)
            if best is None or (col.vtime, col.spec.name) < (
                best[0].vtime,
                best[0].spec.name,
            ):
                best = (col, tb, full)
        return best

    def step(self) -> list[TenantResult]:
        """One scheduling round: apply every collection's queued writes,
        deliver pending cache hits, then dispatch ready micro-batches in
        weighted-fair order (at most ``max_batches_per_step`` when set).
        """
        with obs_prof.annotate("compass/serve/writes"):
            for col in self._collections.values():
                if col.mutable is not None:
                    self._apply_writes(col)
                self._check_epoch(col)
        done = self._drain_cache_served()
        now = self.clock()
        budget = self.max_batches_per_step or float("inf")
        while budget > 0:
            pick = self._pick_ready(now)
            if pick is None:
                break
            col, tb, full = pick
            done.extend(self._dispatch(col, tb, full))
            self._charge(col)
            budget -= 1
        with obs_prof.annotate("compass/serve/gauges"):
            self._publish_gauges()
            if self.monitor is not None:
                self.monitor.tick()
        return done

    def flush(self) -> list[TenantResult]:
        """Dispatch everything queued regardless of deadlines, still in
        weighted-fair order (drain)."""
        with obs_prof.annotate("compass/serve/writes"):
            for col in self._collections.values():
                if col.mutable is not None:
                    self._apply_writes(col)
                self._check_epoch(col)
        done = self._drain_cache_served()
        while True:
            ready = [
                (col, tb)
                for col in self._collections.values()
                for tb, q in col.queues.items()
                if q
            ]
            if not ready:
                break
            col = min(
                {c for c, _ in ready}, key=lambda c: (c.vtime, c.spec.name)
            )
            tbs = [tb for c, tb in ready if c is col]
            tb = max(tbs, key=lambda t: (len(col.queues[t]), -t))
            done.extend(
                self._dispatch(col, tb, full=len(col.queues[tb]) >= self.batch_size)
            )
            self._charge(col)
        with obs_prof.annotate("compass/serve/gauges"):
            self._publish_gauges()
        return done

    def run_until_idle(self) -> list[TenantResult]:
        done = self.step()
        done.extend(self.flush())
        return done

    def poll(self, rid: int) -> Optional[TenantResult]:
        return self._results.pop(rid, None)

    def pending(self) -> int:
        return sum(col.depth() for col in self._collections.values())

    def pending_writes(self) -> int:
        return sum(len(col.writes) for col in self._collections.values())

    def _drain_cache_served(self) -> list[TenantResult]:
        out = self._cache_served
        self._cache_served = []
        return out

    def _store(self, res: TenantResult) -> None:
        self._results[res.rid] = res
        while len(self._results) > self.result_buffer:
            self._results.popitem(last=False)

    # -- execution -----------------------------------------------------------

    def _record_compile(self, cache: str, shape: tuple) -> None:
        obs_events.emit("compile", cache=cache, shape=list(shape), wall_s=None)
        if obs_reg.enabled():
            obs_reg.registry().counter(
                "compass_compiles_total",
                "Search executable compilations",
                labelnames=("cache",),
            ).inc(cache=cache)

    def _record_program(self, label: str, text_fn: Callable[[], str]) -> None:
        self._programs[label] = text_fn
        obs_prof.SERVED.record(label, text_fn)

    def program_texts(self) -> dict[str, str]:
        """``{"B{B}xT{T}": compiled HLO text}`` of the program each bucket
        label served, the label of its ``compass/serve_batch`` span: from
        the cached executable on the AOT path, and on the mutable path from
        ``mutable_search`` lowered again on the abstract shapes of the
        bucket's first dispatch (a compile-cache hit).  For joining a
        device trace to the instructions, and their stage scopes, by name."""
        return {label: self._programs[label]() for label in sorted(self._programs)}

    def _dispatch(self, col: _Collection, t_bucket: int, full: bool) -> list[TenantResult]:
        name = col.spec.name
        index = col.index
        B = self.batch_size
        label = f"B{B}xT{t_bucket}"
        with obs_prof.annotate("compass/serve/pack"):
            q = col.queues[t_bucket]
            jobs = [q.popleft() for _ in range(min(self.batch_size, len(q)))]
            n_fill = B - len(jobs)
            queries = np.zeros((B, index.dim), np.float32)
            for i, job in enumerate(jobs):
                queries[i] = job.query
            preds = [j.pred for j in jobs] + [P.never_true(index.n_attrs)] * n_fill
            pred = P.stack_predicates(preds, n_terms=t_bucket)
            qj = jnp.asarray(queries)

        t0 = self.clock()
        epoch = None
        st = col.stats.setdefault((B, t_bucket), BucketStats())
        if col.mutable is not None:
            snap = col.mutable.snapshot()
            epoch = snap.epoch
            # same key fields as SearchService's mutable path — tenants
            # whose shapes collapse share one entry here AND one compiled
            # program in the global mutable_search jit cache
            key = (B, t_bucket, pred.lo.shape[-1], col.params,
                   snap.index.n_records, snap.delta.cap)
            if key in self._mutable_shapes:
                st.n_cache_hits += 1
            else:
                self._mutable_shapes.add(key)
                st.n_compiles += 1
                self._record_compile(
                    "jit",
                    (B, t_bucket, pred.lo.shape[-1],
                     snap.index.n_records, snap.delta.cap),
                )
                args = (snap.index, snap.base_gids, snap.delta, qj, pred)
                self._record_program(label, functools.partial(
                    _compiled_text, mutable_search, _abstract(args), col.params))
            with obs_prof.annotate(f"compass/serve_batch/{label}"):
                res = mutable_search(
                    snap.index, snap.base_gids, snap.delta, qj, pred, col.params
                )
                res.ids.block_until_ready()
        else:
            key = (B, t_bucket, pred.lo.shape[-1], col.params, _index_sig(index))
            exe = self._executables.get(key)
            if exe is None:
                exe = compass_search_jit.lower(index, qj, pred, col.params).compile()
                self._executables[key] = exe
                st.n_compiles += 1
                self._record_compile("aot", (B, t_bucket, pred.lo.shape[-1]))
                self._record_program(label, exe.as_text)
            else:
                st.n_cache_hits += 1
            with obs_prof.annotate(f"compass/serve_batch/{label}"):
                res = exe(index, qj, pred)
                res.ids.block_until_ready()
        exec_s = self.clock() - t0

        with obs_prof.annotate("compass/serve/unpack"):
            st.n_requests += len(jobs)
            st.n_batches += 1
            st.n_fillers += n_fill
            st.n_full_flush += int(full)
            st.n_deadline_flush += int(not full)
            st.total_exec_s += exec_s
            modes = np.asarray(res.stats.mode)[: len(jobs)]
            st.n_mode_prefilter += int(np.sum(modes == plan_mod.PREFILTER))
            st.n_mode_cooperative += int(np.sum(modes == plan_mod.COOPERATIVE))
            st.n_mode_postfilter += int(np.sum(modes == plan_mod.POSTFILTER))

            ids = np.asarray(res.ids)
            dists = np.asarray(res.dists)
            out = []
            for i, job in enumerate(jobs):
                wait = t0 - job.t_submit
                st.total_wait_s += wait
                r = TenantResult(
                    rid=job.rid,
                    collection=name,
                    ids=ids[i, : job.k].copy(),
                    dists=dists[i, : job.k].copy(),
                    bucket=(B, t_bucket),
                    queue_wait_s=wait,
                    batch_exec_s=exec_s,
                    epoch=epoch,
                )
                self._store(r)
                out.append(r)
                if job.exact_key is not None:
                    # cache the engine's full-k row so the entry replays the
                    # exact bytes the live path would have truncated from
                    col.cache.insert(
                        job.exact_key, job.near_key,
                        ids[i].copy(), dists[i].copy(), epoch=epoch,
                    )
        if obs_reg.enabled():
            with obs_prof.annotate("compass/serve/gauges"):
                lanes = len(jobs)
                sliced = jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:lanes], res.stats
                )
                obs_reg.record_search_stats(
                    sliced, labels={"bucket": label, "tenant": name}
                )
                R = obs_reg.registry()
                R.counter(
                    "compass_serve_requests_total", "Real requests served",
                    labelnames=("bucket", "tenant"),
                ).inc(lanes, bucket=label, tenant=name)
                R.counter(
                    "compass_serve_batches_total", "Micro-batches dispatched",
                    labelnames=("bucket", "tenant"),
                ).inc(bucket=label, tenant=name)
                if n_fill:
                    R.counter(
                        "compass_serve_fillers_total", "Padded filler lanes dispatched",
                        labelnames=("bucket", "tenant"),
                    ).inc(n_fill, bucket=label, tenant=name)
                R.histogram(
                    "compass_serve_exec_seconds", "Micro-batch execution wall time",
                    labelnames=("bucket", "tenant"), buckets=obs_reg.LATENCY_BUCKETS_S,
                ).observe(exec_s, bucket=label, tenant=name)
                wait_h = R.histogram(
                    "compass_serve_wait_seconds", "Per-request queue wait",
                    labelnames=("bucket", "tenant"), buckets=obs_reg.LATENCY_BUCKETS_S,
                )
                for job in jobs:
                    wait_h.observe(t0 - job.t_submit, bucket=label, tenant=name)
                # the batched loop runs until its slowest lane is done: the
                # iterations it ran are the largest n_steps, fillers included
                R.counter(
                    "compass_loop_steps_total", "Engine loop iterations run",
                    labelnames=("bucket", "tenant"),
                ).inc(int(np.max(np.asarray(res.stats.n_steps))), bucket=label, tenant=name)
        return out

    # -- observability -------------------------------------------------------

    def _publish_gauges(self) -> None:
        if not obs_reg.enabled():
            return
        R = obs_reg.registry()
        g_depth = R.gauge(
            "compass_queue_depth", "Queued requests per collection", ("tenant",)
        )
        g_limit = R.gauge(
            "compass_queue_limit", "Admission shed threshold per collection",
            ("tenant",),
        )
        g_entries = R.gauge(
            "compass_result_cache_entries", "Live result-cache entries",
            ("tenant", "tier"),
        )
        for name, col in self._collections.items():
            g_depth.set(col.depth(), tenant=name)
            g_limit.set(col.spec.max_queue_depth, tenant=name)
            ent = col.cache.stats()
            g_entries.set(ent["entries_exact"], tenant=name, tier="exact")
            g_entries.set(ent["entries_near"], tenant=name, tier="near")

    def enable_monitoring(self, **kwargs) -> "obs_health.Monitor":
        kwargs.setdefault("clock", self.clock)
        self.monitor = obs_health.Monitor(**kwargs)
        return self.monitor

    def health(self) -> "obs_health.HealthReport":
        if self.monitor is None:
            self.enable_monitoring()
        return self.monitor.evaluate()

    @property
    def compile_count(self) -> int:
        """Total XLA compilations == occupied shape keys across ALL
        collections (shared caches — never tenants x buckets)."""
        return len(self._executables) + len(self._mutable_shapes)

    def collection_stats(self, name: str) -> dict:
        """JSON-ready per-collection counters (plus the service-level
        compile accounting callers historically read off a
        SearchService: ``compiles`` / ``occupied_buckets``)."""
        col = self._col(name)
        n_req = sum(s.n_requests for s in col.stats.values())
        wait = sum(s.total_wait_s for s in col.stats.values())
        return {
            "collection": name,
            "weight": col.spec.weight,
            "max_queue_depth": col.spec.max_queue_depth,
            "compiles": self.compile_count,
            "occupied_buckets": len(col.stats),
            "pending": col.depth(),
            "n_submitted": col.n_submitted,
            "n_shed": col.n_shed,
            "n_requests": n_req + col.n_cache_served,
            "n_searched": n_req,
            "n_cache_served": col.n_cache_served,
            "n_batches": sum(s.n_batches for s in col.stats.values()),
            "n_fillers": sum(s.n_fillers for s in col.stats.values()),
            "mean_wait_s": wait / n_req if n_req else 0.0,
            "cache": col.cache.stats(),
            "mutable": col.mutable is not None,
            "epoch": None if col.mutable is None else col.mutable.epoch,
            "n_upserts": col.n_upserts,
            "n_deletes": col.n_deletes,
            "n_write_errors": col.n_write_errors,
            "quant": (
                None
                if col.params.quant is None
                else dataclasses.asdict(col.params.quant)
            ),
            "buckets": {
                f"B{b}xT{t}": dataclasses.asdict(s)
                for (b, t), s in sorted(col.stats.items())
            },
        }

    def stats(self) -> dict:
        """Service-wide snapshot: shared-cache accounting + every
        collection's section (disjoint by construction — the isolation
        the tenant label gives the registry, mirrored host-side)."""
        cols = {name: self.collection_stats(name) for name in sorted(self._collections)}
        return {
            "batch_size": self.batch_size,
            "max_wait_s": self.max_wait_s,
            "max_batches_per_step": self.max_batches_per_step,
            "compiles": self.compile_count,
            "occupied_shape_buckets": self.compile_count,
            "n_collections": len(self._collections),
            "n_requests": sum(c["n_requests"] for c in cols.values()),
            "n_submitted": sum(c["n_submitted"] for c in cols.values()),
            "n_shed": sum(c["n_shed"] for c in cols.values()),
            "n_cache_served": sum(c["n_cache_served"] for c in cols.values()),
            "obs_enabled": obs_reg.enabled(),
            "obs_events": dict(obs_events.EVENTS.counts()),
            "health": (
                None
                if self.monitor is None or self.monitor.last_report is None
                else self.monitor.last_report.to_dict()
            ),
            "collections": cols,
        }
