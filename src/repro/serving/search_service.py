"""Continuous-batching serving layer for Compass filtered search.

``compass_search`` is a jitted function over static shapes: every distinct
term count ``T``, batch size ``B``, attribute count ``A`` or
:class:`CompassParams` is a fresh XLA program.  Serving traffic with
arbitrary mixed conjunction/disjunction shapes through it directly would
compile without bound.  :class:`SearchService` closes the gap between a
request stream and the engine:

* **Predicate-shape bucketing** — each request's DNF predicate is padded to
  the next power-of-two term count (``predicate.term_bucket``), so arbitrary
  widths collapse into a logarithmic number of static shapes.
* **Micro-batch formation** — per-bucket admission queues; a bucket flushes
  when it holds ``batch_size`` requests (full flush) or when its oldest
  request has waited ``max_wait_s`` (deadline flush).  Partial batches are
  padded to the fixed ``B`` with unsatisfiable-predicate fillers
  (``predicate.never_true``) whose lanes can never produce a result.
* **Compiled-executable cache** — one AOT-compiled executable per occupied
  ``(B, T, A, CompassParams)`` key (``compass_search_jit.lower(...).compile()``);
  steady-state traffic runs with a bounded, observable number of
  compilations (``stats()["compiles"]`` == occupied buckets).  For mutable
  services the snapshot shapes enter the key too — and because
  ``ShapePolicy`` buckets the base row count across compaction folds and
  fixes the delta capacity, those shapes are *epoch-stable*: a compaction
  swap re-uses the previous epoch's executables and the steady-state
  recompile budget is zero (the bench_updates ``--selfcheck`` tripwire).
* **Padding stripping** — :class:`ServiceResult` drops filler lanes, pad
  terms and the ``k``-prefix, so a response is bitwise-identical to calling
  ``compass_search`` directly on that query with its natural-``T`` predicate
  and the service's ``CompassParams`` (enforced by
  tests/test_search_service.py).

Per-request ``k`` must satisfy ``k <= params.k``: the engine's candidate
flow depends on ``params.k`` (round pacing uses ``k // 2``), so the service
searches at the fixed ``params.k`` and truncates — the response equals the
``k``-prefix of the direct call, not a differently-paced search.

The service is single-threaded by design (JAX dispatch is the bottleneck,
not Python): callers ``submit`` then drive ``step()`` / ``run_until_idle``.
A ``clock`` injection point makes deadline behaviour testable.

Observability: the service is the system's natural sync point (every batch
ends in ``block_until_ready``), so per-batch registry recording happens
here when ``repro.obs`` is enabled — request/batch/filler counters,
exec/wait latency histograms, and the device-side ``SearchStats`` of the
real (non-filler) lanes, all labelled by ``bucket="B{B}xT{T}"``.  Compile
events (both cache families) and write errors flow to the structured event
log.  All of it is off by default and never touches the traced program —
results are bitwise identical with obs on or off.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import OrderedDict, deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import predicate as P
from repro.core.engine import CompassParams, compass_search_jit
from repro.core.index import CompassIndex
from repro.core.mutable import MutableIndex, mutable_search
from repro.core.planner import plan as plan_mod
from repro.obs import events as obs_events
from repro.obs import health as obs_health
from repro.obs import profiling as obs_prof
from repro.obs import registry as obs_reg


@dataclasses.dataclass
class SearchJob:
    """One admitted request, routed to the ``t_bucket`` queue."""

    rid: int
    query: np.ndarray  # (d,) float32
    pred: P.Predicate  # (T, A) natural (unpadded) shape
    k: int
    t_submit: float
    t_bucket: int


@dataclasses.dataclass
class WriteJob:
    """One admitted mutation (mutable-index services only).

    Writes are applied in admission order at scheduling-round boundaries
    (:meth:`SearchService.apply_writes`), never between the formation and
    execution of a search micro-batch — that is what keeps every batch
    pinned to a single index epoch.
    """

    kind: str  # "upsert" | "delete"
    gid: int
    vector: Optional[np.ndarray] = None  # (d,) for upserts
    attrs: Optional[np.ndarray] = None  # (A,) for upserts


@dataclasses.dataclass
class ServiceResult:
    """Response with all padding stripped.

    ``ids``/``dists`` are the first ``k`` rows of the engine result for this
    query's lane; ``ids == index.n_records`` marks empty (unfilled) slots
    exactly as in a direct ``compass_search`` call.
    """

    rid: int
    ids: np.ndarray  # (k,) int32
    dists: np.ndarray  # (k,) float32
    bucket: tuple  # (B, T) shape bucket that served the request
    queue_wait_s: float
    batch_exec_s: float
    # index epoch the whole micro-batch ran against (mutable-index services;
    # None when serving an immutable CompassIndex).  Every result of one
    # batch carries the same epoch — a batch never straddles a compaction.
    epoch: Optional[int] = None


@dataclasses.dataclass
class BucketStats:
    """Per-(B, T) bucket counters, serializable into BENCH JSON."""

    n_requests: int = 0
    n_batches: int = 0
    n_full_flush: int = 0
    n_deadline_flush: int = 0
    n_fillers: int = 0  # padded lanes dispatched
    n_compiles: int = 0
    n_cache_hits: int = 0
    total_wait_s: float = 0.0
    total_exec_s: float = 0.0
    # planner execution modes chosen for real (non-filler) lanes; all
    # cooperative when the planner is off (CompassParams.planner=False)
    n_mode_prefilter: int = 0
    n_mode_cooperative: int = 0
    n_mode_postfilter: int = 0


class SearchService:
    """Continuous-batching filtered-search service over one CompassIndex.

    Parameters
    ----------
    index : the (immutable) index to serve.
    params : engine parameters shared by every request; ``params.k`` is the
        max per-request ``k``.
    batch_size : fixed micro-batch width ``B`` every executable is built for.
    max_wait_s : deadline — a non-empty bucket older than this flushes
        partially padded rather than waiting for a full batch.
    max_terms : reject predicates whose DNF exceeds this many terms
        (bounds the largest compiled shape).
    result_buffer : how many completed results :meth:`poll` retains
        (oldest evicted first).  ``step()``/``flush()`` return values are
        the primary delivery path; the poll buffer exists for callers that
        track request ids, and is bounded so a caller consuming only the
        return values cannot leak memory under sustained traffic.
    clock : monotonic time source (injectable for deterministic tests).
    """

    def __init__(
        self,
        index: "CompassIndex | MutableIndex",
        params: CompassParams = CompassParams(),
        *,
        batch_size: int = 8,
        max_wait_s: float = 0.01,
        max_terms: int = 64,
        result_buffer: int = 4096,
        clock: Callable[[], float] = time.monotonic,
    ):
        # A MutableIndex enables the write path (submit_upsert/submit_delete)
        # and epoch-pinned dispatch; searches then report global ids.
        self.mutable = index if isinstance(index, MutableIndex) else None
        self.params = params
        self.batch_size = int(batch_size)
        self.max_wait_s = float(max_wait_s)
        self.max_terms = int(max_terms)
        self.result_buffer = int(result_buffer)
        self.clock = clock
        self._index = index if self.mutable is None else None
        self._rid = itertools.count()
        self._queues: dict[int, deque[SearchJob]] = {}
        self._writes: deque[WriteJob] = deque()
        self._executables: dict[tuple, Callable] = {}
        self._mutable_shapes: set[tuple] = set()  # compile accounting (jit path)
        self._results: OrderedDict[int, ServiceResult] = OrderedDict()
        self._stats: dict[tuple, BucketStats] = {}
        self.n_upserts = 0
        self.n_deletes = 0
        self.n_write_errors = 0
        # continuous monitoring (obs/health.py): attached explicitly via
        # enable_monitoring() or lazily by the first health() call; when
        # present, step() ticks it — a no-op unless obs is enabled, so the
        # disabled steady-state cost is one None check per round
        self.monitor: Optional[obs_health.Monitor] = None
        if params.quant is not None and self.index.qvecs is None:
            raise ValueError(
                "params.quant requires a quantized index "
                "(core.quant.quantize_index) — fail at construction, not "
                "at the first dispatch"
            )
        if self.mutable is not None:
            # the executable-cache key embeds params.shape while the actual
            # compiled shapes (row bucket, delta cap) come from the index's
            # own policy — a mismatch would make the cache accounting lie
            # about the steady-state recompile budget, so fail loudly here.
            # Compare with the construction-time overrides zeroed: params
            # normalizes shape.ef / shape.refine_factor after adoption.
            mine = dataclasses.replace(params.shape, ef=0, refine_factor=0)
            theirs = dataclasses.replace(self.mutable.shape, ef=0, refine_factor=0)
            if mine != theirs:
                raise ValueError(
                    "params.shape != mutable index's ShapePolicy "
                    f"({mine} vs {theirs}); construct both from one policy "
                    "so cache keys reflect the served shapes"
                )

    @property
    def index(self) -> CompassIndex:
        """The index being served (the current base for mutable services)."""
        return self._index if self.mutable is None else self.mutable.base

    # -- admission -----------------------------------------------------------

    def submit(
        self,
        query: np.ndarray,
        pred: "P.Pred | P.Predicate",
        k: Optional[int] = None,
    ) -> int:
        """Admit one ``(query, pred, k)`` job; returns a request id.

        ``pred`` may be a host-side :class:`Pred` tree (lowered here with
        its natural term count) or an already-lowered ``(T, A)``
        :class:`Predicate`.
        """
        if isinstance(pred, P.Pred):
            pred = pred.tensor(self.index.n_attrs)
        if pred.lo.ndim != 2:
            raise ValueError(f"expected (T, A) predicate, got shape {pred.lo.shape}")
        if pred.n_attrs != self.index.n_attrs:
            raise ValueError(
                f"predicate has {pred.n_attrs} attrs, index has {self.index.n_attrs}"
            )
        k = self.params.k if k is None else int(k)
        if not 0 < k <= self.params.k:
            raise ValueError(f"k={k} outside (0, params.k={self.params.k}]")
        if pred.n_terms > self.max_terms:
            raise ValueError(f"predicate has {pred.n_terms} terms > max_terms={self.max_terms}")
        query = np.asarray(query, np.float32)
        if query.shape != (self.index.dim,):
            raise ValueError(f"query shape {query.shape} != ({self.index.dim},)")
        rid = next(self._rid)
        job = SearchJob(
            rid=rid,
            query=query,
            pred=pred,
            k=k,
            t_submit=self.clock(),
            t_bucket=P.term_bucket(pred.n_terms),
        )
        self._queues.setdefault(job.t_bucket, deque()).append(job)
        return rid

    # -- write admission (mutable services) ----------------------------------

    def _require_mutable(self) -> MutableIndex:
        if self.mutable is None:
            raise ValueError("writes require a SearchService over a MutableIndex")
        return self.mutable

    def submit_upsert(self, gid: int, vector: np.ndarray, attrs: np.ndarray) -> None:
        """Admit an upsert; applied at the next scheduling-round boundary."""
        self._require_mutable()
        vector = np.asarray(vector, np.float32)
        attrs = np.asarray(attrs, np.float32)
        if vector.shape != (self.index.dim,):
            raise ValueError(f"vector shape {vector.shape} != ({self.index.dim},)")
        if attrs.shape != (self.index.n_attrs,):
            raise ValueError(f"attrs shape {attrs.shape} != ({self.index.n_attrs},)")
        self._writes.append(WriteJob("upsert", int(gid), vector, attrs))

    def submit_delete(self, gid: int) -> None:
        """Admit a delete; applied at the next scheduling-round boundary.

        Admission checks the id against the *current* index state — a gid
        queued for upsert in the same round is not yet visible.  The drain
        re-checks (the authoritative ordering is application order), so a
        delete raced by an earlier queued delete degrades to a counted
        no-op rather than poisoning the scheduling round.
        """
        mut = self._require_mutable()
        gid = int(gid)
        if gid not in mut and not any(
            w.kind == "upsert" and w.gid == gid for w in self._writes
        ):
            raise KeyError(f"unknown id {gid}")
        self._writes.append(WriteJob("delete", gid))

    def apply_writes(self) -> int:
        """Drain the write queue into the mutable index (may compact).

        Runs at the top of :meth:`step` / :meth:`flush`, i.e. strictly
        between micro-batches: a batch formed afterwards sees every applied
        write, and a batch already dispatched saw none of them — each batch
        is pinned to exactly one epoch.  Returns the number of writes
        applied.
        """
        applied = 0
        while self._writes:
            w = self._writes.popleft()
            if w.kind == "upsert":
                self.mutable.upsert(w.gid, w.vector, w.attrs)
                self.n_upserts += 1
            else:
                try:
                    self.mutable.delete(w.gid)
                    self.n_deletes += 1
                except KeyError:  # raced by a queued delete of the same gid
                    self.n_write_errors += 1
                    obs_events.emit("write_error", kind_detail="delete_missing", gid=w.gid)
                    if obs_reg.enabled():
                        obs_reg.registry().counter(
                            "compass_write_errors_total",
                            "Rejected/raced write operations",
                            labelnames=("tenant",),
                        ).inc(tenant="")
            applied += 1
        return applied

    # -- batch formation -----------------------------------------------------

    def step(self) -> list[ServiceResult]:
        """One scheduling round: apply queued writes, then flush every full
        bucket and every non-empty bucket whose oldest request has exceeded
        the deadline.  Returns the results completed this round (also
        retrievable via :meth:`poll`)."""
        if self.mutable is not None:
            with obs_prof.annotate("compass/serve/writes"):
                self.apply_writes()
        done: list[ServiceResult] = []
        now = self.clock()
        for t_bucket, q in self._queues.items():
            while len(q) >= self.batch_size:
                done.extend(self._dispatch(t_bucket, full=True))
            if q and now - q[0].t_submit >= self.max_wait_s:
                done.extend(self._dispatch(t_bucket, full=False))
        if self.monitor is not None:
            # after dispatch so this round's sync-point records are in the
            # snapshot; Monitor.tick is a no-op when obs is disabled and
            # rate-limited by its interval_s otherwise
            with obs_prof.annotate("compass/serve/gauges"):
                self.monitor.tick()
        return done

    def flush(self) -> list[ServiceResult]:
        """Dispatch everything queued regardless of deadlines (drain)."""
        if self.mutable is not None:
            with obs_prof.annotate("compass/serve/writes"):
                self.apply_writes()
        done: list[ServiceResult] = []
        for t_bucket, q in self._queues.items():
            while q:
                done.extend(self._dispatch(t_bucket, full=len(q) >= self.batch_size))
        return done

    def run_until_idle(self) -> list[ServiceResult]:
        """Step until queues empty, then drain the remainder."""
        done = self.step()
        done.extend(self.flush())
        return done

    def poll(self, rid: int) -> Optional[ServiceResult]:
        """Pop the result for ``rid`` if its batch has run, else None.

        Only the newest ``result_buffer`` unpolled results are retained.
        """
        return self._results.pop(rid, None)

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    # -- execution -----------------------------------------------------------

    def _record_compile(self, cache: str, shape: tuple, wall_s: float | None) -> None:
        """Structured-event + counter trail for executable-cache misses.

        ``cache`` is "aot" (the immutable ``compass_search_jit.lower``
        cache) or "jit" (the mutable-snapshot shape set, where compilation
        happens inside the first traced call so no wall time is
        attributable here).  The bench_updates steady-state-recompile
        tripwire has a runtime twin now: ``compass_compiles_total`` should
        stop moving once every served shape is occupied.
        """
        obs_events.emit(
            "compile",
            cache=cache,
            shape=list(shape),
            wall_s=None if wall_s is None else round(wall_s, 6),
        )
        if obs_reg.enabled():
            obs_reg.registry().counter(
                "compass_compiles_total",
                "Search executable compilations",
                labelnames=("cache",),
            ).inc(cache=cache)

    def _executable(self, queries: jax.Array, pred: P.Predicate) -> Callable:
        B, T, A = pred.lo.shape
        # self.params embeds CompassParams.quant (a frozen, hashable
        # QuantParams), so quantized and exact configurations hash to
        # distinct keys and their executables coexist in one cache — the
        # same separation the (B, T, A) shape axes get.
        key = (B, T, A, self.params)
        st = self._stats.setdefault((B, T), BucketStats())
        exe = self._executables.get(key)
        if exe is None:
            t0 = self.clock()
            exe = compass_search_jit.lower(
                self.index, queries, pred, self.params
            ).compile()
            self._executables[key] = exe
            st.n_compiles += 1
            self._record_compile("aot", (B, T, A), self.clock() - t0)
        else:
            st.n_cache_hits += 1
        return exe

    def _dispatch(self, t_bucket: int, full: bool) -> list[ServiceResult]:
        B = self.batch_size
        label = f"B{B}xT{t_bucket}"
        with obs_prof.annotate("compass/serve/pack"):
            q = self._queues[t_bucket]
            jobs = [q.popleft() for _ in range(min(self.batch_size, len(q)))]
            n_fill = B - len(jobs)
            queries = np.zeros((B, self.index.dim), np.float32)
            for i, job in enumerate(jobs):
                queries[i] = job.query
            preds = [j.pred for j in jobs] + [P.never_true(self.index.n_attrs)] * n_fill
            pred = P.stack_predicates(preds, n_terms=t_bucket)
            qj = jnp.asarray(queries)

        t0 = self.clock()
        epoch = None
        st = self._stats.setdefault((B, t_bucket), BucketStats())
        if self.mutable is not None:
            # Pin the epoch: take one snapshot and run the whole batch
            # against it.  Writes only apply at round boundaries
            # (apply_writes), so nothing can swap the base mid-batch — the
            # snapshot makes that guarantee explicit and keeps the result's
            # provenance (epoch) reportable.
            snap = self.mutable.snapshot()
            epoch = snap.epoch
            key = (B, t_bucket, pred.lo.shape[-1], self.params,
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
                    None,
                )
            with obs_prof.annotate(f"compass/serve_batch/{label}"):
                res = mutable_search(
                    snap.index, snap.base_gids, snap.delta, qj, pred, self.params
                )
                res.ids.block_until_ready()
        else:
            exe = self._executable(qj, pred)
            with obs_prof.annotate(f"compass/serve_batch/{label}"):
                res = exe(self.index, qj, pred)
                res.ids.block_until_ready()
        exec_s = self.clock() - t0

        with obs_prof.annotate("compass/serve/unpack"):
            st = self._stats[(B, t_bucket)]
            st.n_requests += len(jobs)
            st.n_batches += 1
            st.n_fillers += n_fill
            st.n_full_flush += int(full)
            st.n_deadline_flush += int(not full)
            st.total_exec_s += exec_s
            # planner-chosen execution mode per real lane (filler lanes are the
            # service's padding, not traffic — excluded from the counters)
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
                r = ServiceResult(
                    rid=job.rid,
                    ids=ids[i, : job.k].copy(),
                    dists=dists[i, : job.k].copy(),
                    bucket=(B, t_bucket),
                    queue_wait_s=wait,
                    batch_exec_s=exec_s,
                    epoch=epoch,
                )
                self._results[job.rid] = r
                out.append(r)
            while len(self._results) > self.result_buffer:
                self._results.popitem(last=False)  # evict oldest unpolled
        if obs_reg.enabled():
            with obs_prof.annotate("compass/serve/gauges"):
                # we are already at the batch's sync point (block_until_ready
                # above), so folding device stats into host counters adds no
                # extra synchronization.  Filler lanes are the service's
                # padding, not traffic: slice them off before recording, same
                # rule as the mode counters above.
                lanes = len(jobs)
                sliced = jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[:lanes], res.stats
                )
                obs_reg.record_search_stats(sliced, labels={"bucket": label})
                # the serve families share their declaration with the
                # multi-tenant CollectionService: same (bucket, tenant)
                # schema, this single-index service recording tenant="" (the
                # unset-value convention record_search_stats already uses)
                R = obs_reg.registry()
                R.counter(
                    "compass_serve_requests_total", "Real requests served",
                    labelnames=("bucket", "tenant"),
                ).inc(lanes, bucket=label, tenant="")
                R.counter(
                    "compass_serve_batches_total", "Micro-batches dispatched",
                    labelnames=("bucket", "tenant"),
                ).inc(bucket=label, tenant="")
                if n_fill:
                    R.counter(
                        "compass_serve_fillers_total", "Padded filler lanes dispatched",
                        labelnames=("bucket", "tenant"),
                    ).inc(n_fill, bucket=label, tenant="")
                R.histogram(
                    "compass_serve_exec_seconds", "Micro-batch execution wall time",
                    labelnames=("bucket", "tenant"), buckets=obs_reg.LATENCY_BUCKETS_S,
                ).observe(exec_s, bucket=label, tenant="")
                wait_h = R.histogram(
                    "compass_serve_wait_seconds", "Per-request queue wait",
                    labelnames=("bucket", "tenant"), buckets=obs_reg.LATENCY_BUCKETS_S,
                )
                for job in jobs:
                    wait_h.observe(t0 - job.t_submit, bucket=label, tenant="")
                # the batched loop runs until its slowest lane is done: the
                # iterations it ran are the largest n_steps, fillers included
                R.counter(
                    "compass_loop_steps_total", "Engine loop iterations run",
                    labelnames=("bucket", "tenant"),
                ).inc(int(np.max(np.asarray(res.stats.n_steps))), bucket=label, tenant="")
        return out

    # -- observability -------------------------------------------------------

    def enable_monitoring(self, **kwargs) -> "obs_health.Monitor":
        """Attach (or replace) the continuous :class:`~repro.obs.health
        .Monitor`; ``step()`` ticks it from here on.  kwargs pass through
        to the Monitor (capacity, interval_s, slos, watchdogs); the
        service's clock is the default time source so deadline tests and
        snapshot cadence share one fake clock."""
        kwargs.setdefault("clock", self.clock)
        self.monitor = obs_health.Monitor(**kwargs)
        return self.monitor

    def health(self) -> "obs_health.HealthReport":
        """Evaluate SLOs + watchdogs now and return the report (attaches
        a default Monitor on first use)."""
        if self.monitor is None:
            self.enable_monitoring()
        return self.monitor.evaluate()

    def pending_writes(self) -> int:
        return len(self._writes)

    @property
    def compile_count(self) -> int:
        """Total XLA compilations so far == occupied (B, T, A, pm) keys
        (plus, for mutable services, occupied snapshot shapes)."""
        return len(self._executables) + len(self._mutable_shapes)

    def stats(self) -> dict:
        """JSON-ready snapshot: per-bucket counters plus service totals."""
        buckets = {
            f"B{b}xT{t}": dataclasses.asdict(s) for (b, t), s in sorted(self._stats.items())
        }
        n_req = sum(s.n_requests for s in self._stats.values())
        wait = sum(s.total_wait_s for s in self._stats.values())
        return {
            "batch_size": self.batch_size,
            "max_wait_s": self.max_wait_s,
            "compiles": self.compile_count,
            "occupied_buckets": len(self._stats),
            # the compiled-shape policy in force — with bucket_rows on, the
            # mutable snapshot shapes in the cache keys are epoch-stable,
            # so compiles stays == occupied shapes across compactions
            "shape_policy": dataclasses.asdict(self.params.shape),
            "n_requests": n_req,
            "n_batches": sum(s.n_batches for s in self._stats.values()),
            "n_fillers": sum(s.n_fillers for s in self._stats.values()),
            "mean_wait_s": wait / n_req if n_req else 0.0,
            "planner": self.params.planner,
            # quantized-tier provenance: which quant config this service's
            # executables were keyed on, and the per-row footprint actually
            # being served (codes+amortized codebook vs 4*d float32)
            "quant": (
                None
                if self.params.quant is None
                else dataclasses.asdict(self.params.quant)
            ),
            # footprint of the tier the candidate scans actually read:
            # exact-mode services read the float32 rows even when the
            # served index happens to carry codes alongside
            "bytes_per_vector": (
                round(self.index.qvecs.bytes_per_vector, 2)
                if self.params.quant is not None
                else 4 * self.index.dim
            ),
            "mutable": self.mutable is not None,
            "epoch": None if self.mutable is None else self.mutable.epoch,
            "n_upserts": self.n_upserts,
            "n_deletes": self.n_deletes,
            "n_write_errors": self.n_write_errors,
            "n_compactions": (
                0 if self.mutable is None else len(self.mutable.compaction_log)
            ),
            "modes": {
                "prefilter": sum(s.n_mode_prefilter for s in self._stats.values()),
                "cooperative": sum(s.n_mode_cooperative for s in self._stats.values()),
                "postfilter": sum(s.n_mode_postfilter for s in self._stats.values()),
            },
            # structured-event tallies (compaction / epoch_swap / compile /
            # write_error / ...) — zeros unless obs is enabled or a JSONL
            # sink is configured (REPRO_OBS_EVENTS)
            "obs_events": dict(obs_events.EVENTS.counts()),
            "obs_enabled": obs_reg.enabled(),
            # the last continuous-monitoring report (None until a Monitor
            # is attached and has evaluated at least once)
            "health": (
                None
                if self.monitor is None or self.monitor.last_report is None
                else self.monitor.last_report.to_dict()
            ),
            "buckets": buckets,
        }
