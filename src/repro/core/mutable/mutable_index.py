"""MutableIndex — the LSM-style write path over the immutable CompassIndex.

Layout (DESIGN.md §Mutability):

  * **base** — an ordinary :class:`CompassIndex` (graph + IVF + clustered
    runs + planner stats), immutable between compactions.
  * **tombstones** — a host bitmap over base rows; deleted or superseded
    rows keep *routing* (graph traversal and B+-tree runs still flow
    through them) but the engine masks them out of the result queue and
    the PREFILTER adoption (``CompassIndex.live``).
  * **delta segment** — a fixed-capacity append-only buffer of recent
    upserts with its own vectors/attrs, searched by an exact brute scan
    (delta.py).  Overflow triggers compaction (compact.py).

Search fans out over {base (tombstone-masked), delta (predicate-filtered
scan)} and merges top-k by distance; both tiers are searched under the same
``CompassParams``, so planner modes, backends and metrics all apply.

**Epoch-swapped snapshots, not locks**: every mutation invalidates a cached
:class:`Snapshot`; readers grab the current snapshot object (a plain Python
reference — atomic under the GIL) and run entirely against it.  Compaction
builds the *next* base off to the side and publishes it by swapping the
snapshot reference and bumping ``epoch``; an in-flight search keeps its
old-epoch arrays alive for free (JAX buffers are immutable), which is the
whole point of choosing epochs over a reader–writer lock: zero reader
coordination on the hot path, and a serving batch can pin one epoch for its
entire lifetime (serving/search_service.py).

Ids: callers address records by *global id* (``gid``), stable across
compactions; search results report gids (-1 for empty slots), unlike the
positional ids of raw ``compass_search``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import events as obs_events
from repro.obs import registry as obs_registry
from repro.obs.profiling import stage_scope

from .. import predicate as P
from ..engine.backend import resolve_backend
from ..engine.driver import ShapePolicy
from ..engine.state import SearchResult
from ..index import BuildConfig, CompassIndex, build_index
from ..quant.encode import (
    QuantizedVectors,
    build_luts,
    encode_rows,
    quant_mse,
    quantize_vectors,
)
from ..quant.params import QuantConfig
from .compact import fold_index, pad_index_rows
from .delta import DeltaView, delta_topk, delta_topk_quantized

GID_SENTINEL = -1  # empty result slot / empty delta slot


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One immutable epoch of the mutable index (what a search runs on)."""

    index: CompassIndex  # base with .live tombstone mask attached
    base_gids: jax.Array  # (N + 1,) int32; sentinel row -> -1
    delta: DeltaView
    epoch: int


@functools.partial(jax.jit, static_argnames=("pm",))
def mutable_search(
    index: CompassIndex, base_gids, delta: DeltaView, queries, pred: P.Predicate, pm
) -> SearchResult:
    """Fan-out search: base (tombstone-masked) + delta (brute scan), merged.

    Returns a :class:`SearchResult` whose ids are *global ids* (-1 padding).
    Stats are the base engine stats with the delta's scanned rows folded
    into ``n_dist`` — or, when ``pm.quant`` is active and the snapshot
    carries delta codes, into ``n_adc``/``n_rerank``: the delta then runs
    the same two-stage ADC-scan-then-exact-rerank as the base
    (delta.delta_topk_quantized), so both tiers obey one scoring contract.
    """
    from ..engine import compass_search  # local: avoids import-order cycles

    pmr = pm.resolved()
    backend = resolve_backend(pmr.backend)
    quant_delta = pm.quant is not None and delta.qvecs is not None
    if quant_delta:
        # one ADC table build per query for the whole fan-out: the delta's
        # codebooks ARE the base's frozen codebooks (snapshot), so the same
        # (B, m, ks) tables score both tiers
        luts = build_luts(delta.qvecs, queries, pmr.metric)
    else:
        luts = None
    base = compass_search(index, queries, pred, pm, luts)
    if quant_delta:
        dg, dd, n_adc, n_rr, n_pass = delta_topk_quantized(
            delta, queries, pred, pmr.k, pmr.metric, backend, pm.quant, luts,
        )
        stats = base.stats._replace(
            n_adc=base.stats.n_adc + n_adc,
            n_rerank=base.stats.n_rerank + n_rr,
            n_pass=base.stats.n_pass + n_pass,
        )
        if pm.quant.rerank == "full":  # stage two read float32 delta rows
            stats = stats._replace(n_dist=stats.n_dist + n_rr)
    else:
        dg, dd, n_scanned, n_pass = delta_topk(
            delta, queries, pred, pmr.k, pmr.metric, backend
        )
        stats = base.stats._replace(
            n_dist=base.stats.n_dist + n_scanned,
            n_pass=base.stats.n_pass + n_pass,
        )
    with stage_scope("mutable/delta"):  # the base/delta merge
        bg = jnp.take(base_gids, jnp.clip(base.ids, 0, index.n_records), axis=0)
        bg = jnp.where(jnp.isfinite(base.dists), bg, jnp.int32(GID_SENTINEL))
        all_d = jnp.concatenate([base.dists, dd], axis=1)
        all_g = jnp.concatenate([bg, dg], axis=1)
        neg, sel = jax.lax.top_k(-all_d, pmr.k)
        return SearchResult(jnp.take_along_axis(all_g, sel, axis=1), -neg, stats)


class MutableIndex:
    """Mutable filtered-search index: upsert / delete / search / compact.

    Host-side writes are cheap dictionary-and-array mutations; the device
    snapshot is rebuilt lazily on the next search (write bursts amortize to
    one transfer).  All reads go through :meth:`snapshot`.
    """

    def __init__(
        self,
        base: CompassIndex,
        *,
        delta_cap: int = 256,
        auto_compact: bool = True,
        cfg: BuildConfig | None = None,
        metric: str = "l2",
        gids: np.ndarray | None = None,
        quant_cfg: QuantConfig | None = None,
        shape: ShapePolicy | None = None,
    ):
        if base.astats is None:
            raise ValueError("MutableIndex requires an index built by build_index (astats)")
        if metric == "cos" or (cfg is not None and cfg.metric == "cos"):
            # the delta scan and LUT builds run outside compass_search's
            # cos->ip rewrite; supporting cos here would need a second
            # rewrite point on the write path.  build_index(metric="cos")
            # already stores unit rows, so wrap that index with "ip" and
            # normalize upserted rows/queries upstream.
            raise ValueError(
                "MutableIndex does not support metric='cos'; normalize rows "
                "upstream and use metric='ip' (an index built with "
                "BuildConfig(metric='cos') is already unit-normalized)"
            )
        # CompassIndex does not record its build metric, so a non-l2 index
        # wrapped without an explicit ``cfg`` must pass ``metric`` here or
        # compaction would fold with l2 geometry.
        self._cfg = cfg or BuildConfig(
            m=base.graph.degree,
            nlist=base.nlist,
            metric=metric,
            hist_bins=base.astats.edges.shape[1] - 1,
            cluster_hist_bins=base.astats.cluster_edges.shape[2] - 1,
        )
        # the quantized tier's *training* config, used only by
        # compact(retrain_codebooks=True): QuantizedVectors carries no
        # training hyperparameters (it is a pure-array pytree), so without
        # this the retrain would fall back to shape inference and silently
        # drop a non-default iters/seed choice
        self._quant_cfg = quant_cfg
        # the compiled-shape policy (DESIGN.md §Mutability, bucket-fold
        # contract): row buckets for every base the index ever serves —
        # the wrapped one included, so epoch 0 shares the bucket's
        # executable with every post-compaction epoch — plus the delta
        # capacity (shape.delta_cap wins over the legacy argument)
        self.shape = shape if shape is not None else ShapePolicy()
        self.delta_cap = self.shape.resolve_delta_cap(delta_cap)
        self.auto_compact = bool(auto_compact)
        self.compaction_log: list[float] = []  # fold wall-clock seconds
        # quantized-tier drift: decode MSE of the folded table against the
        # frozen codebooks, appended at every compaction (compare against
        # base.qvecs.train_mse to decide when to retrain — DESIGN.md
        # §Quantization on codebook staleness)
        self.quant_drift_log: list[float] = []
        # registry labels this index's metrics/events carry (e.g.
        # DistributedMutableIndex sets {"shard": "3"} per shard so the
        # per-shard breakdowns are separable series, not pre-summed)
        self.obs_labels: dict[str, str] = {}
        self._epoch = 0
        self._snap: Snapshot | None = None
        n_real = base.n_records
        if self.shape.bucket_rows:
            base = pad_index_rows(
                base._replace(live=None), self.shape.row_bucket(n_real)
            )
        self._install_base(base, gids, n_real=n_real)
        self._reset_delta()

    # -- wiring ------------------------------------------------------------

    def _install_base(
        self, base: CompassIndex, gids: np.ndarray | None, n_real: int | None = None
    ) -> None:
        n = base.n_records
        if n_real is None:
            n_real = n
        if gids is None:
            gids = np.arange(n_real, dtype=np.int64)
        gids = np.asarray(gids, np.int64)
        if gids.shape != (n_real,):
            raise ValueError(f"gids shape {gids.shape} != ({n_real},)")
        self._base = base._replace(live=None)
        self._base_gids_dev = None  # per-epoch device cache (see snapshot)
        # host mirrors consumed by compaction
        self._vectors = np.asarray(base.vectors)[:n]
        self._attrs = np.asarray(base.attrs)[:n]
        self._assign = np.asarray(base.cattrs.assignments)
        self._centroids = np.asarray(base.centroids)
        # rows [n_real, n) are the bucket's dead padding (pad_index_rows):
        # never addressable (sentinel gid), born tombstoned so the engine's
        # live mask excludes them on top of the structural guarantees
        self._n_base_real = n_real
        if n_real < n:
            gids = np.concatenate(
                [gids, np.full((n - n_real,), GID_SENTINEL, np.int64)]
            )
        self._gids = gids
        self._gid2base = {int(g): p for p, g in enumerate(gids[:n_real])}
        self._live = np.ones((n + 1,), bool)
        self._live[n_real:n] = False

    def _reset_delta(self) -> None:
        cap = self.delta_cap
        self._dvec = np.zeros((cap, self.dim), np.float32)
        self._dattr = np.full((cap, self.n_attrs), np.inf, np.float32)
        self._dgid = np.full((cap,), GID_SENTINEL, np.int64)
        self._dvalid = np.zeros((cap,), bool)
        self._dcount = 0
        self._gid2slot: dict[int, int] = {}

    @classmethod
    def build(
        cls,
        vectors: np.ndarray,
        attrs: np.ndarray,
        cfg: BuildConfig = BuildConfig(),
        *,
        delta_cap: int = 256,
        auto_compact: bool = True,
        gids: np.ndarray | None = None,
        shape: ShapePolicy | None = None,
    ) -> "MutableIndex":
        return cls(
            build_index(vectors, attrs, cfg),
            delta_cap=delta_cap,
            auto_compact=auto_compact,
            cfg=cfg,
            gids=gids,
            shape=shape,
        )

    # -- introspection -----------------------------------------------------

    @property
    def base(self) -> CompassIndex:
        return self._base

    @property
    def dim(self) -> int:
        return self._base.dim

    @property
    def n_attrs(self) -> int:
        return self._base.n_attrs

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def gids(self) -> np.ndarray:
        """Global ids of the current base rows (positional order; bucket
        padding rows, which carry no gid, are excluded)."""
        return self._gids[: self._n_base_real]

    @property
    def delta_fill(self) -> int:
        return self._dcount

    @property
    def n_live(self) -> int:
        """Live record count across both tiers."""
        return int(self._live[:-1].sum()) + int(self._dvalid.sum())

    def __contains__(self, gid: int) -> bool:
        gid = int(gid)
        if gid in self._gid2slot:
            return True
        pos = self._gid2base.get(gid)
        return pos is not None and bool(self._live[pos])

    # -- writes ------------------------------------------------------------

    def upsert(self, gids, vectors, attrs) -> None:
        """Insert or replace records by global id (scalar or batched)."""
        gids = np.atleast_1d(np.asarray(gids, np.int64))
        vectors = np.asarray(vectors, np.float32).reshape(len(gids), self.dim)
        attrs = np.asarray(attrs, np.float32).reshape(len(gids), self.n_attrs)
        if gids.size and (gids.min() < 0 or gids.max() >= np.iinfo(np.int32).max):
            raise ValueError("gids must fit in non-negative int32")
        for g, v, a in zip(gids, vectors, attrs):
            g = int(g)
            if self._dcount >= self.delta_cap:
                if not self.auto_compact:
                    raise RuntimeError(
                        f"delta segment full ({self.delta_cap}); call compact()"
                    )
                obs_events.emit(
                    "delta_overflow",
                    delta_cap=self.delta_cap,
                    epoch=self._epoch,
                    **self.obs_labels,
                )
                self.compact()
            old_slot = self._gid2slot.pop(g, None)
            if old_slot is not None:  # superseded within the delta
                self._dvalid[old_slot] = False
            pos = self._gid2base.get(g)
            if pos is not None:  # superseded base version becomes a tombstone
                self._live[pos] = False
            slot = self._dcount
            self._dvec[slot] = v
            self._dattr[slot] = a
            self._dgid[slot] = g
            self._dvalid[slot] = True
            self._gid2slot[g] = slot
            self._dcount += 1
        self._snap = None
        self._record_debt()

    def delete(self, gids) -> None:
        """Delete records by global id; KeyError on unknown/already-deleted."""
        for g in np.atleast_1d(np.asarray(gids, np.int64)):
            g = int(g)
            slot = self._gid2slot.pop(g, None)
            if slot is not None:
                self._dvalid[slot] = False
                continue
            pos = self._gid2base.get(g)
            if pos is None or not self._live[pos]:
                raise KeyError(f"unknown or already-deleted id {g}")
            self._live[pos] = False
        self._snap = None
        self._record_debt()

    def _record_debt(self) -> None:
        """Compaction-debt gauges for the health watchdogs (obs/health.py):
        delta occupancy vs capacity and the tombstone fraction of real base
        rows.  Canonical ``("shard",)`` labels — ``""`` for a standalone
        index — so standalone and sharded indices fold into one series
        family regardless of ``obs_labels``.  Host-side dict writes; no-op
        when observability is off."""
        if not obs_registry.enabled():
            return
        r = obs_registry.registry()
        lab = {"shard": str(self.obs_labels.get("shard", ""))}
        lnames = ("shard",)
        r.gauge(
            "compass_delta_fill", "occupied delta-segment slots", lnames
        ).set(self._dcount, **lab)
        r.gauge(
            "compass_delta_cap", "delta-segment capacity", lnames
        ).set(self.delta_cap, **lab)
        live = self._live[: self._n_base_real]
        r.gauge(
            "compass_tombstone_fraction",
            "dead fraction of real (non-padding) base rows",
            lnames,
        ).set(1.0 - float(live.sum()) / max(1, live.size), **lab)

    # -- reads -------------------------------------------------------------

    def snapshot(self) -> Snapshot:
        """Current epoch's immutable device snapshot (cached until dirty)."""
        if self._snap is None:
            index = self._base._replace(live=jnp.asarray(self._live))
            if self._base_gids_dev is None:  # constant within an epoch
                self._base_gids_dev = jnp.asarray(
                    np.concatenate([self._gids, [GID_SENTINEL]]).astype(np.int32)
                )
            base_gids = self._base_gids_dev
            dqv = None
            if self._base.qvecs is not None:
                # encode the delta buffer against the base's frozen
                # codebooks so the quantized scan covers both tiers; cap is
                # small and the snapshot is cached until the next write, so
                # this stays off the search hot path
                bq = self._base.qvecs
                dcodes = np.asarray(encode_rows(bq.codebooks, bq.mean, self._dvec))
                dcodes = np.concatenate(
                    [dcodes, np.zeros((1, bq.m), np.uint8)], axis=0
                )
                dqv = QuantizedVectors(
                    jnp.asarray(dcodes), bq.codebooks, bq.mean, bq.train_mse
                )
            delta = DeltaView(
                jnp.asarray(
                    np.concatenate([self._dvec, np.zeros((1, self.dim), np.float32)], 0)
                ),
                jnp.asarray(
                    np.concatenate(
                        [self._dattr, np.full((1, self.n_attrs), np.inf, np.float32)], 0
                    )
                ),
                jnp.asarray(self._dgid.astype(np.int32)),
                jnp.asarray(self._dvalid),
                qvecs=dqv,
            )
            self._snap = Snapshot(index, base_gids, delta, self._epoch)
        return self._snap

    def search(self, queries, pred: P.Predicate, pm, *, explain: bool = False):
        """Batched filtered search over base+delta; ids are global ids.

        ``explain=True`` additionally returns per-query
        :class:`~repro.obs.trace.QueryTrace` records (stamped with this
        snapshot's epoch) — same contract as ``compass_search``: the
        traced program is identical either way.
        """
        snap = self.snapshot()
        res = mutable_search(
            snap.index, snap.base_gids, snap.delta, jnp.asarray(queries), pred, pm
        )
        if not explain:
            return res
        from repro.obs.trace import build_traces  # lazy: obs sits above core

        return res, build_traces(res, pm, epoch=snap.epoch)

    def materialize(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The equivalent immutable table: (vectors, attrs, gids) in
        canonical order — surviving base rows first, delta rows after."""
        keep = self._live[:-1]
        dsel = self._dvalid
        vec = np.concatenate([self._vectors[keep], self._dvec[dsel]], 0)
        attr = np.concatenate([self._attrs[keep], self._dattr[dsel]], 0)
        gids = np.concatenate([self._gids[keep], self._dgid[dsel]], 0)
        return vec, attr, gids

    # -- maintenance -------------------------------------------------------

    def compact(self, retrain_codebooks: bool = False) -> None:
        """Fold the delta into a fresh base and swap epochs.

        Local maintenance, not a rebuild: tombstoned rows leave the graph
        (``remove_nodes``), delta rows are locally inserted
        (``insert_nodes``), clustered runs are re-sorted, medoids and
        planner stats refreshed (compact.py).  The swap is the last step,
        so concurrent readers keep their old snapshot untouched.

        When the base carries a quantized tier, the fold *re-encodes* the
        delta rows against the frozen codebooks (kept rows carry their
        codes over) and records the folded table's decode MSE in
        ``quant_drift_log`` — the staleness signal.  Codebooks are only
        retrained on an explicit ``compact(retrain_codebooks=True)``
        (auto-compaction never retrains: retraining changes every ADC table
        and thus every cached executable, so it must be an operator
        decision, not an overflow side effect).
        """
        t0 = time.perf_counter()
        keep = self._live[:-1]
        vec, attr, gids = self.materialize()
        # bucket the fold (ShapePolicy.row_bucket is the identity when
        # bucketing is off): churn that stays within a bucket keeps
        # n_records — and therefore every compiled program — fixed across
        # the epoch swap; the old bucket's padding rows are tombstoned
        # (keep=False) and drop out of the fold like any dead row
        index, assign = fold_index(
            vec,
            attr,
            int(keep.sum()),
            np.asarray(self._base.graph.neighbors),
            keep,
            self._assign,
            self._centroids,
            self._cfg,
            qvecs=self._base.qvecs,
            n_rows=self.shape.row_bucket(vec.shape[0]),
        )
        if index.qvecs is not None:
            if retrain_codebooks:
                # prefer the explicit training config (construction-time
                # ``quant_cfg``); shape inference recovers only the
                # *effective* trained shapes — NOT iters/seed, and a ks
                # that train_codebooks clipped to a small original corpus
                # stays clipped forever even after the corpus grows — so a
                # non-default configuration must be passed in to survive
                cfg = self._quant_cfg or QuantConfig(
                    m=index.qvecs.m,
                    ks=index.qvecs.ks,
                    residual=bool(np.any(np.asarray(index.qvecs.mean))),
                )
                qv = quantize_vectors(vec, cfg, self._cfg.metric)
                if index.n_records != vec.shape[0]:
                    # re-pad the retrained codes to the row bucket (the
                    # retrain sees real rows only — padding must not train)
                    npad = index.n_records - vec.shape[0]
                    codes = np.asarray(qv.codes)
                    codes = np.concatenate(
                        [codes[:-1], np.zeros((npad + 1, qv.m), np.uint8)], 0
                    )
                    qv = QuantizedVectors(
                        jnp.asarray(codes), qv.codebooks, qv.mean, qv.train_mse
                    )
                index = index._replace(qvecs=qv)
            self.quant_drift_log.append(quant_mse(index.qvecs, vec))
        # publish: install the new epoch, then reset the write tiers
        self._install_base(index, gids, n_real=vec.shape[0])
        self._assign = assign
        self._reset_delta()
        self._epoch += 1
        self._snap = None
        wall = time.perf_counter() - t0
        self.compaction_log.append(wall)
        lab = self.obs_labels
        obs_events.emit(
            "compaction",
            epoch=self._epoch,
            wall_s=wall,
            n_rows=vec.shape[0],
            row_bucket=index.n_records,
            retrained=bool(retrain_codebooks and index.qvecs is not None),
            quant_drift_mse=self.quant_drift_log[-1] if index.qvecs is not None else None,
            **lab,
        )
        obs_events.emit("epoch_swap", epoch=self._epoch, **lab)
        if retrain_codebooks and index.qvecs is not None:
            obs_events.emit("codebook_retrain", epoch=self._epoch, **lab)
        if obs_registry.enabled():
            r = obs_registry.registry()
            lnames = tuple(sorted(lab))
            r.counter(
                "compass_compactions_total", "delta folds completed", lnames
            ).inc(1, **lab)
            r.histogram(
                "compass_compaction_seconds", "compaction fold wall time", lnames
            ).observe(wall, **lab)
            r.gauge("compass_epoch", "current snapshot epoch", lnames).set(
                self._epoch, **lab
            )
            if retrain_codebooks and index.qvecs is not None:
                r.counter(
                    "compass_codebook_retrains_total", "explicit codebook retrains",
                    lnames,
                ).inc(1, **lab)
            if index.qvecs is not None:
                r.gauge(
                    "compass_quant_drift_mse",
                    "decode MSE of the folded table vs frozen codebooks",
                    lnames,
                ).set(self.quant_drift_log[-1], **lab)
                # same labelnames as the drift gauge so the quant-staleness
                # watchdog (obs/health.py) can pair the two series by key
                r.gauge(
                    "compass_quant_train_mse",
                    "decode MSE baseline at codebook training time",
                    lnames,
                ).set(float(index.qvecs.train_mse), **lab)
        self._record_debt()
