"""Structured event log — the discrete-lifecycle side of observability.

Counters answer "how much"; the event log answers "what happened, when,
in what order".  Subsystems emit typed events at host-side lifecycle
points (never from traced code):

  * ``compaction``       — MutableIndex.compact: fold wall, rows, drift
  * ``epoch_swap``       — the snapshot publish at the end of a fold
  * ``delta_overflow``   — an upsert hit a full delta and forced a fold
  * ``codebook_retrain`` — an explicit compact(retrain_codebooks=True)
  * ``write_error``      — a raced delete counted as a no-op (serving)
  * ``compile``          — an executable-cache miss (serving AOT / jit)
  * ``slo_burn``         — an SloSpec's burn rate crossed every window
  * ``health``           — a watchdog check changed status (obs/health.py)

Events land in a bounded in-memory ring (``tail()`` for tests and
``SearchService.stats()``) and optionally stream to a JSONL sink — one
``json.dumps`` line per event — opened from ``REPRO_OBS_EVENTS=<path>``
at import or :meth:`EventLog.configure` at runtime.  Each event also
bumps ``compass_events_total{kind=...}`` in the registry so dashboards
see rates without parsing the log.

Emission is active when observability is enabled *or* a sink is
configured; otherwise ``emit`` is one bool check.  Timestamps are host
wall-clock (``time.time()``) taken outside any trace.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from collections import Counter as _TallyCounter
from collections import deque

from . import registry as R

EVENT_KINDS = (
    "compaction",
    "epoch_swap",
    "delta_overflow",
    "codebook_retrain",
    "write_error",
    "compile",
    "slo_burn",
    "health",
    "index_build_phase",
)


class EventLog:
    """Bounded in-memory event ring with an optional JSONL file sink."""

    def __init__(self, capacity: int = 4096, path: str | None = None):
        self._ring: deque[dict] = deque(maxlen=int(capacity))
        self._counts: _TallyCounter = _TallyCounter()
        self._path: str | None = None
        self._fh = None
        if path:
            self.configure(path)

    def configure(self, path: str | None) -> None:
        """Attach (or detach, with None) the JSONL sink."""
        if self._fh is not None:
            self._fh.close()
            self._fh = None
        self._path = path or None
        if self._path:
            self._fh = open(self._path, "a", buffering=1)

    @property
    def path(self) -> str | None:
        return self._path

    def active(self) -> bool:
        return R.enabled() or self._fh is not None

    def emit(self, kind: str, **fields) -> dict | None:
        """Record one event; returns it, or None when inactive."""
        if not self.active():
            return None
        ev = {"ts": time.time(), "kind": str(kind), **fields}
        self._ring.append(ev)
        self._counts[ev["kind"]] += 1
        if self._fh is not None:
            self._fh.write(json.dumps(ev, sort_keys=True, default=str) + "\n")
        if R.enabled():
            R.registry().counter(
                "compass_events_total", "structured lifecycle events", ("kind",)
            ).inc(1, kind=ev["kind"])
        return ev

    def tail(self, n: int = 20, kind: str | None = None) -> list[dict]:
        evs = [e for e in self._ring if kind is None or e["kind"] == kind]
        return evs[-n:]

    def counts(self) -> dict[str, int]:
        """Per-kind totals since the last clear (ring-independent)."""
        return dict(self._counts)

    def clear(self) -> None:
        self._ring.clear()
        self._counts.clear()


#: the process-global log every subsystem emits into; the env var wires a
#: sink before any subsystem import runs
EVENTS = EventLog(path=os.environ.get("REPRO_OBS_EVENTS") or None)


def emit(kind: str, **fields) -> dict | None:
    """Emit onto the global :data:`EVENTS` log."""
    return EVENTS.emit(kind, **fields)


@contextlib.contextmanager
def timed(kind: str, **fields):
    """Emit ``kind`` with the body's wall time as ``wall_s`` (index build
    phases: ``index_build_phase``).  The body must end in a host sync for
    the time to cover device work."""
    t0 = time.perf_counter()
    yield
    emit(kind, wall_s=time.perf_counter() - t0, **fields)
