"""From a profiler trace to metrics.

The traced run records the window with ``jax.profiler`` and reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData``.  Device operations
are the events of the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane;
host spans are the annotations on the host plane (Python function events,
named ``$...``, are left out), among them the harness's own ``bench/submit``,
``bench/step`` and ``bench/deliver`` spans.

* busy time: the union of the device operations' intervals inside the
  window, averaged over the chips traced; idle share = 1 - busy / window.
* top operations: by their own time, nested operations subtracted.
* idle gaps: the spans of the window with no device operation on a chip,
  each labelled by the innermost harness span (``bench/...``) or program
  span that covers the gap's middle on the host.
"""
from __future__ import annotations

import dataclasses
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. ``while.637``
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    ops: dict  # chip index -> [Op] sorted by start, clipped to the window
    spans: list  # host spans (name, start_ns, end_ns)
    window: tuple  # (start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def union_ns(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, window) -> list:
    """The ``(start, end)`` stretches of ``window`` that no interval covers."""
    out, cursor = [], window[0]
    for s, e in sorted(intervals):
        if s > cursor:
            out.append((cursor, min(s, window[1])))
        cursor = max(cursor, e)
        if cursor >= window[1]:
            break
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return [g for g in out if g[1] > g[0]]


def busy_s(trace: Trace) -> float | None:
    """Device busy seconds in the window, averaged over the chips traced."""
    if not trace.ops:
        return None
    per_chip = [union_ns((o.start_ns, o.end_ns) for o in ops) for ops in trace.ops.values()]
    return sum(per_chip) / len(per_chip) * 1e-9


def self_ns(ops: list) -> list:
    """Each operation's own time: its duration less that of the operations
    nested inside it on the same line (a ``while`` holds its body's ops)."""
    out = [o.end_ns - o.start_ns for o in ops]
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= o.start_ns:
            stack.pop()
        if stack:
            out[stack[-1]] -= o.end_ns - o.start_ns
        stack.append(i)
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """The ``n`` operation names that took most device time of their own
    (nested operations subtracted), summed over chips: ``[[name, s]]``."""
    tot: dict[str, float] = {}
    for ops in trace.ops.values():
        for o, own in zip(ops, self_ns(ops)):
            tot[o.name] = tot.get(o.name, 0.0) + own * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """The ``n`` longest idle gaps of chip 0, each as ``[label, s]``."""
    if not trace.ops:
        return []
    chip = min(trace.ops)
    found = gaps([(o.start_ns, o.end_ns) for o in trace.ops[chip]], trace.window)
    found.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in found[:n]:
        mid = (s + e) / 2
        around = [sp for sp in trace.spans if sp[1] <= mid <= sp[2]]
        label = min(around, key=lambda sp: sp[2] - sp[1])[0] if around else "no host span"
        out.append([label, (e - s) * 1e-9])
    return out


def _short(text: str) -> str:
    """``%while.637 = (...) while(...)`` -> ``while.637``."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_line(plane: str, line: str) -> int | None:
    """The chip whose operations the trace line holds, or None: the
    ``XLA Ops`` line of each ``/device:TPU:<i>`` plane."""
    if plane.startswith(DEVICE_PLANE) and line == OPS_LINE:
        return int(plane[len(DEVICE_PLANE):].split()[0])
    return None


def read(path: str, window_ns: tuple | None = None, ops_line=device_line) -> Trace:
    """Reduce the trace at ``path``.  ``window_ns`` is the window on the
    profiler's clock; by default it spans the harness's ``bench/`` spans.
    ``ops_line(plane name, line name)`` says which lines hold the device
    operations, and of which chip."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(path)
    ops: dict[int, list] = {}
    spans = []
    for plane in prof.planes:
        for line in plane.lines:
            chip = ops_line(plane.name, line.name)
            if chip is not None:
                ops.setdefault(chip, []).extend(
                    Op(_short(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif plane.name.startswith(HOST_PLANE):
                spans.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                             for ev in line.events if not ev.name.startswith("$"))
    if window_ns is None:
        mine = [sp for sp in spans if sp[0].startswith("bench/")]
        if not mine:
            raise ValueError(f"no bench/ span in the trace at {path}")
        window_ns = (min(sp[1] for sp in mine), max(sp[2] for sp in mine))
    lo, hi = window_ns
    for chip in ops:
        ops[chip] = sorted(
            (dataclasses.replace(o, start_ns=max(o.start_ns, lo), end_ns=min(o.end_ns, hi))
             for o in ops[chip] if o.end_ns > lo and o.start_ns < hi),
            key=lambda o: o.start_ns)
    return Trace(ops=ops, spans=spans, window=(lo, hi))


def start(directory: str) -> None:
    """Start the profiler: device operations and host annotations, without
    the Python function tracer (it would slow every call of the host loop)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=options)


def find(directory: str) -> str:
    """The ``.xplane.pb`` that ``jax.profiler.stop_trace`` wrote under
    ``directory``."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]
