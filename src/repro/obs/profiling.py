"""Profiling hooks: stage scopes, host spans, trace capture, route counters.

All result-invariant:

* :func:`stage_scope` (and its decorator form :func:`staged`) puts the
  HLO instructions traced inside it under ``compass/<stage>`` in their
  ``op_name`` metadata: the engine's stages (``engine/open``,
  ``engine/loop``, ``engine/bnext``, ``engine/gnext``, ``engine/visit``,
  ``engine/sort``, ``engine/select``), ``planner``, ``mutable/delta`` and
  ``quant/rerank``.  ``jax.named_scope`` only decorates metadata, so the
  compiled program is identical with or without the scope.
* :func:`kernel_scope` wraps each Pallas kernel wrapper (kernels/ops.py)
  in the stage scope ``compass/<kernel>`` and bumps the per-kernel wrapper
  counter.  It opens no host span: the wrapper runs once per compile,
  while JAX traces the program, never per launch.
* :func:`annotate` is the host-phase sibling (no HLO scope) used around
  the serving micro-batch (``compass/serve_batch/B{B}xT{T}``) and the
  phases around it (``compass/serve/{writes,pack,unpack,gauges}``).
* :data:`SERVED` records, by the label of the ``compass/serve_batch``
  span they ran under, how to get the HLO text of the programs the
  process served, so a trace reader can join device events to the
  instructions, and their scopes, by name.
* :func:`profile_capture` drives ``jax.profiler.start_trace`` /
  ``stop_trace`` and dumps an XPlane trace dir (load it in TensorBoard or
  convert to perfetto) when ``REPRO_OBS_PROFILE`` is set — either ``1``
  (default dir ``./obs-profile``) or a target directory path.

Counter semantics: the kernel/fallback/autotune counters record at
**wrapper-call time**, which inside a jit means *trace time* — once per
compiled program, not per execution (exactly the semantics of the
``visit_step.TRACE_COUNT`` tripwire they generalize).  They record even
when observability is disabled: a silent ref fallback during a disabled
trace would otherwise be invisible forever, the cost is a dict add per
*compile*, and steady-state dispatch never re-enters the wrapper.
"""
from __future__ import annotations

import contextlib
import functools
import os
from typing import Callable

import jax

from . import registry as R

#: every Pallas kernel the repo ships (the five wrapped in kernels/ops.py)
KERNELS = (
    "filter_distance",
    "visit_step",
    "ivf_score",
    "pq_score",
    "flash_attention",
)


def count_kernel(kernel: str) -> None:
    """One kernel-wrapper entry (trace time inside jit)."""
    R.registry().counter(
        "compass_kernel_traces_total",
        "kernel wrapper entries (trace-time inside jit)",
        ("kernel",),
    ).inc(1, kernel=kernel)


def count_fallback(kernel: str, reason: str) -> None:
    """A kernel wrapper routed to the jnp reference path instead of the
    Pallas kernel — the silent fallback the CI tripwire hunts, now a
    runtime-visible counter."""
    R.registry().counter(
        "compass_kernel_fallback_total",
        "kernel calls routed to the jnp reference path",
        ("kernel", "reason"),
    ).inc(1, kernel=kernel, reason=reason)


def count_autotune(kernel: str, source: str) -> None:
    """One autotune block-config resolution, labeled by where the config
    came from: ``pin`` (env override), ``table`` (measured cache hit),
    ``measured`` (fresh probe), ``default`` (candidates[0])."""
    R.registry().counter(
        "compass_autotune_total",
        "autotune block-config resolutions by source",
        ("kernel", "source"),
    ).inc(1, kernel=kernel, source=source)


def stage_scope(stage: str):
    """``jax.named_scope("compass/<stage>")``: the one way a stage of the
    program names its HLO instructions (a fresh scope object per use)."""
    return jax.named_scope(f"compass/{stage}")


def staged(stage: str):
    """Decorator form of :func:`stage_scope`: trace ``fn`` inside it."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with stage_scope(stage):
                return fn(*args, **kwargs)

        return inner

    return wrap


@contextlib.contextmanager
def kernel_scope(name: str):
    """Wrap one kernel wrapper call: stage scope + counter."""
    count_kernel(name)
    with stage_scope(name):
        yield


@contextlib.contextmanager
def annotate(name: str):
    """Host-phase timeline annotation (serving micro-batch path)."""
    with jax.profiler.TraceAnnotation(name):
        yield


class ServedPrograms:
    """The programs a process served, by the label of the
    ``compass/serve_batch/B{B}xT{T}`` span each ran under, as zero-argument
    callables that return its compiled HLO text.  A callable holds abstract
    shapes, static params or an executable, never an array, so it outlives
    the service without keeping its data on the device.  The latest program
    recorded under a label wins."""

    def __init__(self):
        self._text_fns: dict[str, Callable[[], str]] = {}

    def record(self, label: str, text_fn: Callable[[], str]) -> None:
        self._text_fns[label] = text_fn

    def texts(self) -> dict[str, str]:
        """``{label: compiled HLO text}`` of every program recorded."""
        return {label: fn() for label, fn in sorted(self._text_fns.items())}


#: process-wide, like the registry and the event log: a trace is read after
#: the service that served it is gone
SERVED = ServedPrograms()


def profile_dir() -> str | None:
    """The capture target from ``REPRO_OBS_PROFILE`` (None = capture off)."""
    v = os.environ.get("REPRO_OBS_PROFILE", "")
    if v in ("", "0"):
        return None
    return "obs-profile" if v == "1" else v


@contextlib.contextmanager
def profile_capture(out_dir: str | None = None, force: bool = False):
    """Capture an XPlane/perfetto trace dir around the with-body.

    Gated on ``REPRO_OBS_PROFILE`` unless ``force=True`` (tests); yields
    the trace directory, or None when capture is off.  The profiler
    writes TensorBoard-loadable XPlane protos plus a ``perfetto`` trace
    under ``<dir>/plugins/profile/<run>/``.
    """
    target = out_dir if out_dir is not None else profile_dir()
    if target is None and force:
        target = "obs-profile"
    if target is None:
        yield None
        return
    os.makedirs(target, exist_ok=True)
    jax.profiler.start_trace(target)
    try:
        yield target
    finally:
        jax.profiler.stop_trace()
