"""Per-shape block-size autotuner for the Pallas kernel wrappers.

Block sizes (rows gathered per visit-step grid step, the ivf_score matmul
tiles) trade VMEM residency against pipeline depth, and the right choice
depends on the problem shape — d, V, m, B — not just the kernel.  Rather
than hard-coding one default per kernel, each wrapper asks :func:`choose`
for its block config.  Resolution order, each outcome counted in
``compass_autotune_total{kernel,source}``:

  1. **pin** — ``REPRO_PALLAS_BLOCK_<KERNEL>`` (parsed by
     ``kernels/interpret.py``), e.g. ``REPRO_PALLAS_BLOCK_VISIT_STEP="rb=8"``.
     A pin wins over everything and is never measured against.
  2. **table** — an in-process ``{(kernel, shape_key): config}`` cache of
     *measured* winners.
  3. **measured** — on first sight of a shape, when the wrapper can supply
     a measure function and measurement is enabled (see
     ``interpret.autotune_measurement_enabled``), every candidate runs on
     the wrapper's own concrete arguments and the fastest wins.  A
     candidate the compiler refuses is skipped and counted as
     ``refused``; when every candidate is refused, :func:`choose` raises.
  4. **default** — ``candidates[0]``.  Used when measurement is off (the
     CPU-interpret path: interpret-mode timings would tune for the
     interpreter, not the hardware) and whenever the wrapper is being
     *traced*: a kernel reached inside an outer jit (the engine hot path)
     only has tracers, and timing a tracer times tracing, not the kernel.
     A default is not stored in the table, so a later concrete call can
     still measure that shape.

Block choice never affects results: every candidate computes the same
values (tests assert bitwise equality across block sizes), so a default,
a pin, or a mis-measured table can cost speed but never correctness.

``decisions()`` reports the last resolution per shape with its source —
what a run actually used (bench provenance, ``chip_smoke.py``).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import jax

from .interpret import autotune_measurement_enabled, block_override

Config = dict[str, int]

_TABLE: dict[tuple[str, tuple], Config] = {}
#: shapes measured this process (bookkeeping, asserted on by tests)
_N_MEASURED: dict[tuple[str, tuple], int] = {}
#: last resolution per shape: (config, source)
_DECISIONS: dict[tuple[str, tuple], tuple[Config, str]] = {}


def clear() -> None:
    """Drop the measured table and the decision log (tests)."""
    _TABLE.clear()
    _N_MEASURED.clear()
    _DECISIONS.clear()


def decisions() -> dict[str, dict]:
    """Every shape resolved this process: its config and where it came
    from (``pin`` / ``table`` / ``measured`` / ``default``)."""
    return {
        f"{k[0]}:{k[1]}": {"config": dict(cfg), "source": src}
        for k, (cfg, src) in sorted(_DECISIONS.items(), key=str)
    }


def _measure(fn: Callable[[Config], Any], cand: Config, reps: int = 3) -> float:
    """Best-of-``reps`` wall time of ``fn(cand)``, waiting for the device.

    Raises if ``fn`` returns tracers: ``block_until_ready`` returns a
    tracer without waiting, so the clock would time tracing."""

    def run():
        out = fn(cand)
        if any(isinstance(x, jax.core.Tracer) for x in jax.tree.leaves(out)):
            raise RuntimeError("autotune measure function returned tracers")
        jax.block_until_ready(out)

    run()  # warmup: compile + first run
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def choose(
    kernel: str,
    shape_key: tuple,
    candidates: Sequence[Config],
    measure_fn: Callable[[Config], Any] | None = None,
) -> Config:
    """Resolve the block config for one kernel launch shape.

    ``measure_fn`` runs one candidate on the wrapper's concrete arguments
    and returns its output; wrappers pass None while being traced.
    ``candidates[0]`` is the built-in default.
    """
    from repro.obs import profiling as prof

    key = (kernel, tuple(shape_key))
    pinned = block_override(kernel)
    if pinned:
        cfg, source = dict(candidates[0]), "pin"
        cfg.update(pinned)
    elif key in _TABLE:
        cfg, source = dict(_TABLE[key]), "table"
    elif measure_fn is not None and autotune_measurement_enabled():
        _N_MEASURED[key] = _N_MEASURED.get(key, 0) + 1
        timed, refused = [], []
        for cand in candidates:
            try:
                timed.append((_measure(measure_fn, dict(cand)), dict(cand)))
            except Exception as e:  # the compiler refused this tiling
                refused.append(f"{cand}: {type(e).__name__}: {e}")
                prof.count_autotune(kernel, "refused")
        if not timed:
            raise RuntimeError(
                f"{kernel}: every block candidate was refused for shape "
                f"{shape_key}:\n" + "\n".join(refused)
            )
        cfg = min(timed, key=lambda tc: tc[0])[1]
        source = "measured"
        _TABLE[key] = dict(cfg)
    else:
        cfg, source = dict(candidates[0]), "default"
    prof.count_autotune(kernel, source)
    _DECISIONS[key] = (dict(cfg), source)
    return cfg
