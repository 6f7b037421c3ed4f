"""Single source of truth for the Pallas kernel mode and env knobs.

Interpret mode follows the platform and nothing else: on a TPU every
kernel lowers natively to Mosaic; everywhere else (CPU containers
included) the Pallas interpreter executes the kernel body for
correctness.  There is no override — a TPU run can never silently fall
into the interpreter.

Block-size pins (consumed by kernels/autotune.py, one variable per
kernel, comma-separated ``field=int`` pairs):

  REPRO_PALLAS_BLOCK_VISIT_STEP="rb=4"
  REPRO_PALLAS_BLOCK_IVF_SCORE="bb=8,bc=128,bd=128"

A pinned override beats both the measured autotune table and the built-in
defaults (see :func:`repro.kernels.autotune.choose`).  Autotune
measurement itself is gated by REPRO_PALLAS_AUTOTUNE=1/0 (default: only
measure when the kernels lower natively — interpret-mode timings would
tune for the interpreter, not the hardware).

The pins are read when the wrapper runs, which for the engine hot
path is at *trace* time inside the outer ``compass_search`` jit — the
result is baked into the cached executable and later in-process env
changes are ignored for already-traced shapes.  Set overrides before the
first traced call (eager kernel calls re-read them every time).
"""
from __future__ import annotations

import os

import jax


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def block_override(kernel: str) -> dict[str, int]:
    """Parse ``REPRO_PALLAS_BLOCK_<KERNEL>`` into a block-config dict.

    Returns {} when the variable is unset or empty; raises ValueError on a
    malformed pin (bad pins should fail loudly, not silently detune)."""
    raw = os.environ.get(f"REPRO_PALLAS_BLOCK_{kernel.upper()}", "").strip()
    if not raw:
        return {}
    out: dict[str, int] = {}
    for part in raw.split(","):
        key, _, val = part.partition("=")
        key, val = key.strip(), val.strip()
        if not key or not val or not val.lstrip("-").isdigit():
            raise ValueError(
                f"malformed REPRO_PALLAS_BLOCK_{kernel.upper()}={raw!r}; "
                "expected comma-separated field=int pairs"
            )
        out[key] = int(val)
    return out


def autotune_measurement_enabled() -> bool:
    """Whether :mod:`repro.kernels.autotune` may time candidates.

    ``REPRO_PALLAS_AUTOTUNE=1`` forces measurement on, ``=0`` off; the
    default measures only when kernels lower natively (interpret-mode
    wall-clock would tune for the interpreter, not the hardware)."""
    flag = os.environ.get("REPRO_PALLAS_AUTOTUNE", "")
    if flag == "1":
        return True
    if flag == "0":
        return False
    return not default_interpret()
