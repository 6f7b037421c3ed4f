"""Validate BENCH_*.json artifacts — the CI gate behind bench-smoke.

Every file must parse as JSON and carry the provenance envelope written by
``benchmarks/run.py`` (``bench`` / ``meta`` / ``wall_s`` / ``rows`` with the
engine-version + backend fields from ``common.bench_metadata``), so a
malformed or provenance-free artifact fails the workflow instead of
silently polluting the benchmark trajectory.

  python -m benchmarks.validate [dir]
"""
from __future__ import annotations

import glob
import json
import os
import sys

REQUIRED = ("bench", "meta", "wall_s", "rows")
META_REQUIRED = ("engine_version", "backend", "platform", "jax_version", "n")

#: the perf-trajectory row schema appended by ``run.py`` to
#: BENCH_HISTORY.jsonl — one row per bench run, carrying the same
#: provenance block as the per-run artifacts plus per-bench wall time and
#: the extract_qps label map the baseline diff consumes
HISTORY_SCHEMA = "repro.bench.history/v1"


def validate_history_row(row) -> list[str]:
    """Schema-check one BENCH_HISTORY.jsonl row (empty == valid)."""
    if not isinstance(row, dict):
        return [f"history row is {type(row).__name__}, expected object"]
    errs = []
    if row.get("schema") != HISTORY_SCHEMA:
        errs.append(f"schema is {row.get('schema')!r}, expected {HISTORY_SCHEMA!r}")
    if not isinstance(row.get("ts"), (int, float)):
        errs.append("ts is not numeric")
    meta = row.get("meta")
    if not isinstance(meta, dict):
        errs.append("meta is not an object")
    else:
        errs.extend(f"meta missing {k!r}" for k in META_REQUIRED if k not in meta)
    benches = row.get("benches")
    if not isinstance(benches, dict) or not benches:
        errs.append("benches is not a non-empty object")
        return errs
    for name, info in benches.items():
        if not isinstance(info, dict):
            errs.append(f"benches[{name}] is not an object")
            continue
        if not isinstance(info.get("wall_s"), (int, float)):
            errs.append(f"benches[{name}].wall_s is not numeric")
        qps = info.get("qps")
        if not isinstance(qps, dict) or any(
            not isinstance(k, str)
            or not isinstance(v, (int, float))
            or isinstance(v, bool)
            for k, v in qps.items()
        ):
            errs.append(f"benches[{name}].qps is not a str->number map")
    return errs


def validate_history_file(path: str) -> list[str]:
    """Every row of a BENCH_HISTORY.jsonl must parse and pass the row
    schema; an empty file is invalid (the trajectory must be non-empty
    once the file exists)."""
    try:
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
    except OSError as e:
        return [f"unreadable: {e}"]
    if not lines:
        return ["history file exists but holds no rows"]
    errs = []
    for i, ln in enumerate(lines):
        try:
            row = json.loads(ln)
        except json.JSONDecodeError as e:
            errs.append(f"row {i}: malformed JSON: {e}")
            continue
        errs.extend(f"row {i}: {e}" for e in validate_history_row(row))
    return errs

# Per-bench row schemas: every row of the named bench must be an object
# carrying these keys (benches whose rows are positional tuples are not
# listed — their shape is covered by the envelope check alone).
ROW_REQUIRED = {
    "bench_planner": ("workload", "passrate", "mode_counts", "planner", "cooperative"),
    # every updates row carries a phase, a qps figure and the compile
    # accounting (the shape-stable serving claim is only a claim if the
    # recompile count ships in the artifact); search rows add
    # workload/recall + p50/p99 latency, the steady_state row the
    # occupied-bucket/per-round compile breakdown, the writes row the
    # compaction profile
    "bench_updates": ("phase", "qps", "n_compiles", "n_cache_hits"),
    # sweep rows add recall_vs_exact + quant/exact RunResults; scan rows
    # (workload == "scan") add adc_scan/exact_scan QPS instead
    "bench_quant": ("workload", "m", "refine_factor", "bytes_per_vector"),
    # visit_step rows add fused/unfused qps arms, pq/ivf rows pallas/ref
    # arms; the trailing autotune_table row carries the tuner's decisions
    "bench_kernels": ("kernel", "metric", "d", "v"),
    # off/on/explain arms plus a summary row with the overhead fraction
    "bench_obs": ("arm", "qps"),
    # one row per tenant (zipfian hot/cold mix) plus a trailing
    # "_aggregate" row that adds the shared-executable compile accounting
    # (n_compiles / occupied_shape_buckets / tenants_x_buckets)
    "bench_tenancy": ("tenant", "n_requests", "p50_ms", "p99_ms",
                      "cache_hit_rate", "qps"),
}


def _validate_rows(bench: str, rows) -> list[str]:
    required = ROW_REQUIRED.get(bench)
    if required is None:
        return []
    if not isinstance(rows, list) or not rows:
        return [f"{bench}: rows must be a non-empty list"]
    errs = []
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            errs.append(f"{bench}: row {i} is {type(row).__name__}, expected object")
            continue
        errs.extend(f"{bench}: row {i} missing {k!r}" for k in required if k not in row)
    return errs


def validate_file(path: str) -> list[str]:
    """Returns a list of problems (empty == valid)."""
    errs = []
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"unreadable/malformed JSON: {e}"]
    if not isinstance(payload, dict):
        return [f"top level is {type(payload).__name__}, expected object"]
    for key in REQUIRED:
        if key not in payload:
            errs.append(f"missing key {key!r}")
    meta = payload.get("meta")
    if not isinstance(meta, dict):
        errs.append("meta is not an object")
    else:
        errs.extend(f"meta missing {k!r}" for k in META_REQUIRED if k not in meta)
    if "wall_s" in payload and not isinstance(payload["wall_s"], (int, float)):
        errs.append("wall_s is not numeric")
    if "bench" in payload and "rows" in payload:
        errs.extend(_validate_rows(payload["bench"], payload["rows"]))
    return errs


def main(argv: list[str] | None = None) -> int:
    args = sys.argv[1:] if argv is None else argv
    bench_dir = args[0] if args else os.path.dirname(os.path.abspath(__file__))
    paths = sorted(glob.glob(os.path.join(bench_dir, "BENCH_*.json")))
    if not paths:
        print(f"FAIL: no BENCH_*.json files under {bench_dir}")
        return 1
    bad = 0
    for path in paths:
        errs = validate_file(path)
        if errs:
            bad += 1
            for e in errs:
                print(f"FAIL {os.path.basename(path)}: {e}")
        else:
            print(f"ok   {os.path.basename(path)}")
    # the observability exports ride next to the bench artifacts with
    # their own schemas (repro.obs.metrics/v1, repro.obs.timeseries/v1) —
    # validate them when present, schema-dispatched
    n_extra = 0
    from repro.obs.validate import validate_any_file

    for extra in ("METRICS.json", "TIMESERIES.json"):
        epath = os.path.join(bench_dir, extra)
        if not os.path.exists(epath):
            continue
        n_extra += 1
        errs = validate_any_file(epath)
        if errs:
            bad += 1
            for e in errs:
                print(f"FAIL {extra}: {e}")
        else:
            print(f"ok   {extra}")
    hpath = os.path.join(bench_dir, "BENCH_HISTORY.jsonl")
    if os.path.exists(hpath):
        n_extra += 1
        errs = validate_history_file(hpath)
        if errs:
            bad += 1
            for e in errs:
                print(f"FAIL BENCH_HISTORY.jsonl: {e}")
        else:
            print("ok   BENCH_HISTORY.jsonl")
    print(f"{len(paths) + n_extra - bad}/{len(paths) + n_extra} artifacts valid")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
