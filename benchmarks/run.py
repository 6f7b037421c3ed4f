"""Benchmark driver — one module per paper table/figure.

  bench_index_size    -> Table IV
  bench_conjunctions  -> Figs. 4/5 + Table V (top)
  bench_disjunctions  -> Figs. 6/7 + Table V (bottom)
  bench_qps_recall    -> Figs. 8-10
  bench_ablation      -> Fig. 11
  bench_serving       -> serving-layer QPS/latency/compile counts (ours)
  bench_planner       -> planner selectivity sweep: mode/QPS/recall (ours)
  bench_updates       -> mutable-index churn: QPS/recall/compaction (ours)
  bench_quant         -> PQ tier: recall/QPS/bytes-per-vector sweep (ours)
  bench_kernels       -> fused-visit / pq / ivf kernel microbench (ours)
  bench_obs           -> observability overhead: obs-on vs obs-off QPS (ours)
  bench_tenancy       -> multi-tenant zipfian workload: per-tenant p50/p99,
                         cache hit rates, shared-executable compiles (ours)

``python -m benchmarks.run [--only name] [--quick] [--json-dir DIR]``

Each module's rows are also written to ``BENCH_<name>.json`` next to this
file (or under ``--json-dir``), wrapped with a provenance block (engine
version, scoring backend, platform, corpus scale — see
``common.bench_metadata``) so benchmark trajectories across PRs are
attributable to the code that produced them.

The driver additionally exports the process-global metrics registry as
``METRICS.json`` (schema ``repro.obs.metrics/v1``; empty-but-valid when
``REPRO_OBS`` is off), a per-bench snapshot timeline as
``TIMESERIES.json`` (schema ``repro.obs.timeseries/v1``: one registry
snapshot before the first bench and after each one, so windowed
rates/quantiles per bench phase are derivable offline), and — unless
``--history ''`` disables it — appends one schema-validated summary row
(``repro.bench.history/v1``: wall time + every extracted QPS label per
bench) to ``BENCH_HISTORY.jsonl``.  The per-run BENCH JSONs are
gitignored; the history file is the committable perf trajectory, and
``benchmarks/compare.py --history`` diffs its latest row against the
committed smoke baselines.  When ``REPRO_OBS_PROFILE`` is set, the whole
run is wrapped in a ``jax.profiler`` capture whose XPlane/perfetto
artifacts land in the named directory.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time


ALL = (
    "bench_index_size",
    "bench_conjunctions",
    "bench_disjunctions",
    "bench_qps_recall",
    "bench_ablation",
    "bench_serving",
    "bench_planner",
    "bench_updates",
    "bench_quant",
    "bench_kernels",
    "bench_obs",
    "bench_tenancy",
)


def write_metrics_json(json_dir: str) -> str:
    """Export the global metrics registry next to the BENCH artifacts.

    Always written: a run with obs disabled exports an empty-but-valid
    payload, so the CI schema gate (``python -m repro.obs.validate``) can
    run unconditionally.
    """
    from repro.obs import registry as obs_reg

    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, "METRICS.json")
    with open(path, "w") as f:
        json.dump(obs_reg.registry().to_json(), f, indent=1)
    return path


def write_timeseries_json(ring, json_dir: str) -> str:
    """Export the run's snapshot ring next to METRICS.json.  Like the
    metrics export this is unconditional: with obs off the snapshots are
    empty and the payload is empty-but-valid, so the CI schema gate runs
    either way."""
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, "TIMESERIES.json")
    with open(path, "w") as f:
        json.dump(ring.to_json(), f, indent=1)
    return path


def append_history(payloads: dict, history_path: str) -> str:
    """Append one ``repro.bench.history/v1`` row summarizing this run.

    The row carries the provenance block plus, per bench, the wall time
    and every QPS figure ``compare.extract_qps`` can see — the same
    labels the baseline diff uses, so history rows and committed
    baselines stay directly comparable.  Validated before the append: a
    malformed row raises instead of poisoning the trajectory.
    """
    from . import common as C
    from . import compare as cmp
    from . import validate as V

    row = {
        "schema": V.HISTORY_SCHEMA,
        "ts": time.time(),
        "meta": C.bench_metadata(),
        "benches": {
            name: {"wall_s": p["wall_s"], "qps": cmp.extract_qps(p)}
            for name, p in payloads.items()
        },
    }
    errs = V.validate_history_row(row)
    if errs:
        raise ValueError(f"refusing to append invalid history row: {errs[0]}")
    d = os.path.dirname(os.path.abspath(history_path))
    os.makedirs(d, exist_ok=True)
    with open(history_path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return history_path


def _jsonable(obj):
    """Benchmark rows are nested tuples/dicts of RunResults and numpy
    scalars; lower them to plain JSON types."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return _jsonable(dataclasses.asdict(obj))
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "tolist"):  # numpy / jax array or scalar
        return _jsonable(obj.tolist())
    if hasattr(obj, "item"):  # other 0-d scalar wrappers
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


def write_json(name: str, rows, wall_s: float, json_dir: str) -> tuple[str, dict]:
    from . import common as C

    payload = {
        "bench": name,
        "meta": C.bench_metadata(),
        "wall_s": wall_s,
        "rows": _jsonable(rows),
    }
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{name.removeprefix('bench_')}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path, payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--quick", action="store_true", help="shrink corpus for CI")
    ap.add_argument(
        "--json-dir", default=os.path.dirname(os.path.abspath(__file__)),
        help="where BENCH_<name>.json files land",
    )
    ap.add_argument(
        "--history",
        default=os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_HISTORY.jsonl"),
        help="perf-trajectory JSONL to append this run's summary row to "
        "('' disables)",
    )
    args = ap.parse_args()
    if args.quick:
        os.environ.setdefault("REPRO_BENCH_N", "20000")
        os.environ.setdefault("REPRO_BENCH_Q", "32")
    names = [args.only] if args.only else list(ALL)
    from repro.compile_cache import configure as configure_compile_cache
    from repro.obs import profiling as obs_prof

    print(f"==== compile cache -> {configure_compile_cache()} ====", flush=True)
    from repro.obs import timeseries as obs_ts

    # one registry snapshot before the first bench and after each one, so
    # TIMESERIES.json holds a per-bench-phase timeline of every series the
    # run recorded (empty snapshots with obs off)
    snapper = obs_ts.Snapshotter(capacity=len(names) + 1, interval_s=0.0)
    snapper.maybe_snapshot()
    payloads: dict[str, dict] = {}
    with obs_prof.profile_capture() as prof_dir:  # no-op without REPRO_OBS_PROFILE
        for name in names:
            mod = __import__(f"benchmarks.{name}", fromlist=["run"])
            t0 = time.time()
            print(f"==== {name} ====", flush=True)
            rows = mod.run()
            wall = time.time() - t0
            path, payloads[name] = write_json(name, rows, wall, args.json_dir)
            snapper.maybe_snapshot()
            print(f"==== {name} done in {wall:.0f}s -> {path} ====", flush=True)
    mpath = write_metrics_json(args.json_dir)
    print(f"==== metrics registry -> {mpath} ====", flush=True)
    tpath = write_timeseries_json(snapper.ring, args.json_dir)
    print(f"==== snapshot timeline -> {tpath} ====", flush=True)
    if args.history:
        hpath = append_history(payloads, args.history)
        print(f"==== history row -> {hpath} ====", flush=True)
    if prof_dir:
        print(f"==== profiler capture -> {prof_dir} ====", flush=True)


if __name__ == "__main__":
    main()
