#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, in one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Generates the corpus and the query pool from ``--seed``.
2. Builds the index on the device, as the configuration says.
3. Warms up one micro-batch of each predicate shape the traffic sends.
4. Drives the window (``bench/window.py``) through ``CollectionService``.
5. Reads the device's memory peak, frees the program's state, compares the
   answers with the plain reference (``bench/check.py``) and prints one JSON
   line: with ``--trace 0`` the cell's end-to-end metrics, with ``--trace 1``
   its per-layer metrics, read from a profiler trace of the window's first
   micro-batches, one for each predicate shape of the traffic's mix, and
   the program's counters over the same batches.

It needs a TPU.  ``--cpu-rehearsal`` runs on the CPU with Pallas in
interpret mode, for tests at tiny sizes; its numbers are not the device's.
``--control`` puts the reference, one precision step below the
configuration's, in the service's place: the comparison must then fail.
Progress goes to standard error; the compared numbers are its last lines.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: queries of the pool beyond those a run can use are never generated; a
#: run that gets through the pool starts it again
DEFAULT_POOL = 16384
#: registry counters that per-layer metrics read, summed over their labels
COUNTERS = ("compass_queries_total", "compass_steps_total", "compass_dist_total",
            "compass_adc_total", "compass_rerank_total")
BUCKET_FIELDS = ("n_batches", "n_requests", "n_fillers", "total_exec_s",
                 "n_mode_prefilter", "n_mode_cooperative", "n_mode_postfilter")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on the CPU with Pallas in interpret mode (tests only)")
    ap.add_argument("--control", action="store_true",
                    help="serve with the reference at the control's precision")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, also copy the window's .xplane.pb into DIR")
    return ap.parse_args(argv)


class Log:
    """Progress lines on standard error, each naming the device."""

    def __init__(self):
        self.tag = "[device unknown]"

    def __call__(self, msg: str) -> None:
        print(f"{self.tag} {msg}", file=sys.stderr, flush=True)


class CompileLog:
    """Backend compilations and persistent-cache lookups seen by JAX."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.count, self.seconds, self.cache_hits, self.cache_misses)


def _counters():
    from repro.obs import registry as obs_reg

    out = {}
    for name in COUNTERS:
        metric = obs_reg.registry().get(name)
        out[name] = sum(s["value"] for s in metric.samples()) if metric is not None else 0.0
    return out


def _buckets(svc, name):
    if not hasattr(svc, "collection_stats"):
        return {f: 0 for f in BUCKET_FIELDS}
    stats = svc.collection_stats(name)["buckets"].values()
    return {f: sum(s[f] for s in stats) for f in BUCKET_FIELDS}


def _diff(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def p95(latencies, missing: int) -> float:
    """Nearest-rank 95th percentile, ``missing`` requests counted as never
    answered."""
    vals = sorted(latencies) + [math.inf] * missing
    return vals[max(0, math.ceil(0.95 * len(vals)) - 1)]


def _drain(svc):
    out = []
    while svc.pending():
        out.extend(svc.step())
    return out


def main(argv=None, root: pathlib.Path | None = None) -> int:
    args = parse_args(argv)
    root = pathlib.Path(root) if root is not None else ROOT
    log = Log()
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no program under {ROOT / 'src'}: nothing to measure")
        return 2
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    if args.cpu_rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from bench import check, data, spec, system, tracing, window

    cell = spec.load_cell(args.workload, root)
    devices = jax.devices()
    dev = devices[0]
    log.tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        log(f"no TPU found (platform {dev.platform!r}); this benchmark measures the chip")
        return 2
    if len(devices) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, {len(devices)} found")
        return 2
    peaks = None if args.cpu_rehearsal else spec.peaks(dev.device_kind, root)
    if not args.cpu_rehearsal:
        # the one persistent compilation cache, at a fixed path in the checkout, set
        # only once a chip is found: a process that finds none writes no cache there
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from repro.compile_cache import configure as configure_compile_cache
    from repro.obs import events as obs_events
    from repro.obs import registry as obs_reg

    cache_dir = None if args.cpu_rehearsal else configure_compile_cache()
    log(f"cell={cell.name} seed={args.seed} seconds={args.seconds} trace={args.trace} "
        f"control={args.control} jax={jax.__version__} compile_cache={cache_dir}")
    compiles = CompileLog()
    cfg, traffic = cell.config, cell.traffic
    corpus, svc_cfg = cfg["corpus"], cfg["service"]
    batch, k, metric = int(svc_cfg["batch_size"]), int(cfg["search"]["k"]), \
        cfg["index"]["metric"]
    mix = traffic["mix"]
    pool = int(traffic.get("pool", DEFAULT_POOL))
    phases: dict[str, float] = {}

    t = time.perf_counter()
    x, attrs, queries = data.make_corpus(args.seed, corpus, pool + batch * len(mix))
    preds = data.make_predicates(data.host_rng(args.seed, 1), pool, mix, int(corpus["attrs"]))
    warm_rng = data.host_rng(args.seed, 2)
    warm = [data.make_predicates(warm_rng, batch, [shape], int(corpus["attrs"]))
            for shape in mix]
    phases["data"] = time.perf_counter() - t
    log(f"data: {x.shape[0]} x {x.shape[1]} rows, {attrs.shape[1]} attributes, pool of "
        f"{pool} queries; {phases['data']:.3f} s")

    obs_reg.set_enabled(True)  # build phases are events of the registry's log
    obs_events.EVENTS.clear()
    c0 = compiles.snapshot()
    t = time.perf_counter()
    if args.control:
        svc, name = system.ControlService(x, attrs, k, metric, batch), "control"
    else:
        svc, name = system.build(cfg, x, attrs, args.seed, phases)
    for ev in obs_events.EVENTS.tail(100, kind="index_build_phase"):
        log(f"   build {ev['phase']}: {ev['wall_s']:.3f} s")
    for ph in ("build_index", "quantize_index", "mutable_wrap"):
        if ph in phases:
            log(f"{ph}: {phases[ph]:.3f} s")
    c1 = compiles.snapshot()
    log(f"build compiles: {c1[0] - c0[0]} backend compiles, {c1[1] - c0[1]:.3f} s; "
        f"persistent cache {c1[2] - c0[2]} hits, {c1[3] - c0[3]} misses")

    from repro.core import predicate as P

    t = time.perf_counter()
    for s, wp in enumerate(warm):
        for i in range(batch):
            lo, hi = wp[i]
            svc.submit(name, queries[pool + s * batch + i], P.Predicate(lo, hi))
        _drain(svc)
    phases["warmup"] = time.perf_counter() - t
    c2 = compiles.snapshot()
    log(f"warm-up: {len(mix)} batches, {phases['warmup']:.3f} s; {c2[0] - c1[0]} backend "
        f"compiles, {c2[1] - c1[1]:.3f} s; served programs from the persistent cache: "
        f"{c2[2] - c1[2]} hits, {c2[3] - c1[3]} misses")

    obs_reg.set_enabled(bool(args.trace))
    traced = {}
    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") if args.trace else None
    if trace_dir is not None:
        tracing.start(trace_dir.name)
    before = (_counters(), _buckets(svc, name))

    shapes_traced: set[int] = set()

    def stop_trace():
        traced["counters"] = _diff(_counters(), before[0])
        traced["buckets"] = _diff(_buckets(svc, name), before[1])
        jax.profiler.stop_trace()

    def on_delivery(now, shape):
        shapes_traced.add(shape)
        if trace_dir is not None and not traced and len(shapes_traced) == len(mix):
            stop_trace()  # one micro-batch of each shape of the mix is in the trace

    c3 = compiles.snapshot()
    setup_s = time.perf_counter() - T_START
    log(f"set-up: {setup_s:.3f} s; window opens")
    win = window.drive(svc, name, queries[:pool], preds, traffic, args.seconds,
                       on_delivery=on_delivery, clock=time.perf_counter)
    c4 = compiles.snapshot()
    window_compiles = c4[0] - c3[0]
    log(f"window: {len(win.answers)} answered in {win.batches} batches over "
        f"{win.seconds:.3f} s; rejected {win.rejected}, lost {win.lost}, still queued at "
        f"close {win.queued_at_close} (in p95_ms at their age at the close, the oldest "
        f"{max(win.open_ages_s, default=0.0):.3f} s); compiles inside the window: "
        f"{window_compiles}")
    for s, shape in enumerate(mix):
        took = [t for t, k in zip(win.step_s, win.step_shape) if k == s]
        log(f"micro-batch seconds, {shape.get('name', s)} (T={shape['terms']}): "
            + " ".join(f"{t:.3f}" for t in took)
            + (f"; mean {sum(took) / len(took):.3f}" if took else ""))
    if window_compiles:
        log("WARNING: the window compiled; its numbers include compilation")

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    run = None
    if args.trace:
        if not traced:  # the window ended before every shape was delivered
            stop_trace()
        path = tracing.find(trace_dir.name)
        if args.keep_trace:
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(path, args.keep_trace)
        t = time.perf_counter()
        trace = tracing.read(path)
        trace_dir.cleanup()
        log(f"trace: {sum(len(o) for o in trace.ops.values())} device operations in "
            f"{trace.window_s:.3f} s traced; read in {time.perf_counter() - t:.3f} s")
        run = types.SimpleNamespace(config=cfg, peaks=peaks, trace=trace,
                                    counters=traced["counters"], buckets=traced["buckets"],
                                    phases=phases, batch_size=batch)
    del svc, on_delivery, stop_trace  # the program's state, before the reference
    gc.collect()

    t = time.perf_counter()
    correct, numbers = check.compare(
        win.answers, win.lost, x, attrs, queries, preds, k, metric, cfg["limits"],
        data.host_rng(args.seed, 3), batch=batch)
    log(f"reference check: {min(len(win.answers), check.MAX_COMPARED)} answers compared in "
        f"{time.perf_counter() - t:.3f} s (not in any metric)")

    attempted = len(win.answers) + win.rejected + len(win.open_ages_s)
    failed = win.rejected + win.lost
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    metrics = {}
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        busy = tracing.busy_s(run.trace)
        device["busy_s"] = busy
        device["window_s"] = run.trace.window_s
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], root)(run)
            if value is None:
                log(f"per-layer metric {m['name']} read nothing in this run: it is left out "
                    "of the result line")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": tracing.top_ops(run.trace),
                            "idle_gaps": tracing.idle_gaps(run.trace)}
    else:
        e2e = {"qps": len(win.answers) / win.seconds,
               "p95_ms": 1000.0 * p95(win.latencies_s + win.open_ages_s, win.rejected),
               "recall_at_10": numbers["recall"]["value"],
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    out["checks"] = numbers
    for key, v in numbers.items():
        log(f"check {key} = {v['value']!r} (limit {v['holds']} {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and pathlib.Path(sys.path[0]).resolve() == HERE:
        sys.path[0] = str(ROOT)  # import the harness as the package ``bench``
    sys.exit(main())
