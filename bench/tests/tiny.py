"""A benchmark root at a tiny size, written into a temporary directory: its
own ``BENCHMARK.json``, configuration, traffic mix and per-layer readers.
The harness finds all of it by name, with no edit of its own files."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil

from bench import run

REAL = pathlib.Path(__file__).resolve().parents[2]
#: the real benchmark's quantized cell
QUANTIZED = "sift1m-pq16.broad"


def tiny_config(name: str, real: str = "sift1m-exact") -> dict:
    base = json.loads((REAL / "bench" / "configs" / f"{real}.json").read_text())
    base["name"] = name
    base["corpus"].update(rows=1000, modes=8)
    base["index"]["nlist"] = 16
    base["search"]["ef"] = 16
    base["service"]["batch_size"] = 4
    return base


def write_root(tmp: pathlib.Path) -> pathlib.Path:
    """Three cells, ``tiny-exact.mixed`` (the real ``broad`` mix),
    ``tiny-exact.conj`` (its conjunctions alone) and ``tiny-pq16.mixed`` (the
    quantized tier, PQ m 16, ks 256, refine factor 4, under ``broad``), with
    the real benchmark's metrics and one extra per-layer metric,
    ``batches_traced``.  A metric the real benchmark keeps to the quantized
    cell is kept to ``tiny-pq16.mixed``; every other applies to all three."""
    bench = json.loads((REAL / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir(parents=True)
    shutil.copytree(REAL / "bench" / "metrics", tmp / "bench" / "metrics",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REAL / "bench" / "peaks.json", tmp / "bench" / "peaks.json")
    (tmp / "bench" / "configs" / "tiny-exact.json").write_text(
        json.dumps(tiny_config("tiny-exact")))
    (tmp / "bench" / "configs" / "tiny-pq16.json").write_text(
        json.dumps(tiny_config("tiny-pq16", "sift1m-pq16")))
    traffic = json.loads((REAL / "bench" / "traffic" / "broad.json").read_text())
    traffic["arrivals"]["clients"] = 8
    traffic["pool"] = 256
    (tmp / "bench" / "traffic" / "mixed.json").write_text(json.dumps(traffic))
    traffic["mix"] = traffic["mix"][:1]
    (tmp / "bench" / "traffic" / "conj.json").write_text(json.dumps(traffic))
    (tmp / "bench" / "metrics" / "batches_traced.py").write_text(
        "def read(run):\n    return float(run.buckets['n_batches']) or None\n")
    bench["configs"] = [{"name": c, "source": "in-test", "file": f"bench/configs/{c}.json",
                         "reduced": ["corpus"], "why": "tiny"}
                        for c in ("tiny-exact", "tiny-pq16")]
    bench["workloads"] = [
        {"name": f"{c}.{mix}", "config": c, "traffic": mix, "chips": 1, "why": "tiny"}
        for c, mix in (("tiny-exact", "mixed"), ("tiny-exact", "conj"), ("tiny-pq16", "mixed"))]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m.pop("workloads", None) == [QUANTIZED]:
            m["workloads"] = ["tiny-pq16.mixed"]
    bench["end_to_end"][1]["workloads"] = ["tiny-exact.mixed"]
    bench["per_layer"].append({"name": "batches_traced", "unit": "batches", "better": "higher",
                               "source": "program_counter", "layer": "front door",
                               "moves": "qps"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def main(root: pathlib.Path, *argv: str):
    """``(exit code, stdout, stderr)`` of the harness run in this process."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        code = run.main(list(argv), root=root)
    return code, out.getvalue(), err.getvalue()
