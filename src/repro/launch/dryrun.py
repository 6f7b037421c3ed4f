import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any other import: jax locks the device count on first init.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape) cell, and for the Compass
distributed-search step, this lowers + compiles the sharded step on the
production mesh — (16,16) single-pod and (2,16,16) = 512-chip multi-pod —
and records memory_analysis / cost_analysis / the collective schedule into
experiments/dryrun/*.json for §Roofline.

Cost calibration: XLA's HloCostAnalysis counts a while-loop body ONCE, so a
scanned L-layer stack under-reports flops/bytes/collectives by ~L x.  Each
cell is therefore lowered twice more at small depths k1 < k2 with the layer
scan *unrolled* and nm=1, giving per-layer costs by finite difference:
    per_layer = (C(k2) - C(k1)) / (k2 - k1)
    total     = C(k1) + (L - k1) * per_layer        (exact for homogeneous
stacks; ~5% approximation for zamba2's trailing mamba layers).  The real
scanned artifact still provides memory_analysis + compile-success + the
collective schedule shape.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod | --both-meshes]
  PYTHONPATH=src python -m repro.launch.dryrun --compass
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import SHAPES, all_configs, get_config, shape_applicable  # noqa: E402
from repro.launch import input_specs as ispec  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline.analysis import collect_cell_report, extract_costs  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _ep_context(cfg, shape, mesh):
    """Expert-parallel context where applicable (MoE + divisible seq)."""
    from repro.launch.mesh import data_axes
    from repro.models.moe import EPContext

    if not cfg.moe or shape.kind == "decode":
        return None
    if shape.seq_len % mesh.shape.get("model", 1):
        return None
    return EPContext(batch_axes=data_axes(mesh))


def _lower(cfg, shape, mesh, specs, *, unroll=False, force_nm=None, use_ep=True):
    ep = _ep_context(cfg, shape, mesh) if use_ep else None
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.optim.adamw import AdamWConfig
            from repro.train.step import TrainConfig, make_train_step

            tc = TrainConfig(
                optimizer=AdamWConfig(),
                n_microbatches=force_nm or specs["n_microbatches"],
                remat=True,
                unroll=unroll,
                act_sharding=specs["act_sharding"],
                ep=ep,
            )
            step = make_train_step(cfg, tc)
            fn = jax.jit(
                step,
                in_shardings=(
                    specs["param_shardings"],
                    specs["opt_shardings"],
                    specs["batch_shardings"],
                ),
                out_shardings=(specs["param_shardings"], specs["opt_shardings"], None),
                donate_argnums=(0, 1),
            )
            lowered = fn.lower(specs["params"], specs["opt_state"], specs["batch"])
        elif shape.kind == "prefill":
            from repro.serving.step import make_prefill_step

            pf = make_prefill_step(cfg, act_sharding=specs["act_sharding"], unroll=unroll, ep=ep)
            fn = jax.jit(
                pf,
                in_shardings=(
                    specs["param_shardings"],
                    specs["batch_shardings"],
                ),
                out_shardings=(None, specs["cache_shardings"]),
            )
            lowered = fn.lower(specs["params"], specs["batch"])
        else:
            from repro.serving.step import make_decode_step

            dec = make_decode_step(cfg, unroll=unroll)
            fn = jax.jit(
                dec,
                in_shardings=(
                    specs["param_shardings"],
                    specs["token_shardings"],
                    specs["cache_shardings"],
                    None,
                ),
                out_shardings=(None, None, specs["cache_shardings"]),
                donate_argnums=(2,),
            )
            lowered = fn.lower(
                specs["params"], specs["tokens"], specs["caches"], specs["cache_pos"]
            )
        return lowered


def _calibration_depths(cfg) -> tuple[int, int]:
    if cfg.hybrid_period:
        return cfg.hybrid_period, 2 * cfg.hybrid_period
    if cfg.moe and cfg.moe.first_dense:
        return cfg.moe.first_dense + 1, cfg.moe.first_dense + 2
    return 1, 2


def calibrate_costs(cfg, shape, mesh, bf16_weights: bool = False) -> dict:
    """Two-point finite-difference extrapolation of per-device costs."""
    k1, k2 = _calibration_depths(cfg)
    costs = {}
    for k in (k1, k2):
        c = dataclasses.replace(cfg, n_layers=k)
        specs = ispec.input_specs(c, shape, mesh, bf16_weights=bf16_weights)
        lowered = _lower(c, shape, mesh, specs, unroll=True, force_nm=1)
        compiled = lowered.compile()
        costs[k] = extract_costs(compiled)
    per_layer = {
        # clamp: XLA occasionally optimizes the k1 program differently
        # (e.g. fusing away a collective), which would extrapolate negative
        key: max((costs[k2][key] - costs[k1][key]) / (k2 - k1), 0.0)
        for key in costs[k1]
    }
    total = {
        key: costs[k1][key] + (cfg.n_layers - k1) * per_layer[key] for key in costs[k1]
    }
    return {
        "k1": k1,
        "k2": k2,
        "c_k1": costs[k1],
        "per_layer": per_layer,
        "total": total,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
             bf16_weights: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "16x16"
    if not shape_applicable(cfg, shape):
        if verbose:
            print(f"SKIP {arch} x {shape_name}: full attention at 500k (DESIGN.md §Skips)")
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "skipped": "long_500k requires sub-quadratic sequence mixing",
        }
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = ispec.input_specs(cfg, shape, mesh, bf16_weights=bf16_weights)
    t0 = time.time()
    lowered = _lower(cfg, shape, mesh, specs)
    t_lower = time.time() - t0
    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = lowered.compile()
    t_compile = time.time() - t0
    meta = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": shape.kind,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
    }
    if shape.kind == "train":
        meta["n_microbatches"] = specs["n_microbatches"]
    calib = calibrate_costs(cfg, shape, mesh, bf16_weights=bf16_weights)
    rec = collect_cell_report(cfg, shape, lowered, compiled, meta, calibrated=calib)
    if verbose:
        ma, rl = rec["memory"], rec["roofline"]
        print(
            f"OK {arch} x {shape_name} [{mesh_name}] "
            f"compile={meta['t_compile_s']}s mem/dev={ma['total_bytes_per_device']/1e9:.2f}GB "
            f"Tc={rl['t_compute_s']:.4f}s Tm={rl['t_memory_s']:.4f}s "
            f"Tcoll={rl['t_collective_s']:.4f}s dom={rl['dominant']} "
            f"useful={rl['useful_flops_ratio']:.2f} mfu_ub={rl['mfu_upper_bound']:.2f}",
            flush=True,
        )
    return rec


def run_compass(multi_pod: bool, verbose: bool = True) -> dict:
    """Distributed Compass filtered-search dry-run (the paper's own step):
    corpus sharded over all devices, per-shard search, global top-k merge."""
    from repro.core.distributed import abstract_distributed_search

    mesh = make_production_mesh(multi_pod=multi_pod)
    return abstract_distributed_search(mesh, verbose=verbose)


def save(rec: dict, out_dir: str = OUT_DIR) -> None:
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json".replace("/", "_")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--compass", action="store_true")
    ap.add_argument("--start-from", default=None)
    ap.add_argument("--bf16-params", action="store_true",
                    help="store >=2D weights bf16 (hillclimb variant; "
                         "records land in *_bf16.json)")
    ap.add_argument("--out-dir", default=OUT_DIR,
                    help="where the per-cell JSON records are written")
    args = ap.parse_args()

    if args.compass:
        for mp in ([False, True] if args.both_meshes else [args.multipod]):
            save(run_compass(mp), args.out_dir)
        return

    failures = []
    if args.all:
        archs = sorted(all_configs().keys())
        if args.start_from:
            archs = archs[archs.index(args.start_from) :]
        for arch in archs:
            for shape_name in SHAPES:
                for mp in [False, True] if args.both_meshes else [args.multipod]:
                    try:
                        save(run_cell(arch, shape_name, mp), args.out_dir)
                    except Exception as e:  # noqa: BLE001
                        traceback.print_exc()
                        failures.append((arch, shape_name, mp, repr(e)))
        if failures:
            print("FAILURES:")
            for f in failures:
                print(" ", f)
            raise SystemExit(1)
        print("all cells OK")
        return

    rec = run_cell(args.arch, args.shape, args.multipod, bf16_weights=args.bf16_params)
    if args.bf16_params:
        rec["variant"] = "bf16_params"
        rec["shape"] = rec["shape"] + "_bf16"
    save(rec, args.out_dir)


if __name__ == "__main__":
    main()
