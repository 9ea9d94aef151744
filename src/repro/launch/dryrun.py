import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: build an ExecutionPlan for every (architecture x
input shape) on the production meshes, compile its executable AOT through
the plan's cache, prove the sharding config is coherent, and extract the
roofline statistics from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun

The XLA_FLAGS line above MUST stay the first statement: jax locks the device
count on first init, and the production mesh needs 512 placeholder devices.
(Only this entry point sets it — smoke tests and benches see 1 device.)
"""

import argparse      # noqa: E402
import json          # noqa: E402
import traceback     # noqa: E402
from typing import Optional  # noqa: E402

from repro.configs import ALIASES, get_config, list_archs  # noqa: E402
from repro.launch.hlo_analysis import analyze_hlo          # noqa: E402
from repro.launch.model_flops import model_flops, param_counts  # noqa: E402
from repro.launch.roofline import roofline_terms, summarize     # noqa: E402
from repro.models.base import SHAPES, supports_shape       # noqa: E402
from repro.plan import MeshSpec, build_plan                # noqa: E402


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mode: Optional[str] = None,
    pipeline_stages: int = 1,
    verbose: bool = True,
    hlo_dir: Optional[str] = None,
    config_overrides: Optional[dict] = None,
) -> dict:
    cfg = get_config(arch)
    if config_overrides:
        cfg = cfg.with_(**config_overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    record = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": mesh_name,
        "mode": mode or cfg.sharding_mode,
        "stages": pipeline_stages,
    }
    ok, reason = supports_shape(cfg, shape_name)
    if not ok:
        record["status"] = "skipped"
        record["reason"] = reason
        if verbose:
            print(f"SKIP {cfg.name} x {shape_name}: {reason}")
        return record
    try:
        plan = build_plan(
            cfg, shape, mode=mode,
            mesh_spec=MeshSpec.production(multi_pod=multi_pod),
            pipeline_stages=pipeline_stages,
        )
        n_chips = plan.mesh.devices.size
        entry = plan.executable()          # AOT lower+compile, counted
        compiled = entry.compiled
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes_est": ma.argument_size_in_bytes
            + ma.output_size_in_bytes + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes,
        }
        ca = compiled.cost_analysis() or {}
        text = compiled.as_text()
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            fn = f"{arch}_{shape_name}_{mesh_name}_{record['mode']}.hlo"
            with open(os.path.join(hlo_dir, fn.replace('/', '_')), "w") as f:
                f.write(text)
        stats = analyze_hlo(text)
        mf = model_flops(cfg, shape)
        total_p, active_p = param_counts(cfg)
        record.update(
            status="ok",
            lower_s=round(entry.lower_seconds, 2),
            compile_s=round(entry.compile_seconds, 2),
            params_total=total_p,
            params_active=active_p,
            memory=mem,
            xla_cost_analysis={
                "flops_module_once": ca.get("flops", 0.0),
                "bytes_module_once": ca.get("bytes accessed", 0.0),
            },
            roofline=roofline_terms(stats, n_chips, mf, mem),
        )
        if pipeline_stages > 1:
            record["stage_slices"] = [s.as_dict() for s in plan.ir.stages]
        if verbose:
            print(f"== {cfg.name} x {shape_name} on {mesh_name} "
                  f"({record['mode']}) ==")
            print(f"memory_analysis (per device): {mem}")
            print(f"cost_analysis: flops(once)={ca.get('flops', 0):.3e}")
            print(summarize(record))
    except Exception as e:  # noqa: BLE001 — record the failure, keep batch
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"ERROR {cfg.name} x {shape_name} on {mesh_name}: "
                  f"{record['error']}")
    return record


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default=None,
                   help=f"one of {list(ALIASES)} (or module id)")
    p.add_argument("--shape", default=None, choices=list(SHAPES))
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--single-pod", action="store_true")
    p.add_argument("--mode", default=None,
                   choices=["cascade", "megatron", "megatron_sp"],
                   help="sharding mode override (default: per-arch config)")
    p.add_argument("--stages", type=int, default=1,
                   help="pipeline stages (PlaceStages pass)")
    p.add_argument("--all", action="store_true",
                   help="every (arch x shape) on the requested mesh(es)")
    p.add_argument("--moe-groups", type=int, default=None,
                   help="group-limited MoE dispatch (0/None = global sort)")
    p.add_argument("--q-chunk", type=int, default=None)
    p.add_argument("--microbatches", type=int, default=None,
                   help="gradient-accumulation factor for train shapes")
    p.add_argument("--out", default=None, help="write JSON records here")
    p.add_argument("--hlo-dir", default=None, help="dump compiled HLO text")
    args = p.parse_args()

    if args.single_pod and not args.multi_pod:
        meshes = [False]
    elif args.multi_pod and not args.single_pod:
        meshes = [True]
    else:  # default: prove both the single-pod and the multi-pod mesh
        meshes = [False, True]

    cells = []
    if args.all:
        for arch in list_archs():
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            p.error("--arch and --shape required unless --all")
        cells.append((args.arch, args.shape))

    overrides = {}
    if args.moe_groups is not None:
        overrides["moe_groups"] = args.moe_groups
    if args.q_chunk is not None:
        overrides["q_chunk"] = args.q_chunk
    if args.microbatches is not None:
        overrides["microbatches"] = args.microbatches

    records = []
    for arch, shape in cells:
        for mp in meshes:
            records.append(
                run_cell(arch, shape, multi_pod=mp, mode=args.mode,
                         pipeline_stages=args.stages,
                         hlo_dir=args.hlo_dir,
                         config_overrides=overrides or None)
            )
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out if args.out.endswith(".json")
                  else args.out + ".json", "w") as f:
            json.dump(records, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"/ {len(records)} cells")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
