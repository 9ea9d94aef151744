"""Production training launcher: thin CLI over ``repro.plan``.

The ExecutionPlan owns all execution wiring — mesh construction, the
sharding rule table, pipeline-stage placement, and parameter/optimizer
state sharding; this module parses flags, builds one plan, and hands the
restart-safe Trainer loop the plan's mesh/rules.

Default (production) path: 16x16 single-pod mesh — or 2x16x16 with
--multi-pod — with the full architecture config and the --shape
ShapeSpec. With --debug: a reduced config on a 1x1 host mesh with seq=32,
batch=4 (the 8-device integration tests exercise the same path on a 2x4
mesh). ``--stages N`` engages the plan's PlaceStages pass: the layer
stack splits into N pipeline stages assigned to mesh slices by the
``core.placement`` cost model, sharding the stacked layer weights across
the data axis instead of replicating them.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --debug --steps 20

Flags:
  --arch          architecture alias (required), e.g. yi-6b
  --shape         production ShapeSpec name (default train_4k); ignored
                  under --debug
  --mode          sharding mode override: cascade | megatron | megatron_sp
                  (default: the config's sharding_mode)
  --multi-pod     use the 2x16x16 ("pod","data","model") mesh
  --debug         reduced config on a tiny local mesh
  --stages        pipeline stages for the PlaceStages pass (default 1)
  --steps         training steps (default 50)
  --ckpt-dir      checkpoint directory (resume is automatic from the
                  newest checkpoint found there)
  --microbatches  gradient-accumulation factor
  --compress-grads  int8 error-feedback gradient compression
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.data.pipeline import make_train_iterator
from repro.models import SHAPES
from repro.models.base import ShapeSpec
from repro.plan import MeshSpec, build_plan
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser(
        description="Sharded training on a production or debug mesh with "
                    "the restart-safe Trainer loop, wired by one "
                    "ExecutionPlan.")
    ap.add_argument("--arch", required=True,
                    help="architecture alias, e.g. yi-6b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES),
                    help="production ShapeSpec (ignored under --debug)")
    ap.add_argument("--mode", default=None,
                    choices=["cascade", "megatron", "megatron_sp"],
                    help="sharding mode override (default: per-arch config)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (pod,data,model) mesh instead of 16x16")
    ap.add_argument("--debug", action="store_true",
                    help="reduced config on a tiny local mesh (seq=32, batch=4)")
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stages (PlaceStages pass; layers shard "
                         "across mesh slices chosen by the cost model)")
    ap.add_argument("--steps", type=int, default=50,
                    help="training steps to run")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train",
                    help="checkpoint dir (resumes from the newest found)")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation factor")
    ap.add_argument("--compress-grads", action="store_true",
                    help="int8 error-feedback gradient compression")
    args = ap.parse_args()

    if args.debug:
        shape = ShapeSpec("debug_train", 32, 4, "train")
        mesh_spec = MeshSpec.debug(1, 1)
    else:
        shape = SHAPES[args.shape]
        mesh_spec = MeshSpec.production(multi_pod=args.multi_pod)

    plan = build_plan(args.arch, shape, mode=args.mode, mesh_spec=mesh_spec,
                      pipeline_stages=args.stages, debug=args.debug)
    params, opt_state = plan.init_train_state(seed=0)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"{plan.cfg.name}: {n/1e6:.1f}M params on mesh "
          f"{plan.mesh.devices.shape} mode={plan.mode} "
          f"stages={args.stages}")
    if plan.ir.stages:
        for s in plan.ir.stages:
            print(f"  stage {s.index}: layers [{s.first_layer}, "
                  f"{s.first_layer + s.n_layers}) on rows "
                  f"[{s.row}, {s.row + s.height}) (cost model: "
                  f"{plan.ir.placement_method})")

    tcfg = TrainerConfig(
        steps=args.steps, ckpt_every=max(args.steps // 4, 1),
        ckpt_dir=args.ckpt_dir, log_every=5,
        microbatches=args.microbatches, compress_grads=args.compress_grads,
    )
    trainer = Trainer(plan.model.loss, plan.optimizer, tcfg,
                      mesh=plan.mesh, rules=plan.rules)

    seq, batch = shape.seq_len, shape.global_batch

    def iters(start):
        return make_train_iterator(plan.cfg.vocab, seq, batch, seed=0,
                                   start_step=start)

    _, _, hist = trainer.fit(params, opt_state, iters)
    if hist:
        print(f"done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}")
    else:
        print(f"done: checkpoint in {args.ckpt_dir} is already at "
              f">= {args.steps} steps; nothing to do (use a fresh "
              "--ckpt-dir or raise --steps)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
