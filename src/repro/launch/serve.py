"""Serving launcher: thin CLI over ``repro.plan`` + ``repro.serve``.

All execution wiring — mesh construction, sharding rules, quantization
calibration, AOT executable compilation — happens inside the
:class:`repro.plan.ExecutionPlan` built by ``build_plan``; the batcher and
this CLI are thin consumers. This module only parses flags, builds the
plan, submits synthetic requests, and prints the counters. It dispatches
``--rounds`` request waves so the executable-cache hit counter is
observable after the first wave (the CI smoke job asserts hits > 0 on
the second).

Default path: the full published config on a mesh of every device this
process sees, all on the model axis (1x1 on one chip, 1x4 on a four-chip
host), with bucket shapes from the --shape ShapeSpec (default serve_v5e:
batch 8, context 2048 — sized so yi-6b serves at full depth from one
16 GB TPU v5e). The 16x16 / 2x16x16 production meshes belong to the
dry-run (``repro.launch.dryrun``). With --debug: a reduced config on a
1x1 mesh with 2-sequence buckets. Each wave prints its wall-clock
tokens/s next to the device it ran on.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --debug --tokens 4
    python -m repro.launch.serve --arch yi-6b --schedule continuous \
        --steps-per-dispatch 4 --paged        # on the chip, full width

Flags:
  --arch       architecture alias (required), e.g. yi-6b
  --shape      ShapeSpec name for the bucket shapes (default serve_v5e);
               ignored under --debug
  --mode       sharding mode override: cascade | megatron | megatron_sp
               (default: the config's sharding_mode)
  --debug      reduced config on a tiny local mesh
  --tokens     tokens to decode per request (default 8, must be >= 1)
  --quantized  int8 qmatmul decode LM head + a16w8 MLP down-projection
               (shifts calibrated from the loaded weights by the plan's
               Quantize pass)
  --rounds     request waves to dispatch (default 2: warm + cache-hit)
  --schedule   fifo (fixed dispatch groups, default) | continuous
               (slot reuse inside in-flight dispatches via the
               ContinuousScheduler — one masked decode executable per
               bucket)
  --steps-per-dispatch
               continuous micro-run length k: scan k masked steps per
               executable call (amortizes dispatch overhead; long
               prompts are chunk-prefilled k tokens per call). Needs
               --schedule continuous when > 1; bucket max_len must be a
               multiple of k. Default 1.
  --policy     boundary-time admission policy (continuous only):
               fifo (arrival order, default) | priority (strict classes,
               per-tenant fairness, aging) | edf (earliest deadline
               first, expired requests shed)
  --stream     drive the waves through the asyncio streaming front-end
               (repro.serve.server.AsyncServeServer): concurrent
               submission, per-micro-run token streams, p50/p99 TTFT
               printed from the server's client-side stats
  --paged      paged KV cache (needs --schedule continuous): one shared
               physical page pool instead of dense per-bucket KV slabs,
               with content-hashed shared-prefix reuse that skips
               prefill for common prompt openings. Optionally takes the
               page size in tokens (default 16); the pool is auto-sized
               so paged mode is never less capable than dense. Prints
               the allocator counters (pages in use, peak, prefix hits,
               prefill-skip rate) after the waves. See
               docs/memory_model.md.
  --speculative K
               speculative decode lanes (needs --schedule continuous):
               a layer-prefix draft proposes K tokens per micro-run and
               the full target verifies them in the same fused dispatch;
               K must equal --steps-per-dispatch. Accepted tokens are
               committed at micro-run boundaries, rejections roll the
               slot back. Greedy streams stay bit-exact. Composes with
               --paged: draft+verify writes land in revocable draft-page
               leases that commit or roll back with the tokens (see
               docs/memory_model.md). Prints the acceptance counters
               after the waves. See docs/serving.md.
  --draft      draft model spec for --speculative: "prefix:N" runs the
               first N layers of the target as a self-speculative draft
               (default: half the stack).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time
from typing import Any, Dict, List

import jax

from repro.models import SHAPES
from repro.plan import MeshSpec, build_plan
from repro.serve import BucketPolicy, DecodeRequest, ServeBatcher, make_policy
from repro.serve.batcher import RequestResult


def build_batcher(args) -> ServeBatcher:
    """One ExecutionPlan -> a ServeBatcher with demo params."""
    if args.debug:
        mesh_spec = MeshSpec.debug(1, 1)
        policy = BucketPolicy.debug()
    else:
        mesh_spec = MeshSpec.local()
        shape = SHAPES[args.shape]
        policy = BucketPolicy.production(shape.global_batch, shape.seq_len)
    plan = build_plan(args.arch, None, mode=args.mode, mesh_spec=mesh_spec,
                      quantized=args.quantized, debug=args.debug)
    admission = make_policy(args.policy) if args.policy != "fifo" else None
    batcher = plan.make_batcher(policy=policy, schedule=args.schedule,
                                steps_per_dispatch=args.steps_per_dispatch,
                                admission=admission, paged=args.paged,
                                speculative=args.speculative,
                                draft=args.draft)
    with plan.activate():
        batcher.init_demo_params(seed=0)
    return batcher


def device_label(mesh) -> str:
    """``platform device_kind xN`` of the devices a mesh spans."""
    d = mesh.devices.flat[0]
    return f"{d.platform} {d.device_kind} x{mesh.devices.size}"


@dataclasses.dataclass
class Wave:
    """One request wave: its results, wall time, and the executable-cache
    counters right after it (the next wave's lowerings/compiles must not
    grow once the buckets are warm)."""

    results: Dict[str, RequestResult]
    seconds: float
    cache: Dict[str, Any]


@dataclasses.dataclass
class ServeRun:
    """What :func:`main` served: the batcher (plan, params, counters) and
    its waves, for in-process callers such as ``chip_smoke.py``."""

    batcher: ServeBatcher
    waves: List[Wave]


def main(argv=None) -> ServeRun:
    ap = argparse.ArgumentParser(
        description="Bucketed batch decode over AOT-cached executables "
                    "and resident KV/SSM state pools, wired by one "
                    "ExecutionPlan.",
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="""\
continuous-batching extras (all need --schedule continuous):
  --steps-per-dispatch k   scan k masked steps per executable call
  --policy priority|edf    boundary-time admission ordering / shedding
  --stream                 asyncio streaming front-end with client TTFT
  --paged [PAGE_SIZE]      paged KV cache with shared-prefix prefill
                           skipping (docs/memory_model.md)
  --speculative K          fused draft+verify lanes, K = micro-run length
                           (greedy streams stay bit-exact; composes with
                           --paged via revocable draft-page leases)

examples:
  %(prog)s --arch yi-6b --debug --schedule continuous \\
      --steps-per-dispatch 4 --paged --tokens 8
  %(prog)s --arch yi-6b --debug --schedule continuous \\
      --policy edf --stream""")
    ap.add_argument("--arch", required=True,
                    help="architecture alias, e.g. yi-6b")
    ap.add_argument("--shape", default="serve_v5e", choices=list(SHAPES),
                    help="ShapeSpec sizing the buckets (ignored under "
                         "--debug)")
    ap.add_argument("--mode", default=None,
                    choices=["cascade", "megatron", "megatron_sp"],
                    help="sharding mode override (default: per-arch config)")
    ap.add_argument("--debug", action="store_true",
                    help="reduced config on a tiny local mesh (batch=2)")
    ap.add_argument("--tokens", type=int, default=8,
                    help="tokens to decode per request (>= 1)")
    ap.add_argument("--quantized", action="store_true",
                    help="int8 qmatmul decode LM head + quantized MLP "
                         "down-projection (calibrated shifts)")
    ap.add_argument("--rounds", type=int, default=2,
                    help="request waves (2nd+ hit the executable cache)")
    ap.add_argument("--schedule", default="fifo",
                    choices=["fifo", "continuous"],
                    help="fixed FIFO dispatch groups, or continuous "
                         "batching with in-flight slot reuse")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="continuous micro-run length k: scan k masked "
                         "steps per executable call (>= 1; > 1 needs "
                         "--schedule continuous)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "priority", "edf"],
                    help="boundary-time admission policy (non-fifo needs "
                         "--schedule continuous)")
    ap.add_argument("--stream", action="store_true",
                    help="drive the waves through the asyncio streaming "
                         "front-end (needs --schedule continuous)")
    ap.add_argument("--paged", nargs="?", const=True, default=None,
                    type=int, metavar="PAGE_SIZE",
                    help="paged KV cache with shared-prefix reuse (needs "
                         "--schedule continuous); optional page size in "
                         "tokens, default 16")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decode: draft K tokens per micro-run "
                         "and verify them in the same fused dispatch "
                         "(needs --schedule continuous; K must equal "
                         "--steps-per-dispatch; composes with --paged)")
    ap.add_argument("--draft", default=None, metavar="PREFIX:N",
                    help="draft model for --speculative: 'prefix:N' = "
                         "first N target layers (default: half the stack)")
    args = ap.parse_args(argv)
    if args.tokens < 1:
        ap.error("--tokens must be >= 1")
    if args.rounds < 1:
        ap.error("--rounds must be >= 1")
    if args.steps_per_dispatch < 1:
        ap.error("--steps-per-dispatch must be >= 1")
    if args.steps_per_dispatch > 1 and args.schedule != "continuous":
        ap.error("--steps-per-dispatch > 1 needs --schedule continuous")
    if args.policy != "fifo" and args.schedule != "continuous":
        ap.error("--policy needs --schedule continuous")
    if args.stream and args.schedule != "continuous":
        ap.error("--stream needs --schedule continuous")
    if args.paged is not None and args.schedule != "continuous":
        ap.error("--paged needs --schedule continuous")
    if args.paged is not None and args.paged is not True and args.paged < 1:
        ap.error("--paged page size must be >= 1")
    if args.speculative:
        if args.schedule != "continuous":
            ap.error("--speculative needs --schedule continuous")
        if args.speculative != args.steps_per_dispatch:
            ap.error("--speculative must equal --steps-per-dispatch "
                     "(the draft proposes exactly one micro-run)")
    if args.draft is not None and not args.speculative:
        ap.error("--draft needs --speculative")

    batcher = build_batcher(args)
    cfg, mesh = batcher.cfg, batcher.mesh
    device = device_label(mesh)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(batcher.params))
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv} kv x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; {param_bytes / 1e9:.2f} GB of "
          f"params on {device} (mesh {dict(mesh.shape)}, {batcher.plan.mode})")
    batch = batcher.policy.buckets[0].batch
    # continuous batching is about refilling freed slots from a deep
    # queue: submit two requests per slot so slot reuse is observable
    wave_size = batch * 2 if args.schedule == "continuous" else batch

    def wave_requests(wave: int):
        # priorities/tenants cycle so --policy priority has classes to
        # order; deadlines are generous (nothing sheds in a smoke run)
        deadline = (time.monotonic() + 120.0
                    if args.policy == "edf" and args.stream else
                    1_000_000.0 if args.policy == "edf" else None)
        # under --paged every request opens with the same one-page
        # system prompt, so shared-prefix reuse is observable in the
        # printed allocator counters from the second admission on
        system = [1 + (j * 5) % 50 for j in range(16)] if args.paged else []
        return [DecodeRequest(
            f"w{wave}r{i}",
            system + [1 + (i + j) % 7 for j in range(i % 3 + 2)],
            max_new_tokens=args.tokens, priority=i % 3,
            tenant=f"tenant{i % 2}", deadline=deadline)
            for i in range(wave_size)]

    waves: List[Wave] = []

    def finish_wave(wave: int, results: Dict[str, RequestResult],
                    seconds: float, how: str = "") -> None:
        waves.append(Wave(results, seconds, dict(batcher.cache.stats())))
        sample = results[sorted(results)[0]]
        n_tok = sum(len(r.tokens) for r in results.values())
        print(f"wave {wave}: {len(results)} requests x {args.tokens} "
              f"tokens{how}, sample {sample.request_id} -> "
              f"{sample.tokens[:8]}; {n_tok} tokens in {seconds:.3f}s = "
              f"{n_tok / seconds:.1f} tok/s wall-clock on {device}")

    t_first = None
    if args.stream:
        from repro.serve import AsyncServeServer

        async def run_streaming():
            async with AsyncServeServer(batcher) as server:
                for wave in range(args.rounds):
                    t0 = time.perf_counter()
                    results = await asyncio.gather(*[
                        server.generate(r) for r in wave_requests(wave)])
                    finish_wave(wave, {r.request_id: r for r in results},
                                time.perf_counter() - t0, " (streamed)")
                return server.stats()

        with batcher.plan.activate():
            sstats = asyncio.run(run_streaming())
        print(f"stream: p50 TTFT {sstats['p50_ttft_s']}s, "
              f"p99 TTFT {sstats['p99_ttft_s']}s, "
              f"outcomes {sstats['outcomes']}")
    else:
        with batcher.plan.activate():
            for wave in range(args.rounds):
                t0 = time.perf_counter()
                for r in wave_requests(wave):
                    batcher.submit(r)
                results = batcher.run()
                if t_first is None and results:
                    t_first = min(r.prefill_seconds
                                  for r in results.values())
                finish_wave(wave, results, time.perf_counter() - t0)

    stats = batcher.stats()
    for label, m in stats["buckets"].items():
        print(f"bucket {label}: {m['requests']} reqs, "
              f"{m['new_tokens']} tokens, "
              f"{m['tokens_per_second']:.1f} tok/s wall-clock on {device}, "
              f"p50 {m['p50_latency_s']:.3f}s p99 {m['p99_latency_s']:.3f}s")
    if "scheduler" in stats:
        s = stats["scheduler"]
        print(f"scheduler: {s['admissions']} admissions over "
              f"{s['dispatches']} dispatches, busy slot fraction "
              f"{s['busy_slot_fraction']}, mean refill gap "
              f"{s['mean_refill_gap']} steps")
    if "scheduler" in stats and args.speculative:
        s = stats["scheduler"]["spec"]
        print(f"speculative: k={s['spec_k']} draft_layers="
              f"{s['draft_layers']}, {s['accepted_tokens']}/"
              f"{s['draft_tokens']} draft tokens accepted "
              f"({s['accepted_tokens_per_dispatch']} per verify), "
              f"{s['rollbacks']} rollbacks, "
              f"{s['continuations']} continuations")
    if "paged" in stats:
        p = stats["paged"]
        print(f"paged: {p['pages_in_use']}/{p['page_count']} pages in "
              f"use (peak {p['peak_pages']}), {p['prefix_hits']} prefix "
              f"hits, {p['skipped_prefill_tokens']} prompt tokens "
              f"skipped (rate {p['prefill_skip_rate']:.3f}), "
              f"{p['evictions']} evictions")
        if args.speculative:
            print(f"draft leases: {p['draft_pages_committed']} pages "
                  f"committed, {p['draft_pages_rolled_back']} rolled back")
    c = stats["cache"]
    first = f"{t_first:.2f}s" if t_first is not None else "n/a"
    print(f"{batcher.cfg.name}: first token {first}; cache entries="
          f"{c['entries']} hits={c['hits']} misses={c['misses']} "
          f"lowerings={c['lowerings']} compiles={c['compiles']}")
    return ServeRun(batcher, waves)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
