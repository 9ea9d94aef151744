#!/usr/bin/env python3
"""Serve yi-6b at full width on a TPU through the normal serve entry point.

    python chip_smoke.py              # one chip: float, then quantized
    python chip_smoke.py --chips 4    # four chips: megatron 1x4 serve path
                                      # against the one-chip run

One process, no children: it calls ``repro.launch.serve.main`` in
process, so the CLI a user runs is the path checked here. The weights are
random (seed 0) at yi-6b's published widths: 32 layers, d_model 4096,
GQA 32/4 x 128, d_ff 11008, vocab 64000, bf16.

One chip:

* float phase — two waves through the continuous scheduler
  (``--steps-per-dispatch 4 --paged``): tokens in range and identical
  across the waves for identical prompts, finite first-step logits, and
  no new lowering or compile in the second wave;
* quantized phase — the float plan is freed first (device bytes in use
  must fall back), then one ``--quantized`` wave: the compiled step holds
  the Mosaic kernel (``tpu_custom_call``), and ``qlinear`` equals
  ``qlinear_ref`` bit for bit on the chip at the LM-head and MLP
  down-projection shapes.

Four chips (``--chips 4``): only the megatron 1x4 serve path and what it
is compared with — the per-device parameter bytes are about a quarter of
the total, and the first-step logits match a one-chip run of the same
weights within a bf16 tolerance.

Timings printed here are smoke timings (one short run, compile included
where said), not benchmark results. JAX's persistent compilation cache is
on: ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` in this
checkout. Any failed check exits non-zero before the last line, which is
one JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "yi-6b"
# yi-6b's published widths (arXiv:2403.04652)
WIDTHS = dict(n_layers=32, d_model=4096, n_heads=32, n_kv=4, head_dim=128,
              d_ff=11008, vocab=64000)
TOKENS = 8         # new tokens per request
SERVE_ARGS = ["--arch", ARCH, "--schedule", "continuous",
              "--steps-per-dispatch", "4", "--paged", "--tokens", str(TOKENS)]
GB = 1e9


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  ok: {what}")


def hbm(device) -> dict:
    return device.memory_stats() or {}


def first_step_logits(plan, params, batch: int = 8, max_len: int = 64):
    """Logits [batch, vocab] of one decode step from a zero state at
    position 0 — the plan's single-token decode executable."""
    import jax
    import numpy as np

    exe = plan.serve_executable("decode", batch=batch, max_len=max_len)
    tok_sh, pos_sh = exe.bundle.in_shardings[2:4]
    tokens = np.arange(1, batch + 1, dtype=np.int32) * 997 % plan.cfg.vocab
    with plan.activate():
        logits, _ = exe.compiled(
            params, plan.fresh_decode_state(batch, max_len),
            jax.device_put(tokens, tok_sh),
            jax.device_put(np.int32(0), pos_sh))
    return np.asarray(jax.device_get(logits), np.float32)


def serve(extra, label: str):
    from repro.launch import serve as serve_cli

    print(f"[{label}] python -m repro.launch.serve "
          + " ".join(SERVE_ARGS + extra))
    t0 = time.perf_counter()
    run = serve_cli.main(SERVE_ARGS + extra)
    stats = run.batcher.cache.stats()
    print(f"[{label}] smoke timing: {time.perf_counter() - t0:.2f}s wall "
          f"for the CLI run (init + compile + waves); lower+compile "
          f"{stats['compile_seconds']:.2f}s over {stats['compiles']} "
          f"executables")
    return run


def check_widths(cfg) -> None:
    got = {k: getattr(cfg, k) for k in WIDTHS}
    check(got == WIDTHS, f"{cfg.name} at full width {got}")


def float_phase(device) -> None:
    import numpy as np

    run = serve([], "float")
    b = run.batcher
    check_widths(b.cfg)
    check(len(run.waves) == 2, "two waves served")
    w0, w1 = run.waves
    toks = [t for w in run.waves for r in w.results.values() for t in r.tokens]
    check(len(toks) == sum(len(w.results) for w in run.waves) * TOKENS
          and all(0 <= t < b.cfg.vocab for t in toks),
          f"{len(toks)} tokens, all in [0, {b.cfg.vocab})")
    same = all(w1.results["w1" + rid[2:]].tokens == r.tokens
               for rid, r in w0.results.items())
    check(same, "identical prompts gave identical tokens in both waves")
    check(w1.cache["lowerings"] == w0.cache["lowerings"]
          and w1.cache["compiles"] == w0.cache["compiles"],
          f"wave 1 added 0 lowerings and 0 compiles (cache after wave 0 "
          f"{w0.cache}, after wave 1 {w1.cache})")
    sample = w0.results[sorted(w0.results)[0]]
    print(f"  sample tokens {sample.request_id}: {sample.tokens}")

    bucket = b.policy.buckets[0]
    step = b.plan.serve_executable(
        "masked_decode", batch=bucket.batch, max_len=bucket.max_len,
        steps_per_dispatch=4, paged=b.paged).compiled
    ma = step.memory_analysis()
    print(f"  masked step {bucket.label}: arguments "
          f"{ma.argument_size_in_bytes / GB:.3f} GB (weights + paged KV "
          f"pool {b.paged}), temporaries {ma.temp_size_in_bytes / GB:.3f} GB")
    logits = first_step_logits(b.plan, b.params)
    check(logits.shape == (8, b.cfg.vocab) and np.isfinite(logits).all(),
          f"first-step logits {logits.shape} finite (|max| "
          f"{np.abs(logits).max():.4f})")
    m = hbm(device)
    print(f"  hbm: peak_bytes_in_use {m.get('peak_bytes_in_use', 0) / GB:.3f}"
          f" GB of bytes_limit {m.get('bytes_limit', 0) / GB:.3f} GB")


def quantized_phase(device, baseline: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.qmatmul.ops import qlinear
    from repro.kernels.qmatmul.ref import qlinear_ref

    gc.collect()
    in_use = hbm(device).get("bytes_in_use", 0)
    check(in_use <= baseline + 256 * 2**20,
          f"float plan freed: {in_use / GB:.3f} GB in use "
          f"(before serving {baseline / GB:.3f} GB)")

    run = serve(["--quantized", "--rounds", "1"], "quantized")
    b = run.batcher
    check_widths(b.cfg)
    toks = [t for r in run.waves[0].results.values() for t in r.tokens]
    check(all(0 <= t < b.cfg.vocab for t in toks),
          f"{len(toks)} quantized tokens in range")
    bucket = b.policy.buckets[0]
    step = b.plan.serve_executable(
        "masked_decode", batch=bucket.batch, max_len=bucket.max_len,
        steps_per_dispatch=4, paged=b.paged).compiled
    check("tpu_custom_call" in step.as_text(),
          "the quantized step holds the qmatmul kernel (tpu_custom_call)")

    cfg = b.cfg
    rng = np.random.default_rng(0)
    x_s, w_s, o_s = cfg.mlp_x_shift, cfg.mlp_w_shift, cfg.mlp_out_shift
    cases = [
        # decode LM head: int8 x int8 -> int16 (decode_head_logits shifts)
        ("LM head", (8, cfg.d_model), jnp.int8, (cfg.d_model, cfg.vocab),
         5 + 8 - 11),
        # MLP down-projection: a16w8 at the calibrated shifts
        ("MLP down", (8, cfg.d_ff), jnp.int16, (cfg.d_ff, cfg.d_model),
         x_s + w_s - o_s),
    ]
    for name, xs, x_dtype, ws, shift in cases:
        info = np.iinfo(x_dtype)
        x = jnp.asarray(rng.integers(info.min, info.max + 1, xs), x_dtype)
        w = jnp.asarray(rng.integers(-128, 128, ws), jnp.int8)
        got = np.asarray(jax.device_get(
            qlinear(x, w, shift=shift, out_dtype="int16")))
        want = np.asarray(jax.device_get(
            qlinear_ref(x, w, shift=shift, out_dtype="int16")))
        check(np.array_equal(got, want),
              f"qlinear == qlinear_ref bit for bit on the chip, {name} "
              f"{xs} x {ws} shift {shift}")


def four_chip_phase(devices) -> None:
    import jax
    import numpy as np

    from repro.plan import MeshSpec, build_plan

    # the one-chip run of the same weights (seed 0) on device 0 alone
    t0 = time.perf_counter()
    one = build_plan(ARCH, None, mesh_spec=MeshSpec.debug(1, 1))
    params = one.init_params(0)
    ref = first_step_logits(one, params)
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    del one, params
    gc.collect()
    print(f"[one chip] smoke timing: {time.perf_counter() - t0:.2f}s for "
          f"init + compile + first step; {total / GB:.3f} GB of params")

    run = serve(["--rounds", "1"], "four chips")
    b = run.batcher
    check_widths(b.cfg)
    check(dict(b.mesh.shape) == {"data": 1, "model": 4}
          and b.plan.mode == "megatron",
          f"megatron on a 1x4 mesh ({dict(b.mesh.shape)})")
    toks = [t for r in run.waves[0].results.values() for t in r.tokens]
    check(all(0 <= t < b.cfg.vocab for t in toks),
          f"{len(toks)} tokens in range")
    mine = sum(s.data.nbytes for x in jax.tree.leaves(b.params)
               for s in x.addressable_shards if s.device == devices[0])
    share = mine / total
    # col/row weights, embedding and head split four ways; the norms and
    # the GQA kv projections (4 kv heads) stay whole on every device
    check(0.25 <= share <= 0.3,
          f"device 0 holds {mine / GB:.3f} GB = {share:.4f} of the "
          f"{total / GB:.3f} GB of params")
    got = first_step_logits(b.plan, b.params)
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    top1 = float((got.argmax(-1) == ref.argmax(-1)).mean())
    # bf16 tolerance: the sharded row-parallel sums reorder f32 partials,
    # which flips an occasional bf16 rounding (2^-8 relative) in the
    # residual stream; over 32 layers that stays well under 5% relative
    # L2 on the logits, while a wrong shard or a missing psum is O(1)
    check(np.isfinite(got).all() and rel < 0.05,
          f"first-step logits match the one-chip run: relative L2 "
          f"{rel:.6f} < 0.05, max |diff| "
          f"{float(np.abs(got - ref).max()):.6f}, top-1 agreement {top1}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the megatron 1x4 serve phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from "
              f"{ROOT / 'src'}: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d.platform} "
              f"({d.device_kind}); no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    print(f"device: {d.platform} {d.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phase(devices)
        else:
            baseline = hbm(d).get("bytes_in_use", 0)
            float_phase(d)
            quantized_phase(d, baseline)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"smoke timing: {time.perf_counter() - t0:.2f}s for all phases")
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
