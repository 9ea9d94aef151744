"""``repro.plan``: the one compile-plan API.

Acceptance properties pinned here:

* the launchers and the serve batcher are THIN consumers — the RA501
  layering rule of ``repro.analysis`` (real import/call-graph analysis,
  re-export aware) proves none of them imports step builders or
  sharding wiring, constructs a mesh, calls ``jax.jit``, or lowers
  directly; all executable construction goes through ``ExecutionPlan``;
* the pass pipeline runs in order and records every decision
  (``describe()`` is JSON-able);
* PlaceStages: beam mode matches exact branch-and-bound on small grids,
  stage slices never overlap, and a 2-stage plan on the 8-device debug
  mesh shards the ``layers`` axis across the mesh slice chosen by the
  ``core.placement`` cost model while reproducing the unpipelined loss;
* Quantize calibrates per-tensor MLP shifts and keeps the SRS shift >= 0;
* Compile routes everything through the shared ExecutableCache: a warm
  bucket performs zero new lowerings.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.dist.sharding import init_params
from repro.models import build_model
from repro.models.base import ShapeSpec
from repro.plan import (
    MeshSpec,
    PLAN_PIPELINE,
    assign_stage_slices,
    build_plan,
    stack_depth,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(ROOT, "src")

PASS_ORDER = ["ResolveMesh", "ResolveSharding", "PlaceStages", "Quantize",
              "Compile"]


@pytest.fixture(scope="module")
def cfg():
    return reduced_config("yi_6b").with_(n_layers=2, vocab=64)


# ---------------------------------------------------------------------------
# ACCEPTANCE: launchers/batcher contain no direct execution wiring
# ---------------------------------------------------------------------------

def test_launchers_are_thin_plan_consumers():
    """The RA501 layering rule (import-graph analysis, not a grep) must
    report zero unbaselined findings over the shipped tree — launchers,
    the batcher, and the benchmarks build nothing the plan should
    build. See docs/static_analysis.md for the rule's exact contract."""
    from repro.analysis import analyze

    report = analyze(
        [os.path.join(SRC, "repro"), os.path.join(ROOT, "benchmarks")],
        rules=["RA501"],
        baseline=os.path.join(ROOT, "analysis_baseline.json"))
    assert not report.findings, "\n".join(
        f.render() for f in report.findings)


def test_token_argmax_is_plan_owned_and_cached(cfg):
    """Regression for the one real RA501 finding the analyzer surfaced:
    the batcher used to ``jax.jit`` its greedy-argmax helper itself.
    The helper now lives on the plan and caches per output sharding."""
    plan = build_plan(cfg, ShapeSpec("t", 32, 2, "decode"),
                      mesh_spec=MeshSpec.debug(1, 1))
    exe = plan.serve_executable("decode", batch=2, max_len=32)
    tok_sh = exe.bundle.in_shardings[2]
    fn = plan.token_argmax(tok_sh)
    assert plan.token_argmax(tok_sh) is fn, (
        "same sharding must reuse the compiled helper")
    logits = jnp.zeros((2, cfg.vocab)).at[:, 3].set(1.0)
    out = fn(logits)
    assert out.dtype == jnp.int32
    assert list(map(int, out)) == [3, 3]


# ---------------------------------------------------------------------------
# pipeline order + introspection
# ---------------------------------------------------------------------------


def test_pass_pipeline_order_and_describe(cfg):
    assert [name for name, _ in PLAN_PIPELINE] == PASS_ORDER
    plan = build_plan(cfg, ShapeSpec("t", 32, 2, "train"),
                      mesh_spec=MeshSpec.debug(1, 1))
    assert plan.ir.pass_names() == PASS_ORDER
    d = plan.describe()
    json.dumps(d)                              # CI artifact must serialize
    assert d["passes"][0]["pass"] == "ResolveMesh"
    assert d["params"], "ResolveSharding must record param PartitionSpecs"
    assert d["executables"] == {"train": {"batch": 2, "seq_len": 32,
                                          "shape": "t"}}
    # single stage: the layers axis stays replicated
    assert plan.rules.get("layers") is None
    assert d["stages"] == []


def test_build_plan_validation(cfg):
    with pytest.raises(ValueError, match="unknown sharding mode"):
        build_plan(cfg, None, mode="nope", mesh_spec=MeshSpec.debug(1, 1))
    with pytest.raises(ValueError, match="pipeline_stages"):
        build_plan(cfg, None, mesh_spec=MeshSpec.debug(1, 1),
                   pipeline_stages=0)
    with pytest.raises(ValueError, match="exceeds the layer stack"):
        build_plan(cfg, None, mesh_spec=MeshSpec.debug(1, 1),
                   pipeline_stages=99)
    # arch aliases + --debug resolve through the registry
    plan = build_plan("yi-6b", None, debug=True)
    assert plan.cfg.name == "yi-6b" and plan.mesh.devices.size == 1


def test_stack_depth_per_family():
    assert stack_depth(reduced_config("yi_6b")) == 4
    hybrid = reduced_config("zamba2_2_7b")     # 4 layers in groups of 2
    assert stack_depth(hybrid) == 2


# ---------------------------------------------------------------------------
# PlaceStages: beam == exact, no overlap, graceful fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cols,rows,stages", [
    (4, 2, 2), (2, 4, 2), (2, 4, 4), (4, 8, 4), (8, 4, 2), (16, 16, 4),
])
def test_stage_placement_beam_matches_exact(cols, rows, stages):
    exact = assign_stage_slices(cols, rows, stages, beam=None)
    beam = assign_stage_slices(cols, rows, stages, beam=4)
    assert beam.cost == pytest.approx(exact.cost), (
        "beam placement must not lose optimality on small stage counts")


def _overlaps(a, b):
    return not (a.col + a.width <= b.col or b.col + b.width <= a.col
                or a.row + a.height <= b.row or b.row + b.height <= a.row)


@pytest.mark.parametrize("cols,rows,stages", [
    (4, 2, 2), (2, 8, 4), (4, 4, 2), (2, 16, 8),
])
def test_stage_slices_never_overlap_and_tile_the_mesh(cols, rows, stages):
    res = assign_stage_slices(cols, rows, stages)
    pos = res.positions
    for i in range(len(pos)):
        for j in range(i + 1, len(pos)):
            assert not _overlaps(pos[i], pos[j]), (i, j, pos)
    assert sum(p.width * p.height for p in pos) == cols * rows


def test_stage_fallback_on_tiny_mesh_is_recorded():
    cfg4 = reduced_config("yi_6b")             # 4 layers
    plan = build_plan(cfg4, None, mesh_spec=MeshSpec.debug(1, 1),
                      pipeline_stages=2)
    assert plan.ir.stage_axis is None and plan.ir.stages == []
    assert plan.rules.get("layers") is None    # still replicated
    fallbacks = [e for name, e in plan.ir.decisions
                 if name == "PlaceStages" and "fallback" in e]
    assert fallbacks, "fallback reason must be recorded in the decisions"
    assert "stages" in fallbacks[0]["fallback"]


# ---------------------------------------------------------------------------
# ACCEPTANCE: 2-stage plan on the 8-device debug mesh — layers sharded on
# the cost-model slice, loss identical to the unpipelined plan
# ---------------------------------------------------------------------------


def _run8(body: str, timeout=900):
    prog = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import reduced_config
        from repro.dist.sharding import init_params
        from repro.models import build_model
        from repro.models.base import ShapeSpec
        from repro.plan import MeshSpec, build_plan
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_two_stage_plan_matches_unpipelined_loss_8dev():
    out = _run8("""
    cfg = reduced_config("yi_6b").with_(vocab=64)        # 4 layers
    shape = ShapeSpec("t", 16, 8, "train")
    p1 = build_plan(cfg, shape, mesh_spec=MeshSpec.debug(2, 4),
                    pipeline_stages=1)
    p2 = build_plan(cfg, shape, mesh_spec=MeshSpec.debug(2, 4),
                    pipeline_stages=2)
    # the layers axis shards across the data slice the cost model chose
    assert p2.ir.stage_axis == "data"
    assert p2.rules.get("layers") == "data"
    assert p2.ir.placement_method == "bnb"
    assert [ (s.first_layer, s.n_layers, s.row, s.height)
             for s in p2.ir.stages ] == [(0, 2, 0, 1), (2, 2, 1, 1)]
    sp = p2.ir.param_pspecs["['blocks']['attn']['wq']['w']"]
    assert sp.startswith("PartitionSpec('data'"), sp
    # stacked weights replicate under the single-stage plan
    sp1 = p1.ir.param_pspecs["['blocks']['attn']['wq']['w']"]
    assert not sp1.startswith("PartitionSpec('data'"), sp1

    batch = {"tokens": jnp.ones((8, 16), jnp.int32),
             "labels": jnp.ones((8, 16), jnp.int32)}
    model = build_model(cfg)
    ref = float(model.loss(
        init_params(jax.random.PRNGKey(0), model.param_specs()), batch))
    losses = []
    for plan in (p1, p2):
        params, opt = plan.init_train_state(seed=0)
        exe = plan.executable("train")
        _, _, metrics = exe.compiled(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - losses[1]) < 1e-3, losses
    assert abs(losses[1] - ref) < 1e-2, (losses, ref)
    # the two plans compiled distinct executables (stages is in the key)
    keys = {k.stages for k in p1.cache._entries} | \
           {k.stages for k in p2.cache._entries}
    assert keys == {1, 2}
    print("STAGE PARITY OK", losses, ref)
    """)
    assert "STAGE PARITY OK" in out


def test_two_stage_decode_state_shards_8dev():
    out = _run8("""
    cfg = reduced_config("yi_6b").with_(vocab=64)
    plan = build_plan(cfg, None, mesh_spec=MeshSpec.debug(2, 4),
                      pipeline_stages=2)
    state = plan.fresh_decode_state(8, 32)
    shard = state["cache_k"].sharding
    # the KV cache's layer dim rides the same stage slices as the weights
    assert str(shard.spec).startswith("PartitionSpec('data'"), shard.spec
    b = plan.make_batcher()
    from repro.serve import DecodeRequest
    with plan.activate():
        b.init_demo_params(0)
        for i in range(4):
            b.submit(DecodeRequest(f"r{i}", [1 + i, 2, 3], max_new_tokens=4))
        res = b.run()
    assert all(len(r.tokens) == 4 for r in res.values())
    print("STAGED DECODE OK")
    """)
    assert "STAGED DECODE OK" in out


# ---------------------------------------------------------------------------
# Quantize: calibration invariants
# ---------------------------------------------------------------------------


def test_quantize_pass_records_and_calibrates():
    full = reduced_config("yi_6b")
    plan = build_plan(full, None, mesh_spec=MeshSpec.debug(1, 1),
                      quantized=True)
    assert plan.cfg.quantized and plan.cfg.quantized_mlp
    assert plan.ir.quant["mlp"] and not plan.ir.quant["calibrated"]
    params = init_params(jax.random.PRNGKey(0),
                         build_model(full).param_specs())
    plan.calibrate(params)
    assert plan.ir.quant["calibrated"]
    x_s, w_s, o_s = plan.ir.quant["mlp_shifts"]
    assert o_s <= x_s + w_s                    # SRS shift stays >= 0
    assert (plan.cfg.mlp_x_shift, plan.cfg.mlp_w_shift,
            plan.cfg.mlp_out_shift) == (x_s, w_s, o_s)
    names = [name for name, _ in plan.ir.decisions]
    assert names.count("Quantize") == 2        # pass + calibration record


def test_quantized_train_plan_keeps_float_mlp(cfg):
    """MLP quantization is a decode-path decision: a quantized TRAIN plan
    keeps the float MLP (only serve plans route it through the kernel)."""
    plan = build_plan(cfg, ShapeSpec("t", 32, 2, "train"),
                      mesh_spec=MeshSpec.debug(1, 1), quantized=True)
    assert plan.cfg.quantized and not plan.cfg.quantized_mlp


class _FakeMesh:
    """Just enough of a Mesh for the passes: axis names, axis sizes and a
    device grid."""

    def __init__(self, shape):
        self.axis_names = ("data", "model")
        self.shape = dict(zip(self.axis_names, shape))
        self.devices = np.empty(shape, dtype=object)


@pytest.mark.parametrize("shape", [(1, 4), (2, 2)])
def test_quantize_pass_rejects_sharded_model_axis(cfg, shape):
    """GSPMD cannot partition the Mosaic qmatmul call, so a quantized plan
    on a model axis > 1 fails in the plan, naming the kernel, instead of
    in the chip's compiler."""
    with pytest.raises(ValueError, match="qmatmul"):
        build_plan(cfg, None, mesh_spec=MeshSpec.from_mesh(_FakeMesh(shape)),
                   quantized=True)
    # a model axis of 1 is fine, and the float plan is never rejected
    build_plan(cfg, None, mesh_spec=MeshSpec.from_mesh(_FakeMesh((4, 1))),
               quantized=True)
    build_plan(cfg, None, mesh_spec=MeshSpec.from_mesh(_FakeMesh(shape)))


def test_local_mesh_spec_spans_every_device(cfg):
    plan = build_plan(cfg, None, mesh_spec=MeshSpec.local())
    assert plan.mesh.axis_names == ("data", "model")
    assert plan.mesh.devices.shape == (1, len(jax.devices()))
    assert plan.describe()["mesh"] == "local"


# ---------------------------------------------------------------------------
# Compile: everything AOT through the shared cache
# ---------------------------------------------------------------------------


def test_plan_serve_zero_new_lowerings_after_warmup(cfg):
    from repro.serve import DecodeRequest

    plan = build_plan(cfg, None, mesh_spec=MeshSpec.debug(1, 1))
    assert set(plan.ir.executables) == {"decode", "prefill",
                                        "masked_decode"}
    batcher = plan.make_batcher()
    with plan.activate():
        batcher.init_demo_params(0)
        batcher.submit(DecodeRequest("w0", [1, 2], max_new_tokens=3))
        batcher.run()
        warm = dict(plan.stats())
        batcher.submit(DecodeRequest("w1", [2, 3], max_new_tokens=3))
        out = batcher.run()
    after = plan.stats()
    assert len(out) == 1
    assert after["hits"] > warm["hits"]
    assert after["lowerings"] == warm["lowerings"]
    assert after["compiles"] == warm["compiles"]


def test_plan_train_executable_counted_and_cached(cfg):
    plan = build_plan(cfg, ShapeSpec("t", 32, 2, "train"),
                      mesh_spec=MeshSpec.debug(1, 1))
    e1 = plan.executable("train")
    stats = plan.stats()
    assert stats["compiles"] == 1 and stats["lowerings"] == 1
    e2 = plan.executable("train")
    assert e2 is e1                            # cache hit, same executable
    assert plan.stats()["hits"] == 1
    params, opt = plan.init_train_state(seed=0)
    batch = {"tokens": jnp.ones((2, 32), jnp.int32),
             "labels": jnp.ones((2, 32), jnp.int32)}
    _, _, metrics = e1.compiled(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
