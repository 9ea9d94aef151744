"""The compile-plan pass pipeline (framework analog of paper Fig. 2).

    ResolveMesh -> ResolveSharding -> PlaceStages -> Quantize -> Compile

Each pass consumes and enriches one :class:`repro.plan.ir.PlanIR`, exactly
as ``repro.core.passes`` enriches the small-graph IR. Every decision is
appended to ``ir.decisions`` so the resulting plan is fully introspectable
(``ExecutionPlan.describe()``).

* **ResolveMesh** materializes the device mesh from the declarative
  :class:`~repro.plan.ir.MeshSpec` — the only place a plan touches
  ``jax.devices()``.
* **ResolveSharding** builds the mode's logical-axis rule table and records
  the fully fitted PartitionSpec of every parameter.
* **PlaceStages** splits the scan-over-layers stack into contiguous
  pipeline stages, models each stage as a ``core.placement.Block``
  (width = model-parallel extent, height = mesh rows per stage), and
  reuses the branch-and-bound :class:`~repro.core.placement.Placer` /
  Eq. 2 cost model to assign stages to contiguous mesh slices. The chosen
  slices turn into a ``layers -> data`` rule-table override, so the
  stacked layer weights (and decode state) shard across the slice instead
  of replicating everywhere.
* **Quantize** decides the int8 serving paths: the decode LM head (always,
  when ``quantized``) and the MLP down-projection with per-tensor
  calibrated shifts (``calibrate_mlp_shifts`` refines the defaults once
  real weights exist).
* **Compile** registers the executable catalogue; every entry is built AOT
  through ``repro.serve.cache.ExecutableCache`` so train-step, prefill,
  and decode executables are all counted by the same hit/lowering/compile
  counters.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.placement import Block, Placer, PlacementResult
from repro.dist.sharding import rules_for_mode, spec_to_pspec
from repro.models.base import ArchConfig, build_model
from repro.plan.ir import PlanIR, StagePlacement
from repro.quant.qtensor import choose_shift

# Families whose transformer blocks carry a dense SwiGLU "ffn" whose
# down-projection the Quantize pass can route through the qmatmul kernel.
# (encdec is excluded: its decoder uses the gelu ``mlp`` path, which has
# no quantized route — listing it would report calibrated MLP
# quantization while every projection stayed float. moe/ssm likewise.)
MLP_QUANT_FAMILIES = ("dense", "vlm", "hybrid")

# Decode LM-head shifts (PR 2): rmsnorm'd activations (absmax < 4),
# fan-in-scaled head weights (absmax < 0.5), int16 SRS out.
HEAD_SHIFTS = (5, 8, 11)


def _is_spec(x) -> bool:
    from repro.dist.sharding import ParamSpec

    return isinstance(x, ParamSpec)


def stack_depth(cfg: ArchConfig) -> int:
    """Length of the outer scan-over-layers dim (the stage-splittable one).

    The hybrid family scans over layer *groups* (one shared attention block
    per group), so its stackable depth is the group count.
    """
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _resolve_param_pspecs(ir: PlanIR) -> Dict[str, str]:
    """Flat {param path: PartitionSpec} map under the current rule table."""
    specs = build_model(ir.cfg).param_specs()
    leaves, _ = jax.tree_util.tree_flatten_with_path(specs, is_leaf=_is_spec)
    out = {}
    for path, spec in leaves:
        key = jax.tree_util.keystr(path)
        out[key] = str(spec_to_pspec(spec, ir.mesh, ir.rules))
    return out


# ---------------------------------------------------------------------------
# 1. ResolveMesh
# ---------------------------------------------------------------------------


def resolve_mesh_pass(ir: PlanIR) -> PlanIR:
    ir.mesh = ir.mesh_spec.build()
    axes = dict(zip(ir.mesh.axis_names, ir.mesh.devices.shape))
    ir.record("ResolveMesh", mesh=ir.mesh_spec.label(), axes=axes,
              devices=int(ir.mesh.devices.size))
    return ir


# ---------------------------------------------------------------------------
# 2. ResolveSharding
# ---------------------------------------------------------------------------


def resolve_sharding_pass(ir: PlanIR) -> PlanIR:
    ir.rules = rules_for_mode(ir.mode)
    ir.param_pspecs = _resolve_param_pspecs(ir)
    sharded = {k: v for k, v in ir.param_pspecs.items()
               if v != "PartitionSpec()"}
    ir.record("ResolveSharding", mode=ir.mode,
              params=len(ir.param_pspecs), sharded=len(sharded))
    return ir


# ---------------------------------------------------------------------------
# 3. PlaceStages
# ---------------------------------------------------------------------------


def assign_stage_slices(
    n_cols: int,
    n_rows: int,
    n_stages: int,
    *,
    lam: float = 1.0,
    mu: float = 0.05,
    beam: Optional[int] = 64,
) -> PlacementResult:
    """Assign ``n_stages`` equal stage blocks to mesh slices with the
    paper's Eq. 2 branch-and-bound. The grid is the (model, data) device
    plane: columns = model axis, rows = data axis; each stage is a
    full-width block ``n_rows // n_stages`` rows tall. ``beam=None`` is
    exact; tests pin beam mode against it.

    Because the blocks are identical full-width rectangles, every
    feasible placement is a permutation of the same row bands — the
    search certifies that the banded layout is Eq. 2-optimal (and
    records its cost/expansions in the plan) rather than choosing among
    structurally different layouts. It earns its keep the day stages get
    per-stage widths or user ``fixed`` pins, both of which the Placer
    already supports.
    """
    if n_rows % n_stages:
        raise ValueError(
            f"{n_stages} stages do not divide the {n_rows}-row axis")
    height = n_rows // n_stages
    blocks = [Block(n_cols, height, f"stage{i}") for i in range(n_stages)]
    placer = Placer(n_cols, n_rows, lam=lam, mu=mu, beam=beam)
    return placer.branch_and_bound(blocks, start=(0, 0))


def place_stages_pass(ir: PlanIR) -> PlanIR:
    S = ir.pipeline_stages
    depth = stack_depth(ir.cfg)
    if S < 1:
        raise ValueError(f"pipeline_stages must be >= 1, got {S}")
    if S > depth:
        raise ValueError(
            f"pipeline_stages={S} exceeds the layer stack depth {depth}")
    if S == 1:
        ir.record("PlaceStages", stages=1, stage_axis=None,
                  note="single stage: layers axis replicated")
        return ir

    sizes = dict(zip(ir.mesh.axis_names, ir.mesh.devices.shape))
    n_rows = sizes.get("data", 1)
    n_cols = sizes.get("model", 1)
    fallback = None
    if n_rows < S or n_rows % S:
        fallback = (f"data axis ({n_rows} rows) cannot hold {S} equal "
                    "stages")
    elif depth % n_rows:
        fallback = (f"layer stack ({depth}) does not divide over the "
                    f"data axis ({n_rows} rows)")
    if fallback:
        ir.record("PlaceStages", stages=S, stage_axis=None,
                  fallback=fallback)
        return ir

    result = assign_stage_slices(n_cols, n_rows, S)
    # GSPMD shards the stacked layer dim across the axis in row order, so
    # stage k (layers [k*per, (k+1)*per)) goes to the k-th row band; the
    # sort also canonicalizes any cost-tied permutation the search
    # returns (identical blocks make all permutations cost-equal).
    order = sorted(range(S), key=lambda i: result.positions[i].row)
    per = depth // S
    ir.stages = [
        StagePlacement(k, k * per, per, p.col, p.row, p.width, p.height)
        for k, p in ((k, result.positions[i]) for k, i in enumerate(order))
    ]
    ir.stage_axis = "data"
    ir.placement_cost = result.cost
    ir.placement_method = result.method
    ir.rules = ir.rules.replace(layers="data")
    ir.param_pspecs = _resolve_param_pspecs(ir)
    ir.record(
        "PlaceStages", stages=S, stage_axis="data",
        cost=round(result.cost, 4), method=result.method,
        expanded=result.nodes_expanded,
        slices=[s.as_dict() for s in ir.stages],
    )
    return ir


# ---------------------------------------------------------------------------
# 4. Quantize
# ---------------------------------------------------------------------------


def _observe_mlp_ranges(cfg: ArchConfig, params, model, steps: int,
                        batch: int) -> Dict[str, float]:
    """Short eager greedy decode of the FLOAT model under the swiglu
    calibration scope, returning the observed absmax of the
    down-projection input ("act") and output ("out")."""
    import jax.numpy as jnp

    from repro.dist.sharding import init_params
    from repro.layers.mlp import swiglu_calibration

    record: Dict[str, float] = {}
    max_len = steps + 2
    state = init_params(jax.random.PRNGKey(0),
                        model.decode_state_specs(batch, max_len))
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(1, cfg.vocab, (batch,)), jnp.int32)
    with jax.disable_jit(), swiglu_calibration(record):
        for i in range(steps):
            logits, state = model.decode_step(params, state, tok,
                                              jnp.int32(i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return record


def calibrate_mlp_shifts(
    cfg: ArchConfig,
    params,
    model=None,
    *,
    steps: int = 6,
    batch: int = 2,
) -> Tuple[int, int, int]:
    """Per-tensor calibrated shifts for the a16w8 MLP down-projection.

    ``w_shift`` comes from the observed absmax of every ``ffn/down``
    weight tensor (the per-tensor calibration the core quantize_pass does
    for imported weights). With a float ``model`` the activation/output
    shifts come from the ranges a short calibration decode actually
    observes (one headroom bit reserved for unseen data); without one they
    fall back to the analytic worst case ``|x|_max * max
    column-abs-sum(w)``. The output shift is always capped so the SRS
    shift stays >= 0.
    """
    x_shift = cfg.mlp_x_shift
    w_shift, colsum = None, 0.0
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        if "ffn" not in key or "down" not in key or not key.endswith("'w']"):
            continue
        w = np.asarray(leaf, np.float32)
        s = choose_shift(w, "int8")
        w_shift = s if w_shift is None else min(w_shift, s)
        # stacked [L, d_ff, d_model]: column abs-sum over the contraction dim
        colsum = max(colsum, float(np.abs(w).sum(axis=-2).max()))
    if w_shift is None:
        return (x_shift, cfg.mlp_w_shift, cfg.mlp_out_shift)

    record: Dict[str, float] = {}
    if model is not None:
        record = _observe_mlp_ranges(cfg, params, model, steps, batch)
    if record.get("act"):
        x_shift = choose_shift(np.asarray([record["act"]]), "int16",
                               margin_bits=1)
        out_amax = max(record.get("out", 0.0), 1e-12)
        out_shift = choose_shift(np.asarray([out_amax]), "int16",
                                 margin_bits=1)
    else:
        x_amax = 2.0 ** (15 - x_shift)       # full int16 range at x_shift
        out_shift = choose_shift(
            np.asarray([max(x_amax * colsum, 1e-12)]), "int16")
    out_shift = min(out_shift, x_shift + w_shift)
    return (x_shift, w_shift, out_shift)


def quantize_pass(ir: PlanIR) -> PlanIR:
    if not ir.quantized:
        ir.record("Quantize", enabled=False)
        return ir
    model_axis = ir.mesh.shape.get("model", 1)
    if model_axis > 1:
        # GSPMD cannot split a Mosaic custom call across devices: the
        # qmatmul pallas_call would need an explicit shard_map wrapper
        raise ValueError(
            f"quantized serving needs a model axis of 1, got {model_axis}: "
            "the Pallas qmatmul kernel (kernels/qmatmul) cannot be "
            "partitioned automatically over a sharded mesh")
    cfg = ir.cfg.with_(quantized=True)
    # MLP quantization is a *serving* decision: only decode-path plans
    # (serve plans have shape=None; dry-runs may pin a decode ShapeSpec)
    # route the down-projection through the qmatmul kernel.
    decode_plan = ir.shape is None or ir.shape.kind == "decode"
    mlp = decode_plan and cfg.family in MLP_QUANT_FAMILIES
    if mlp:
        cfg = cfg.with_(quantized_mlp=True)
    ir.cfg = cfg
    ir.quant = {
        "head_shifts": HEAD_SHIFTS,
        "mlp": mlp,
        "mlp_shifts": (cfg.mlp_x_shift, cfg.mlp_w_shift, cfg.mlp_out_shift),
        "calibrated": False,
    }
    ir.record("Quantize", enabled=True, head_shifts=HEAD_SHIFTS, mlp=mlp,
              mlp_shifts=ir.quant["mlp_shifts"])
    return ir


# ---------------------------------------------------------------------------
# 5. Compile
# ---------------------------------------------------------------------------


def compile_pass(ir: PlanIR) -> PlanIR:
    """Register the executable catalogue (kind -> cache-key template).

    Executables are built lazily through ``ExecutionPlan.executable`` /
    ``serve_executable`` so a plan stays cheap to construct; every build
    goes through the shared ExecutableCache and shows up in its counters.
    """
    cat: Dict[str, Dict[str, object]] = {}
    if ir.shape is not None:
        cat[ir.shape.kind] = {
            "batch": ir.shape.global_batch,
            "seq_len": ir.shape.seq_len,
            "shape": ir.shape.name,
        }
    if ir.shape is None or ir.shape.kind == "decode":
        cat.setdefault("decode", {"batch": "per-bucket",
                                  "seq_len": "per-bucket"})
        cat["prefill"] = {"batch": "per-bucket", "seq_len": "per-bucket",
                          "note": "prefill->decode scan handoff"}
        cat["masked_decode"] = {
            "batch": "per-bucket", "seq_len": "per-bucket",
            "steps_per_dispatch": "per-scheduler",
            "note": "slot-masked continuous-batching micro-run (scans k "
                    "masked steps per call; cache-keyed by k). Variants: "
                    "paged=(page_count, page_size) pooled-KV layout; "
                    "spec=(spec_k, draft_layers) fused speculative "
                    "draft-scan + block-verify — both join the cache key",
        }
    ir.executables = cat
    ir.record("Compile", kinds=sorted(cat), cache="serve.ExecutableCache",
              aot=True)
    return ir


PLAN_PIPELINE: List[Tuple[str, object]] = [
    ("ResolveMesh", resolve_mesh_pass),
    ("ResolveSharding", resolve_sharding_pass),
    ("PlaceStages", place_stages_pass),
    ("Quantize", quantize_pass),
    ("Compile", compile_pass),
]
