"""PlanIR: the record every compile-plan pass enriches.

``repro.plan`` is the framework-level analog of ``repro.core.passes``: where
the core pipeline enriches a small-graph IR node by node, the plan pipeline
enriches ONE PlanIR describing how a whole model runs on a device mesh.
Each pass writes what it decided (mesh axes, rule table, per-param
PartitionSpecs, stage placements, quantization shifts, executable keys)
into the IR, and every decision is appended to an ordered ``decisions``
log so ``ExecutionPlan.describe()`` can replay the pipeline verbatim.

Nothing here touches jax device state at import time: ``MeshSpec`` is a
declarative mesh description; devices are only enumerated when the
ResolveMesh pass calls :meth:`MeshSpec.build`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from repro.models.base import ArchConfig, ShapeSpec


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh description (resolved by the ResolveMesh pass).

    Launchers hand the plan a MeshSpec instead of calling
    ``make_debug_mesh`` / ``make_production_mesh`` themselves — the plan is
    the only component that materializes device meshes. ``from_mesh`` wraps
    an already-built Mesh (tests, embedding the plan in an outer harness).
    """

    kind: str = "debug"      # "debug" | "local" | "production" | "explicit"
    data: int = 1                        # debug: data-axis extent
    model: int = 1                       # debug: model-axis extent
    multi_pod: bool = False              # production: 2x16x16 vs 16x16
    mesh: Optional[Any] = None           # explicit: a prebuilt jax Mesh

    @classmethod
    def debug(cls, data: int = 1, model: int = 1) -> "MeshSpec":
        return cls(kind="debug", data=data, model=model)

    @classmethod
    def local(cls) -> "MeshSpec":
        """Every device present, on the model axis (1x1 on one chip, 1x4
        on a four-chip host) — resolved when the plan is built."""
        return cls(kind="local")

    @classmethod
    def production(cls, multi_pod: bool = False) -> "MeshSpec":
        return cls(kind="production", multi_pod=multi_pod)

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        return cls(kind="explicit", mesh=mesh)

    def build(self):
        """Materialize the jax Mesh (the only device-touching call)."""
        if self.kind == "explicit":
            if self.mesh is None:
                raise ValueError("explicit MeshSpec needs a mesh")
            return self.mesh
        from repro.launch.mesh import (
            make_debug_mesh,
            make_local_mesh,
            make_production_mesh,
        )

        if self.kind == "debug":
            return make_debug_mesh(self.data, self.model)
        if self.kind == "local":
            return make_local_mesh()
        if self.kind == "production":
            return make_production_mesh(multi_pod=self.multi_pod)
        raise ValueError(f"unknown MeshSpec kind {self.kind!r}")

    def label(self) -> str:
        if self.kind == "debug":
            return f"debug:{self.data}x{self.model}"
        if self.kind == "local":
            return "local"
        if self.kind == "production":
            return "production:2x16x16" if self.multi_pod \
                else "production:16x16"
        m = self.mesh
        return "explicit:" + "x".join(str(s) for s in m.devices.shape) \
            if m is not None else "explicit:?"


@dataclasses.dataclass(frozen=True)
class StagePlacement:
    """One pipeline stage's layer range and its mesh-slice rectangle.

    The rectangle lives on the (model, data) grid the PlaceStages pass
    hands to ``core.placement.Placer``: ``col``/``width`` span the model
    axis, ``row``/``height`` span the stage (data) axis.
    """

    index: int
    first_layer: int
    n_layers: int
    col: int
    row: int
    width: int
    height: int

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class PlanIR:
    """The one record the plan passes consume and enrich.

    The first block is the *request* (what the caller asked for); every
    field below it is filled in by a pass. ``decisions`` is the ordered
    (pass name, record) log behind ``ExecutionPlan.describe()``.
    """

    # -- request ------------------------------------------------------------
    cfg: ArchConfig
    shape: Optional[ShapeSpec]           # None: serve plan (bucketed shapes)
    mode: str
    mesh_spec: MeshSpec
    quantized: bool = False
    pipeline_stages: int = 1

    # -- ResolveMesh --------------------------------------------------------
    mesh: Optional[Any] = None

    # -- ResolveSharding ----------------------------------------------------
    rules: Optional[Any] = None          # ShardingRules
    param_pspecs: Dict[str, str] = dataclasses.field(default_factory=dict)

    # -- PlaceStages --------------------------------------------------------
    stages: List[StagePlacement] = dataclasses.field(default_factory=list)
    stage_axis: Optional[str] = None     # mesh axis the layers dim shards on
    placement_cost: float = 0.0
    placement_method: str = ""

    # -- Quantize -----------------------------------------------------------
    quant: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- Compile ------------------------------------------------------------
    executables: Dict[str, Dict[str, Any]] = dataclasses.field(
        default_factory=dict)

    # -- audit trail --------------------------------------------------------
    decisions: List[Tuple[str, Dict[str, Any]]] = dataclasses.field(
        default_factory=list)

    def record(self, pass_name: str, **entry: Any) -> None:
        self.decisions.append((pass_name, entry))

    def pass_names(self) -> List[str]:
        return [name for name, _ in self.decisions]
