"""``build_plan`` and :class:`ExecutionPlan`: the one compile-plan API.

Every executable in this framework — train step, prefill, decode — is
built by handing ``build_plan`` an architecture, a shape, and a
:class:`~repro.plan.ir.MeshSpec`, and asking the resulting plan for the
executable. Launchers, the serve batcher, benchmarks, and examples are all
thin consumers; none of them touch ``make_*_mesh``, ``rules_for_mode``,
``specs_to_shardings``, or ``lower().compile()`` directly.

    from repro.plan import MeshSpec, build_plan
    plan = build_plan("yi-6b", shape="train_4k",
                      mesh_spec=MeshSpec.production())
    params, opt_state = plan.init_train_state(seed=0)
    step = plan.executable("train")          # AOT, cached, counted
    print(plan.describe())                   # every pass decision

The plan is produced by the ordered pass pipeline in
``repro.plan.passes`` (ResolveMesh -> ResolveSharding -> PlaceStages ->
Quantize -> Compile) over a :class:`~repro.plan.ir.PlanIR`; the IR records
what each pass decided and ``describe()`` dumps it for CI artifacts.
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Dict, Optional, Union

import jax

from repro.dist.sharding import (
    abstract_params,
    init_params_sharded,
    sharding_ctx,
    specs_to_shardings,
)
from repro.launch.steps import (
    make_masked_decode_step,
    make_prefill_decode_step,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.models.base import ArchConfig, SHAPES, ShapeSpec, build_model
from repro.plan.ir import MeshSpec, PlanIR
from repro.plan.passes import PLAN_PIPELINE, calibrate_mlp_shifts
from repro.serve.cache import CacheKey, CachedExecutable, ExecutableCache


class ExecutionPlan:
    """A fully resolved execution recipe: mesh + rules + stages + quant +
    the AOT executable catalogue. Construct via :func:`build_plan`."""

    def __init__(self, ir: PlanIR, cache: Optional[ExecutableCache] = None):
        self.ir = ir
        self.cache = cache or ExecutableCache()
        self._model = None
        self._model_cfg = None
        self._optimizer = None
        self._built_any = False
        self._token_argmax_fns: Dict[Any, Any] = {}

    # -- resolved views -------------------------------------------------------

    @property
    def cfg(self) -> ArchConfig:
        return self.ir.cfg

    @property
    def mesh(self):
        return self.ir.mesh

    @property
    def rules(self):
        return self.ir.rules

    @property
    def mode(self) -> str:
        return self.ir.mode

    @property
    def shape(self) -> Optional[ShapeSpec]:
        return self.ir.shape

    @property
    def model(self):
        if self._model is None or self._model_cfg is not self.ir.cfg:
            self._model = build_model(self.ir.cfg)
            self._model_cfg = self.ir.cfg
        return self._model

    @property
    def optimizer(self):
        if self._optimizer is None:
            from repro.optim.optimizers import make_optimizer

            self._optimizer = make_optimizer(self.cfg.optimizer)
        return self._optimizer

    @contextmanager
    def activate(self):
        """``with mesh, sharding_ctx(...)`` — tracing/eager context."""
        with self.mesh, sharding_ctx(self.mesh, self.rules):
            yield self

    # -- parameters / state ---------------------------------------------------

    def param_specs(self):
        return self.model.param_specs()

    def abstract_params(self):
        return abstract_params(self.param_specs())

    def param_shardings(self):
        return specs_to_shardings(self.param_specs(), self.mesh, self.rules)

    def shard_params(self, params):
        """Place (and stage/mode-shard) an existing parameter pytree."""
        self.calibrate(params)
        return jax.device_put(params, self.param_shardings())

    def _materialize(self, specs, seed: int):
        """Random/zero leaves built straight into the plan's shardings."""
        return init_params_sharded(
            jax.random.PRNGKey(seed), specs,
            specs_to_shardings(specs, self.mesh, self.rules))

    def init_params(self, seed: int = 0):
        """Random sharded parameters (demos, benchmarks, tests)."""
        params = self._materialize(self.param_specs(), seed)
        self.calibrate(params)
        return params

    def init_train_state(self, seed: int = 0):
        """(sharded params, optimizer state) ready for the train step."""
        params = self.init_params(seed)
        with self.activate():
            opt_state = self.optimizer.init(params)
        return params, opt_state

    def state_shardings(self, batch: int, max_len: int):
        sspecs = self.model.decode_state_specs(batch, max_len)
        return specs_to_shardings(sspecs, self.mesh, self.rules)

    def fresh_decode_state(self, batch: int, max_len: int, paged=None,
                           only: Optional[str] = None, spec=None):
        """A zeroed, sharded decode-state pytree for one bucket shape.

        With ``paged=(page_count, page_size)`` the KV leaves come back in
        the pooled ``[..., page_count, page_size, ...]`` layout produced
        by :func:`repro.models.base.paged_state_specs` (batch-free; the
        page table maps slots onto them) while recurrent/cross leaves
        keep their dense per-slot shape. ``only`` restricts a paged build
        to one half of the split: ``"pool"`` returns just the pooled KV
        leaves (bucket-independent; the StatePool builds them once and
        shares them across buckets), ``"dense"`` just the per-slot
        remainder. With ``spec=(spec_k, draft_layers)`` the tree also
        carries the ``draft_``-prefixed layer-prefix KV leaves the fused
        speculative executable scans (the pool and per-slot wipes treat
        them like any other batch-laned leaf; combined with ``paged``
        the draft KV twins move to the pooled layout too — they ride the
        slot's page table).
        """
        sspecs = self.model.decode_state_specs(batch, max_len)
        if spec is not None:
            from repro.models.base import spec_state_specs

            sspecs = dict(sspecs, **spec_state_specs(sspecs, spec[1]))
        if paged is not None:
            from repro.models.base import is_paged_state_key, paged_state_specs

            sspecs = paged_state_specs(sspecs, *paged)
            if only == "pool":
                sspecs = {k: s for k, s in sspecs.items()
                          if is_paged_state_key(k)}
            elif only == "dense":
                sspecs = {k: s for k, s in sspecs.items()
                          if not is_paged_state_key(k)}
        return self._materialize(sspecs, seed=0)

    # -- quantization calibration ---------------------------------------------

    def calibrate(self, params) -> "ExecutionPlan":
        """Refine the Quantize pass's MLP shifts from real weights.

        Runs once, before any executable is built (a calibration after
        compilation would silently mismatch the cached executables, so it
        is skipped and recorded instead).
        """
        if not self.cfg.quantized_mlp or self.ir.quant.get("calibrated"):
            return self
        if self._built_any:
            self.ir.record("Quantize", skipped_calibration=(
                "executables already compiled with default shifts"))
            return self
        # fully float: the eager calibration decode must not enter the
        # Pallas kernels (pallas_call can't run under jax.disable_jit)
        float_model = build_model(
            self.cfg.with_(quantized=False, quantized_mlp=False))
        x_s, w_s, o_s = calibrate_mlp_shifts(self.cfg, params,
                                             model=float_model)
        self.ir.cfg = self.cfg.with_(
            mlp_x_shift=x_s, mlp_w_shift=w_s, mlp_out_shift=o_s)
        self.ir.quant.update(mlp_shifts=(x_s, w_s, o_s), calibrated=True)
        self.ir.record("Quantize", calibrated_mlp_shifts=(x_s, w_s, o_s))
        return self

    def _qsig(self):
        cfg = self.cfg
        if not cfg.quantized_mlp:
            return ()
        return (("mlp", cfg.mlp_x_shift, cfg.mlp_w_shift, cfg.mlp_out_shift),)

    # -- executables ----------------------------------------------------------

    def _key(self, kind: str, batch: int, max_len: int,
             prefill_len: int = 0, steps: int = 1,
             paged=(), spec=()) -> CacheKey:
        return CacheKey(
            arch=self.cfg.name, kind=kind, batch=batch, max_len=max_len,
            prefill_len=prefill_len, mode=self.mode,
            mesh_axes=CacheKey.mesh_signature(self.mesh),
            quantized=self.cfg.quantized,
            stages=self.ir.pipeline_stages, qsig=self._qsig(),
            steps=steps, paged=tuple(paged), spec=tuple(spec),
        )

    def executable(self, kind: Optional[str] = None) -> CachedExecutable:
        """The AOT executable for this plan's ShapeSpec (train/prefill/
        decode). Compiled once through the ExecutableCache and counted."""
        shape = self.shape
        if shape is None:
            raise ValueError(
                "this plan has no pinned ShapeSpec (serve plans build "
                "per-bucket executables via serve_executable)")
        kind = kind or shape.kind
        builders = {
            "train": lambda: make_train_step(
                self.cfg, shape, self.mesh, rules=self.rules),
            "prefill": lambda: make_prefill_step(
                self.cfg, shape, self.mesh, rules=self.rules),
            "decode": lambda: make_serve_step(
                self.cfg, shape, self.mesh, rules=self.rules),
        }
        if kind not in builders:
            raise ValueError(f"unknown executable kind {kind!r}")
        key = self._key(kind, shape.global_batch, shape.seq_len)
        self._built_any = True
        return self.cache.get_or_build(key, builders[kind])

    def serve_executable(self, kind: str, *, batch: int, max_len: int,
                         prefill_len: int = 0,
                         steps_per_dispatch: int = 1,
                         paged=None, spec=None) -> CachedExecutable:
        """A bucketed serving executable: ``kind`` is "decode" (single
        token against resident state), "prefill" (the prefill->decode
        scan handoff padded to ``prefill_len``), or "masked_decode" (the
        slot-masked continuous-batching micro-run — per-slot
        active/fresh lane schedules and attention windows, scanning
        ``steps_per_dispatch`` masked steps per call; one shape-stable
        executable per (bucket, k), keyed separately in the cache).
        ``paged=(page_count, page_size)`` (masked_decode only) swaps the
        dense per-slot KV slabs for the pooled paged layout plus a
        per-slot page-table input; requires ``max_len % page_size == 0``.
        ``spec=(spec_k, draft_layers)`` (masked_decode only) builds the
        fused speculative variant: a layer-prefix draft scans the
        micro-run and the full target block-verifies it in the same
        dispatch (see ``make_masked_decode_step``); the draft signature
        joins the cache key so plans differing only in draft depth never
        share an executable. ``spec`` composes with ``paged`` — the key
        carries both fields, so the four layout/schedule combinations
        never collide.
        """
        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        if steps_per_dispatch > 1 and kind != "masked_decode":
            raise ValueError(
                "steps_per_dispatch only applies to masked_decode "
                f"executables, not {kind!r}")
        if paged is not None:
            if kind != "masked_decode":
                raise ValueError(
                    "paged KV only applies to masked_decode executables, "
                    f"not {kind!r}")
            page_count, page_size = paged
            if page_size < 1 or page_count < 1:
                raise ValueError(f"bad paged geometry {paged!r}")
            if max_len % page_size:
                raise ValueError(
                    f"max_len {max_len} must be a multiple of page_size "
                    f"{page_size}")
        if spec is not None:
            if kind != "masked_decode":
                raise ValueError(
                    "speculative decode only applies to masked_decode "
                    f"executables, not {kind!r}")
            spec_k, draft_layers = spec
            if spec_k != steps_per_dispatch:
                raise ValueError(
                    f"spec_k ({spec_k}) must equal steps_per_dispatch "
                    f"({steps_per_dispatch})")
            if not 1 <= draft_layers <= self.cfg.n_layers:
                raise ValueError(
                    f"draft_layers must be in [1, {self.cfg.n_layers}], "
                    f"got {draft_layers}")
        if kind == "decode":
            shape = ShapeSpec(f"b{batch}xl{max_len}", max_len, batch,
                              "decode")
            build = lambda: make_serve_step(  # noqa: E731
                self.cfg, shape, self.mesh, rules=self.rules)
        elif kind == "prefill":
            build = lambda: make_prefill_decode_step(  # noqa: E731
                self.cfg, batch, prefill_len, max_len, self.mesh,
                rules=self.rules)
        elif kind == "masked_decode":
            build = lambda: make_masked_decode_step(  # noqa: E731
                self.cfg, batch, max_len, self.mesh, rules=self.rules,
                steps_per_dispatch=steps_per_dispatch, paged=paged, spec=spec)
        else:
            raise ValueError(f"unknown serve executable kind {kind!r}")
        key = self._key(kind, batch, max_len, prefill_len,
                        steps=steps_per_dispatch,
                        paged=paged if paged is not None else (),
                        spec=spec if spec is not None else ())
        self._built_any = True
        return self.cache.get_or_build(key, build)

    def token_argmax(self, tok_sharding):
        """The greedy token-selection helper, compiled by the plan.

        Thin clients (the batcher's legacy dense path) must not call
        ``jax.jit`` themselves — compilation outside the plan is
        invisible to the cache's lowering counters, which is exactly
        what the RA501 layering rule enforces. Cached per output
        sharding, so repeat buckets on the same mesh reuse one
        compilation.
        """
        fn = self._token_argmax_fns.get(tok_sharding)
        if fn is None:
            import jax.numpy as jnp

            fn = jax.jit(lambda l: jnp.argmax(l, -1).astype(jnp.int32),
                         out_shardings=tok_sharding)
            self._token_argmax_fns[tok_sharding] = fn
        return fn

    def make_batcher(self, policy=None, **kw):
        """A ServeBatcher whose executables all come from this plan."""
        from repro.serve.batcher import ServeBatcher

        return ServeBatcher(self, policy=policy, **kw)

    # -- observability --------------------------------------------------------

    def describe(self) -> Dict[str, Any]:
        """JSON-able dump of every pass decision (CI artifact / debugging)."""
        ir = self.ir
        return {
            "arch": self.cfg.name,
            "family": self.cfg.family,
            "shape": ir.shape.name if ir.shape else None,
            "mode": ir.mode,
            "mesh": ir.mesh_spec.label(),
            "mesh_axes": dict(zip(ir.mesh.axis_names,
                                  (int(s) for s in ir.mesh.devices.shape))),
            "quantized": ir.quantized,
            "pipeline_stages": ir.pipeline_stages,
            "stage_axis": ir.stage_axis,
            "stages": [s.as_dict() for s in ir.stages],
            "quant": dict(ir.quant),
            "executables": ir.executables,
            "params": dict(ir.param_pspecs),
            "passes": [{"pass": name, **entry}
                       for name, entry in ir.decisions],
            "cache": self.cache.stats(),
        }

    def stats(self) -> Dict[str, Any]:
        return self.cache.stats()


def build_plan(
    arch: Union[str, ArchConfig],
    shape: Union[str, ShapeSpec, None] = None,
    *,
    mode: Optional[str] = None,
    mesh_spec: Optional[Union[MeshSpec, Any]] = None,
    quantized: bool = False,
    pipeline_stages: int = 1,
    debug: bool = False,
    config_overrides: Optional[Dict[str, Any]] = None,
    cache: Optional[ExecutableCache] = None,
) -> ExecutionPlan:
    """Run the plan pass pipeline and return the ExecutionPlan.

    ``arch`` is an architecture alias ("yi-6b") or an ArchConfig;
    ``shape`` a ShapeSpec / SHAPES name, or None for a serve plan whose
    decode/prefill shapes come per bucket. ``mesh_spec`` is a MeshSpec
    (or an already-built Mesh); defaults to the 1x1 debug mesh under
    ``debug`` and the single-pod production mesh otherwise.
    ``pipeline_stages`` > 1 engages the PlaceStages pass.
    """
    if isinstance(arch, ArchConfig):
        cfg = arch
    else:
        from repro.configs import get_config, reduced_config

        cfg = reduced_config(arch) if debug else get_config(arch)
    if config_overrides:
        cfg = cfg.with_(**config_overrides)
    if mode:
        cfg = cfg.with_(sharding_mode=mode)
    if isinstance(shape, str):
        shape = SHAPES[shape]
    if mesh_spec is None:
        mesh_spec = MeshSpec.debug(1, 1) if debug else MeshSpec.production()
    elif not isinstance(mesh_spec, MeshSpec):
        mesh_spec = MeshSpec.from_mesh(mesh_spec)

    ir = PlanIR(
        cfg=cfg, shape=shape, mode=cfg.sharding_mode, mesh_spec=mesh_spec,
        quantized=quantized, pipeline_stages=pipeline_stages,
    )
    for _name, pass_fn in PLAN_PIPELINE:
        ir = pass_fn(ir)
    return ExecutionPlan(ir, cache)
