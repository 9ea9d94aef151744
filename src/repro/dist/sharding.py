"""Logical-axis sharding: the TPU-mesh retargeting of the paper's 2D fabric.

The paper parallelizes each layer over a physical AIE array two ways at
once: *cascade rows* stream partial sums west->east (the contraction dim is
spatial), and *column replicas* split the output features. On a TPU mesh
the same decomposition becomes a choice of PartitionSpec per tensor dim.
This module keeps that choice out of the layers: layers annotate tensors
with LOGICAL axis names ("batch", "act_heads", "cascade_in", ...) and a
per-mode rule table resolves those names to physical mesh axes
("pod", "data", "model") at trace time.

Three rule tables ship (``rules_for_mode``):

* ``cascade``   — paper-faithful: every weight's contraction dim maps to
                  the model axis (the west->east cascade reduction becomes
                  one psum per linear); the non-contracted dim carries FSDP
                  over (pod, data).
* ``megatron``  — tensor parallelism: "col" weights split their output dim
                  on model, "row" weights their input dim; one psum per
                  col+row pair. FSDP over (pod, data) on the other dim.
* ``megatron_sp`` — megatron + sequence parallelism: activations are
                  additionally split along "seq" on the model axis between
                  TP regions (a seq-sharded KV cache takes precedence over
                  head sharding; ``fit_pspec`` drops the duplicate axis).

Resolution is two-stage and total (it never fails): ``logical_to_pspec``
maps names -> mesh axes through the rule table, dropping axes the mesh
doesn't have (the "pod" axis on a single-pod mesh); ``fit_pspec`` then
drops or trims any axis whose size doesn't divide the tensor dim, and
de-duplicates mesh axes used by more than one dim (first dim wins). A
tensor that can't be sharded is simply replicated — the rule tables are
hints to GSPMD, never correctness requirements.

``sharding_ctx`` installs (mesh, rules) in a thread-local; ``shard_act``
is an activation constraint (``jax.lax.with_sharding_constraint``) under
an active context and a no-op otherwise, so every layer runs unchanged on
a single device.
"""

from __future__ import annotations

import dataclasses
import threading
from contextlib import contextmanager
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A rule-table entry: replicate (None), one mesh axis ("model"), or a
# composite of mesh axes (("pod", "data")) applied to a single tensor dim.
MeshAxes = Union[None, str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# ParamSpec: shape + logical axes + init recipe for one parameter
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative spec for one parameter (or state) tensor.

    ``logical`` names each dim with a logical axis (or None = replicated);
    ``init`` picks the initializer ("normal" | "zeros" | "ones" | "embed" |
    "small"); ``scale`` overrides the initializer's stddev. ParamSpec trees
    are pytree LEAVES (deliberately unregistered) so ``jax.tree.map(...,
    is_leaf=lambda x: isinstance(x, ParamSpec))`` sees whole specs.
    """

    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"
    scale: Optional[float] = None

    def __post_init__(self):
        object.__setattr__(self, "shape", tuple(self.shape))
        object.__setattr__(self, "logical", tuple(self.logical))
        if len(self.shape) != len(self.logical):
            raise ValueError(
                f"ParamSpec rank mismatch: shape {self.shape} vs "
                f"logical axes {self.logical}"
            )


_IS_SPEC = lambda x: isinstance(x, ParamSpec)  # noqa: E731


# ---------------------------------------------------------------------------
# Rule tables: logical axis name -> mesh axes, per sharding mode
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Immutable logical-axis -> mesh-axes table for one sharding mode."""

    mode: str
    table: Tuple[Tuple[str, MeshAxes], ...]

    def __post_init__(self):
        # lookup cache: get() runs once per tensor dim at trace time
        object.__setattr__(self, "_map", dict(self.table))

    def get(self, name: str, default: MeshAxes = None) -> MeshAxes:
        return self._map.get(name, default)

    def __getitem__(self, name: str) -> MeshAxes:
        return self._map[name]

    def __contains__(self, name: str) -> bool:
        return name in self._map

    def items(self):
        return self.table

    def replace(self, **updates: MeshAxes) -> "ShardingRules":
        merged = dict(self.table)
        merged.update(updates)
        return ShardingRules(self.mode, tuple(merged.items()))


# Axes shared by every mode. "batch"/"fsdp" use the composite
# ("pod", "data") so the same table serves the 16x16 single-pod and the
# 2x16x16 multi-pod mesh — logical_to_pspec drops "pod" when absent.
_COMMON: Mapping[str, MeshAxes] = {
    # data-parallel / FSDP dims
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),
    # scan-over-layers dim: never sharded
    "layers": None,
    "seq": None,
    # embedding / LM head
    "vocab": "model",
    "embed": None,
    # tensor-parallel weight dims (megatron roles)
    "col_out": "model",
    "row_in": "model",
    # MoE: experts on model (EP), capacity slots on data
    "experts": "model",
    "expert_cap": "data",
    # SSM / RWKV inner dims
    "mlp": "model",
    "q_heads": "model",
    "conv_k": None,
    # KV-cache dims
    "cache_heads": "model",
    "cache_hd": None,
    # activation dims
    "act_embed": None,
    "act_heads": "model",
    "act_mlp": "model",
}

_MODE_OVERRIDES: Mapping[str, Mapping[str, MeshAxes]] = {
    # Paper-faithful: contraction dim on model (the cascade psum), output
    # dim FSDP over (pod, data). Activations keep their feature dim on
    # model so the next linear contracts locally before its psum.
    "cascade": {
        "cascade_in": "model",
        "cascade_out": ("pod", "data"),
        "act_embed": "model",
    },
    # Megatron TP: roles already in _COMMON; activations replicated on
    # model between the col->row psum pairs.
    "megatron": {},
    # Megatron + sequence parallelism: activations shard "seq" on model
    # between TP regions. Where both "seq" and "act_heads" resolve to
    # model, fit_pspec keeps the first (seq) and drops the duplicate.
    "megatron_sp": {"seq": "model"},
}

MODES = tuple(_MODE_OVERRIDES)


def rules_for_mode(mode: str) -> ShardingRules:
    """The logical->mesh rule table for "cascade" | "megatron" | "megatron_sp"."""
    if mode not in _MODE_OVERRIDES:
        raise ValueError(f"unknown sharding mode {mode!r}; expected {MODES}")
    table = dict(_COMMON)
    table.update(_MODE_OVERRIDES[mode])
    return ShardingRules(mode, tuple(table.items()))


# ---------------------------------------------------------------------------
# Resolution: logical names -> PartitionSpec -> mesh-fitted PartitionSpec
# ---------------------------------------------------------------------------


def _mesh_axis_sizes(mesh) -> Mapping[str, int]:
    # via devices.shape (not mesh.shape) so duck-typed meshes work in tests
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def logical_to_pspec(
    axes: Sequence[Optional[str]], mesh, rules: ShardingRules
) -> P:
    """Map logical axis names to a PartitionSpec of mesh axes.

    Names missing from the rule table resolve to None (replicated), and
    mesh axes the mesh doesn't have (e.g. "pod" on a 2D mesh) are dropped.
    The result may still name an axis more than once or not divide the
    tensor — ``fit_pspec`` repairs both.
    """
    present = set(mesh.axis_names)
    out = []
    for name in axes:
        entry = rules.get(name) if name is not None else None
        if entry is None:
            out.append(None)
        elif isinstance(entry, str):
            out.append(entry if entry in present else None)
        else:
            kept = tuple(ax for ax in entry if ax in present)
            out.append(kept if kept else None)
    return P(*out)


def fit_pspec(shape: Sequence[int], pspec: P, mesh) -> P:
    """Repair ``pspec`` so it is legal for ``shape`` on ``mesh``.

    Per dim: an axis whose size doesn't divide the dim is dropped; a
    composite entry keeps its longest divisible prefix; a mesh axis already
    consumed by an earlier dim is dropped (first dim wins). The result
    always partitions validly — worst case fully replicated.
    """
    sizes = _mesh_axis_sizes(mesh)
    entries = tuple(pspec)
    used = set()
    out = []
    for i, dim in enumerate(shape):
        entry = entries[i] if i < len(entries) else None
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        kept = []
        prod = 1
        for ax in axes:
            if ax in used or ax not in sizes or dim % (prod * sizes[ax]):
                break
            prod *= sizes[ax]
            kept.append(ax)
        if not kept:
            out.append(None)
        else:
            out.append(kept[0] if isinstance(entry, str) else tuple(kept))
            used.update(kept)
    return P(*out)


def spec_to_pspec(spec: ParamSpec, mesh, rules: ShardingRules) -> P:
    """Fully resolved PartitionSpec for one ParamSpec."""
    return fit_pspec(spec.shape, logical_to_pspec(spec.logical, mesh, rules),
                     mesh)


def specs_to_shardings(specs, mesh: Mesh, rules: ShardingRules):
    """ParamSpec pytree -> NamedSharding pytree (device_put / jit shardings)."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_to_pspec(s, mesh, rules)),
        specs, is_leaf=_IS_SPEC,
    )


# ---------------------------------------------------------------------------
# Context: install (mesh, rules) for activation constraints
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextmanager
def sharding_ctx(mesh: Mesh, rules: ShardingRules):
    """Install (mesh, rules) so ``shard_act`` emits sharding constraints.

    Tracing (jit / lower) must happen inside this context for activation
    constraints to resolve; outside it every ``shard_act`` is the identity.
    Re-entrant and thread-local.
    """
    prev = getattr(_CTX, "active", None)
    _CTX.active = (mesh, rules)
    try:
        yield (mesh, rules)
    finally:
        _CTX.active = prev


def current_ctx() -> Optional[Tuple[Mesh, ShardingRules]]:
    """The innermost active (mesh, rules), or None."""
    return getattr(_CTX, "active", None)


def shard_act(x: jnp.ndarray, *logical_axes: Optional[str]) -> jnp.ndarray:
    """Constrain an activation's sharding by logical axis names.

    Under an active ``sharding_ctx`` this resolves the names through the
    rule table and applies ``jax.lax.with_sharding_constraint``; with no
    context (single-device tests, eager debugging) it returns ``x``
    unchanged. Trailing unnamed dims replicate.
    """
    ctx = current_ctx()
    if ctx is None:
        return x
    mesh, rules = ctx
    pspec = fit_pspec(x.shape, logical_to_pspec(logical_axes, mesh, rules),
                      mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


# ---------------------------------------------------------------------------
# Initialization / abstract values
# ---------------------------------------------------------------------------


def _fan_in(shape: Tuple[int, ...]) -> int:
    # weights are (..., d_in, d_out); the stacked layer dim sits in front
    return shape[-2] if len(shape) >= 2 else max(shape[-1], 1)


def _init_std(spec: ParamSpec) -> Optional[float]:
    """The initializer's stddev, or None for the constant inits."""
    if spec.init in ("zeros", "ones"):
        return None
    if spec.scale is not None:
        return spec.scale
    if spec.init == "normal":
        return _fan_in(spec.shape) ** -0.5
    if spec.init == "embed":
        # lookup table: variance set by the embedding dim, not the vocab
        return spec.shape[-1] ** -0.5
    if spec.init == "small":
        # token-shift mixing coefficients and per-head bonuses start near 0
        return 0.02
    raise ValueError(f"unknown init {spec.init!r} for shape {spec.shape}")


def _init_one(key, spec: ParamSpec, std, keep=None) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    x = jax.random.normal(key, spec.shape, jnp.float32)
    if keep is not None:
        # under jit: a select on a runtime flag (always true) that XLA
        # cannot see through, so it does not fold ``std`` into the
        # normal's own sqrt(2) factor and round differently than eager
        x = jnp.where(keep, x, 0.0)
    return (x * std).astype(spec.dtype)


def init_params(key, specs):
    """Materialize a ParamSpec pytree: one fresh RNG split per leaf.

    Deterministic in (key, tree structure): the key is split once into
    len(leaves) subkeys in flattening order, so the same spec tree under
    the same key always produces identical parameters.
    """
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_IS_SPEC)
    if not leaves:
        return specs
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten(
        [_init_one(k, s, _init_std(s)) for k, s in zip(keys, leaves)])


def init_params_sharded(key, specs, shardings):
    """:func:`init_params` as ONE jitted program writing ``shardings``.

    The float32 draws fuse into their casts instead of occupying device
    memory, and on a sharded mesh every device computes only its own
    shard (partitionable threefry makes the draws independent of the
    layout). The values are bit-identical to the eager
    :func:`init_params` under the same key.
    """
    leaves, treedef = jax.tree.flatten(specs, is_leaf=_IS_SPEC)
    if not leaves:
        return specs
    stds = [_init_std(s) for s in leaves]

    def build(key, keep):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [_init_one(k, s, d, keep)
             for k, s, d in zip(keys, leaves, stds)])

    return jax.jit(build, out_shardings=shardings)(key, True)


def abstract_params(specs):
    """ParamSpec pytree -> ShapeDtypeStruct pytree (AOT lowering inputs)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs, is_leaf=_IS_SPEC,
    )
