"""Compile the serving path for a described TPU v5e, without the chip.

The TPU compiler is installed even where no TPU is attached: it compiles
for a ``v5e:2x2`` topology that is only described. That refuses what
interpret mode accepts — a Mosaic kernel the chip cannot run, a program
that does not fit one chip's HBM, a kernel that cannot be partitioned —
at no chip time. Nothing runs, so these tests say nothing about results
or speed.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and every test worker imports this
file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.qmatmul.ops import _auto_blocks, _ceil_to
from repro.kernels.qmatmul.qmatmul import qmatmul_pallas
from repro.plan import MeshSpec, build_plan
from repro.serve.batcher import BucketPolicy, auto_paged

HBM_BYTES = 16 * 2**30         # one TPU v5e chip
YI = get_config("yi-6b")
SERVE = BucketPolicy.production(8, 2048)     # the serve_v5e buckets


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _mesh(topo, n: int) -> Mesh:
    return Mesh(np.asarray(topo.devices[:n]).reshape(1, n),
                ("data", "model"))


def _peak(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _masked_step(plan, max_len: int = 2048, paged=auto_paged(SERVE)):
    """The micro-run the serve CLI dispatches: k=4, paged, batch 8."""
    return plan.serve_executable(
        "masked_decode", batch=8, max_len=max_len, steps_per_dispatch=4,
        paged=paged).compiled


_BYTES = {"bf16": 2, "f32": 4, "s32": 4}
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w-]+)\(", re.M)
_COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
                "collective-permute", "reduce-scatter")


def _instructions(text: str):
    """(name, opcode, dtype, shape) of every array-valued instruction in
    compiled HLO text."""
    return [(n, op, d, tuple(int(x) for x in s.split(",") if x))
            for n, d, s, op in _INSTR.findall(text)]


@pytest.mark.parametrize("name,M,K,N,x_dtype,shift", [
    # decode LM head: int8 x int8, int16 out
    ("head", 8, YI.d_model, YI.vocab, jnp.int8, 2),
    # MLP down-projection: a16w8 (int16 activations x int8 weights)
    ("down", 8, YI.d_ff, YI.d_model, jnp.int16, 8),
])
def test_qlinear_compiles_for_v5e(topo, name, M, K, N, x_dtype, shift):
    one_chip = SingleDeviceSharding(topo.devices[0])
    block, acc = _auto_blocks(M, K, N, on_tpu=True)
    (bm, bk, bn), (qm, qn) = block, acc
    x = jax.ShapeDtypeStruct((_ceil_to(M, qm * bm), _ceil_to(K, bk)),
                             x_dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((_ceil_to(K, bk), _ceil_to(N, qn * bn)),
                             jnp.int8, sharding=one_chip)

    def kernel(x, w):
        return qmatmul_pallas(x, w, None, shift=shift, out_dtype="int16",
                              block=block, acc_blocks=acc)

    compiled = jax.jit(kernel).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_full_depth_float_micro_run_fits_one_chip(topo):
    plan = build_plan(YI, None, mesh_spec=MeshSpec.from_mesh(_mesh(topo, 1)))
    assert plan.cfg.n_layers == 32
    compiled = _masked_step(plan)
    # resident bf16 weights + the paged KV pool + step temporaries
    assert _peak(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_quantized_micro_run_compiles_the_kernel(topo, monkeypatch):
    plan = build_plan(YI, None, mesh_spec=MeshSpec.from_mesh(_mesh(topo, 1)),
                      quantized=True)
    assert plan.cfg.quantized and plan.cfg.quantized_mlp
    # qlinear picks interpret mode off the default backend, which is the
    # CPU here: steer it so the Mosaic kernel itself is compiled
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _masked_step(plan, max_len=256)
    assert "tpu_custom_call" in compiled.as_text()
    assert _peak(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_megatron_1x4_float_step_compiles(topo):
    plan = build_plan(YI.with_(sharding_mode="megatron"), None,
                      mesh_spec=MeshSpec.from_mesh(_mesh(topo, 4)))
    compiled = _masked_step(plan)
    one = build_plan(YI, None, mesh_spec=MeshSpec.from_mesh(_mesh(topo, 1)))
    per_device = compiled.memory_analysis().argument_size_in_bytes
    whole = _masked_step(one).memory_analysis().argument_size_in_bytes
    # weights split four ways on the model axis; norms, the GQA kv
    # projections and the paged pool's replicated page axes stay whole
    assert per_device < 0.3 * whole, (per_device, whole)


@pytest.mark.parametrize("arch,max_len,paged,model", [
    ("yi-6b", 256, auto_paged(SERVE), 1),
    ("yi-6b", 2048, auto_paged(SERVE), 1),
    # 20 KV heads: the TPU compiler keeps this pool heads-major, which a
    # [KV, hd] row write would make the step relay out on entry and exit
    ("qwen1.5-4b", 2048, (336, 16), 1),
    # megatron 1x4: the pool's head axis is split over the model axis,
    # and the per-head row write indexes it
    ("yi-6b", 2048, auto_paged(SERVE), 4),
    ("qwen1.5-4b", 2048, (336, 16), 4),
])
def test_paged_micro_run_updates_the_pool_in_place(topo, arch, max_len,
                                                   paged, model):
    cfg = get_config(arch)
    if model > 1:
        cfg = cfg.with_(sharding_mode="megatron")
    plan = build_plan(cfg, None,
                      mesh_spec=MeshSpec.from_mesh(_mesh(topo, model)))
    compiled = _masked_step(plan, max_len, paged)
    text = compiled.as_text()
    whole = (cfg.n_layers, paged[0], paged[1], cfg.n_kv, cfg.head_dim)
    pool = whole[:3] + (cfg.n_kv // model, cfg.head_dim)    # one device's
    slabs = (pool[1:], (1,) + pool[1:])
    relaid = 0
    for name, op, dtype, shape in _instructions(text):
        # the layer scan carries the donated pool: no whole-pool copy, no
        # stacked write-back and no layer slab sliced out of it
        if shape == pool:
            assert op != "copy" and "dynamic-update-slice" not in \
                f"{op} {name}", name
        assert not (shape in slabs and op in
                    ("dynamic-slice", "fusion", "copy")), name
        if model > 1:
            # a sharded pool stays a quarter on each device, and no page
            # crosses the mesh
            assert shape != whole, name
            assert not (op.startswith(_COLLECTIVES)
                        and paged[0] in shape), name
        if op == "copy" and len(shape) == 3 and shape[0] == cfg.n_layers:
            relaid += _BYTES[dtype] * math.prod(shape)
    # what the step keeps besides: re-laid-out copies of stacked weights
    # (or of this device's slice of them), which the layer scan slices
    # per layer
    pool_leaf = 2 * math.prod(pool)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp - relaid < pool_leaf, (temp, relaid, pool_leaf)
