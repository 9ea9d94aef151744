"""Paged KV cache: dense-parity, prefix reuse, and zero-lowering churn.

The acceptance properties this file pins down (docs/memory_model.md):

* **token-for-token parity with dense** — the same request set produces
  identical greedy tokens under ``schedule="fifo"`` (dense slabs) and
  ``schedule="continuous", paged=...`` for ``steps_per_dispatch`` in
  {1, 2, 4}, float, quantized, and hybrid-SSM alike: paged attention
  runs at LOCAL positions through the page table, and RoPE's
  relative-position property makes that invisible to the scores;
* **shared-prefix reuse** — requests sharing a system prompt map the
  published prefix pages read-only, skip that prefill span, and still
  produce exactly the dense tokens;
* **zero new lowerings after warmup** — the paged masked-decode program
  is ONE executable per (bucket, k), keyed apart from the dense one;
  churning traffic (prefix hits and misses alike) only moves the cache
  hit counter;
* **boundary-time reclaim** — finish, cancellation, and drain all hand
  pages back: after every run() the pool holds only scratch pages and
  live prefix-cache entries.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.dist.sharding import init_params
from repro.launch.mesh import make_debug_mesh
from repro.models import build_model
from repro.models.base import PAGED_STATE_KEYS, paged_state_specs
from repro.plan import MeshSpec, build_plan
from repro.serve import Bucket, BucketPolicy, DecodeRequest, ServeBatcher

PAGED = (64, 16)          # (page_count, page_size) used throughout


@pytest.fixture(scope="module")
def cfg():
    return reduced_config("yi_6b").with_(n_layers=2, vocab=64)


@pytest.fixture(scope="module")
def mesh():
    return make_debug_mesh(1, 1)


@pytest.fixture(scope="module")
def params(cfg, test_seed):
    return init_params(jax.random.PRNGKey(test_seed),
                       build_model(cfg).param_specs())


@pytest.fixture(scope="module")
def hybrid_setup(test_seed):
    hcfg = reduced_config("zamba2_2_7b")
    return hcfg, init_params(jax.random.PRNGKey(test_seed),
                             build_model(hcfg).param_specs())


# same gap-robust trace as test_scheduler.py: every decode step's top-2
# logit gap clears float-rounding noise at any admission offset
_PARITY_TRACE = [
    ("p0", [63, 51, 50], 7),
    ("p1", [33, 17, 32], 5),
    ("p2", [63, 1], 2),
    ("p3", [30, 52], 4),
    ("p4", [39, 53], 7),
    ("p5", [55, 44, 23], 7),
]

# two waves sharing one 18-token system prompt (> one 16-token page):
# wave 2 must hit the prefix published by wave 1
_SYSTEM = [7, 3, 11, 2, 9, 40, 41, 5, 8, 60, 13, 21, 34, 55, 1, 6, 17, 28]
_SHARED_TRACE = [
    [("s0", _SYSTEM + [63, 51], 6), ("s1", _SYSTEM + [33, 17, 9], 5)],
    [("s2", _SYSTEM + [12], 4), ("s3", _SYSTEM + [44, 2], 5)],
]


@pytest.fixture(scope="module")
def fifo_reference(cfg, mesh, params, hybrid_setup):
    """Lazy per-variant DENSE fifo token reference."""
    cache = {}

    def get(variant, trace=None):
        trace = trace or _PARITY_TRACE
        key = (variant, id(trace))
        if key in cache:
            return cache[key]
        with mesh:
            if variant == "hybrid":
                hcfg, hparams = hybrid_setup
                b = ServeBatcher(hcfg, mesh,
                                 policy=BucketPolicy([Bucket(64, 2)]),
                                 ).load_params(hparams)
            else:
                b = ServeBatcher(cfg, mesh,
                                 quantized=(variant == "quantized"),
                                 ).load_params(params)
            out = {}
            for wave in (trace if isinstance(trace[0], list) else [trace]):
                for rid, p, n in wave:
                    b.submit(DecodeRequest(rid, p, max_new_tokens=n))
                out.update({r: v.tokens for r, v in b.run().items()})
            cache[key] = out
        return cache[key]

    return get


def _paged_batcher(cfg_, mesh, params_, k, quantized=False):
    b = ServeBatcher(cfg_, mesh, quantized=quantized,
                     schedule="continuous", steps_per_dispatch=k,
                     policy=BucketPolicy([Bucket(64, 2)]),
                     paged=PAGED).load_params(params_)
    return b


def _assert_reclaimed(b):
    """After a drained run(), only scratch + prefix-cache pages remain."""
    s = b.stats()["paged"]
    assert s["pages_in_use"] == s["scratch_pages"] + s["prefix_entries"], s


# ---------------------------------------------------------------------------
# ACCEPTANCE: paged == dense tokens across the k x variant matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["float", "quantized"])
def test_paged_matches_dense_argmax(cfg, mesh, params, quantized, k,
                                    fifo_reference):
    ref = fifo_reference("quantized" if quantized else "float")
    with mesh:
        b = _paged_batcher(cfg, mesh, params, k, quantized=quantized)
        for rid, p, n in _PARITY_TRACE:
            b.submit(DecodeRequest(rid, p, max_new_tokens=n))
        rc = b.run()
    assert b.scheduler.refills > 0          # parity held ACROSS slot reuse
    for rid, _, n in _PARITY_TRACE:
        assert ref[rid] == rc[rid].tokens, (k, rid)
        assert len(rc[rid].tokens) == n
    for key in b.cache._entries:
        if key.kind == "masked_decode":
            assert key.steps == k and key.paged == PAGED
    _assert_reclaimed(b)


@pytest.mark.parametrize("k", [1, 4])
def test_paged_matches_dense_on_hybrid_ssm(mesh, k, fifo_reference,
                                           hybrid_setup):
    """Hybrid: KV leaves go paged while the SSM/conv recurrence stays
    dense and still gets the fresh-lane wipe on slot reuse."""
    ref = fifo_reference("hybrid")
    hcfg, hparams = hybrid_setup
    with mesh:
        b = _paged_batcher(hcfg, mesh, hparams, k)
        for rid, p, n in _PARITY_TRACE:
            b.submit(DecodeRequest(rid, p, max_new_tokens=n))
        res = {r: v.tokens for r, v in b.run().items()}
    for rid, _, _ in _PARITY_TRACE:
        assert ref[rid] == res[rid], (k, rid)
    _assert_reclaimed(b)


def test_micro_run_writes_pool_rows_at_layer_page_offset(cfg, params):
    """One paged micro-run writes each layer's K/V rows into the stacked
    pool at ``(layer, table[b, local // ps], local % ps)`` — the rows the
    dense-slab micro-run writes at the same positions — and leaves every
    other row of the pool, and every page no table holds, as it was.

    Both paths start from the same history: the dense slabs are the pool
    read through the tables. Positions 14..17 cross a page boundary."""
    k, B, S, ps, pos = 4, 2, 64, PAGED[1], 14
    plan = build_plan(cfg, None, mesh_spec=MeshSpec.debug(1, 1))
    table = np.array([[5, 9, 2, 30], [17, 3, 40, 11]], np.int32)
    L, KV, hd = cfg.n_layers, cfg.n_kv, cfg.head_dim
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    pool = {n: jax.random.normal(key, (L, PAGED[0], ps, KV, hd),
                                 jnp.bfloat16)
            for n, key in zip(PAGED_STATE_KEYS, keys)}
    dense = {n: v[:, table].reshape(L, B, S, KV, hd)
             for n, v in pool.items()}
    before = {n: np.asarray(v) for n, v in pool.items()}   # state donated
    feed = np.array([[11, 40], [3, 7], [59, 21], [8, 33]], np.int32)
    lanes = (feed, np.zeros(B, np.int32), np.int32(pos),
             np.zeros((k, B), np.int32), np.ones((k, B), bool),
             np.zeros((k, B), bool))
    with plan.activate():
        run = plan.serve_executable("masked_decode", batch=B, max_len=S,
                                    steps_per_dispatch=k, paged=PAGED)
        toks_p, _, out_p = run.compiled(params, pool, *lanes, table)
        run = plan.serve_executable("masked_decode", batch=B, max_len=S,
                                    steps_per_dispatch=k)
        toks_d, _, out_d = run.compiled(params, dense, *lanes)
    assert np.array_equal(toks_p, toks_d)
    in_no_table = np.setdiff1d(np.arange(PAGED[0]), table)
    rows = np.arange(pos, pos + k)
    for n in PAGED_STATE_KEYS:
        after = np.asarray(out_p[n])
        assert np.array_equal(after[:, in_no_table],
                              before[n][:, in_no_table]), n
        want = before[n].copy()
        for b in range(B):
            want[:, table[b, rows // ps], rows % ps] = \
                np.asarray(out_d[n])[:, b, rows]
        assert np.array_equal(after, want), n


# ---------------------------------------------------------------------------
# ACCEPTANCE: shared-prefix requests skip prefill and keep dense tokens
# ---------------------------------------------------------------------------


def test_shared_prefix_skips_prefill_with_dense_parity(cfg, mesh, params,
                                                       fifo_reference):
    """Two waves sharing one system prompt: the second wave's requests
    reuse the published prefix pages (prefill_skip_rate > 0, one page
    table entry per shared page) and still produce exactly the dense
    FIFO tokens."""
    ref = fifo_reference("float", _SHARED_TRACE)
    with mesh:
        b = _paged_batcher(cfg, mesh, params, k=4)
        out = {}
        for wave in _SHARED_TRACE:
            for rid, p, n in wave:
                b.submit(DecodeRequest(rid, p, max_new_tokens=n))
            out.update({r: v.tokens for r, v in b.run().items()})
    for wave in _SHARED_TRACE:
        for rid, _, n in wave:
            assert ref[rid] == out[rid], rid
    s = b.stats()["paged"]
    assert s["prefix_hits"] >= len(_SHARED_TRACE[1])
    assert s["skipped_prefill_tokens"] >= len(_SHARED_TRACE[1]) * 16
    assert s["prefill_skip_rate"] > 0
    # metrics surface the same counters per bucket
    m = b.stats()["buckets"]["b2xl64"]
    assert m["prefix_hits"] == s["prefix_hits"]
    assert m["peak_pages"] == s["peak_pages"]
    _assert_reclaimed(b)


# ---------------------------------------------------------------------------
# ACCEPTANCE: zero new lowerings after warmup; paged keys never collide
# ---------------------------------------------------------------------------


def test_paged_zero_new_lowerings_under_churn(cfg, mesh, params):
    with mesh:
        b = _paged_batcher(cfg, mesh, params, k=4)
        for rid, p, n in _PARITY_TRACE[:3]:
            b.submit(DecodeRequest(rid, p, max_new_tokens=n))
        b.run()
        warm = dict(b.cache.stats())
        assert warm["compiles"] == 1        # ONE paged executable

        for wave in range(3):
            for rid, p, n in _PARITY_TRACE:
                b.submit(DecodeRequest(f"w{wave}-{rid}", p,
                                       max_new_tokens=n))
            # alternate waves hit the shared system prompt so churn
            # exercises prefix hits AND misses on the warm executable
            if wave % 2:
                for rid, p, n in _SHARED_TRACE[0]:
                    b.submit(DecodeRequest(f"w{wave}-{rid}", p,
                                           max_new_tokens=n))
            b.run()
        after = b.cache.stats()

    assert after["lowerings"] == warm["lowerings"]
    assert after["compiles"] == warm["compiles"]
    assert after["misses"] == warm["misses"]
    assert after["hits"] > warm["hits"]
    _assert_reclaimed(b)


def test_paged_and_dense_executables_key_separately(cfg, mesh, params):
    """Same bucket geometry, paged vs dense: two distinct cache entries
    (the paged program has a ninth input and a pooled state layout)."""
    with mesh:
        plan_kw = dict(schedule="continuous",
                       policy=BucketPolicy([Bucket(64, 2)]))
        bd = ServeBatcher(cfg, mesh, **plan_kw).load_params(params)
        bd.submit(DecodeRequest("d", [5, 9], max_new_tokens=2))
        dense = bd.run()
        bp = ServeBatcher(cfg, mesh, paged=PAGED,
                          **plan_kw).load_params(params)
        bp.submit(DecodeRequest("d", [5, 9], max_new_tokens=2))
        paged = bp.run()
    assert dense["d"].tokens == paged["d"].tokens
    keys = [k for k in bp.cache._entries if k.kind == "masked_decode"]
    assert {k.paged for k in keys} == {PAGED}
    keys_d = [k for k in bd.cache._entries if k.kind == "masked_decode"]
    assert {k.paged for k in keys_d} == {()}


# ---------------------------------------------------------------------------
# reclaim on cancellation; validation; spec transform
# ---------------------------------------------------------------------------


def test_cancel_returns_pages_at_boundary(cfg, mesh, params):
    canceled = []

    def on_boundary(pos, slots):
        if pos == 4 and not canceled:
            canceled.append(True)
            b.cancel("victim")

    with mesh:
        b = _paged_batcher(cfg, mesh, params, k=4)
        b.scheduler.on_boundary = on_boundary
        b.submit(DecodeRequest("victim", [9, 5, 3], max_new_tokens=12))
        b.submit(DecodeRequest("stays", [63, 51, 50], max_new_tokens=7))
        out = b.run()
    assert "victim" not in out and "stays" in out
    assert b.scheduler.cancellations == 1
    _assert_reclaimed(b)


def test_paged_requires_continuous_schedule(cfg, mesh):
    with pytest.raises(ValueError, match="continuous"):
        ServeBatcher(cfg, mesh, schedule="fifo", paged=PAGED)


def test_paged_requires_page_aligned_buckets(cfg, mesh):
    with pytest.raises(ValueError, match="multiple of"):
        ServeBatcher(cfg, mesh, schedule="continuous",
                     policy=BucketPolicy([Bucket(72, 2)]), paged=(8, 16))


@pytest.mark.parametrize("arch", ["yi_6b", "zamba2_2_7b", "rwkv6_7b",
                                  "llama_3_2_vision_90b",
                                  "seamless_m4t_large_v2"])
def test_paged_state_specs_page_kv_only(arch):
    """Across all five families: cache_k/cache_v swap [batch, max_len]
    for [page_count, page_size]; cross caches and recurrent state keep
    their dense per-slot shapes (and their batch axis)."""
    model = build_model(reduced_config(arch))
    dense = model.decode_state_specs(2, 64)
    paged = paged_state_specs(dense, 8, 16)
    assert set(dense) == set(paged)
    for name, spec in paged.items():
        if name in PAGED_STATE_KEYS and name in dense:
            assert spec.shape[-4:-2] == (8, 16), name
            assert "batch" not in spec.logical, name
        else:
            assert spec.shape == dense[name].shape, name
