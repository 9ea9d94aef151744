"""Serving subsystem: executable cache, state pools, bucketed batching.

The two acceptance properties this file pins down:

* a second request group hitting an already-seen (arch, shape, mode)
  bucket is served straight from the ExecutableCache — the hit counter
  increments and the lowering/compile counters do NOT move;
* int8 ``quantized`` debug decode produces the same greedy argmax tokens
  as the float path for (at least) the first 4 steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_skip_stub

from repro.configs import reduced_config
from repro.dist.sharding import init_params
from repro.launch.mesh import make_debug_mesh
from repro.models import build_model
from repro.serve import (
    Bucket,
    BucketPolicy,
    DecodeRequest,
    ServeBatcher,
    StatePool,
)
from repro.serve.batcher import _pow2ceil

given, settings, st = hypothesis_or_skip_stub()


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
def batcher(cfg, mesh, params):
    """One warm float batcher shared by the read-only tests."""
    with mesh:
        return ServeBatcher(cfg, mesh).load_params(params)


# ---------------------------------------------------------------------------
# bucket policy / request admission
# ---------------------------------------------------------------------------


def test_bucket_policy_smallest_fit():
    policy = BucketPolicy([Bucket(256, 2), Bucket(64, 2)])
    assert policy.bucket_for(10) == Bucket(64, 2)
    assert policy.bucket_for(64) == Bucket(64, 2)
    assert policy.bucket_for(65) == Bucket(256, 2)
    with pytest.raises(ValueError, match="positions"):
        policy.bucket_for(257)


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        DecodeRequest("r", [], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        DecodeRequest("r", [1], 0)
    # need_len pads the prompt to a power of two
    assert DecodeRequest("r", [1, 2, 3], 4).need_len == 4 + 4


def test_submit_rejects_oversized_request(batcher):
    with pytest.raises(ValueError, match="positions"):
        batcher.submit(DecodeRequest("big", [1] * 300, 8))


def test_submit_rejects_duplicate_request_id(cfg, mesh, params):
    """Two queued requests with one id would last-write-win in results."""
    with mesh:
        b = ServeBatcher(cfg, mesh).load_params(params)
        b.submit(DecodeRequest("dup", [1, 2], max_new_tokens=2))
        with pytest.raises(ValueError, match="duplicate request id"):
            b.submit(DecodeRequest("dup", [3, 4], max_new_tokens=2))
        b.run()
        # the id is free again once its result has been returned
        b.submit(DecodeRequest("dup", [5, 6], max_new_tokens=2))
        out = b.run()
    assert len(out["dup"].tokens) == 2


# ---------------------------------------------------------------------------
# property-based: _pow2ceil and BucketPolicy.bucket_for
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=1 << 20))
@settings(max_examples=200, deadline=None)
def test_pow2ceil_minimal_covering_power(n):
    p = _pow2ceil(n)
    assert p >= n                          # covers
    assert p & (p - 1) == 0                # a power of two
    assert p == 1 or p // 2 < n            # and the SMALLEST such


@given(st.integers(min_value=1, max_value=1 << 20),
       st.integers(min_value=0, max_value=1 << 10))
@settings(max_examples=100, deadline=None)
def test_pow2ceil_monotone(n, delta):
    assert _pow2ceil(n + delta) >= _pow2ceil(n)


_BUCKET_LENS = st.lists(
    st.integers(min_value=4, max_value=15).map(lambda e: 1 << e),
    min_size=1, max_size=5, unique=True)


@given(_BUCKET_LENS, st.integers(min_value=1, max_value=1 << 16))
@settings(max_examples=200, deadline=None)
def test_bucket_for_minimal_covering_bucket(lens, need):
    policy = BucketPolicy([Bucket(n, 2) for n in lens])
    fitting = [n for n in sorted(lens) if need <= n]
    if not fitting:
        # over-long requests are rejected at submit time, never queued
        with pytest.raises(ValueError, match="positions"):
            policy.bucket_for(need)
        return
    b = policy.bucket_for(need)
    assert b.max_len == fitting[0]         # the smallest bucket that fits


@given(_BUCKET_LENS,
       st.integers(min_value=1, max_value=1 << 14),
       st.integers(min_value=0, max_value=1 << 14))
@settings(max_examples=100, deadline=None)
def test_bucket_for_monotone_in_need(lens, need, delta):
    """A larger request never lands in a smaller bucket."""
    policy = BucketPolicy([Bucket(n, 2) for n in lens])
    try:
        small = policy.bucket_for(need)
    except ValueError:
        small = None
    try:
        big = policy.bucket_for(need + delta)
    except ValueError:
        return                              # bigger need may only overflow
    assert small is not None               # need <= need+delta must fit too
    assert big.max_len >= small.max_len


# ---------------------------------------------------------------------------
# ACCEPTANCE: warm bucket -> zero new lowerings, hit counter moves
# ---------------------------------------------------------------------------


def test_second_request_hits_cache_zero_new_lowerings(batcher, mesh):
    with mesh:
        batcher.submit(DecodeRequest("warm0", [1, 2], max_new_tokens=3))
        batcher.submit(DecodeRequest("warm1", [3, 4, 5], max_new_tokens=3))
        batcher.run()
        warm = batcher.cache.stats()
        assert warm["compiles"] >= 2          # prefill + decode compiled once

        batcher.submit(DecodeRequest("hit0", [2, 3], max_new_tokens=3))
        batcher.submit(DecodeRequest("hit1", [4, 5, 6], max_new_tokens=3))
        out = batcher.run()
        after = batcher.cache.stats()

    assert len(out) == 2 and all(len(r.tokens) == 3 for r in out.values())
    assert after["hits"] > warm["hits"]                   # served from cache
    assert after["compiles"] == warm["compiles"]          # zero new compiles
    assert after["lowerings"] == warm["lowerings"]        # zero new lowerings
    assert after["misses"] == warm["misses"]


def test_cache_single_flight_concurrent_misses():
    """Two threads missing the same key build once; a hit on a different
    key never waits behind an in-flight compile."""
    import threading
    import time as _time

    from repro.serve import ExecutableCache

    class FakeBundle:
        def lower(self):
            _time.sleep(0.2)
            return self

        def compile(self):
            return object()

    from repro.serve import CacheKey

    cache = ExecutableCache()
    key = CacheKey("a", "decode", 1, 8, 0, "megatron", (("data", 1),))
    other = CacheKey("a", "decode", 2, 8, 0, "megatron", (("data", 1),))
    builds = []
    results = []

    def get(k):
        results.append(cache.get_or_build(
            k, lambda: builds.append(k) or FakeBundle()))

    threads = [threading.Thread(target=get, args=(key,)) for _ in range(3)]
    for t in threads:
        t.start()
    _time.sleep(0.05)                     # builders are inside the compile
    t0 = _time.perf_counter()
    get(other)                            # different key: only its own 0.2s
    # serialized behind the other build this would be >= 0.35s
    assert _time.perf_counter() - t0 < 0.35
    for t in threads:
        t.join()
    assert builds.count(key) == 1         # single-flight per key
    assert cache.stats()["compiles"] == 2
    assert len({id(r.compiled) for r in results if r.key == key}) == 1


def test_cache_keys_distinct_steps_per_dispatch(cfg, mesh):
    """Micro-run executables are keyed by k: a k-step scanned program is
    a different executable than the single-step one, so distinct k
    values must never collide — and re-requesting a warm k must be a
    pure cache hit (zero new lowerings)."""
    from repro.plan import build_plan

    plan = build_plan(cfg, None, mesh_spec=mesh)
    e1 = plan.serve_executable("masked_decode", batch=2, max_len=64,
                               steps_per_dispatch=1)
    e4 = plan.serve_executable("masked_decode", batch=2, max_len=64,
                               steps_per_dispatch=4)
    assert e1 is not e4
    assert e1.key != e4.key
    assert (e1.key.steps, e4.key.steps) == (1, 4)
    warm = dict(plan.cache.stats())
    assert warm["entries"] == 2 and warm["compiles"] == 2

    again = plan.serve_executable("masked_decode", batch=2, max_len=64,
                                  steps_per_dispatch=4)
    assert again is e4                       # same k: resident executable
    after = plan.cache.stats()
    assert after["hits"] == warm["hits"] + 1
    assert after["lowerings"] == warm["lowerings"]   # zero new lowerings
    assert after["compiles"] == warm["compiles"]


def test_steps_per_dispatch_rejected_for_other_kinds(cfg, mesh):
    """k only parameterizes the masked-decode micro-run; silently keying
    a prefill/decode build by k would fracture the cache."""
    from repro.plan import build_plan
    from repro.serve import CacheKey

    plan = build_plan(cfg, None, mesh_spec=mesh)
    with pytest.raises(ValueError, match="masked_decode"):
        plan.serve_executable("decode", batch=2, max_len=64,
                              steps_per_dispatch=4)
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        plan.serve_executable("masked_decode", batch=2, max_len=64,
                              steps_per_dispatch=0)
    # CacheKey default keeps pre-micro-run keys stable (steps == 1)
    key = CacheKey("a", "decode", 1, 8, 0, "megatron", (("data", 1),))
    assert key.steps == 1


def test_distinct_buckets_get_distinct_executables(cfg, mesh, params):
    with mesh:
        b = ServeBatcher(cfg, mesh,
                         policy=BucketPolicy([Bucket(64, 2), Bucket(256, 2)]),
                         ).load_params(params)
        b.submit(DecodeRequest("short", [1, 2], max_new_tokens=2))
        b.submit(DecodeRequest("long", [1] * 40, max_new_tokens=60))
        res = b.run()
    assert res["short"].bucket == "b2xl64"
    assert res["long"].bucket == "b2xl256"
    # 2 buckets x (prefill + decode)
    assert b.cache.stats()["entries"] == 4


# ---------------------------------------------------------------------------
# correctness: batched prefill->decode == unbatched greedy loop
# ---------------------------------------------------------------------------


def _unbatched_greedy(model, params, prompt, n_new, max_len=64):
    state = jax.tree.map(
        jnp.zeros_like,
        init_params(jax.random.PRNGKey(0),
                    model.decode_state_specs(1, max_len)))
    toks, tok = [], None
    for i in range(len(prompt) + n_new - 1):
        t = jnp.array([prompt[i] if i < len(prompt) else tok], jnp.int32)
        logits, state = model.decode_step(params, state, t, jnp.int32(i))
        tok = int(jnp.argmax(logits, -1)[0])
        if i >= len(prompt) - 1:
            toks.append(tok)
    return toks


def test_batched_decode_matches_unbatched(cfg, mesh, params, batcher):
    """Mixed prompt lengths in one group reproduce per-sequence greedy
    decode exactly: teacher-forced prefill never pollutes the cache."""
    model = build_model(cfg)
    prompts = [[1, 2], [5, 11, 23, 8]]
    refs = [_unbatched_greedy(model, params, p, 5) for p in prompts]
    with mesh:
        for i, p in enumerate(prompts):
            batcher.submit(DecodeRequest(f"m{i}", p, max_new_tokens=5))
        got = batcher.run()
    for i, ref in enumerate(refs):
        assert got[f"m{i}"].tokens == ref, (i, got[f"m{i}"].tokens, ref)


# ---------------------------------------------------------------------------
# ACCEPTANCE: int8 quantized decode matches float argmax for 4 steps
# ---------------------------------------------------------------------------


def test_quantized_decode_matches_float_argmax(mesh, test_seed):
    """On the FULL debug config (the one ``--debug --quantized`` serves),
    quantized decode — int8 LM head AND the a16w8 MLP down-projection with
    plan-calibrated shifts — must reproduce the float greedy tokens for 4
    steps. Prompts are chosen so every decode step's top-2 logit gap
    clears the ~0.02 int8-weight noise floor; gaps below it may flip (the
    int8 contract, not a bug)."""
    full = reduced_config("yi_6b")
    full_params = init_params(jax.random.PRNGKey(test_seed),
                              build_model(full).param_specs())
    prompts = [[7, 3], [2, 3, 4], [6, 2, 8], [2, 4, 8, 16]]
    with mesh:
        bf = ServeBatcher(full, mesh).load_params(full_params)
        bq = ServeBatcher(full, mesh,
                          quantized=True).load_params(full_params)
        for i, p in enumerate(prompts):
            bf.submit(DecodeRequest(f"f{i}", p, max_new_tokens=4))
            bq.submit(DecodeRequest(f"q{i}", p, max_new_tokens=4))
        rf, rq = bf.run(), bq.run()
    # --quantized now covers the MLP too, with calibrated shifts
    assert bq.cfg.quantized_mlp
    assert bq.plan.ir.quant["calibrated"]
    for i in range(len(prompts)):
        assert rf[f"f{i}"].tokens[:4] == rq[f"q{i}"].tokens[:4], i
    # quantized executables are keyed separately, never shared
    assert all(k.quantized for k in bq.cache._entries)


# ---------------------------------------------------------------------------
# state pool
# ---------------------------------------------------------------------------


def test_state_pool_reuses_and_zeroes(cfg, mesh):
    from repro.plan import build_plan

    pool = StatePool(build_plan(cfg, None, mesh_spec=mesh))
    s1 = pool.acquire(2, 64)
    dirty = jax.tree.map(lambda x: x + 1, s1)        # simulate used state
    pool.release(2, 64, dirty)
    s2 = pool.acquire(2, 64)
    stats = pool.stats()["2x64"]
    assert stats["created"] == 1 and stats["reused"] == 1
    assert stats["in_use"] == 1 and stats["free"] == 0
    for leaf in jax.tree.leaves(s2):
        assert not np.asarray(leaf, np.float32).any()


def test_batcher_pool_cycles_states(batcher):
    """Every dispatch in the earlier tests released its state back."""
    stats = batcher.pool.stats()
    assert stats and all(p["in_use"] == 0 for p in stats.values())


def test_state_pool_reuse_is_per_bucket(cfg, mesh):
    """Buckets never share buffers: re-acquiring a released bucket reuses
    (no fresh allocation), while a different shape allocates its own."""
    from repro.plan import build_plan

    pool = StatePool(build_plan(cfg, None, mesh_spec=mesh))
    s64 = pool.acquire(2, 64)
    pool.release(2, 64, s64)
    s128 = pool.acquire(2, 128)            # different bucket: fresh
    pool.release(2, 128, s128)
    pool.acquire(2, 64)                    # released bucket: reused
    pool.acquire(2, 128)
    assert pool.stats()["2x64"] == {
        "created": 1, "reused": 1, "in_use": 1, "free": 0,
        "slot_resets": 0, "slots_wiped": 0}
    assert pool.stats()["2x128"] == {
        "created": 1, "reused": 1, "in_use": 1, "free": 0,
        "slot_resets": 0, "slots_wiped": 0}


def test_state_pool_reset_slots_no_leak(cfg, mesh):
    """The donated per-slot reset wipes exactly the masked lanes — a
    reused slot can never inherit its predecessor's KV — and leaves the
    surviving requests' state bit-identical."""
    from repro.plan import build_plan

    pool = StatePool(build_plan(cfg, None, mesh_spec=mesh))
    state = pool.acquire(2, 64)
    dirty = jax.tree.map(lambda x: x + 1, state)     # both slots "used"
    wiped = pool.reset_slots(2, 64, dirty, np.array([True, False]))
    sspecs = pool.plan.model.decode_state_specs(2, 64)
    leaves = jax.tree.leaves(wiped)
    axes = [s.logical.index("batch") for s in jax.tree.leaves(
        sspecs, is_leaf=lambda x: hasattr(x, "logical"))]
    assert pool.slot_resets == 1
    for leaf, axis in zip(leaves, axes):
        arr = np.moveaxis(np.asarray(leaf, np.float32), axis, 0)
        assert not arr[0].any()                      # slot 0 wiped clean
        assert (arr[1] == 1.0).all()                 # slot 1 untouched


# ---------------------------------------------------------------------------
# CLI argument validation (satellite: --tokens 0 summary crash)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("argv", [
    ["--arch", "yi-6b", "--debug", "--tokens", "0"],
    ["--arch", "yi-6b", "--debug", "--rounds", "0"],
    ["--arch", "yi-6b", "--debug", "--steps-per-dispatch", "0"],
    ["--arch", "yi-6b", "--debug", "--steps-per-dispatch", "4"],
])
def test_serve_cli_rejects_bad_counts(monkeypatch, argv):
    from repro.launch import serve

    monkeypatch.setattr("sys.argv", ["serve.py"] + argv)
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2


def test_serve_cli_main_returns_its_waves():
    """``main(argv)`` is callable in process (chip_smoke.py drives it):
    it returns the batcher and one record per wave, and a warm second
    wave of identical prompts reproduces the tokens with no new
    lowering or compile."""
    from repro.launch import serve

    run = serve.main(["--arch", "yi-6b", "--debug", "--tokens", "3",
                      "--schedule", "continuous", "--steps-per-dispatch",
                      "2", "--paged"])
    w0, w1 = run.waves
    assert len(w0.results) == len(w1.results) == 4
    for rid, res in w0.results.items():
        assert w1.results["w1" + rid[2:]].tokens == res.tokens
        assert len(res.tokens) == 3
    assert (w1.cache["lowerings"], w1.cache["compiles"]) == \
        (w0.cache["lowerings"], w0.cache["compiles"])
    assert w0.seconds > 0 and run.batcher.params is not None


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(tmp_path, env_dir):
    """The entry points' cache helper defers to JAX_COMPILATION_CACHE_DIR
    and otherwise pins <checkout>/.jax_cache. Run in a child process so
    this test process never turns the cache on."""
    import os
    import subprocess
    import sys

    from repro.launch.compile_cache import DEFAULT_CACHE_DIR

    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    prog = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", prog], env=env, text=True,
                         capture_output=True, timeout=300, check=True)
    returned, configured = out.stdout.split()
    want = str(tmp_path / env_dir) if env_dir else str(DEFAULT_CACHE_DIR)
    assert returned == configured == want
    assert DEFAULT_CACHE_DIR.name == ".jax_cache"
