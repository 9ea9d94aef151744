"""Async streaming front-end: parity, streaming, disconnect, shedding.

The contracts pinned here (all on the real model, debug mesh):

* **async == blocking** — requests driven concurrently through
  :class:`AsyncServeServer` produce token-for-token the results of the
  blocking ``ServeBatcher.run()`` path, with ZERO new lowerings once the
  bucket's masked-decode executable is warm (streaming is a host fetch
  per micro-run, never a new program);
* **streamed deltas ARE the result** — concatenating a request's
  per-micro-run stream yields exactly its final token list;
* **disconnect cancels at the boundary** — a consumer that abandons its
  stream after the first token triggers a boundary cancellation: the
  slot is freed mid-prefill or mid-decode, its state lanes are wiped
  (``StatePool.reset_slots``), and the slot's next tenant decodes
  exactly as if the canceled request never ran. Made deterministic by
  gating the scheduler's ``on_tokens`` hook on a threading.Event so the
  worker cannot reach the next boundary until the client has
  disconnected;
* **deadline shedding surfaces as** :class:`RequestShed` — an EDF-shed
  request raises in its waiting coroutine instead of hanging, and
  feasible requests on the same server still complete.
"""

import asyncio
import threading
import time

import pytest
from _serve_stubs import named, traced

from repro.configs import reduced_config
from repro.plan import MeshSpec, build_plan
from repro.serve import (
    AsyncServeServer,
    Bucket,
    BucketPolicy,
    DecodeRequest,
    RequestShed,
    ServeBatcher,
    make_policy,
)

K = 2          # steps_per_dispatch shared by every batcher in this module

# gap-robust prompts (top-2 logit gap clears float noise at any admission
# offset) — the same trace test_scheduler.py pins fifo/continuous parity on
_TRACE = [
    ("p0", [63, 51, 50], 7),
    ("p1", [33, 17, 32], 5),
    ("p2", [63, 1], 2),
    ("p3", [30, 52], 4),
    ("p4", [39, 53], 7),
    ("p5", [55, 44, 23], 7),
]


@pytest.fixture(scope="module")
def plan(test_seed):
    """One ExecutionPlan (shared executable cache) for the module,
    pre-warmed so every test can assert zero new lowerings."""
    cfg = reduced_config("yi_6b").with_(n_layers=2, vocab=64)
    p = build_plan(cfg, None, mesh_spec=MeshSpec.debug(1, 1))
    with p.activate():
        b = p.make_batcher(policy=BucketPolicy([Bucket(64, 2)]),
                           schedule="continuous", steps_per_dispatch=K)
        b.init_demo_params(seed=0)
        b.submit(DecodeRequest("warmup", [1, 2], max_new_tokens=2))
        b.run()
    return p


def make_batcher(plan, test_seed, admission=None):
    with plan.activate():
        b = plan.make_batcher(policy=BucketPolicy([Bucket(64, 2)]),
                              schedule="continuous",
                              steps_per_dispatch=K, admission=admission)
        b.init_demo_params(seed=test_seed)
    return b


# ---------------------------------------------------------------------------
# ACCEPTANCE: async concurrent submission == blocking run(), zero lowerings
# ---------------------------------------------------------------------------


def test_async_generate_matches_blocking_run(plan, test_seed):
    bb = make_batcher(plan, test_seed)
    with plan.activate():
        for rid, p, n in _TRACE:
            bb.submit(DecodeRequest(rid, p, max_new_tokens=n))
        ref = bb.run()

    ba = make_batcher(plan, test_seed)
    warm_lowerings = ba.cache.stats()["lowerings"]

    async def drive():
        async with AsyncServeServer(ba) as server:
            return await asyncio.gather(*[
                server.generate(DecodeRequest(rid, p, max_new_tokens=n))
                for rid, p, n in _TRACE])

    with plan.activate():
        results = asyncio.run(drive())

    assert len(results) == len(_TRACE)
    for res in results:
        assert res.tokens == ref[res.request_id].tokens, res.request_id
    # streaming + concurrent arrival churn lowered NOTHING new
    assert ba.cache.stats()["lowerings"] == warm_lowerings
    assert ba.scheduler.refills > 0      # parity held across slot reuse


def test_streamed_deltas_equal_result_tokens(plan, test_seed):
    """For every request, the concatenation of its per-micro-run stream
    is exactly the blocking path's token list — no token is dropped,
    duplicated, or delivered out of order, and prompt-echo steps never
    leak into a stream."""
    bb = make_batcher(plan, test_seed)
    with plan.activate():
        for rid, p, n in _TRACE:
            bb.submit(DecodeRequest(rid, p, max_new_tokens=n))
        ref = bb.run()

    ba = make_batcher(plan, test_seed)

    async def consume(server, rid, p, n):
        toks = []
        async for t in server.stream(DecodeRequest(rid, p,
                                                   max_new_tokens=n)):
            toks.append(t)
        return rid, toks

    async def drive():
        async with AsyncServeServer(ba) as server:
            return await asyncio.gather(*[consume(server, rid, p, n)
                                          for rid, p, n in _TRACE])

    with plan.activate():
        streamed = dict(asyncio.run(drive()))
    for rid, _, n in _TRACE:
        assert streamed[rid] == ref[rid].tokens, rid
        assert len(streamed[rid]) == n


# ---------------------------------------------------------------------------
# disconnect -> boundary cancellation (deterministic via on_tokens gate)
# ---------------------------------------------------------------------------


def test_disconnect_cancels_and_slot_state_is_wiped(plan, test_seed):
    ref_b = make_batcher(plan, test_seed)
    with plan.activate():
        ref_b.submit(DecodeRequest("late", [7, 11, 13], max_new_tokens=4))
        ref = ref_b.run()["late"].tokens

    b = make_batcher(plan, test_seed)
    sched = b.scheduler
    warm_lowerings = b.cache.stats()["lowerings"]
    gate = threading.Event()

    async def drive():
        async with AsyncServeServer(b) as server:
            # gate the worker: after it emits the victim's first delta it
            # blocks until the client has disconnected, so the cancel is
            # GUARANTEED to land while the victim is still in flight
            orig = sched.on_tokens

            def gated(deltas):
                orig(deltas)
                if "victim" in deltas:
                    gate.wait(timeout=30)

            sched.on_tokens = gated
            gen = server.stream(DecodeRequest("victim", [5, 9],
                                              max_new_tokens=30))
            first = await gen.__anext__()
            await gen.aclose()           # disconnect: cancel hits intake
            gate.set()                   # NOW let the worker reach the
            #                              boundary that applies it
            late = await server.generate(
                DecodeRequest("late", [7, 11, 13], max_new_tokens=4))
            return first, late, server.stats()

    with plan.activate():
        first, late, stats = asyncio.run(drive())

    assert isinstance(first, int)
    assert sched.cancellations == 1      # boundary cancel actually ran
    assert b.pool.slot_resets >= 1       # ... and wiped the state lanes
    assert stats["outcomes"].get("cancelled") == 1
    assert stats["outcomes"].get("done") == 1
    # the canceled slot's successor decodes as if victim never existed
    assert late.tokens == ref
    assert b.cache.stats()["lowerings"] == warm_lowerings


def test_abandoned_stream_mid_prefill_cancels(plan, test_seed):
    """Disconnect while the victim's long prompt is still being chunk-fed
    (no tokens streamed yet): the cancel must still free the slot and
    wipe the partial prefill; a later request reusing the server decodes
    correctly."""
    ref_b = make_batcher(plan, test_seed)
    with plan.activate():
        ref_b.submit(DecodeRequest("late", [7, 11, 13], max_new_tokens=4))
        ref = ref_b.run()["late"].tokens

    b = make_batcher(plan, test_seed)
    sched = b.scheduler
    long_prompt = [1 + (i * 7) % 61 for i in range(24)]   # 12 k=2 chunks
    mid_prefill = threading.Event()      # set when victim is mid-feed
    gate = threading.Event()
    fed_seen = []

    async def drive():
        async with AsyncServeServer(b) as server:
            orig_boundary = sched.on_boundary

            def hooked(pos, slots):
                for s in slots:
                    if s is not None and s.req.request_id == "victim" \
                            and 0 < s.fed < len(long_prompt) \
                            and not mid_prefill.is_set():
                        fed_seen.append(s.fed)
                        mid_prefill.set()
                        gate.wait(timeout=30)
                orig_boundary(pos, slots)

            sched.on_boundary = hooked
            gen = server.stream(DecodeRequest("victim", long_prompt,
                                              max_new_tokens=8))
            task = asyncio.ensure_future(gen.__anext__())
            # wait (off-thread) until the prompt is partially fed
            await asyncio.get_running_loop().run_in_executor(
                None, mid_prefill.wait, 30)
            task.cancel()                # client hangs up mid-prefill
            try:
                await task
            except asyncio.CancelledError:
                pass
            await gen.aclose()
            gate.set()
            late = await server.generate(
                DecodeRequest("late", [7, 11, 13], max_new_tokens=4))
            return late

    with plan.activate():
        late = asyncio.run(drive())

    assert fed_seen and 0 < fed_seen[0] < len(long_prompt)
    assert sched.cancellations == 1
    assert b.pool.slot_resets >= 1
    assert late.tokens == ref


# ---------------------------------------------------------------------------
# deadline shedding -> RequestShed; submission errors propagate
# ---------------------------------------------------------------------------


def test_shed_raises_request_shed_and_server_survives(plan, test_seed):
    b = make_batcher(plan, test_seed, admission=make_policy("edf"))

    async def drive():
        async with AsyncServeServer(b) as server:
            with pytest.raises(RequestShed):
                # monotonic clock is far past 0.001 — expired on arrival
                await server.generate(DecodeRequest(
                    "doomed", [1, 2], max_new_tokens=4, deadline=0.001))
            ok = await server.generate(DecodeRequest(
                "ok", [5, 9], max_new_tokens=3,
                deadline=time.monotonic() + 300.0))
            return ok, server.stats()

    with plan.activate():
        ok, stats = asyncio.run(drive())
    assert len(ok.tokens) == 3
    assert b.scheduler.sheds == 1
    assert stats["outcomes"] == {"shed": 1, "done": 1}
    assert "doomed" not in b._pending_ids    # id freed, reusable


def test_duplicate_id_and_unservable_shape_raise(plan, test_seed):
    b = make_batcher(plan, test_seed)

    async def drive():
        async with AsyncServeServer(b) as server:
            t1 = asyncio.ensure_future(server.generate(
                DecodeRequest("dup", [5, 9], max_new_tokens=3)))
            await asyncio.sleep(0)       # let t1 register its stream
            with pytest.raises(ValueError, match="duplicate"):
                await server.generate(
                    DecodeRequest("dup", [1, 2], max_new_tokens=2))
            # shape no bucket can hold: error posted back to the stream
            with pytest.raises(ValueError, match="positions"):
                await server.generate(
                    DecodeRequest("huge", list(range(1, 60)),
                                  max_new_tokens=60))
            return await t1

    with plan.activate():
        res = asyncio.run(drive())
    assert len(res.tokens) == 3


def test_server_requires_continuous_schedule_and_start(plan, test_seed):
    with plan.activate():
        fifo_b = plan.make_batcher(policy=BucketPolicy([Bucket(64, 2)]))
    with pytest.raises(ValueError, match="continuous"):
        AsyncServeServer(fifo_b)

    b = make_batcher(plan, test_seed)
    server = AsyncServeServer(b)

    async def unstarted():
        with pytest.raises(RuntimeError, match="not started"):
            await server.generate(DecodeRequest("r", [1], max_new_tokens=1))

    asyncio.run(unstarted())


def test_intake_stamp_spans_and_tokens_with_profiler_on_and_off(
        plan, test_seed, tmp_path):
    """The server stamps each request at intake (``ServeBatcher.submit``
    stamps one nobody did, on a copy), every admission is a
    ``serve.admit_request`` span with the request's id and its queue
    wait, the idle worker is in ``serve.idle``, and the tokens do not
    depend on whether the profiler is on."""
    b = make_batcher(plan, test_seed)
    req = DecodeRequest("stamp", [1, 2], max_new_tokens=2)
    t0 = time.perf_counter()
    b.submit(req)
    assert req.t_intake is None
    assert t0 <= b._pending[-1].t_intake <= time.perf_counter()

    def serve():
        ba = make_batcher(plan, test_seed)

        async def drive():
            async with AsyncServeServer(ba) as server:
                await asyncio.sleep(0.02)           # idle worker
                return await asyncio.gather(*[
                    server.generate(DecodeRequest(rid, p, max_new_tokens=n))
                    for rid, p, n in _TRACE])

        with plan.activate():
            return {r.request_id: r.tokens for r in asyncio.run(drive())}

    off = serve()
    on, spans = traced(tmp_path, serve)
    assert on == off
    admits = named(spans, "serve.admit_request")
    assert sorted(sp[3]["rid"] for sp in admits) == \
        sorted(rid for rid, _, _ in _TRACE)
    assert all(sp[3]["queue_us"] >= 0 for sp in admits)
    assert named(spans, "serve.idle")


def test_quantile_nearest_rank_small_samples():
    """Regression for the stats() percentile helper: the old
    ``int(p * n)`` index overshot on small samples (p50 of two TTFTs
    reported the slower one; p50 of three skipped the median by luck of
    truncation). The shared nearest-rank definition — index
    ``ceil(p * n) - 1``, clamped — is pinned across n in {1, 2, 3, 100}
    and is what server TTFT, bucket latency, and benchmark tick
    percentiles all use now."""
    from repro.serve.batcher import quantile

    assert quantile([], 0.5) == 0.0
    # n=1: the only sample answers every quantile
    assert quantile([7.0], 0.5) == 7.0
    assert quantile([7.0], 0.99) == 7.0
    # n=2: p50 is the FIRST (rank ceil(1.0) = 1), p99 the second
    assert quantile([2.0, 1.0], 0.5) == 1.0
    assert quantile([1.0, 2.0], 0.99) == 2.0
    # n=3: p50 is the true median
    assert quantile([3.0, 1.0, 2.0], 0.5) == 2.0
    assert quantile([3.0, 1.0, 2.0], 0.99) == 3.0
    # n=100: classic nearest-rank ranks (p50 -> 50th, p99 -> 99th)
    v = [float(i) for i in range(1, 101)]
    assert quantile(v, 0.50) == 50.0
    assert quantile(v, 0.99) == 99.0
    assert quantile(v, 1.00) == 100.0
    assert quantile(v, 0.0) == 1.0
