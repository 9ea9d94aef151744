"""Host-level stand-ins for driving the REAL continuous scheduler.

Shared by ``test_scheduler.py`` (slot/admission invariants), the policy
property suite (``test_policies.py``), and any future host-level serving
test. The scheduler under test is the production
:class:`~repro.serve.scheduler.ContinuousScheduler`; only the executable
and the state pool are faked, so every invariant checked here is a fact
about the shipped scheduling code, not about a model.

The fake executable emits token ``pos + i + 1`` on every active
lane-step, which makes result slices *positional receipts*: request r
admitted at ``start`` must receive exactly
``[start+len(prompt), ..., start+len(prompt)+n-1]`` — any slot overlap,
mis-slice, or double-completion corrupts the receipt.
"""

from __future__ import annotations

import collections
import glob
import os
import types

import jax
import numpy as np

from repro.serve import Bucket, BucketPolicy, DecodeRequest
from repro.serve.scheduler import ContinuousScheduler


class HostExe:
    """Fake masked-decode executable: positional-receipt tokens."""

    def __init__(self):
        self.bundle = types.SimpleNamespace(in_shardings=(None,) * 8)
        self.calls = 0

    def compiled(self, params, state, feed, prev, pos, start, active,
                 fresh):
        self.calls += 1
        active = np.asarray(active)
        k, B = active.shape
        base = int(pos)
        toks = (np.arange(base + 1, base + k + 1, dtype=np.int32)[:, None]
                * active)
        return toks, toks[-1], state


class PagedHostExe(HostExe):
    """HostExe with the paged 9th input (the page table)."""

    def __init__(self):
        super().__init__()
        self.bundle = types.SimpleNamespace(in_shardings=(None,) * 9)

    def compiled(self, params, state, feed, prev, pos, start, active,
                 fresh, table):
        return super().compiled(params, state, feed, prev, pos, start,
                                active, fresh)


class HostPlan:
    """Plan stand-in: one HostExe (PagedHostExe under ``paged=``) per
    (batch, max_len, k, paged)."""

    def __init__(self):
        self.exes = {}

    def serve_executable(self, kind, *, batch, max_len,
                         steps_per_dispatch=1, paged=None, **kw):
        assert kind == "masked_decode"
        key = (batch, max_len, steps_per_dispatch, paged)
        if key not in self.exes:
            self.exes[key] = HostExe() if paged is None else PagedHostExe()
        return self.exes[key]


class NullPool:
    """State pool stand-in that only counts per-slot wipes."""

    def __init__(self):
        self.slot_resets = 0

    def acquire(self, batch, max_len):
        return {}

    def release(self, batch, max_len, state):
        pass

    def reset_slots(self, batch, max_len, state, slot_mask):
        self.slot_resets += 1
        return state


def make_host_scheduler(batch, max_len=64, k=1, admission=None,
                        clock=None) -> ContinuousScheduler:
    """A real scheduler over the host fakes, ready to ``run()``."""
    policy = BucketPolicy([Bucket(max_len, batch)])
    return ContinuousScheduler(HostPlan(), policy, NullPool(),
                               steps_per_dispatch=k, admission=admission,
                               clock=clock)


def expected_receipt(start, plen, n):
    first = start + plen - 1
    return list(range(first + 1, first + 1 + n))


def check_invariants(sched, reqs, results, k, canceled=(), shed=()):
    """Slot non-overlap + conservation + positional receipts + gap <= k.

    ``canceled``/``shed`` ids must complete zero times; every other
    submitted id exactly once, with exactly ``max_new_tokens`` tokens
    whose values prove which steps its slot actually held.
    """
    canceled, shed = set(canceled), set(shed)
    assert set(results) == ({r.request_id for r in reqs}
                            - canceled - shed)
    by_id = {r.request_id: r for r in reqs}
    admit_at = {}
    for e in sched.events:
        if e.kind == "admit":
            admit_at[e.request_id] = e.step
    for rid in shed:
        assert rid not in admit_at, f"shed id {rid} was admitted"
    for rid, res in results.items():
        req = by_id[rid]
        assert len(res.tokens) == req.max_new_tokens
        # positional receipt: the slot held exactly these steps
        assert res.tokens == expected_receipt(
            admit_at[rid], len(req.prompt), req.max_new_tokens), rid

    # slot non-overlap: per slot, the event stream alternates
    # admit -> (free | cancel) -> admit -> ...  ("shed" never holds one)
    occupancy = collections.defaultdict(lambda: None)
    for e in sched.events:
        if e.kind == "shed":
            continue
        if e.kind == "admit":
            assert occupancy[e.slot] is None, (
                f"slot {e.slot} double-admitted at {e.step}")
            occupancy[e.slot] = e.request_id
        else:
            assert occupancy[e.slot] == e.request_id, (
                f"slot {e.slot} freed by non-tenant at {e.step}")
            occupancy[e.slot] = None

    # refill gap bounded by the micro-run length
    if sched.refills:
        assert 1 <= sched.max_refill_gap <= k


class SpecHostExe:
    """Fake fused speculative executable: LOCAL positional receipts.

    ``verify[i, b] = (pos + i - start[i, b]) + 1`` — one past the lane's
    local cursor, so a step's value depends only on how many tokens the
    slot has actually consumed, never on which micro-run replayed it.
    The scheduler's rollback bumps ``slot.start`` by exactly the
    rejected count, so the committed stream for a request with prompt
    length P must be exactly ``[P, P+1, ..., P+n-1]`` no matter how many
    drafts were rejected, requeued, or replayed along the way: the
    accept-prefix law as an arithmetic identity on receipts.

    ``drafts`` mirrors ``verify`` except where the lane's local cursor
    sits in ``mismatch`` — those steps propose a wrong token, forcing
    the host to roll back every later step of that micro-run.
    """

    def __init__(self, mismatch=frozenset()):
        self.bundle = types.SimpleNamespace(in_shardings=(None,) * 8)
        self.calls = 0
        self.mismatch = frozenset(mismatch)

    def compiled(self, params, state, feed, prev, pos, start, active,
                 fresh):
        self.calls += 1
        active = np.asarray(active)
        start = np.asarray(start)
        k, B = active.shape
        local = (int(pos) + np.arange(k, dtype=np.int32)[:, None]
                 - start)                       # [k, B] local cursor
        verify = ((local + 1) * active).astype(np.int32)
        drafts = verify.copy()
        if self.mismatch:
            bad = np.isin(local, list(self.mismatch)) & active
            drafts[bad] += 997                  # draft disagrees here
        return verify, drafts, state


class SpecHostPlan:
    """Plan stand-in: one SpecHostExe per (batch, max_len, k, spec)."""

    def __init__(self, mismatch=frozenset()):
        self.exes = {}
        self.mismatch = frozenset(mismatch)

    def serve_executable(self, kind, *, batch, max_len,
                         steps_per_dispatch=1, spec=None, **kw):
        assert kind == "masked_decode" and spec is not None
        key = (batch, max_len, steps_per_dispatch, spec)
        if key not in self.exes:
            self.exes[key] = SpecHostExe(self.mismatch)
        return self.exes[key]


class PagedSpecHostExe(SpecHostExe):
    """SpecHostExe with the paged 9th input (the page table).

    Receipts stay LOCAL — page indirection must be invisible to the
    committed stream — but every active step's write position has to be
    covered by the table the scheduler built (committed run + draft
    lease), which is exactly the contract ``draft_lease`` exists for.
    """

    def __init__(self, mismatch=frozenset(), page_size=4):
        super().__init__(mismatch)
        self.bundle = types.SimpleNamespace(in_shardings=(None,) * 9)
        self.page_size = page_size

    def compiled(self, params, state, feed, prev, pos, start, active,
                 fresh, table):
        table = np.asarray(table)
        act = np.asarray(active)
        k, B = act.shape
        assert table.shape[0] == B and table.dtype == np.int32
        local = (int(pos) + np.arange(k, dtype=np.int32)[:, None]
                 - np.asarray(start))
        for i in range(k):
            for b in range(B):
                if act[i, b]:
                    assert 0 <= local[i, b] // self.page_size \
                        < table.shape[1], (i, b, local[i, b])
        return super().compiled(params, state, feed, prev, pos, start,
                                active, fresh)


class PagedSpecHostPlan(SpecHostPlan):
    """Plan stand-in for speculative x paged micro-runs."""

    def serve_executable(self, kind, *, batch, max_len,
                         steps_per_dispatch=1, spec=None, paged=None,
                         **kw):
        assert kind == "masked_decode"
        assert spec is not None and paged is not None
        key = (batch, max_len, steps_per_dispatch, spec, paged)
        if key not in self.exes:
            self.exes[key] = PagedSpecHostExe(self.mismatch, paged[1])
        return self.exes[key]


class PagedNullPool(NullPool):
    """NullPool plus a REAL PageAllocator: the scheduler's paged branch
    (lazy admission, draft leases, boundary resolution, publish/release,
    page-table builds) runs against real host bookkeeping while the
    device state stays fake."""

    def __init__(self, page_count, page_size):
        super().__init__()
        from repro.serve.paging import PageAllocator

        self.paged = (page_count, page_size)
        self.allocator = PageAllocator(page_count, page_size)


def check_page_invariants(alloc, slots) -> None:
    """Boundary-time page conservation over the live slots' leases."""
    assert alloc.pages_free + alloc.pages_in_use == alloc.page_count
    cached = set(alloc._prefix.values())
    writable = []
    for s in slots:
        if s is None or s.pages is None:
            continue
        for i, p in enumerate(s.pages.pages):
            assert p in alloc._refs, p
            if i >= s.pages.shared and i >= s.pages.published:
                writable.append(p)
        writable.extend(s.pages.draft)
    # one writer per page, and shared (cached) pages never draft-writable
    assert len(writable) == len(set(writable)), writable
    assert cached.isdisjoint(writable)


def run_paged_spec_host_trace(lengths, k, batch, max_len=64, page_size=4,
                              page_count=None, mismatch=(),
                              cancel_at=None, reqs=None):
    """Drive the real scheduler in SPECULATIVE x PAGED mode over the
    host fakes (real PageAllocator, fake executable/state).

    Page invariants are checked at EVERY micro-run boundary through the
    ``on_boundary`` hook, and page conservation is asserted after the
    drain: whatever mix of accepts, rollbacks, continuation requeues,
    and cancels the trace produced, only scratch and prefix-cache pages
    may remain in use. Returns ``(sched, reqs, results, canceled)``.
    """
    policy = BucketPolicy([Bucket(max_len, batch)])
    if page_count is None:
        # enough to fully back every lane plus the spec draft headroom
        page_count = (batch * (max_len // page_size) + batch
                      + (-(-k // page_size) + 1))
    pool = PagedNullPool(page_count, page_size)
    sched = ContinuousScheduler(PagedSpecHostPlan(mismatch), policy,
                                pool, steps_per_dispatch=k, spec=(k, 1))
    if reqs is None:
        reqs = [DecodeRequest(
            f"s{i}", [1 + (i + j) % 7 for j in range(plen)],
            max_new_tokens=n)
            for i, (plen, n) in enumerate(lengths)]
    canceled = []
    cancel_state = {"rid": None}
    if cancel_at is not None:
        boundary, idx = cancel_at
        cancel_state["rid"] = reqs[idx % len(reqs)].request_id
        cancel_state["boundary"] = boundary

    def hook(pos, slots):
        rid = cancel_state["rid"]
        if rid is not None and pos >= cancel_state["boundary"] and \
                rid not in canceled and any(
                    s is not None and s.req.request_id == rid
                    for s in slots):
            sched.cancel(rid)
            canceled.append(rid)
        check_page_invariants(pool.allocator, slots)

    sched.on_boundary = hook
    pending = collections.deque(reqs)
    results = sched.run(pending, None, {})
    alloc = pool.allocator
    assert alloc.pages_in_use == len(alloc._scratch) + len(alloc._prefix)
    return sched, reqs, results, canceled


def spec_expected_receipt(plen, n):
    """Local receipts: token j of a prompt-P request is P + j."""
    return list(range(plen, plen + n))


def run_spec_host_trace(lengths, k, batch, max_len=64, mismatch=(),
                        cancel_at=None, reqs=None):
    """Drive the real scheduler in SPECULATIVE mode over the host fakes.

    ``mismatch`` is a set of local cursor positions where the fake draft
    proposes a wrong token (forcing a rollback of everything after it in
    that micro-run). Returns ``(sched, reqs, results, canceled)``.
    """
    policy = BucketPolicy([Bucket(max_len, batch)])
    sched = ContinuousScheduler(SpecHostPlan(mismatch), policy,
                                NullPool(), steps_per_dispatch=k,
                                spec=(k, 1))
    if reqs is None:
        reqs = [DecodeRequest(
            f"s{i}", [1 + (i + j) % 7 for j in range(plen)],
            max_new_tokens=n)
            for i, (plen, n) in enumerate(lengths)]
    canceled = []
    if cancel_at is not None:
        boundary, idx = cancel_at
        rid = reqs[idx % len(reqs)].request_id

        def hook(pos, slots):
            if pos >= boundary and rid not in canceled and any(
                    s is not None and s.req.request_id == rid
                    for s in slots):
                sched.cancel(rid)
                canceled.append(rid)

        sched.on_boundary = hook
    pending = collections.deque(reqs)
    results = sched.run(pending, None, {})
    return sched, reqs, results, canceled


def check_spec_invariants(sched, reqs, results, canceled=()):
    """Conservation + local receipts + no leaked carry, spec mode.

    Every non-canceled id completes exactly once with EXACTLY its
    ``max_new_tokens`` receipts ``[P, ..., P+n-1]`` — rollbacks and
    continuation requeues may stretch the schedule but can never change,
    duplicate, or drop a committed token — and the continuation carry
    map must be empty once ``run()`` returns.
    """
    canceled = set(canceled)
    assert set(results) == {r.request_id for r in reqs} - canceled
    by_id = {r.request_id: r for r in reqs}
    for rid, res in results.items():
        req = by_id[rid]
        exp = spec_expected_receipt(len(req.prompt), req.max_new_tokens)
        if sched.spec_partial_results:
            # a continuation outgrew every bucket: the committed prefix
            # is delivered as-is — still exact, still non-empty
            assert res.tokens and res.tokens == exp[:len(res.tokens)], rid
        else:
            assert res.tokens == exp, rid
    assert sched._spec_carry == {}, "continuation carry leaked past run()"


def run_host_trace(lengths, k, batch, max_len=64, cancel_at=None,
                   admission=None, reqs=None):
    """Drive the real scheduler over the host fakes; returns
    ``(sched, reqs, results, canceled)``.

    ``lengths`` is a list of ``(prompt_len, max_new_tokens)`` pairs used
    to synthesize requests ``h0, h1, ...`` — or pass ``reqs`` to supply
    your own (priorities, tenants, deadlines). ``cancel_at=(boundary,
    idx)`` cancels the idx-th request from the ``on_boundary`` hook at
    the first boundary >= ``boundary`` where it is in flight.
    """
    sched = make_host_scheduler(batch, max_len=max_len, k=k,
                                admission=admission)
    if reqs is None:
        reqs = [DecodeRequest(
            f"h{i}", [1 + (i + j) % 7 for j in range(plen)],
            max_new_tokens=n)
            for i, (plen, n) in enumerate(lengths)]
    canceled = []
    if cancel_at is not None:
        boundary, idx = cancel_at
        rid = reqs[idx % len(reqs)].request_id

        def hook(pos, slots):
            if pos >= boundary and rid not in canceled and any(
                    s is not None and s.req.request_id == rid
                    for s in slots):
                sched.cancel(rid)
                canceled.append(rid)

        sched.on_boundary = hook
    pending = collections.deque(reqs)
    results = sched.run(pending, None, {})
    return sched, reqs, results, canceled


def traced(log_dir, fn):
    """``fn()`` under the profiler; returns its result and the trace's
    ``serve.*`` events as ``(name, start_ns, end_ns, stats)``."""
    jax.profiler.start_trace(str(log_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = float(ev.start_ns)
                    spans.append((ev.name, s, s + float(ev.duration_ns),
                                  dict(ev.stats)))
    return out, spans


def named(spans, name):
    return [sp for sp in spans if sp[0] == name]
