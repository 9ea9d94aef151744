"""Continuous-batching scheduler: slot reuse inside an in-flight dispatch.

The FIFO batcher dispatches fixed groups: every slot in a bucket runs
until the LONGEST request finishes, so a short request's slot idles for
the remainder of the group — the utilization gap the paper's
sustained-throughput argument is about (peak single-dispatch numbers say
nothing about the fabric staying busy). :class:`ContinuousScheduler`
closes it with iteration-level scheduling over ONE shape-stable
executable per (bucket, k) (``make_masked_decode_step``):

* every batch lane ("slot") carries its own request lifecycle — teacher-
  forced chunked prefill, greedy decode, finished — controlled by
  per-slot lane *schedules* (``feed``/``start``/``active``/``fresh``,
  shape ``[k, slots]``) that are plain inputs, so the compiled program
  never changes shape and a churning request mix performs ZERO lowerings
  after warmup;
* the event horizon is a **micro-run** of ``steps_per_dispatch`` (k)
  masked steps scanned inside one executable call: admission, refill,
  cancellation, and completion all land on micro-run boundaries, and the
  host precomputes the whole ``[k, slots]`` schedule ahead of each call
  (finish steps are known at admission, so mid-scan self-masking needs
  no device readback). k amortizes per-dispatch overhead k-fold and
  admits a long prompt as successive k-token feed-lane chunks — a
  512-token prompt costs ~512/k dispatches, not 512;
* the moment a request's micro-run completes, its slot is freed and the
  next queued request is admitted at the NEXT boundary (refill gap <= k
  steps, == 1 for k=1): the ``fresh`` lane zeroes the slot's KV/SSM
  state in-step (donated buffers — the StatePool per-slot reset
  contract), and the attention window ``[start, pos]`` guarantees the
  newcomer never sees its predecessor's cache. RoPE attention depends
  only on relative position, so a request admitted at position 37
  decodes exactly as it would from 0;
* admission is capacity-checked: a request needing ``n`` positions joins
  an in-flight dispatch only while ``pos + n <= bucket.max_len``; when
  the bucket's positions run out the dispatch drains and a new one
  starts at position 0 on freshly reset pooled state.

Scheduling is deterministic: a request's finish step is known at
admission (``start + len(prompt) + max_new_tokens - 2``), so the host
never reads back tokens mid-dispatch — per-step outputs stay on device
and are fetched once when the dispatch drains.

:meth:`ContinuousScheduler.cancel` marks an in-flight request for
removal; its slot is freed (and its state lanes wiped through
``StatePool.reset_slots``) at the next micro-run boundary, and it never
appears in the results.

Boundary seams (all host-side, none touch the compiled step):

* ``admission`` — an :class:`~repro.serve.policy.AdmissionPolicy` that
  picks which queued request takes each freed slot (FIFO by default;
  strict-priority with per-tenant fairness and EDF with deadline-miss
  shedding ship in ``repro.serve.policy``). Requests the policy sheds
  are reported through ``on_shed`` / :meth:`drain_shed` and never run.
* ``on_boundary`` — host hook invoked at every boundary before frees and
  admission (where the async server drains its intake queue and where
  tests inject cancels).
* ``on_tokens`` — streaming hook: when set, each micro-run's tokens are
  fetched at the boundary and delivered as ``{request_id: [tokens]}``
  deltas (the async server's per-request streams); when unset the
  scheduler keeps its fetch-once-at-drain behavior.
* ``clock`` — the admission policy's time source: ``None`` means the
  deterministic global step counter; the async server installs
  ``time.monotonic`` so deadlines are wall-clock.

Host spans (``jax.profiler.TraceAnnotation``, about a microsecond each
with the profiler off) put every phase of the dispatching thread on the
profiler's timeline, beside the device's operations: ``serve.dispatch``;
``serve.fetch``, the blocking fetch of a micro-run's tokens;
``serve.boundary``, from that fetch's return (from the launch's where
tokens stay on the device, from the dispatch's start for its first) to
the return of the next launch, with children ``serve.emit``,
``serve.intake``, ``serve.free``, ``serve.admit`` (one
``serve.admit_request`` per admission inside, with stats ``rid``,
``lane`` and ``queue_us``), ``serve.schedule`` and ``serve.launch``; and
``serve.drain``. A dispatch holds one boundary per micro-run plus its
last, which launches nothing. :meth:`ContinuousScheduler.stats` counts
the same sections: ``boundary_seconds``, ``admit_chances``,
``admit_stalls`` and ``admit_stalls_pages`` (docs/serving.md, "Observing
a running server").

Speculative mode (``spec=(spec_k, draft_layers)``): each micro-run
dispatches the FUSED draft-scan + block-verify executable (see
``make_masked_decode_step``) instead of the plain k-step scan. At
the boundary the host fetches the draft and verify token lanes, accepts
each lane's longest draft prefix the target agrees with, commits those
tokens (``_Slot.acc`` — results and streaming deltas publish only
accepted tokens, so greedy streams stay bit-exact), and rolls the rest
back by bumping ``_Slot.start`` — in the executable's local coordinates
a start bump replays the rejected cache positions for free. Rollbacks
consume extra bucket positions; when a request runs out, it requeues as
a *continuation* whose prompt carries everything committed so far (the
carry map merges legs into one result), preserving the plain-mode
invariant that a dispatch always terminates.

Speculative x paged composes through revocable **draft leases** (see
``PageAllocator.draft_lease`` and docs/memory_model.md): admission
leases only the prompt span (``lazy=True``), each micro-run extends the
lease's page run with draft pages covering the speculative write front
``[local0, local0 + live)``, and the boundary accept decision resolves
them — pages fully below the committed cursor splice into the run, the
rest roll back to the free list alongside the ``start`` bump that
replays their positions. Draft-lease demand is reserved at admission
(``can_admit(reserve=...)``) so speculation can never admit itself into
a pool too full to extend any lane's lease; if eviction pressure still
starves a lane mid-dispatch, the lane parks — it requeues as a
continuation, releasing its lease so the other lanes progress.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Deque, Dict, List, Optional, Set

import jax
import numpy as np

from repro.serve.batcher import (
    Bucket,
    BucketMetrics,
    BucketPolicy,
    DecodeRequest,
    RequestResult,
)
from repro.serve.state_pool import StatePool

_EVENT_WINDOW = 4096      # bounded: a resident server must not grow per-req
_span = jax.profiler.TraceAnnotation


class _Boundary:
    """The open ``serve.boundary`` span of a dispatch, and its host time."""

    def __init__(self):
        self._span = None
        self._t0 = 0.0

    def open(self) -> None:
        self._t0 = time.perf_counter()
        self._span = _span("serve.boundary")
        self._span.__enter__()

    def close(self) -> float:
        """End the span; returns its seconds."""
        self._span.__exit__(None, None, None)
        self._span = None
        return time.perf_counter() - self._t0


@dataclasses.dataclass(frozen=True)
class SlotEvent:
    """One admission, free, or cancellation, for tests and traces."""

    kind: str             # "admit" | "free" | "cancel"
    step: int             # global position at which it happened
    slot: int
    request_id: str


@dataclasses.dataclass
class _Slot:
    """One in-flight request bound to a batch lane.

    Paged mode: ``pages`` is the lane's
    :class:`~repro.serve.paging.SlotPages` lease, and a prefix-cache hit
    of ``shared_len`` tokens backdates ``start`` to ``pos - shared_len``
    (possibly negative) with ``fed`` starting at ``shared_len`` — the
    lane behaves exactly as if it had already teacher-forced the shared
    prefix, so every downstream formula (``end_step``, result slicing,
    streaming deltas) holds unchanged.
    """

    req: DecodeRequest
    start: int            # global position of the request's first token
    fed: int = 0          # prompt tokens teacher-forced so far
    pages: Optional[object] = None   # SlotPages lease (paged mode only)
    # speculative mode only: tokens committed (target-verified) so far
    # this admission, and the last committed token — the host rebuilds
    # the executable's ``prev`` input from it each micro-run, because a
    # boundary rollback makes the device-resident carry meaningless
    acc: Optional[List[int]] = None
    prev_tok: int = 0

    @property
    def end_step(self) -> int:
        # the step that produces the request's last generated token
        return self.start + len(self.req.prompt) + self.req.max_new_tokens - 2


class ContinuousScheduler:
    """Admit queued requests into in-flight buckets as sequences finish.

    A thin state machine over the plan's ``masked_decode`` executable:
    the plan owns compilation, the :class:`StatePool` owns the resident
    KV/SSM buffers, and the scheduler only decides, per micro-run, which
    slot runs which request. ``ServeBatcher(schedule="continuous")``
    drives it; the fixed-group path stays available as the
    ``schedule="fifo"`` fallback. ``steps_per_dispatch`` (k) is the
    micro-run length: every bucket's ``max_len`` must be a multiple of k
    so micro-runs tile the position space exactly.
    """

    def __init__(self, plan, policy: BucketPolicy, pool: StatePool,
                 steps_per_dispatch: int = 1, admission=None,
                 clock: Optional[Callable[[], float]] = None,
                 spec: Optional[tuple] = None):
        from repro.serve.policy import FifoPolicy

        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        for b in policy.buckets:
            if b.max_len % steps_per_dispatch:
                raise ValueError(
                    f"bucket {b.label}: max_len must be a multiple of "
                    f"steps_per_dispatch={steps_per_dispatch} so micro-runs "
                    "tile the position space")
        paged = getattr(pool, "paged", None)
        if paged is not None:
            for b in policy.buckets:
                if b.max_len % paged[1]:
                    raise ValueError(
                        f"bucket {b.label}: max_len must be a multiple of "
                        f"page_size={paged[1]} so page tables tile the "
                        "position space")
        spec = tuple(spec) if spec else None
        if spec is not None:
            from repro.serve.validation import (
                validate_paged_spec,
                validate_spec_geometry,
            )

            validate_spec_geometry(spec, steps_per_dispatch)
            if paged is not None:
                validate_paged_spec(spec, paged, policy.buckets)
        self.spec = spec
        self.plan = plan
        self.policy = policy
        self.pool = pool
        self.steps_per_dispatch = steps_per_dispatch
        self.admission = admission if admission is not None else FifoPolicy()
        self.clock = clock
        # counters (tests + benchmark): slot_steps counts every lane-step
        # of every dispatch; idle_slot_steps the lanes that ran inert
        self.dispatches = 0
        self.micro_runs = 0
        self.steps = 0
        self.admissions = 0
        self.cancellations = 0
        self.sheds = 0
        self.slot_steps = 0
        self.idle_slot_steps = 0
        self.refills = 0
        self.refill_gap_total = 0
        self.max_refill_gap = 0
        # host time of the serve.boundary sections; boundaries where a
        # lane was free and the queue was not empty (chances), those
        # that admitted nothing (stalls), and the stalls where some
        # request's positions fitted but the page budget refused it
        self.boundary_seconds = 0.0
        self.admit_chances = 0
        self.admit_stalls = 0
        self.admit_stalls_pages = 0
        # speculative decode: (lane, micro-run) verify events, draft
        # tokens proposed/accepted across them, boundary rollbacks, and
        # continuations requeued when rollbacks exhaust a bucket's
        # position space mid-request
        self.spec_verifies = 0
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rollbacks = 0
        self.spec_continuations = 0
        self.spec_partial_results = 0
        # committed tokens of requeued continuations, by request id;
        # merged into the final result when the continuation finishes
        self._spec_carry: Dict[str, List[int]] = {}
        self.events: Deque[SlotEvent] = collections.deque(
            maxlen=_EVENT_WINDOW)
        # requests to drop at the next micro-run boundary (see cancel());
        # marks never survive a boundary, so a later request reusing a
        # canceled id can never be swallowed by a stale mark
        self._canceled: Set[str] = set()
        # cancels that arrived after their request already completed in
        # an EARLIER dispatch of the current run(); run() drops their
        # results before merging anything newer
        self._stale_cancels: Set[str] = set()
        # ids the admission policy shed (deadline already missed); they
        # never run and never appear in results — the batcher drains
        # this set after run() to free the ids, the async server is
        # notified per-id through on_shed at shed time
        self._shed_ids: Set[str] = set()
        # host hook run at every boundary BEFORE frees/admission — the
        # plug-in point for cancellation and admission-policy experiments
        self.on_boundary: Optional[Callable[[int, List[Optional[_Slot]]],
                                            None]] = None
        # streaming: per-micro-run {request_id: [new tokens]} deltas,
        # fetched at the boundary right after the executable call
        self.on_tokens: Optional[Callable[[Dict[str, List[int]]],
                                          None]] = None
        # per-id shed notification (async server stream termination)
        self.on_shed: Optional[Callable[[str], None]] = None

    # -- cancellation ---------------------------------------------------------

    def cancel(self, request_id: str) -> None:
        """Drop an in-flight request at the next micro-run boundary.

        The current micro-run finishes undisturbed (its schedule is
        already on device); at the boundary the slot is freed for the
        next queued request, its state lanes are wiped through the
        pool's donated per-slot reset, and the request never appears in
        the results. A cancel that races its request's completion still
        drops the tokens. Call from the dispatching thread (e.g. the
        ``on_boundary`` hook). Queued-but-unadmitted requests are the
        batcher's job (``ServeBatcher.cancel`` removes them from the
        queue before they reach the scheduler).
        """
        self._canceled.add(request_id)

    # -- shedding -------------------------------------------------------------

    def drain_shed(self) -> Set[str]:
        """Ids the admission policy shed since the last drain (EDF
        deadline misses). The batcher calls this after ``run()`` so the
        ids become reusable; they completed zero times."""
        shed, self._shed_ids = self._shed_ids, set()
        return shed

    # -- admission ------------------------------------------------------------

    def _now(self) -> float:
        """The admission policy's clock: global steps unless overridden."""
        return self.clock() if self.clock is not None else float(self.steps)

    def _admit(self, pending: Deque[DecodeRequest], bucket: Bucket,
               slots: List[Optional[_Slot]], pos: int,
               freed_at: List[int]) -> List[int]:
        """Fill free slots from the queue; returns freshly admitted lanes.

        Which request takes a slot is the admission policy's call (FIFO
        default: first queued request that fits, skipped-prefix order
        preserved). Requests a deadline policy sheds here never run:
        they are removed from the queue, counted, and reported through
        the shed channel. Each admission is a ``serve.admit_request``
        span whose ``queue_us`` is the time since the request's intake
        stamp (``DecodeRequest.t_intake``), where it has one.
        """
        now = self._now()
        t_boundary = time.perf_counter()
        for req in self.admission.shed(pending, now):
            self.sheds += 1
            # a shed speculative continuation delivers nothing: drop its
            # committed prefix too, so the carry map stays bounded
            self._spec_carry.pop(req.request_id, None)
            self._shed_ids.add(req.request_id)
            self.events.append(SlotEvent("shed", pos, -1, req.request_id))
            if self.on_shed is not None:
                self.on_shed(req.request_id)

        alloc = getattr(self.pool, "allocator", None)
        lazy = self.spec is not None
        chance = bool(pending) and any(s is None for s in slots)
        refused_pages = False

        def fits(req: DecodeRequest) -> bool:
            nonlocal refused_pages
            need = len(req.prompt) + req.max_new_tokens - 1
            if req.need_len > bucket.max_len:
                return False
            if alloc is None:
                return pos + need <= bucket.max_len
            # prefix-cache hits shrink the positions the request consumes
            # (its start is backdated by the shared span); admission also
            # requires the page budget to cover the private pages.
            # Speculative lanes lease lazily (prompt span only) but must
            # reserve draft-lease headroom for every live lane plus this
            # one, so speculation can never admit itself into a pool too
            # full to extend any lane's write front
            reserve = 0
            if lazy:
                occupied = 1 + sum(1 for s in slots if s is not None)
                reserve = alloc.spec_demand(self.steps_per_dispatch) \
                    * occupied
            shared = alloc.probe(req.prompt)
            if pos + (need - shared) > bucket.max_len:
                return False
            if alloc.can_admit(req.prompt, need, reserve=reserve,
                               lazy=lazy):
                return True
            refused_pages = True
            return False

        admitted: List[int] = []
        for b in range(bucket.batch):
            if slots[b] is not None or not pending:
                continue
            chosen = self.admission.select(pending, fits, now)
            if chosen is None:
                break
            span_stats = {"rid": chosen.request_id, "lane": b}
            if chosen.t_intake is not None:
                span_stats["queue_us"] = round(
                    (t_boundary - chosen.t_intake) * 1e6)
            with _span("serve.admit_request", **span_stats):
                if alloc is not None:
                    need = len(chosen.prompt) + chosen.max_new_tokens - 1
                    lease = alloc.admit(chosen.prompt, need, lazy=lazy)
                    if lease is None:
                        # the page budget moved between fits and admit
                        # (eviction edge): requeue at the head, stop
                        pending.appendleft(chosen)
                        refused_pages = True
                        break
                    slots[b] = _Slot(
                        chosen, start=pos - lease.shared_len,
                        fed=lease.shared_len, pages=lease,
                        acc=[] if self.spec is not None else None)
                else:
                    slots[b] = _Slot(
                        chosen, start=pos,
                        acc=[] if self.spec is not None else None)
            admitted.append(b)
            self.admissions += 1
            self.events.append(SlotEvent("admit", pos, b, chosen.request_id))
            if freed_at[b] >= 0:
                gap = pos - freed_at[b]
                self.refills += 1
                self.refill_gap_total += gap
                self.max_refill_gap = max(self.max_refill_gap, gap)
        if chance:
            self.admit_chances += 1
            if not admitted:
                self.admit_stalls += 1
                self.admit_stalls_pages += refused_pages
        return admitted

    # -- dispatch -------------------------------------------------------------

    def run(self, pending: Deque[DecodeRequest], params,
            metrics: Dict[str, BucketMetrics]) -> Dict[str, RequestResult]:
        """Drain the queue through successive continuous dispatches."""
        results: Dict[str, RequestResult] = {}
        while pending:
            with _span("serve.dispatch"):
                res = self._dispatch(pending, params, metrics)
            # cancels that raced a completion from an EARLIER dispatch:
            # drop the old tokens BEFORE merging this dispatch's results,
            # so a request legitimately resubmitted under the same id
            # after the cancel keeps its fresh tokens
            for rid in self._stale_cancels:
                if results.pop(rid, None) is not None:
                    self.cancellations += 1
                if rid in self._spec_carry:
                    # the cancel raced a speculative continuation that was
                    # requeued at the last drain: drop it before the next
                    # dispatch re-admits it
                    self._spec_carry.pop(rid)
                    for req in list(pending):
                        if req.request_id == rid:
                            pending.remove(req)
                            self.cancellations += 1
                            break
            self._stale_cancels.clear()
            results.update(res)
        return results

    def _park(self, slots, b, pos, freed_at, done, requeues):
        """Requeue lane ``b``'s request as a continuation at ``pos``.

        Two callers: the end-of-dispatch drain (rollbacks pushed
        ``end_step`` past the bucket's positions) and the mid-dispatch
        draft-lease valve (the page pool could not cover the lane's
        speculative write front). The continuation's prompt carries
        everything committed so far; the page lease — if any — is
        published then released, so the prompt pages enter the prefix
        cache (the continuation's re-admission skips them) and the freed
        pages let the other lanes progress. If no bucket can hold the
        continuation, the committed prefix is delivered as a (counted)
        partial result instead.
        """
        slot = slots[b]
        rid = slot.req.request_id
        alloc = getattr(self.pool, "allocator", None)
        if alloc is not None and slot.pages is not None:
            alloc.publish(slot.pages, slot.fed)
            alloc.release(slot.pages)
        carry = self._spec_carry.pop(rid, []) + slot.acc
        cont = dataclasses.replace(
            slot.req,
            prompt=list(slot.req.prompt) + slot.acc,
            max_new_tokens=slot.req.max_new_tokens - len(slot.acc))
        if cont.need_len > max(bk.max_len for bk in self.policy.buckets):
            self.spec_partial_results += 1
            done.append((slot.req, b, slot.start, carry))
        else:
            self._spec_carry[rid] = carry
            requeues.append(cont)
            self.spec_continuations += 1
            self.events.append(SlotEvent("requeue", pos, b, rid))
        freed_at[b] = pos - 1
        slots[b] = None

    def _free(self, slots, b, pos, freed_at, done=None):
        """Release lane ``b`` at boundary ``pos`` (finish or cancel)."""
        slot = slots[b]
        alloc = getattr(self.pool, "allocator", None)
        if alloc is not None and slot.pages is not None:
            if done is not None:
                # a finished request has teacher-forced its whole prompt:
                # publish its full prompt pages to the prefix cache so a
                # follower sharing the prefix skips that prefill span
                alloc.publish(slot.pages, slot.fed)
            alloc.release(slot.pages)
        if done is not None:
            done.append((slot.req, b, slot.start, slot.acc))
            # the free happened when the request produced its last token
            self.events.append(
                SlotEvent("free", slot.end_step, b, slot.req.request_id))
            freed_at[b] = slot.end_step
        else:
            # a canceled speculative request forfeits its committed prefix
            self._spec_carry.pop(slot.req.request_id, None)
            self.events.append(
                SlotEvent("cancel", pos, b, slot.req.request_id))
            # the lane was occupied through the previous micro-run's end
            freed_at[b] = pos - 1
        slots[b] = None

    def _dispatch(self, pending: Deque[DecodeRequest], params,
                  metrics: Dict[str, BucketMetrics]
                  ) -> Dict[str, RequestResult]:
        t0 = time.perf_counter()
        boundary = _Boundary()
        boundary.open()
        k = self.steps_per_dispatch
        # the policy's top pick sizes the dispatch bucket (FIFO: queue
        # head — unchanged; priority/EDF: the most urgent request)
        head = self.admission.peek(pending, self._now())
        bucket = self.policy.bucket_for(head.need_len)
        B, L = bucket.batch, bucket.max_len
        paged = getattr(self.pool, "paged", None)
        alloc = getattr(self.pool, "allocator", None)
        kw = {"paged": paged} if paged is not None else {}
        if self.spec is not None:
            kw["spec"] = self.spec
        exe = self.plan.serve_executable("masked_decode", batch=B, max_len=L,
                                         steps_per_dispatch=k, **kw)
        sched_sh = exe.bundle.in_shardings[2]
        pos_sh = exe.bundle.in_shardings[4]
        prev_sh = exe.bundle.in_shardings[3]
        if paged is not None:
            table_sh = exe.bundle.in_shardings[8]
            n_tables = L // paged[1]
            # pinned per-lane scratch pages: empty and self-masked lanes
            # still execute the step, and their (masked, never read)
            # writes must land somewhere harmless
            scratch = alloc.scratch(B)

        state = self.pool.acquire(B, L)
        slots: List[Optional[_Slot]] = [None] * B
        freed_at = [-1] * B
        idle_steps = [0] * B
        ever_used = [False] * B
        done: List[tuple] = []        # (req, slot idx, start, acc-or-None)
        outs = []                     # per-micro-run device token blocks [k,B]
        prev_host = np.zeros((B,), np.int32)
        prev = jax.device_put(prev_host, prev_sh)
        pos = 0
        runs = 0                      # micro-runs this dispatch executed

        # lane schedules only change on admission/free/prefill events;
        # in the steady decode state reuse the resident device buffers
        lane_cache: Dict[str, tuple] = {}

        def lane(name, host, sh=sched_sh):
            cached = lane_cache.get(name)
            if cached is not None and np.array_equal(cached[0], host):
                return cached[1]
            dev = jax.device_put(host, sh)
            lane_cache[name] = (host, dev)
            return dev

        def drain_cancels():
            """Resolve every pending cancel mark against this dispatch's
            finished-but-unreturned requests; anything left completed in
            an earlier dispatch (or was bogus) and is handed to run().
            Marks never survive a boundary, so a future request reusing
            a canceled id cannot be swallowed."""
            for rid in list(self._canceled):
                self._canceled.discard(rid)
                idx = next((i for i, (req, _, _, _) in enumerate(done)
                            if req.request_id == rid), None)
                if idx is not None:
                    del done[idx]             # finished: drop the tokens
                    self._spec_carry.pop(rid, None)
                    self.cancellations += 1
                else:
                    self._stale_cancels.add(rid)

        while pos + k <= L:
            # ---- micro-run boundary: hook, cancels, frees, admission ----
            if self.on_boundary is not None:
                with _span("serve.intake"):
                    self.on_boundary(pos, slots)
            with _span("serve.free"):
                cancel_mask = np.zeros((B,), bool)
                for b, slot in enumerate(slots):
                    if slot is None:
                        continue
                    if slot.req.request_id in self._canceled:
                        self._canceled.discard(slot.req.request_id)
                        self.cancellations += 1
                        cancel_mask[b] = True
                        self._free(slots, b, pos, freed_at)
                    elif slot.end_step < pos:
                        self._free(slots, b, pos, freed_at, done)
                if cancel_mask.any():
                    # wipe the canceled lanes NOW: even if no successor is
                    # admitted this dispatch, the state pytree must not carry
                    # a dead request's KV/SSM past the boundary
                    state = self.pool.reset_slots(B, L, state, cancel_mask)
                drain_cancels()
                if alloc is not None:
                    # incremental publish: every fully teacher-forced prompt
                    # page of a still-running request becomes a prefix-cache
                    # entry NOW, so a follower admitted at this boundary can
                    # already share it
                    for slot in slots:
                        if slot is not None and slot.pages is not None:
                            alloc.publish(slot.pages, slot.fed)

            with _span("serve.admit"):
                fresh = np.zeros((k, B), bool)
                for b in self._admit(pending, bucket, slots, pos, freed_at):
                    fresh[0, b] = True
                    ever_used[b] = True
                if self.spec is not None and alloc is not None:
                    # extend every live lane's lease with revocable draft
                    # pages covering this micro-run's write front BEFORE the
                    # page table is built; a lane the pool cannot cover parks
                    # (requeued as a continuation, lease released) so the
                    # remaining lanes keep making progress — the deadlock
                    # valve for eviction-pressure corner cases the admission
                    # reserve does not see
                    parked: List[DecodeRequest] = []
                    for b, slot in enumerate(slots):
                        if slot is None:
                            continue
                        live = min(k, slot.end_step - pos + 1)
                        if alloc.draft_lease(slot.pages,
                                             pos - slot.start + live):
                            continue
                        if sum(1 for s in slots if s is not None) == 1:
                            raise RuntimeError(
                                "page pool cannot extend the sole speculative "
                                "lane's draft lease: page_count is too small "
                                "for spec mode (validate_paged_spec should "
                                "have rejected this geometry)")
                        self._park(slots, b, pos, freed_at, done, parked)
                        fresh[0, b] = False
                    for cont in reversed(parked):
                        pending.appendleft(cont)
            if all(s is None for s in slots):
                break                  # drained, or out of positions

            with _span("serve.schedule"):
                # ---- precompute the [k, B] schedule for this micro-run ----
                feed = np.zeros((k, B), np.int32)
                # empty lanes window to their own single position: harmless
                start = np.broadcast_to(
                    np.arange(pos, pos + k, dtype=np.int32)[:, None],
                    (k, B)).copy()
                active = np.zeros((k, B), bool)
                lives = [0] * B           # spec acceptance re-walks live steps
                feeds_n = [0] * B         # prompt feeds among them (never rolled
                for b, slot in enumerate(slots):     # back: feeds come first)
                    if slot is None:
                        idle_steps[b] += k
                        self.idle_slot_steps += k
                        continue
                    # steps this request still runs inside the micro-run;
                    # beyond them the slot self-masks (active False)
                    live = min(k, slot.end_step - pos + 1)
                    lives[b] = live
                    active[:live, b] = True
                    start[:, b] = slot.start
                    idle_steps[b] += k - live
                    self.idle_slot_steps += k - live
                    for i in range(live):
                        if slot.fed < len(slot.req.prompt):
                            feed[i, b] = slot.req.prompt[slot.fed]
                            slot.fed += 1
                            feeds_n[b] += 1
                        else:
                            feed[i, b] = -1   # continue from the slot's argmax

                table = None
                if paged is not None:
                    # [B, n_tables] page table: the lease's pages first, the
                    # lane's pinned scratch page everywhere else (tail entries
                    # absorb clamped post-end writes; gathers of them are
                    # masked by kv_valid)
                    table = np.empty((B, n_tables), np.int32)
                    for b, slot in enumerate(slots):
                        table[b, :] = scratch[b]
                        if slot is not None and slot.pages is not None:
                            # speculative mode appends the revocable draft
                            # pages after the committed run, so the table
                            # covers the lane's whole write front this
                            # micro-run; ``draft`` is empty otherwise
                            pg = slot.pages.pages + slot.pages.draft
                            table[b, :len(pg)] = pg
            with _span("serve.launch"):
                extra = ()
                if table is not None:
                    extra = (lane("table", table, table_sh),)
                if self.spec is None:
                    toks, prev, state = exe.compiled(
                        params, state,
                        lane("feed", feed), prev,
                        jax.device_put(np.int32(pos), pos_sh),
                        lane("start", start),
                        lane("active", active),
                        lane("fresh", fresh),
                        *extra)
                else:
                    verify, drafts, state = exe.compiled(
                        params, state,
                        lane("feed", feed),
                        jax.device_put(prev_host.copy(), prev_sh),
                        jax.device_put(np.int32(pos), pos_sh),
                        lane("start", start),
                        lane("active", active),
                        lane("fresh", fresh),
                        *extra)
            # the device now has work: the boundary ends here, and the
            # next one opens when this micro-run's tokens are on the host
            self.boundary_seconds += boundary.close()
            if self.spec is not None:
                # fused draft-scan + block-verify: the host accepts the
                # longest draft prefix the target agrees with and rolls
                # the rest back by bumping the slot's window start (free
                # in the executable's local coordinates)
                with _span("serve.fetch"):
                    vt = np.asarray(jax.device_get(verify))
                    dt = np.asarray(jax.device_get(drafts))
                boundary.open()
                with _span("serve.emit"):
                    deltas: Dict[str, List[int]] = {}
                    for b, slot in enumerate(slots):
                        if slot is None:
                            continue
                        live = lives[b]
                        # step i consumed the right token iff it was a prompt
                        # feed, the host-correct prev (i == 0), or the draft
                        # matched the target at step i-1; validity is closed
                        # under prefixes, so the accepted set is {0..n-1}
                        n = 0
                        for i in range(live):
                            if feed[i, b] >= 0 or i == 0 or \
                                    dt[i - 1, b] == vt[i - 1, b]:
                                n += 1
                            else:
                                break
                        n_dec = live - feeds_n[b]
                        if n_dec > 0:
                            self.spec_verifies += 1
                            self.spec_draft_tokens += n_dec
                            self.spec_accepted_tokens += n - feeds_n[b]
                        first = slot.start + len(slot.req.prompt) - 1
                        new = [int(vt[i, b]) for i in range(n)
                               if pos + i >= first]
                        slot.acc.extend(new)
                        slot.prev_tok = int(vt[n - 1, b])
                        prev_host[b] = slot.prev_tok
                        if n < live:
                            self.spec_rollbacks += 1
                        if alloc is not None and slot.pages is not None:
                            # resolve the lane's draft pages against the
                            # committed cursor IN THE CURRENT local frame —
                            # before the start bump below moves the origin:
                            # pages fully below ``local0 + n`` splice into
                            # the committed run, the rest roll back
                            alloc.resolve_draft(slot.pages,
                                                pos - slot.start + n)
                        # the universal bump k - n advances the slot's local
                        # cursor by exactly n: rejected steps replay next
                        # micro-run, and a fully-accepted short lane (live <
                        # k) still lands end_step at pos + live - 1 + (k -
                        # live) = pos + k - 1, so it frees at the boundary
                        slot.start += k - n
                        if new:
                            deltas[slot.req.request_id] = new
                    if deltas and self.on_tokens is not None:
                        self.on_tokens(deltas)
                self.micro_runs += 1
                self.steps += k
                self.slot_steps += k * B
                pos += k
                runs += 1
                continue
            if self.on_tokens is not None:
                # streaming: fetch this micro-run's block at the boundary
                # and hand each live request its newly GENERATED tokens
                # (prompt-echo steps are not part of any stream). The
                # fetched array replaces the device block in `outs`, so
                # drain-time assembly pays no second transfer.
                with _span("serve.fetch"):
                    toks = np.asarray(jax.device_get(toks))
                boundary.open()
                with _span("serve.emit"):
                    deltas: Dict[str, List[int]] = {}
                    for b, slot in enumerate(slots):
                        if slot is None:
                            continue
                        first = slot.start + len(slot.req.prompt) - 1
                        lo = max(pos, first)
                        hi = min(pos + k - 1, slot.end_step)
                        if lo <= hi:
                            deltas[slot.req.request_id] = [int(t) for t in
                                toks[lo - pos:hi - pos + 1, b]]
                    if deltas:
                        self.on_tokens(deltas)
            else:
                boundary.open()
            outs.append(toks)
            self.micro_runs += 1
            self.steps += k
            self.slot_steps += k * B
            pos += k
            runs += 1

        self.boundary_seconds += boundary.close()

        with _span("serve.free"):
            # every admitted request ends inside the loop (admission bounds
            # end_step < L and micro-runs tile [0, L)), so drain the rest —
            # except in spec mode, where rollback bumps can push a request's
            # end_step past the bucket's positions: those requeue as
            # continuations whose prompt carries everything committed so far
            requeues: List[DecodeRequest] = []
            for b, slot in enumerate(slots):
                if slot is None:
                    continue
                if self.spec is not None and slot.end_step >= pos:
                    self._park(slots, b, pos, freed_at, done, requeues)
                else:
                    self._free(slots, b, pos, freed_at, done)
            for cont in reversed(requeues):
                pending.appendleft(cont)
            drain_cancels()   # marks set during the final micro-run

        with _span("serve.drain"):
            if outs:
                jax.block_until_ready(outs[-1])
            self.pool.release(B, L, state)
            t_total = time.perf_counter() - t0
            self.dispatches += 1

            toks = (np.concatenate(
                [np.asarray(jax.device_get(t)) for t in outs], axis=0)
                if outs else np.zeros((0, B), np.int32))   # [steps, B]
            results: Dict[str, RequestResult] = {}
            for req, b, s, acc in done:
                if acc is not None:
                    # spec mode: host-committed tokens, prefixed by whatever
                    # earlier continuation legs carried over
                    tokens = self._spec_carry.pop(req.request_id, []) + acc
                else:
                    first = s + len(req.prompt) - 1
                    tokens = [int(t) for t in
                              toks[first:first + req.max_new_tokens, b]]
                results[req.request_id] = RequestResult(
                    request_id=req.request_id,
                    tokens=tokens,
                    bucket=bucket.label,
                    prefill_seconds=0.0,   # prefill is folded into the steps
                    total_seconds=t_total,
                )

            m = metrics.setdefault(bucket.label, BucketMetrics())
            m.dispatches += 1
            m.requests += len(results)
            # same unit as the fifo path: slots this dispatch never filled
            # (mid-dispatch idling lives in slot_steps/busy_slot_steps)
            m.padded_slots += B - sum(ever_used)
            m.new_tokens += sum(len(r.tokens) for r in results.values())
            m.decode_seconds += t_total
            m.latencies.extend([t_total] * len(results))
            span = runs * k
            m.slot_steps += span * B
            for b in range(B):
                m.busy_slot_steps += span - idle_steps[b]
                m.slot_idle.append(idle_steps[b])
            if alloc is not None:
                # gauges, not sums: the page pool is shared process-wide
                m.pages_in_use = alloc.pages_in_use
                m.peak_pages = alloc.peak_pages
                m.prefix_hits = alloc.prefix_hits
            return results

    # -- observability --------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        busy = self.slot_steps - self.idle_slot_steps
        out = {
            "dispatches": self.dispatches,
            "micro_runs": self.micro_runs,
            "steps_per_dispatch": self.steps_per_dispatch,
            "steps": self.steps,
            "policy": self.admission.name,
            "admissions": self.admissions,
            "cancellations": self.cancellations,
            "sheds": self.sheds,
            "slot_steps": self.slot_steps,
            "idle_slot_steps": self.idle_slot_steps,
            "busy_slot_fraction": round(busy / self.slot_steps, 4)
            if self.slot_steps else 0.0,
            "refills": self.refills,
            "mean_refill_gap": round(
                self.refill_gap_total / self.refills, 3)
            if self.refills else 0.0,
            "max_refill_gap": self.max_refill_gap,
            "boundary_seconds": self.boundary_seconds,
            "admit_chances": self.admit_chances,
            "admit_stalls": self.admit_stalls,
            "admit_stalls_pages": self.admit_stalls_pages,
        }
        if self.spec is not None:
            out["spec"] = {
                "spec_k": self.spec[0],
                "draft_layers": self.spec[1],
                "verifies": self.spec_verifies,
                "draft_tokens": self.spec_draft_tokens,
                "accepted_tokens": self.spec_accepted_tokens,
                "rollbacks": self.spec_rollbacks,
                "continuations": self.spec_continuations,
                "partial_results": self.spec_partial_results,
                # the headline: committed tokens per (lane, micro-run)
                # verify event — > 1 means speculation beats one-at-a-time
                "accepted_tokens_per_dispatch": round(
                    self.spec_accepted_tokens / self.spec_verifies, 3)
                if self.spec_verifies else 0.0,
            }
        return out
