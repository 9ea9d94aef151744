"""Request batcher: shape buckets, AOT executables, prefill->decode handoff.

The serving hot path must run only code that was compiled ahead of time
(ROADMAP open item #1). To make that possible with dynamic request sizes,
the batcher quantizes every request group onto a small closed set of
declared shape buckets:

* a ``Bucket(batch, max_len)`` fixes the decode executable's shapes —
  requests are padded up to the bucket batch with inert slots and their
  KV/SSM capacity to ``max_len``;
* the prompt block is padded to a power-of-two ``prefill_len`` (>= 8), so
  each bucket owns at most log2(max_len) prefill executables.

Dispatch then runs exactly two cached executables per group — one
``make_prefill_decode_step`` scan that teacher-forces prompts straight
into resident state while already generating for short sequences, and one
``make_serve_step`` single-token step looped for the remaining tokens —
both served from the process-wide :class:`ExecutableCache` and fed from
the per-bucket :class:`StatePool`. After warmup a dispatch performs zero
lowerings and zero compiles; the cache counters prove it.

This fixed-group FIFO path is the ``schedule="fifo"`` default;
``schedule="continuous"`` routes ``run()`` through the
:class:`~repro.serve.scheduler.ContinuousScheduler`, which reuses slots
INSIDE an in-flight dispatch (masked per-slot lane schedules over one
``make_masked_decode_step`` executable per (bucket, ``steps_per_dispatch``))
instead of idling them until the group's longest request finishes.
``steps_per_dispatch`` (k) scans k masked steps per executable call —
micro-runs that amortize dispatch overhead and chunk long prompts k
tokens at a time. See docs/serving.md.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Deque, Dict, List, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh

from repro.models.base import ArchConfig
from repro.serve.cache import CachedExecutable, ExecutableCache
from repro.serve.state_pool import StatePool

_MIN_PREFILL = 8


def _pow2ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def quantile(vals: Sequence[float], p: float) -> float:
    """Nearest-rank quantile of an (unsorted) sample; 0.0 when empty.

    The index is ``ceil(p * n) - 1`` clamped to ``[0, n - 1]`` — the
    classic nearest-rank definition. The previous ad-hoc ``int(p * n)``
    overshot on small samples (p50 of two values picked the LARGER one;
    p50 of [a, b, c] picked b only by accident of truncation), which is
    exactly where the serve smoke runs live. Every percentile the serve
    stack reports (bucket latencies, async TTFT, benchmark ticks) goes
    through here so the definitions cannot drift again.
    """
    if not vals:
        return 0.0
    v = sorted(vals)
    n = len(v)
    return v[max(0, min(n - 1, math.ceil(p * n) - 1))]


@dataclasses.dataclass
class DecodeRequest:
    """One sequence to continue: prompt token ids + how many to generate.

    The scheduling fields are read by the continuous scheduler's
    admission policy (``repro.serve.policy``) and ignored everywhere
    else: ``priority`` is the strict-priority class (0 = most urgent),
    ``tenant`` the fairness bucket inside a class, and ``deadline`` an
    absolute time in the scheduler's clock domain (global steps by
    default, wall-clock seconds under the async server) by which the
    last token must be produced — EDF sheds the request instead of
    admitting it once the deadline has passed. ``t_intake`` is the
    ``time.perf_counter()`` at which the serving stack took the request
    in (stamped by ``AsyncServeServer`` or :meth:`ServeBatcher.submit`);
    the scheduler reports the wait from it to admission as ``queue_us``.
    """

    request_id: str
    prompt: Sequence[int]
    max_new_tokens: int = 8
    priority: int = 0
    tenant: str = "default"
    deadline: Optional[float] = None
    t_intake: Optional[float] = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        self.prompt = [int(t) for t in self.prompt]
        if not self.prompt:
            raise ValueError(f"{self.request_id}: empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"{self.request_id}: max_new_tokens must be >= 1")

    @property
    def need_len(self) -> int:
        """KV positions this request can consume under bucket padding."""
        return _pow2ceil(len(self.prompt)) + self.max_new_tokens


@dataclasses.dataclass
class RequestResult:
    request_id: str
    tokens: List[int]
    bucket: str
    prefill_seconds: float
    total_seconds: float


@dataclasses.dataclass(frozen=True, order=True)
class Bucket:
    """One declared decode shape: padded batch x padded state capacity."""

    max_len: int
    batch: int

    @property
    def label(self) -> str:
        return f"b{self.batch}xl{self.max_len}"


class BucketPolicy:
    """Smallest-fit over a closed, sorted set of buckets."""

    def __init__(self, buckets: Sequence[Bucket]):
        if not buckets:
            raise ValueError("need at least one bucket")
        for b in buckets:
            # the prompt block is padded to >= _MIN_PREFILL positions, so
            # a smaller capacity could overrun the KV/SSM state
            if b.max_len <= _MIN_PREFILL:
                raise ValueError(
                    f"bucket {b.label}: max_len must exceed {_MIN_PREFILL}")
            if b.batch < 1:
                raise ValueError(f"bucket {b.label}: batch must be >= 1")
        self.buckets = sorted(buckets)

    @classmethod
    def debug(cls) -> "BucketPolicy":
        return cls([Bucket(64, 2), Bucket(256, 2)])

    @classmethod
    def production(cls, batch: int = 128, max_len: int = 32768
                   ) -> "BucketPolicy":
        # one decile of short-context buckets under the headline shape
        return cls([Bucket(max_len // 8, batch), Bucket(max_len, batch)])

    def bucket_for(self, need_len: int) -> Bucket:
        for b in self.buckets:
            if need_len <= b.max_len:
                return b
        raise ValueError(
            f"request needs {need_len} positions; largest bucket holds "
            f"{self.buckets[-1].max_len}")


_DEFAULT_PAGE_SIZE = 16


def auto_paged(policy: "BucketPolicy",
               page_size: int = _DEFAULT_PAGE_SIZE) -> tuple:
    """A ``(page_count, page_size)`` geometry sized so paged mode is never
    less capable than dense: enough pages to back every slot of every
    bucket at full length, plus one pinned scratch page per lane of the
    widest bucket. Real deployments size ``page_count`` to the HBM budget
    instead — the paged benchmark's requests-per-HBM-byte metric is about
    how few of these pages a live mix actually touches."""
    pages = sum(b.batch * (b.max_len // page_size) for b in policy.buckets)
    scratch = max(b.batch for b in policy.buckets)
    return (pages + scratch, page_size)


_LATENCY_WINDOW = 4096     # p50/p99 over the most recent N requests


@dataclasses.dataclass
class BucketMetrics:
    dispatches: int = 0
    requests: int = 0
    padded_slots: int = 0
    new_tokens: int = 0
    prefill_seconds: float = 0.0
    decode_seconds: float = 0.0
    # slot occupancy: every (slot, step) of every dispatch is a lane-step;
    # busy lane-steps carried a request's prompt or generated token. The
    # gap between them is exactly what continuous batching reclaims.
    slot_steps: int = 0
    busy_slot_steps: int = 0
    # bounded: a resident server must not grow one float per request
    latencies: Deque[float] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=_LATENCY_WINDOW))
    # per-slot idle steps, one entry per (dispatch, slot)
    slot_idle: Deque[int] = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=_LATENCY_WINDOW))
    # paged-KV gauges (snapshot of the shared PageAllocator after the
    # bucket's most recent dispatch; all zero in dense mode)
    pages_in_use: int = 0
    peak_pages: int = 0
    prefix_hits: int = 0

    def summary(self) -> Dict[str, float]:
        lat = list(self.latencies)
        idle = list(self.slot_idle)
        pct = quantile
        busy = self.prefill_seconds + self.decode_seconds
        return {
            "dispatches": self.dispatches,
            "requests": self.requests,
            "padded_slots": self.padded_slots,
            "new_tokens": self.new_tokens,
            "prefill_seconds": round(self.prefill_seconds, 4),
            "decode_seconds": round(self.decode_seconds, 4),
            "p50_latency_s": round(pct(lat, 0.50), 4),
            "p99_latency_s": round(pct(lat, 0.99), 4),
            "tokens_per_second": round(self.new_tokens / busy, 2)
            if busy else 0.0,
            "slot_steps": self.slot_steps,
            "busy_slot_fraction": round(
                self.busy_slot_steps / self.slot_steps, 4)
            if self.slot_steps else 0.0,
            "p50_slot_idle_steps": pct(idle, 0.50),
            "p99_slot_idle_steps": pct(idle, 0.99),
            "pages_in_use": self.pages_in_use,
            "peak_pages": self.peak_pages,
            "prefix_hits": self.prefix_hits,
        }


class ServeBatcher:
    """Admit DecodeRequests, dispatch bucketed groups on AOT executables.

    A thin consumer of :class:`repro.plan.ExecutionPlan`: the plan owns
    the mesh, the rule table, quantization decisions, and every compiled
    executable; the batcher only groups requests into buckets and drives
    the dispatch loop. Construct from an existing plan
    (``plan.make_batcher(...)``), or pass ``(cfg, mesh)`` and one is built
    internally — ``quantized=True`` then routes the decode LM head *and*
    MLP down-projection through the Pallas qmatmul paths, with shifts
    calibrated from the loaded weights (separately keyed in the cache).
    """

    def __init__(self, plan_or_cfg: Union["ExecutionPlan", ArchConfig],  # noqa: F821
                 mesh: Optional[Mesh] = None, *,
                 quantized: bool = False,
                 policy: Optional[BucketPolicy] = None,
                 cache: Optional[ExecutableCache] = None,
                 schedule: str = "fifo",
                 steps_per_dispatch: int = 1,
                 admission=None,
                 paged=None,
                 speculative: int = 0,
                 draft: Optional[str] = None):
        from repro.plan import ExecutionPlan, build_plan

        if isinstance(plan_or_cfg, ExecutionPlan):
            if mesh is not None:
                raise ValueError("pass either a plan or (cfg, mesh), "
                                 "not both")
            if quantized or cache is not None:
                raise ValueError("quantized/cache are plan decisions: set "
                                 "them in build_plan, not on the batcher")
            self.plan = plan_or_cfg
        else:
            if mesh is None:
                raise ValueError("ServeBatcher(cfg, mesh) needs a mesh")
            self.plan = build_plan(plan_or_cfg, None, mesh_spec=mesh,
                                   quantized=quantized, cache=cache)
        if schedule not in ("fifo", "continuous"):
            raise ValueError(
                f"schedule must be 'fifo' or 'continuous', got {schedule!r}")
        if steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}")
        if steps_per_dispatch > 1 and schedule != "continuous":
            raise ValueError(
                "steps_per_dispatch > 1 needs schedule='continuous' — the "
                "fifo path amortizes prompts through its prefill scan")
        if admission is not None and schedule != "continuous":
            raise ValueError(
                "admission policies need schedule='continuous' — the "
                "fixed-group fifo path has no boundary seam to apply them")
        self.schedule = schedule
        self.steps_per_dispatch = steps_per_dispatch
        self.policy = policy or BucketPolicy.debug()
        # paged KV: True -> auto geometry, int -> auto with that page
        # size, (page_count, page_size) -> exact. False must mean "dense",
        # not "auto with page_size=0": bool is an int subclass, so it has
        # to be caught before the page-size branch
        if paged is True:
            paged = auto_paged(self.policy)
        elif paged is False:
            paged = None
        elif isinstance(paged, int):
            paged = auto_paged(self.policy, page_size=paged)
        elif paged is not None:
            paged = tuple(paged)
        if paged is not None:
            if schedule != "continuous":
                raise ValueError(
                    "paged KV needs schedule='continuous' — only the "
                    "masked-decode path threads page tables")
            for b in self.policy.buckets:
                if b.max_len % paged[1]:
                    raise ValueError(
                        f"bucket {b.label}: max_len must be a multiple of "
                        f"page_size={paged[1]}")
        self.paged = paged
        # speculative decode: ``speculative`` = spec_k (draft tokens per
        # micro-run, must equal steps_per_dispatch), ``draft`` names the
        # draft model — "prefix:N" runs the first N layers of the target
        # as a self-speculative draft (default: half the stack). All
        # spec/paged constraints live in repro.serve.validation — the
        # scheduler re-checks the resolved tuple through the same module
        from repro.serve.validation import (
            resolve_speculative,
            validate_paged_spec,
        )

        spec = resolve_speculative(
            speculative, draft, schedule=schedule,
            steps_per_dispatch=steps_per_dispatch,
            n_layers=self.plan.cfg.n_layers, model=self.plan.model,
            family=self.plan.cfg.family)
        if spec is not None and paged is not None:
            validate_paged_spec(spec, paged, self.policy.buckets)
        self.spec = spec
        self.pool = StatePool(self.plan, paged=paged, spec=spec)
        self.params = None
        self.metrics: Dict[str, BucketMetrics] = {}
        self._pending: Deque[DecodeRequest] = collections.deque()
        self._pending_ids: set = set()
        # ids the scheduler's admission policy shed during the last run()
        # (EDF deadline misses): completed zero times, ids reusable
        self.last_shed: set = set()
        self._scheduler = None
        if schedule == "continuous":
            from repro.serve.scheduler import ContinuousScheduler

            self._scheduler = ContinuousScheduler(
                self.plan, self.policy, self.pool,
                steps_per_dispatch=steps_per_dispatch,
                admission=admission, spec=spec)

    @property
    def scheduler(self):
        """The ContinuousScheduler (None under schedule="fifo")."""
        return self._scheduler

    # plan views (kept as attributes of record for tests/telemetry)
    @property
    def cfg(self) -> ArchConfig:
        return self.plan.cfg

    @property
    def mesh(self) -> Mesh:
        return self.plan.mesh

    @property
    def rules(self):
        return self.plan.rules

    @property
    def model(self):
        return self.plan.model

    @property
    def cache(self) -> ExecutableCache:
        return self.plan.cache

    # -- parameters -----------------------------------------------------------

    def load_params(self, params) -> "ServeBatcher":
        """Install (calibrate quantization shifts, then shard) params."""
        self.params = self.plan.shard_params(params)
        return self

    def init_demo_params(self, seed: int = 0) -> "ServeBatcher":
        """Random sharded parameters (CLI demos, benchmarks, tests)."""
        self.params = self.plan.init_params(seed)
        return self

    # -- admission ------------------------------------------------------------

    def submit(self, request: DecodeRequest) -> str:
        self.policy.bucket_for(request.need_len)   # reject unservable now
        if request.request_id in self._pending_ids:
            # silently accepting a duplicate id would last-write-win in
            # the results dict and one caller would lose their tokens
            raise ValueError(
                f"duplicate request id {request.request_id!r}: a request "
                "with this id is already queued")
        if request.t_intake is None:
            request = dataclasses.replace(request,
                                          t_intake=time.perf_counter())
        self._pending_ids.add(request.request_id)
        self._pending.append(request)
        return request.request_id

    def cancel(self, request_id: str) -> bool:
        """Cancel a queued or in-flight request; returns True if known.

        A queued request is removed from the admission queue immediately
        (it never reaches a slot). An in-flight request — only possible
        under ``schedule="continuous"`` — is marked for the scheduler,
        which frees its slot (and wipes its state lanes) at the next
        micro-run boundary; it never appears in the results. The id
        becomes reusable the moment this returns True. Under
        ``schedule="fifo"`` a request already inside a dispatch group
        cannot be canceled (the group runs to completion) and this
        returns False.

        Call this from the dispatching thread only — between ``run()``
        calls, or mid-run from the scheduler's ``on_boundary`` hook (the
        queue is not locked against a concurrently draining ``run()``;
        an async front-end that feeds cancels from other threads is the
        ROADMAP follow-on).
        """
        if request_id not in self._pending_ids:
            return False
        for i, req in enumerate(self._pending):
            if req.request_id == request_id:
                del self._pending[i]
                self._pending_ids.discard(request_id)
                return True
        if self._scheduler is not None:
            self._scheduler.cancel(request_id)
            self._pending_ids.discard(request_id)
            return True
        return False

    def warmup(self, bucket: Bucket, prompt_len: int = 1) -> None:
        """Compile a bucket's executables ahead of traffic."""
        if self.schedule == "continuous":
            self._executable("masked_decode", bucket, 0)
        else:
            self._executable("prefill", bucket,
                             self._prefill_len(prompt_len))
            self._executable("decode", bucket, 0)

    # -- dispatch -------------------------------------------------------------

    def run(self) -> Dict[str, RequestResult]:
        """Drain the queue: group -> dispatch until empty.

        ``schedule="continuous"`` hands the whole queue to the
        :class:`~repro.serve.scheduler.ContinuousScheduler` (slot reuse
        inside in-flight dispatches); the default fixed-group FIFO path
        below is kept as the fallback.
        """
        if self.params is None:
            raise RuntimeError("no parameters loaded "
                               "(load_params / init_demo_params)")
        results: Dict[str, RequestResult] = {}
        if self._scheduler is not None:
            results = self._scheduler.run(self._pending, self.params,
                                          self.metrics)
            self.last_shed = self._scheduler.drain_shed()
            self._pending_ids.difference_update(self.last_shed)
        else:
            while self._pending:
                group, bucket = self._form_group()
                for res in self._dispatch(group, bucket):
                    results[res.request_id] = res
        self._pending_ids.difference_update(results)
        return results

    def _form_group(self):
        """FIFO head picks the bucket; fill with queued requests that fit."""
        first = self._pending.popleft()
        bucket = self.policy.bucket_for(first.need_len)
        group = [first]
        kept: Deque[DecodeRequest] = collections.deque()
        while self._pending and len(group) < bucket.batch:
            req = self._pending.popleft()
            if req.need_len <= bucket.max_len:
                group.append(req)
            else:
                kept.append(req)
        kept.extend(self._pending)
        self._pending = kept
        return group, bucket

    def _prefill_len(self, max_prompt: int) -> int:
        return max(_MIN_PREFILL, _pow2ceil(max_prompt))

    def _executable(self, kind: str, bucket: Bucket,
                    prefill_len: int) -> CachedExecutable:
        kw = {}
        if kind == "masked_decode" and self.paged is not None:
            kw["paged"] = self.paged
        if kind == "masked_decode" and self.spec is not None:
            kw["spec"] = self.spec
        return self.plan.serve_executable(
            kind, batch=bucket.batch, max_len=bucket.max_len,
            prefill_len=prefill_len,
            steps_per_dispatch=self.steps_per_dispatch
            if kind == "masked_decode" else 1, **kw)

    def _dispatch(self, group: List[DecodeRequest],
                  bucket: Bucket) -> List[RequestResult]:
        t0 = time.perf_counter()
        B, P = bucket.batch, self._prefill_len(
            max(len(r.prompt) for r in group))
        prefill = self._executable("prefill", bucket, P)
        decode = self._executable("decode", bucket, 0)

        prompt = np.zeros((B, P), np.int32)
        lengths = np.ones((B,), np.int32)       # inert slots: 1-token prompt
        for slot, req in enumerate(group):
            prompt[slot, :len(req.prompt)] = req.prompt
            lengths[slot] = len(req.prompt)

        _, _, prompt_sh, len_sh = prefill.bundle.in_shardings
        state = self.pool.acquire(B, bucket.max_len)
        tok_out, state = prefill.compiled(
            self.params, state,
            jax.device_put(prompt, prompt_sh),
            jax.device_put(lengths, len_sh))
        jax.block_until_ready(tok_out)
        t_prefill = time.perf_counter() - t0
        prefill_np = np.asarray(jax.device_get(tok_out))     # [B, P]

        # decode loop: everyone continues from position P in lockstep
        steps = max((r.max_new_tokens - (P - len(r.prompt) + 1)
                     for r in group), default=0)
        steps = max(steps, 0)
        tok_sh = decode.bundle.in_shardings[2]
        pos_sh = decode.bundle.in_shardings[3]
        argmax = self.plan.token_argmax(tok_sh)
        last = jax.device_put(tok_out[:, -1], tok_sh)
        decoded = []
        for t in range(steps):
            logits, state = decode.compiled(
                self.params, state, last,
                jax.device_put(np.int32(P + t), pos_sh))
            last = argmax(logits)
            decoded.append(last)
        if decoded:
            jax.block_until_ready(decoded[-1])
        decoded_np = (np.stack([np.asarray(jax.device_get(t))
                                for t in decoded], axis=1)
                      if decoded else np.zeros((B, 0), np.int32))
        self.pool.release(B, bucket.max_len, state)
        t_total = time.perf_counter() - t0

        results = []
        for slot, req in enumerate(group):
            li = len(req.prompt)
            gen = np.concatenate(
                [prefill_np[slot, li - 1:], decoded_np[slot]])
            results.append(RequestResult(
                request_id=req.request_id,
                tokens=[int(t) for t in gen[:req.max_new_tokens]],
                bucket=bucket.label,
                prefill_seconds=t_prefill,
                total_seconds=t_total,
            ))

        m = self.metrics.setdefault(bucket.label, BucketMetrics())
        m.dispatches += 1
        m.requests += len(group)
        m.padded_slots += B - len(group)
        m.new_tokens += sum(len(r.tokens) for r in results)
        m.prefill_seconds += t_prefill
        m.decode_seconds += t_total - t_prefill
        m.latencies.extend([t_total] * len(group))
        # slot occupancy: the group runs P prefill + `steps` decode
        # positions in lockstep; a slot is busy while it still carries
        # prompt or requested tokens, idle from its finish to group end
        span = P + steps
        m.slot_steps += span * B
        for slot in range(B):
            busy_slot = 0
            if slot < len(group):
                req, res = group[slot], results[slot]
                busy_slot = min(span, len(req.prompt) + len(res.tokens) - 1)
            m.busy_slot_steps += busy_slot
            m.slot_idle.append(span - busy_slot)
        return results

    # -- observability --------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        out = {
            "cache": self.cache.stats(),
            "pool": self.pool.stats(),
            "buckets": {k: m.summary() for k, m in self.metrics.items()},
        }
        if self._scheduler is not None:
            out["scheduler"] = self._scheduler.stats()
        if getattr(self.pool, "allocator", None) is not None:
            out["paged"] = self.pool.allocator.stats()
        return out
