"""Async streaming serve front-end over the continuous scheduler.

``ServeBatcher.run()`` is a blocking drain: admissions, cancels, and
results all live on the dispatching thread, which caps the system at
benchmark-shaped traffic. :class:`AsyncServeServer` turns it into a
resident serving loop without touching the compiled step or the
scheduler's determinism:

* **requests arrive concurrently** — ``stream()`` / ``generate()`` are
  called from any number of asyncio tasks; submissions land on a
  thread-safe intake queue and are fed to the batcher at micro-run
  boundaries (the scheduler's ``on_boundary`` hook), so a request that
  arrives while a dispatch is in flight is admitted into it mid-run,
  exactly like the continuous scheduler promises;
* **tokens stream back per micro-run boundary** — the scheduler's
  ``on_tokens`` hook fetches each micro-run's ``[k, slots]`` block at
  the boundary and routes every live request its newly generated tokens;
  ``stream()`` is an async generator yielding them as they arrive
  (time-to-first-token is a few micro-runs, not a full drain). Under
  speculative lanes (``speculative=k`` on the batcher) the deltas carry
  only ACCEPTED tokens — the host commits the verified draft prefix at
  each boundary before publishing, so a client never sees a token a
  rollback would retract, and greedy streams stay bit-exact with plain
  continuous decode;
* **client disconnect maps to cancellation** — a consumer that abandons
  its stream (``break``, task cancelled, connection dropped) enqueues a
  cancel that :meth:`ServeBatcher.cancel` applies at the next boundary:
  the slot is freed, its state lanes wiped, and the tokens never leave
  the device;
* **deadline shedding surfaces as** :class:`RequestShed` — when the
  batcher's admission policy (``repro.serve.policy``) drops a request
  whose deadline already passed, the waiting stream raises instead of
  hanging. Under the async server the scheduler's clock is
  ``time.monotonic``, so ``DecodeRequest.deadline`` is wall-clock
  seconds.

One worker thread owns ALL batcher/scheduler calls (their documented
single-thread contract): it blocks on intake when idle and drives
``batcher.run()`` when requests are queued; the asyncio side only ever
touches its own per-request queues. Every hot-path executable is the
same warm ``masked_decode`` the blocking path uses — streaming adds one
host fetch per micro-run and ZERO lowerings (pinned in
``tests/test_server.py`` along with token parity against ``run()``).

Each request is stamped (``DecodeRequest.t_intake``) when the server
takes it in, so the scheduler's ``serve.admit_request`` span can say how
long it queued; the worker's wait for work with nothing pending is the
span ``serve.idle``. The scheduler's own spans and counters name the
rest of the worker's time (``repro.serve.scheduler``).
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import queue
import threading
import time
from typing import AsyncIterator, Deque, Dict, List, Optional

import jax

from repro.serve.batcher import (
    DecodeRequest,
    RequestResult,
    ServeBatcher,
    quantile,
)

_TTFT_WINDOW = 4096      # bounded: a resident server must not grow per-req


class RequestShed(RuntimeError):
    """The admission policy dropped this request (deadline already
    missed); it consumed no slot steps and produced no tokens."""


@dataclasses.dataclass
class _Stream:
    """Per-request plumbing between the worker thread and one consumer."""

    request: DecodeRequest
    queue: "asyncio.Queue"
    t_submit: float
    t_first: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    outcome: Optional[str] = None    # done | shed | cancelled | error
    result: Optional[RequestResult] = None


class AsyncServeServer:
    """Asyncio front-end for a continuous-schedule :class:`ServeBatcher`.

    Usage::

        server = AsyncServeServer(batcher)     # schedule="continuous"
        async with server:
            async for tok in server.stream(DecodeRequest("r0", [1, 2])):
                ...                            # per-micro-run tokens
            res = await server.generate(DecodeRequest("r1", [3, 4]))

    ``poll_s`` bounds the idle wake-up latency (how quickly the worker
    notices the first request of a quiet period); once traffic flows,
    admission latency is micro-run boundaries, not polls.
    """

    def __init__(self, batcher: ServeBatcher, *, poll_s: float = 0.005):
        if batcher.scheduler is None:
            raise ValueError(
                "AsyncServeServer needs schedule='continuous' — the "
                "fixed-group fifo path has no boundary seam to stream "
                "from or cancel into")
        self.batcher = batcher
        self.poll_s = poll_s
        self._intake: "queue.Queue" = queue.Queue()
        self._streams: Dict[str, _Stream] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._stop_flag = threading.Event()
        # aggregate client-side latency stats (bounded)
        self.ttfts: Deque[float] = collections.deque(maxlen=_TTFT_WINDOW)
        self.totals: Deque[float] = collections.deque(maxlen=_TTFT_WINDOW)
        self.outcomes: Dict[str, int] = collections.defaultdict(int)

    # -- lifecycle ------------------------------------------------------------

    async def start(self) -> "AsyncServeServer":
        if self._thread is not None:
            raise RuntimeError("server already started")
        self._loop = asyncio.get_running_loop()
        self._stop_flag.clear()
        sched = self.batcher.scheduler
        sched.on_boundary = self._boundary_hook
        sched.on_tokens = self._emit_tokens
        sched.on_shed = self._notify_shed
        # wall-clock deadlines for the admission policy under async serving
        sched.clock = time.monotonic
        self._thread = threading.Thread(target=self._worker,
                                        name="serve-worker", daemon=True)
        self._thread.start()
        return self

    async def stop(self) -> None:
        """Drain nothing, stop now: in-flight streams end with an error."""
        if self._thread is None:
            return
        self._stop_flag.set()
        self._intake.put(("stop", None))
        await asyncio.get_running_loop().run_in_executor(
            None, self._thread.join)
        self._thread = None
        sched = self.batcher.scheduler
        sched.on_boundary = None
        sched.on_tokens = None
        sched.on_shed = None
        sched.clock = None
        for rid in list(self._streams):
            self._post(rid, ("error",
                             RuntimeError("server stopped mid-stream")))

    async def __aenter__(self) -> "AsyncServeServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # -- client API -----------------------------------------------------------

    def _register(self, request: DecodeRequest) -> _Stream:
        if self._thread is None:
            raise RuntimeError("server not started")
        rid = request.request_id
        if rid in self._streams:
            raise ValueError(f"duplicate request id {rid!r}: a stream "
                             "with this id is already open")
        request = dataclasses.replace(request, t_intake=time.perf_counter())
        s = _Stream(request, asyncio.Queue(), t_submit=time.monotonic())
        self._streams[rid] = s
        self._intake.put(("submit", request))
        return s

    async def _consume(self, s: _Stream) -> AsyncIterator[int]:
        rid = s.request.request_id
        try:
            while True:
                kind, payload = await s.queue.get()
                if kind == "tokens":
                    now = time.monotonic()
                    if s.t_first is None:
                        s.t_first = now
                        self.ttfts.append(now - s.t_submit)
                    s.tokens.extend(payload)
                    for tok in payload:
                        yield tok
                elif kind == "done":
                    s.outcome = "done"
                    s.result = payload
                    self.totals.append(time.monotonic() - s.t_submit)
                    return
                elif kind == "shed":
                    s.outcome = "shed"
                    raise RequestShed(
                        f"{rid}: deadline passed before admission")
                else:                      # "error"
                    s.outcome = "error"
                    raise payload
        finally:
            self._streams.pop(rid, None)
            if s.outcome is None:          # consumer walked away
                s.outcome = "cancelled"
                self._intake.put(("cancel", rid))
            self.outcomes[s.outcome] += 1

    async def stream(self, request: DecodeRequest) -> AsyncIterator[int]:
        """Submit and yield tokens as micro-run boundaries produce them.

        Abandoning the iterator (``break`` / cancellation / disconnect)
        cancels the request at the next boundary. Raises
        :class:`RequestShed` if the admission policy sheds it, and
        re-raises submission errors (duplicate id, unservable shape).
        """
        gen = self._consume(self._register(request))
        try:
            async for tok in gen:
                yield tok
        finally:
            # a consumer that abandons the outer iterator must close the
            # inner one NOW (not at GC) so the cancel reaches the intake
            # queue before the next micro-run boundary
            await gen.aclose()

    async def generate(self, request: DecodeRequest) -> RequestResult:
        """Consume the whole stream; returns the batcher's
        :class:`RequestResult` — the same record the blocking ``run()``
        path yields, so end-to-end parity is checkable. The streamed
        tokens and the result's tokens are the same list (asserted in
        tests, not here)."""
        s = self._register(request)
        async for _ in self._consume(s):
            pass
        return s.result

    # -- worker thread --------------------------------------------------------

    def _worker(self) -> None:
        batcher = self.batcher
        while True:
            # run() returned with the queue drained: wait for work
            with jax.profiler.TraceAnnotation("serve.idle"):
                try:
                    item = self._intake.get(timeout=self.poll_s)
                except queue.Empty:
                    item = None
            if item is not None:
                self._apply(item)
            self._drain_intake()
            if self._stop_flag.is_set():
                return
            if batcher._pending:
                results = batcher.run()
                for rid, res in results.items():
                    self._finish(rid, res)

    def _drain_intake(self) -> None:
        while True:
            try:
                self._apply(self._intake.get_nowait())
            except queue.Empty:
                return

    def _apply(self, item) -> None:
        kind, payload = item
        if kind == "submit":
            try:
                self.batcher.submit(payload)
            except Exception as exc:      # duplicate id, unservable shape
                self._post(payload.request_id, ("error", exc))
        elif kind == "cancel":
            self.batcher.cancel(payload)
        # "stop" only wakes the worker; the flag does the rest

    def _boundary_hook(self, pos, slots) -> None:
        # every micro-run boundary: let concurrently-arrived submissions
        # join the in-flight dispatch and disconnects cancel into it
        self._drain_intake()

    # -- worker -> asyncio handoff -------------------------------------------

    def _post(self, rid: str, event) -> None:
        s = self._streams.get(rid)
        if s is None or self._loop is None:
            return
        try:
            self._loop.call_soon_threadsafe(s.queue.put_nowait, event)
        except RuntimeError:
            pass                           # loop already closed (shutdown)

    def _emit_tokens(self, deltas: Dict[str, List[int]]) -> None:
        for rid, toks in deltas.items():
            self._post(rid, ("tokens", toks))

    def _notify_shed(self, rid: str) -> None:
        self._post(rid, ("shed", None))

    def _finish(self, rid: str, res: RequestResult) -> None:
        self._post(rid, ("done", res))

    # -- observability --------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        def pct(vals, p):
            # nearest-rank with small-sample clamping — the shared serve
            # definition (the old int(p * n) index overshot: p50 TTFT of
            # a two-request smoke run reported the SLOWER request)
            return round(quantile(vals, p), 4)

        return {
            "open_streams": len(self._streams),
            "outcomes": dict(self.outcomes),
            "p50_ttft_s": pct(self.ttfts, 0.50),
            "p99_ttft_s": pct(self.ttfts, 0.99),
            "p50_total_s": pct(self.totals, 0.50),
            "p99_total_s": pct(self.totals, 0.99),
            "scheduler": self.batcher.scheduler.stats(),
        }
