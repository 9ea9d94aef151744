"""The serving loop's host spans and admission counters.

Spans (``jax.profiler.TraceAnnotation``) land in the profiler's trace; each
test takes one CPU trace of a scheduler run over the host fakes of
``_serve_stubs`` (or of the tiny model behind ``AsyncServeServer``) and
reads the ``serve.*`` events back. Pinned here:

* one ``serve.boundary`` per micro-run, plus the last of each dispatch,
  which launches nothing; each boundary's phases nest inside it, and the
  blocking token fetch lies between boundaries;
* one ``serve.admit_request`` per admission, carrying ``rid``, ``lane`` and
  ``queue_us >= 0`` where the request was stamped at intake;
* ``admit_chances`` / ``admit_stalls`` / ``admit_stalls_pages`` count a
  head that needs more positions than the dispatch has left apart from a
  page pool too small for it;
``tests/test_server.py`` pins the intake stamp through ``AsyncServeServer``
on the tiny model, and that its tokens do not depend on whether the
profiler is on.
"""

import collections
import time

import pytest
from _serve_stubs import (
    HostPlan,
    NullPool,
    PagedNullPool,
    check_invariants,
    named,
    traced,
)

from repro.serve import Bucket, BucketPolicy, DecodeRequest
from repro.serve.scheduler import ContinuousScheduler

CHILDREN = ("serve.emit", "serve.intake", "serve.admit", "serve.schedule",
            "serve.launch")


def inside(sp, outer):
    return any(o[1] <= sp[1] and sp[2] <= o[2] for o in outer)


def host_run(reqs, batch=2, max_len=16, k=4, pool=None, stream=True):
    sched = ContinuousScheduler(HostPlan(),
                                BucketPolicy([Bucket(max_len, batch)]),
                                pool or NullPool(), steps_per_dispatch=k)
    streamed = collections.defaultdict(list)
    if stream:
        sched.on_tokens = lambda deltas: [
            streamed[r].extend(t) for r, t in deltas.items()]
    sched.on_boundary = lambda pos, slots: None
    results = sched.run(collections.deque(reqs), None, {})
    return sched, results, streamed


def _reqs(lengths, stamp=False):
    t = time.perf_counter() if stamp else None
    return [DecodeRequest(f"h{i}", [1 + (i + j) % 7 for j in range(p)],
                          max_new_tokens=n, t_intake=t)
            for i, (p, n) in enumerate(lengths)]


@pytest.mark.parametrize("stream", [True, False])
def test_one_boundary_per_micro_run_with_its_phases_inside(tmp_path,
                                                           stream):
    reqs = _reqs([(3, 5), (2, 9), (5, 2), (2, 12), (4, 4)])
    (sched, results, streamed), spans = traced(
        tmp_path, lambda: host_run(reqs, stream=stream))
    check_invariants(sched, reqs, results, 4)
    st = sched.stats()
    runs, dispatches = st["micro_runs"], st["dispatches"]
    assert dispatches >= 2          # the queue outlasts the first dispatch
    bounds = named(spans, "serve.boundary")
    assert len(bounds) == runs + dispatches
    assert len(named(spans, "serve.launch")) == runs
    assert len(named(spans, "serve.schedule")) == runs
    assert len(named(spans, "serve.dispatch")) == dispatches
    assert len(named(spans, "serve.drain")) == dispatches
    for name in CHILDREN:
        for sp in named(spans, name):
            assert inside(sp, bounds), (name, sp)
    # the frees at each boundary, and the drain's at the dispatch's end
    frees = named(spans, "serve.free")
    assert sum(not inside(sp, bounds) for sp in frees) == dispatches
    fetches = named(spans, "serve.fetch")
    assert len(fetches) == (runs if stream else 0)
    assert not any(inside(sp, bounds) for sp in fetches)
    assert all(inside(sp, named(spans, "serve.dispatch"))
               for sp in bounds + fetches)
    # the counter times the same sections as the spans
    span_s = sum(e - s for _, s, e, _ in bounds) * 1e-9
    assert st["boundary_seconds"] == pytest.approx(span_s, rel=0.25,
                                                   abs=2e-3)
    if stream:
        assert {r: res.tokens for r, res in results.items()} == \
            dict(streamed)


def test_one_admit_request_span_per_admission(tmp_path):
    reqs = _reqs([(3, 5), (2, 9), (5, 2), (2, 12), (4, 4)], stamp=True)
    (sched, _, _), spans = traced(tmp_path, lambda: host_run(reqs))
    admits = named(spans, "serve.admit_request")
    assert len(admits) == sched.stats()["admissions"] == len(reqs)
    assert sorted(sp[3]["rid"] for sp in admits) == \
        sorted(r.request_id for r in reqs)
    assert all(sp[3]["queue_us"] >= 0 and sp[3]["lane"] in (0, 1)
               for sp in admits)
    assert all(inside(sp, named(spans, "serve.admit")) for sp in admits)
    # a request nobody stamped carries no queue_us
    (_, _, _), spans = traced(tmp_path / "unstamped",
                              lambda: host_run(_reqs([(2, 3)])))
    sp, = named(spans, "serve.admit_request")
    assert sp[3]["rid"] == "h0" and "queue_us" not in sp[3]


@pytest.mark.parametrize("lengths,page_count,counts", [
    # h0 and h1 fill both lanes at 0; h1 leaves at 4, but h2 needs 14
    # positions, which no later boundary of the dispatch has (4, 8, 12:
    # three stalls, none for pages); the next dispatch admits it at 0
    ([(2, 10), (2, 3), (2, 13)], None, (5, 3, 0)),
    # two scratch pages and three to lease: h0 takes all three at 0, so
    # h1's positions fit at 4 but its pages do not; at 8 and 12 its 9
    # positions no longer fit; the next dispatch admits it at 0
    ([(2, 8), (2, 8)], 5, (5, 3, 1)),
], ids=["out_of_positions", "out_of_pages"])
def test_admission_stall_counters(lengths, page_count, counts):
    reqs = _reqs(lengths)
    pool = PagedNullPool(page_count, 4) if page_count else None
    sched, results, _ = host_run(reqs, pool=pool)
    check_invariants(sched, reqs, results, 4)
    st = sched.stats()
    assert st["dispatches"] == 2
    assert (st["admit_chances"], st["admit_stalls"],
            st["admit_stalls_pages"]) == counts
