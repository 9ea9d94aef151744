"""The readers of the scheduler's boundary and admission counters, on
hand-made window deltas, and on the counters of a program that lacks
them (as the benchmark's parent commits do), where they read None."""

import bench_paths  # noqa: F401  (before the benchmark's modules)

import types
from pathlib import Path

import pytest

import catalog

ROOT = Path(__file__).resolve().parents[2]
BASE = {"micro_runs": 10, "slot_steps": 320, "idle_slot_steps": 32}


def _ctx(start, end):
    return types.SimpleNamespace(counters_start={"scheduler": start},
                                 counters_end={"scheduler": end})


def _read(name, ctx):
    return catalog.metric_reader(ROOT, name)(ctx)


def test_boundary_ms_is_boundary_seconds_per_micro_run():
    a = dict(BASE, boundary_seconds=0.5)
    b = dict(BASE, micro_runs=410, boundary_seconds=4.5)
    assert _read("boundary_ms", _ctx(a, b)) == pytest.approx(10.0)


def test_admit_stall_share_is_stalls_over_chances():
    a = dict(BASE, admit_chances=7, admit_stalls=2, admit_stalls_pages=0)
    b = dict(BASE, admit_chances=207, admit_stalls=52,
             admit_stalls_pages=5)
    assert _read("admit_stall_share", _ctx(a, b)) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["boundary_ms", "admit_stall_share"])
def test_reads_none_without_the_counters_or_the_work(name):
    assert _read(name, _ctx(dict(BASE), dict(BASE, micro_runs=400))) is None
    same = dict(BASE, boundary_seconds=1.0, admit_chances=3,
                admit_stalls=1, admit_stalls_pages=0)
    assert _read(name, _ctx(same, dict(same))) is None
