"""The host-stage reduction, on hand-built traces with exact answers, on
the recorded v5e probe trace, and through ``stages.py`` at smoke size on
the CPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace as TR  # noqa: E402
import hostspans as HS  # noqa: E402

MS = 1e6          # ns in a millisecond: the hand-built times are in ms


def _sp(name, s, e, **stats):
    return HS.StatSpan(name, s * MS, e * MS, stats)


def _round(t0=0.0):
    """One 1,000 ms round: every stage, one sub-batch client."""
    return [
        _sp("bench.round", t0, t0 + 1000),
        _sp("round", t0 + 10, t0 + 990),
        _sp("prepare", t0 + 10, t0 + 100),
        _sp("cohort_dispatch", t0 + 100, t0 + 110),
        _sp("cohort.pull", t0 + 110, t0 + 400, nbytes=2_000_000),
        _sp("upload", t0 + 400, t0 + 800),
        _sp("client", t0 + 400, t0 + 500),
        _sp("fallback", t0 + 420, t0 + 480, steps=1, rows=5),
        _sp("client", t0 + 500, t0 + 600),
        _sp("arbitrate", t0 + 800, t0 + 850, pruned=3),
        _sp("evaluate", t0 + 850, t0 + 950),
    ]


def _trace(spans, busy):
    return TR.Trace(sorted(spans, key=lambda sp: sp.start), [],
                    {0: [(s * MS, e * MS) for s, e in busy]}, 0.0)


BUSY = [(105, 390), (430, 470), (900, 920)]


@pytest.fixture
def one_round():
    return _trace(_round(), BUSY)


def test_segments_name_the_innermost_open_span():
    segs = HS.segments(_round(), 0, 1000 * MS)
    got = [(a / MS, b / MS, n) for a, b, n in segs]
    assert got == [(0, 10, "bench.round"), (10, 100, "prepare"),
                   (100, 110, "cohort_dispatch"), (110, 400, "cohort.pull"),
                   (400, 420, "client"), (420, 480, "fallback"),
                   (480, 600, "client"), (600, 800, "upload"),
                   (800, 850, "arbitrate"), (850, 950, "evaluate"),
                   (950, 990, "round"), (990, 1000, "bench.round")]


def test_segments_of_overlapping_spans_and_gaps():
    spans = [_sp("a", 0, 10), _sp("b", 5, 20), _sp("c", 30, 40)]
    got = [(a / MS, b / MS, n) for a, b, n in
           HS.segments(spans, 0, 50 * MS)]
    # a (10 ms) is shorter than b (15 ms) while both are open
    assert got == [(0, 10, "a"), (10, 20, "b"), (20, 30, HS.NO_SPAN),
                   (30, 40, "c"), (40, 50, HS.NO_SPAN)]


def test_idle_split_across_the_spans_it_straddles(one_round):
    by = {k: v / MS for k, v in
          HS.idle_by_span(one_round, 0, 1000 * MS).items()}
    # idle [0,105) straddles bench.round, prepare and cohort_dispatch;
    # [390,430) cohort.pull, client and fallback; [470,900) five spans;
    # [920,1000) evaluate, round and bench.round
    assert by == {"bench.round": 20, "prepare": 90, "cohort_dispatch": 5,
                  "cohort.pull": 10, "client": 140, "fallback": 20,
                  "upload": 200, "arbitrate": 50, "evaluate": 80,
                  "round": 40}
    assert sum(by.values()) == 1000 - (285 + 40 + 20)
    assert HS.idle_unattributed_share(one_round, 0, 1000 * MS) \
        == pytest.approx(100 * 60 / 655)


def test_readings_of_one_round(one_round):
    r = HS.readings(one_round, 0, 1000 * MS, 1)
    assert r == pytest.approx({
        "host_ms.prepare": 90, "host_ms.wait": 290,
        "host_ms.upload": 340, "host_ms.fallback": 60,
        "host_ms.aggregate": 50, "host_ms.eval": 100,
        "host_ms.dispatch": 10, "d2h_mb_per_round": 2.0,
        "coverage_pct": 94.0,
        "idle_unattributed_share": 100 * 60 / 655})


def test_readings_average_over_rounds_and_clip_to_the_window():
    spans = _round(0) + _round(1000)
    busy = BUSY + [(s + 1000, e + 1000) for s, e in BUSY]
    tr = _trace(spans, busy)
    r = HS.readings(tr, 0, 2000 * MS, 2)
    assert r["host_ms.wait"] == pytest.approx(290)
    assert r["d2h_mb_per_round"] == pytest.approx(2.0)
    # a window that ends inside the second round's pull counts its part
    # of the pull, and the stats of the spans that start in it
    half = HS.readings(tr, 0, 1300 * MS, 1)
    assert half["host_ms.wait"] == pytest.approx(290 + 190)
    assert half["d2h_mb_per_round"] == pytest.approx(4.0)


def test_unions_and_differences():
    tr = TR.Trace([_sp("x", 0, 10), _sp("x", 5, 20), _sp("y", 8, 12),
                   _sp("x", 30, 40)], [], {}, 0.0)
    x = HS.union(tr, "x", 0, 35 * MS)
    assert [(s / MS, e / MS) for s, e in x] == [(0, 20), (30, 35)]
    y = HS.union(tr, "y", 0, 35 * MS)
    assert HS.minus(x, y) / MS == pytest.approx(25 - 4)
    assert HS.stat_sum(tr, "x", "nbytes", 0, 35 * MS) == 0.0
    # no device planes: no idle intervals, no idle share
    assert HS.idle_intervals(tr, 0, 35 * MS) == []
    assert HS.idle_unattributed_share(tr, 0, 35 * MS) is None
    # a chip that ran nothing is idle throughout
    idle = _trace(tr.spans, [])
    assert HS.idle_intervals(idle, 0, 35 * MS) == [(0, 35 * MS)]
    assert HS.idle_unattributed_share(idle, 0, 35 * MS) \
        == pytest.approx(100 * 10 / 35)      # 20-30 ms: no span


def test_load_keeps_devtrace_reduction_and_adds_stats():
    probe = str(HERE / "testdata" / "v5e_probe.xplane.pb")
    tr = HS.load(probe, {"outer.call"})
    ref = TR.load(probe, {"outer.call"})
    assert [(sp.name, sp.start, sp.end) for sp in tr.spans] \
        == [(sp.name, sp.start, sp.end) for sp in ref.spans]
    assert all(isinstance(sp, HS.StatSpan) and sp.stats == {}
               for sp in tr.spans)
    assert len(tr.launches) == len(ref.launches) == 9
    assert tr.offset_ns == ref.offset_ns
    lo, hi = tr.spans[0].start, tr.spans[-1].end
    by = HS.idle_by_span(tr, lo, hi)
    # every idle moment of the probe window lies in a call or between them
    assert set(by) <= {"outer.call", HS.NO_SPAN}
    idle = sum(e - s for s, e in HS.idle_intervals(tr, lo, hi))
    assert sum(by.values()) == pytest.approx(idle)
    assert idle == pytest.approx(hi - lo - TR.busy_ns(tr, lo, hi))


def test_stage_tool_at_smoke_size():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "stages.py"), "--workload",
         "fedara.distilbert.dir0.1", "--seed", str(2**31 + 5),
         "--seconds", "1", "--mode", "traced", "--checks", "0", "--smoke"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    st = out["stages"]
    assert out["mode"] == "traced" and out["window"]["rounds"] > 0
    assert st["host_ms.wait"] > 0 and st["host_ms.prepare"] > 0
    assert st["d2h_mb_per_round"] > 0
    assert 50 < st["coverage_pct"] <= 100
    # the tracer has no reader in a traced run: no drift computed
    assert set(out["reader_work"]) == {"_emit_drift", "record_ranks",
                                       "sample_memory", "shape_signature"}
    assert out["reader_work"]["record_ranks"]["calls"] == 0
