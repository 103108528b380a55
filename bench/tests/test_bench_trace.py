"""The trace reduction: interval arithmetic, and every number the harness
reads from a small serving trace recorded on a TPU v5e (one chip,
``bench/tests/data/serve_small.xplane.pb``; the reduction's own readings
of it on the chip are in ``serve_small_summary.json`` beside it)."""
import json
import pathlib

import pytest

import cells  # noqa: F401  (puts bench/ on the path)
from tracereduce import Trace, clip, covered, gaps, union

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_touching_intervals():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        (0, 4), (5, 7), (8, 9)]


def test_clip_and_gaps_partition_the_window():
    busy = clip(union([(-5, 2), (4, 6), (9, 20)]), 0, 10)
    assert busy == [(0, 2), (4, 6), (9, 10)]
    idle = gaps(busy, 0, 10)
    assert idle == [(2, 4), (6, 9)]
    assert covered(busy) + covered(idle) == 10


@pytest.fixture(scope="module")
def recorded():
    tr = Trace.from_file(DATA / "serve_small.xplane.pb")
    want = json.loads((DATA / "serve_small_summary.json").read_text())
    return tr, want


def test_recorded_trace_has_a_device_and_harness_spans(recorded):
    tr, want = recorded
    assert tr.device_ids() == [0]
    assert len(tr.spans) == want["spans"] > 0
    assert len(tr.devices[0]["ops"]) == want["ops"]
    assert len(tr.devices[0]["modules"]) == want["modules"]


def test_recorded_trace_readings(recorded):
    tr, want = recorded
    assert tr.window_s() == pytest.approx(want["window_s"], rel=1e-12)
    assert tr.busy_s(0) == pytest.approx(want["busy_s"], rel=1e-12)
    assert 0 < tr.busy_s(0) < tr.window_s()
    assert tr.module_count(0, "_active_apply") == want["active_n"] > 0
    assert tr.module_count(0, "_collab_apply") == want["collab_n"]
    assert tr.module_s(0, "_active_apply") == pytest.approx(
        want["active_s"], rel=1e-12)


def test_busy_is_the_union_of_operations(recorded):
    tr, _ = recorded
    lo, hi = tr.window()
    # a sweep that counts open operations, independent of union()
    edges = []
    for s, e, _ in tr.devices[0]["ops"]:
        if e > lo and s < hi:
            edges += [(max(s, lo), 1), (min(e, hi), -1)]
    edges.sort(key=lambda te: (te[0], -te[1]))
    busy, depth, t0 = 0.0, 0, None
    for t, step in edges:
        if depth == 0 and step == 1:
            t0 = t
        depth += step
        if depth == 0:
            busy += t - t0
    assert tr.busy_s(0) == pytest.approx(busy * 1e-9, rel=1e-9)


def test_idle_gaps_add_up_to_the_idle_time(recorded):
    tr, want = recorded
    idle = tr.idle_gaps(0)
    assert [n for n, _ in idle] == [n for n, _ in want["idle"]]
    assert sum(t for _, t in idle) == pytest.approx(
        tr.window_s() - tr.busy_s(0), rel=1e-9)
    top = tr.top_ops([0], 3)
    assert [t for _, t in top] == sorted((t for _, t in top), reverse=True)
