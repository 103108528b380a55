"""The reduction of the program's own spans (``bench/spanreduce.py``) and
the readers built on it, on a synthetic profile whose planes, lines and
events are plain objects, and on the recorded serving trace, which has no
program spans."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from cells import BENCH, run
from spanreduce import ProgramSpans
from tracereduce import Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
NEW = ["idle_psi_ms.fit", "idle_stages_ms.fit", "idle_lanes_ms.fit",
       "engine_g1_ms.fit", "engine_g3_ms.fit", "idle_lane_prep_ms.scale",
       "idle_lane_shard_ms.scale"]


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end))


def device(n, ops, modules):
    return NS(name=f"/device:TPU:{n}", lines=[
        NS(name="XLA Ops", events=[ev("fusion", s, e) for s, e in ops]),
        NS(name="XLA Modules", events=[ev(m, s, e) for m, s, e in modules])])


# Two fits (harness spans) in [0, 2000] ns.  Chip 0 is busy 650 ns of it;
# chip 1 runs only the second fit's engine.  One program span lies after
# the window and is left out.
HOST = [
    ev("bench.protocol_fit", 0, 1000), ev("bench.protocol_fit", 1100, 2000),
    ev("apcvfl.psi", 10, 200),
    ev("apcvfl.g1", 250, 950),
    ev("apcvfl.lanes.prep", 260, 320),
    ev("apcvfl.lanes.launch", 330, 340),
    ev("apcvfl.lanes.sync", 340, 920),
    ev("apcvfl.lanes.unstack", 920, 940),
    ev("apcvfl.g3", 1150, 1900),
    ev("apcvfl.lanes.sync", 1200, 1600),
    ev("apcvfl.psi", 2100, 2200),
    ev("PjitFunction(run_fit_k)", 330, 335),
]
CHIP0 = device(0, [(50, 100), (300, 400), (700, 900), (1200, 1500)],
               [("jit_other", 50, 100), ("jit_run_fit_k", 300, 400),
                ("jit_run_fit_k", 700, 900), ("jit_run_fit_k", 1200, 1500)])
CHIP1 = device(1, [(1200, 1500)], [("jit_run_fit_k", 1200, 1500)])
# chip 0's idle (ns) by innermost span, worked out by hand
IDLE0 = {"protocol_fit": 10 + 50 + 50 + 50 + 100, "outside_spans": 100,
         "apcvfl.psi": 40 + 100, "apcvfl.g1": 10 + 10,
         "apcvfl.lanes.prep": 40, "apcvfl.lanes.sync": 320 + 100,
         "apcvfl.lanes.unstack": 20, "apcvfl.g3": 50 + 300}


def profile(host=HOST, chips=(CHIP0, CHIP1)):
    return NS(planes=[NS(name="/host:CPU", lines=[NS(name="python",
                                                     events=host)]),
                      *chips])


@pytest.fixture
def spans():
    p = profile()
    return ProgramSpans(Trace(p), p)


def test_window_ignores_program_spans(spans):
    tr = spans.trace
    assert tr.window() == (0.0, 2000.0)
    assert [n for _, _, n in tr.spans] == ["bench.protocol_fit"] * 2
    assert [n for _, _, n in spans.spans].count("apcvfl.psi") == 1
    assert "PjitFunction(run_fit_k)" not in spans.names()


def test_idle_is_split_at_span_boundaries_by_innermost_span(spans):
    idle = spans.idle_s(0)
    assert idle == pytest.approx({n: t * 1e-9 for n, t in IDLE0.items()})
    assert spans.idle_s(1) == pytest.approx({
        "protocol_fit": 260e-9, "outside_spans": 100e-9,
        "apcvfl.psi": 190e-9, "apcvfl.g1": 30e-9,
        "apcvfl.lanes.prep": 60e-9, "apcvfl.lanes.launch": 10e-9,
        "apcvfl.lanes.sync": 580e-9 + 100e-9, "apcvfl.lanes.unstack": 20e-9,
        "apcvfl.g3": 350e-9})


@pytest.mark.parametrize("dev", [0, 1])
def test_split_idle_adds_up_to_the_idle_time(spans, dev):
    tr = spans.trace
    idle = spans.idle_s(dev)
    inside = sum(t for n, t in idle.items() if n.startswith("apcvfl."))
    outside = sum(t for n, t in idle.items() if not n.startswith("apcvfl."))
    assert inside + outside == pytest.approx(
        tr.window_s() - tr.busy_s(dev), rel=1e-12)


def test_self_time_leaves_out_nested_spans(spans):
    assert spans.self_s("apcvfl.g1") == pytest.approx(30e-9)
    assert spans.self_s("apcvfl.lanes.sync") == pytest.approx(980e-9)
    assert spans.self_s("apcvfl.g3") == pytest.approx(350e-9)
    assert spans.self_s("apcvfl.psi") == pytest.approx(190e-9)
    assert spans.self_s("protocol_fit") == pytest.approx(
        2000e-9 - 100e-9 - 190e-9 - 700e-9 - 750e-9)


def test_module_time_is_clipped_to_a_span(spans):
    assert spans.module_in_s(0, "run_fit_k", "apcvfl.g1") == pytest.approx(
        300e-9)
    assert spans.module_in_s(0, "run_fit_k", "apcvfl.g3") == pytest.approx(
        300e-9)
    assert spans.module_in_s(0, "run_fit_k", "apcvfl.psi") == 0
    assert spans.module_in_s(0, "jit_other", "apcvfl.psi") == pytest.approx(
        50e-9)
    # a module partly inside a span counts its part inside: 60 of 100 ns
    assert spans.module_in_s(0, "run_fit_k",
                             "apcvfl.lanes.sync") == pytest.approx(
        (60 + 200 + 300) * 1e-9)


def read(name, ctx):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_readers_per_fit(spans):
    fits = 2
    ctx = {"trace": spans.trace, "program_spans": spans, "devices": [0, 1],
           "window": {"fits": fits}}
    ms = lambda ns: ns * 1e-6 / fits
    assert read("idle_psi_ms.fit", ctx) == pytest.approx(ms(140))
    assert read("idle_stages_ms.fit", ctx) == pytest.approx(ms(20 + 350))
    assert read("idle_lanes_ms.fit", ctx) == pytest.approx(ms(40 + 420 + 20))
    assert read("engine_g1_ms.fit", ctx) == pytest.approx(ms(300))
    assert read("engine_g3_ms.fit", ctx) == pytest.approx(ms(300))
    # chip 1 is the idlest
    assert read("idle_lane_prep_ms.scale", ctx) == pytest.approx(ms(60))
    assert read("idle_lane_shard_ms.scale", ctx) is None


def test_readers_find_nothing_without_program_spans():
    p = profile(host=HOST[:2])
    ps = ProgramSpans(Trace(p), p)
    ctx = {"trace": ps.trace, "program_spans": ps, "devices": [0, 1],
           "window": {"fits": 2}}
    assert {name: read(name, ctx) for name in NEW} == dict.fromkeys(NEW)


def test_recorded_trace_without_program_spans():
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(DATA / "serve_small.xplane.pb"))
    tr = Trace(prof)
    ps = ProgramSpans(tr, prof)
    assert ps.names() == set()
    assert sum(ps.idle_s(0).values()) == pytest.approx(
        tr.window_s() - tr.busy_s(0), rel=1e-9)
    ctx = {"trace": tr, "program_spans": ps, "devices": [0],
           "window": {"fits": 1}}
    assert {name: read(name, ctx) for name in NEW} == dict.fromkeys(NEW)
