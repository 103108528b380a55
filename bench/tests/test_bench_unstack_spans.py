"""The readers of the chip's idle inside ``apcvfl.lanes.unstack``, on the
synthetic two-fit profile of ``test_bench_spans``: chip 0 idles 20 ns in
the span, the fit reader reads chip 0 and the scale reader the idlest
chip."""
import pytest

# test_bench_spans imports cells, which puts bench/ on the path
from test_bench_spans import CHIP0, HOST, device, profile, read

from spanreduce import ProgramSpans
from tracereduce import Trace

UNSTACK = ["idle_lane_unstack_ms.fit", "idle_lane_unstack_ms.scale"]
FITS = 2


def ms(ns):
    return ns * 1e-6 / FITS


def ctx_of(p):
    ps = ProgramSpans(Trace(p), p)
    return {"trace": ps.trace, "program_spans": ps, "devices": [0, 1],
            "window": {"fits": FITS}}


def test_both_readers_give_the_hand_worked_idle():
    # chip 0 is busy until 900 ns and again from 1200 ns, so the whole
    # span [920, 940] is idle; chip 1 idles through it as well
    ctx = ctx_of(profile())
    assert read("idle_lane_unstack_ms.fit", ctx) == pytest.approx(ms(20))
    assert read("idle_lane_unstack_ms.scale", ctx) == pytest.approx(ms(20))


@pytest.mark.parametrize("chip1_busy, fit_ns, scale_ns", [
    # chip 1, busy 310 ns, is the idlest and runs 10 ns inside the span
    ([(925, 935), (1200, 1500)], 20, 10),
    # chip 1, busy 710 ns, is not: the scale reader reads chip 0
    ([(925, 935), (1000, 1700)], 20, 20),
])
def test_scale_reader_reads_the_idlest_chip(chip1_busy, fit_ns, scale_ns):
    chip1 = device(1, chip1_busy, [("jit_run_fit_k", s, e)
                                   for s, e in chip1_busy])
    ctx = ctx_of(profile(chips=(CHIP0, chip1)))
    assert read("idle_lane_unstack_ms.fit", ctx) == pytest.approx(ms(fit_ns))
    assert read("idle_lane_unstack_ms.scale", ctx) == pytest.approx(
        ms(scale_ns))


def test_readers_find_nothing_without_the_span():
    host = [e for e in HOST if e.name != "apcvfl.lanes.unstack"]
    ctx = ctx_of(profile(host=host))
    assert {name: read(name, ctx) for name in UNSTACK} \
        == dict.fromkeys(UNSTACK)
