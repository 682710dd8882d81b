"""The reduction from a flattened profiler trace to busy time, idle share,
per-module device time and idle gaps by harness span."""
import os

import pytest

from bench.lib import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    return trace.load_events(os.path.join(DATA, name))


def test_handmade_trace_reduces_exactly():
    s = trace.reduce(_load("trace_handmade.json"))
    assert s.window_s == pytest.approx(1.0)
    # ops [0.10, 0.30] and [0.25, 0.40] overlap; [0.60, 0.70] apart; the
    # op after the window does not count
    assert s.busy_s == pytest.approx(0.40)
    assert s.idle_share == pytest.approx(0.60)
    assert s.prefix_s("jit__program") == pytest.approx(0.35)
    assert s.prefix_s("jit__hop_segment") == pytest.approx(0.10)
    assert s.prefix_s("jit__nothing") is None
    assert s.op_s["jit__program/fusion.3"] == pytest.approx(0.20)
    # gaps: [0, .1] in a step, [.4, .6] in the clients span, [.7, 1] in a step
    assert s.gaps == pytest.approx({"bench.step": 0.40, "bench.clients": 0.20})
    b = s.breakdown()
    assert b["device_ops"][0] == ["jit__program/fusion.3", pytest.approx(0.2)]
    assert b["idle_gaps"][0][0] == "bench.step"


def test_no_window_or_no_device_op_gives_nothing():
    ev = _load("trace_handmade.json")
    assert trace.reduce([e for e in ev if e.name != "bench.window"]) is None
    assert trace.reduce([e for e in ev if e.kind != "op"]) is None


def test_recorded_chip_trace_is_consistent():
    """A slice of a trace recorded on a v5e chip: busy time within the
    window, and busy plus every idle gap is the whole window."""
    s = trace.reduce(_load("trace_recorded.json"))
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s + sum(s.gaps.values()) == pytest.approx(s.window_s)
    assert sum(s.module_s.values()) >= s.busy_s * 0.999
    assert set(s.gaps) <= {"bench.step", "bench.clients", "host.other"}
