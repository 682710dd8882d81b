"""Host span trees over a hand-made trace: self times, and idle gaps under
the innermost span, with more nested spans in a step than a look-back
over the last few spans would see."""
import json
import os

import pytest

from bench.lib import spans, trace

# trace_nested.json: one bench.step [0, 900] ms holding 19 nested program
# spans (mv4pg.serve.step [10, 890] and its phases, a fence's phases under
# mv4pg.maint.apply_writes), bench.clients [900, 1030] crossing the window's
# end, and a second host thread's span [925, 935].  The device idles in ten
# gaps, each inside one innermost span.
PATH = os.path.join(os.path.dirname(__file__), "data", "trace_nested.json")

GAPS = {
    "bench.step": 0.008, "mv4pg.plan.rewrite": 0.010,
    "mv4pg.exec.slice_rebuild": 0.010, "mv4pg.plan.rows_to_host": 0.040,
    "mv4pg.serve.finish": 0.060, "mv4pg.maint.base": 0.070,
    "mv4pg.exec.to_host": 0.010, "mv4pg.maint.apply": 0.050,
    # [884, 888] ms: after the fence, in the step's own code; the span that
    # covers it started 18 spans earlier
    "mv4pg.serve.step": 0.004,
    "bench.clients": 0.060,
}


def _load():
    with open(PATH) as f:
        d = json.load(f)
    return ([spans.Span(*row) for row in d["spans"]],
            [trace.Event(*row) for row in d["ops"]])


def test_idle_gaps_go_to_the_innermost_span():
    s, ops = _load()
    assert spans.split(ops, s).gaps == pytest.approx(GAPS)


def test_span_self_time_is_exact():
    s, ops = _load()
    assert spans.split(ops, s).span_self_s == pytest.approx({
        "bench.step": 0.020,
        # 880 ms less its ten children's 860, and 10 ms on thread 2
        "mv4pg.serve.step": 0.030,
        "mv4pg.serve.collect": 0.050, "mv4pg.plan.rewrite": 0.030,
        "mv4pg.serve.group": 0.050, "mv4pg.plan.launch": 0.030,
        "mv4pg.exec.slice_rebuild": 0.060, "mv4pg.plan.wait": 0.150,
        "mv4pg.plan.rows_to_host": 0.150, "mv4pg.serve.finish": 0.100,
        "mv4pg.maint.apply_writes": 0.020, "mv4pg.maint.base": 0.090,
        "mv4pg.maint.sweep": 0.040, "mv4pg.exec.to_host": 0.020,
        "mv4pg.maint.apply": 0.070,
        "bench.clients": 0.100,          # cut at the window's end
    })


def test_nested_busy_plus_gaps_is_the_window():
    s, ops = _load()
    w = next(x for x in s if x.name == trace.WINDOW_SPAN)
    summary = trace.reduce(ops + [trace.Event("span", -1, w.name, "",
                                              w.start_ns, w.dur_ns)])
    gaps = spans.split(ops, s).gaps
    assert summary.busy_s == pytest.approx(1.0 - sum(GAPS.values()))
    assert summary.busy_s + sum(gaps.values()) == pytest.approx(
        summary.window_s)
    assert sum(summary.gaps.values()) == pytest.approx(sum(gaps.values()))
