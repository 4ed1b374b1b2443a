"""The trace reducer, on a recorded one-chip trace and on hand-made planes."""

import os
import types

import pytest

from perfbench.lib import trace

SAMPLE = os.path.join(os.path.dirname(__file__), "data", "sample.xplane.pb")


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name,
        lines=[types.SimpleNamespace(name=n, events=evs) for n, evs in lines.items()],
    )


def test_hand_made_planes():
    host = _plane("/host:CPU", {"python": [
        _ev("bench.window", 0, 1000),
        _ev("bench.solve", 0, 1000),  # encloses open and run
        _ev("bench.open", 0, 400),
        _ev("bench.run", 400, 600),
    ]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [_ev("%fusion.1 = f64[8] fusion(...)", 450, 100),
                    _ev("%fusion.2 = f64[8] fusion(...)", 500, 200),
                    _ev("%dot.3", 800, 100), _ev("%fusion.1 = f64[8] fusion(...)", 1500, 50)],
        "XLA Modules": [_ev("jit_round", 0, 2000)],  # ignored: ops line exists
    })
    s = trace.reduce_planes([host, dev])
    # busy: [450, 700] and [800, 900] inside [0, 1000]
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(350e-9)
    assert s.idle_share == pytest.approx(0.65)
    assert s.top_ops[0] == ["%fusion.2", pytest.approx(200e-9)]
    assert s.top_ops[1] == ["%fusion.1", pytest.approx(100e-9)]  # 1500: outside
    assert s.idle_gaps[0] == ["open", pytest.approx(450e-9)]
    # the first gap, [0, 450], lies 400 in open and 50 in run
    assert s.idle_by_span == {"open": pytest.approx(400e-9), "run": pytest.approx(250e-9)}


def test_nothing_to_read():
    host = _plane("/host:CPU", {"python": [_ev("bench.window", 0, 1000)]})
    assert trace.reduce_planes([host]) is None  # no device plane
    dev = _plane("/device:TPU:0", {"XLA Ops": [_ev("op", 2000, 10)]})
    assert trace.reduce_planes([host, dev]) is None  # nothing in the window


def test_recorded_chip_trace():
    # three bench.run spans each wait on one float64 product; the host
    # sleeps 20 ms in each bench.open span while the device idles
    s = trace.reduce_file(SAMPLE)
    assert s is not None and s.device_planes == 1
    assert 0.06 < s.window_s < 1.0
    assert 0.0 < s.busy_s < s.window_s
    assert s.idle_by_span.get("open", 0.0) >= 0.055
    assert s.idle_gaps[0][0] == "open"
    assert s.top_ops and all(t > 0 for _, t in s.top_ops)
