"""Trace reduction, on a trace recorded on one TPU v5e and on hand-made
planes whose busy time and gaps are known exactly."""
import os
from types import SimpleNamespace as NS

import pytest

from bench.lib import trace

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur),
              stats=stats)


def line(name, *events):
    return NS(name=name, events=list(events))


def plane(name, *lines):
    return NS(name=name, lines=list(lines))


def test_recorded_tpu_trace():
    # `bench.window` around three `bench.step` spans, each running one
    # jitted 512x512 matmul on the chip and sleeping 2 ms
    s = trace.reduce_file(TINY)
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.010279749)
    assert set(s.module_s) == {"jit__lambda"}
    assert s.module_calls["jit__lambda"] == 2        # one ran before it
    assert s.busy_s == pytest.approx(s.module_s["jit__lambda"])
    assert 0.99 < s.idle_share < 1.0
    b = s.breakdown()
    assert b["device_ops"] == [["jit__lambda", s.module_s["jit__lambda"]]]
    assert {k for k, _ in b["idle_gaps"]} == {"step", "host:idle"}
    assert sum(v for _, v in b["idle_gaps"]) == pytest.approx(
        s.window_s - s.busy_s)


def test_union_gaps_and_attribution():
    host = plane("/host:CPU", line(
        "python3", ev("bench.window", 0, 1000), ev("bench.prep", 100, 300),
        ev("bench.prep.inner", 150, 50), ev("other", 0, 1000)))
    dev = plane("/device:TPU:0",
                line("XLA Modules", ev("jit_run(12)", 50, 100),
                     ev("jit_run(-3)", 120, 80), ev("jit_point(7)", 600, 100),
                     ev("jit_run(12)", 990, 100)),
                line("XLA Ops", ev("%fusion = f32[]", 50, 10)))
    noise = plane("/device:CUSTOM:Megascale Trace",
                  line("x", ev("y", 0, 1000)))
    s = trace.reduce_planes([host, dev, noise])
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [50, 200) + [600, 700) + [990, 1000) clipped to the window
    assert s.busy_s == pytest.approx(260e-9)
    assert s.idle_share == pytest.approx(0.74)
    assert s.module_s == pytest.approx({"jit_run": 190e-9,
                                        "jit_point": 100e-9})
    assert s.module_calls == {"jit_run": 3, "jit_point": 1}
    # gaps [0,50) host idle, [200,600) mid 400 in prep, [700,990) idle
    assert s.idle_by_span == pytest.approx({"host:idle": 340e-9,
                                            "prep": 400e-9})
    assert s.breakdown()["idle_gaps"][0] == ["prep", pytest.approx(400e-9)]


def test_window_required():
    dev = plane("/device:TPU:0", line("XLA Modules", ev("jit_a(1)", 0, 5)))
    with pytest.raises(ValueError):
        trace.reduce_planes([dev])
    host = plane("/host:CPU", line("python3", ev("bench.window", 0, 10)))
    with pytest.raises(ValueError):
        trace.reduce_planes([host])


@pytest.mark.parametrize("raw,name", [("jit_run(123)", "jit_run"),
                                      ("jit_run(-9)", "jit_run"),
                                      ("jit__lambda", "jit__lambda")])
def test_module_name(raw, name):
    assert trace.module_name(raw) == name
