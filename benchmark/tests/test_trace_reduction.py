"""The reduction from a profiler trace to numbers, on a small trace
recorded on the chip (data/recorded_trace.json: two runs of
`jit_verify_kernel_msg32` at bucket 65,536 on one TPU v5 lite, its
`XLA Ops` line cut to the first 400 events) and on intervals by hand."""

import json
import os

import pytest

from benchmark.harness import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        doc = json.load(f)
    planes = [(p, [(ln, [(s, d, n, st) for s, d, n, st in evs])
                   for ln, evs in lines]) for p, lines in doc["planes"]]
    return doc, planes


def test_merge_and_clip():
    assert T.merge_intervals([[3, 4], [0, 1], [0.5, 2], [2, 2.5]]) == \
        [[0, 2.5], [3, 4]]
    assert T.clip([[0, 2.5], [3, 4]], 1, 3.5) == [[1, 2.5], [3, 3.5]]
    assert T.covered([[1, 2.5], [3, 3.5]]) == 2.0


def test_self_times_nested():
    # a while of 10 s that spans two body operations of 3 s and 4 s
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
              (11.0, 12.0, "c")]
    assert T.self_times(events) == {"while": 3.0, "a": 3.0, "b": 4.0,
                                    "c": 1.0}


def test_short_op_name():
    name = ("%fusion.10504 = s32[65536,4,32]{0,2,1:T(8,128)S(1)} "
            "fusion(s32[16,4,32,65536]{3,2,1,0:T(8,128)} %gte.1), kind=kLoop")
    assert T.short_op_name(name) == "%fusion.10504 fusion"
    name = ("%while.58 = (s32[]{:T(128)}, s32[32,65536]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b")
    assert T.short_op_name(name) == "%while.58 while"


def test_recorded_trace(recorded, monkeypatch):
    doc, planes = recorded
    monkeypatch.setattr(T, "MARKER", doc["marker"])
    marker_t = None
    for pname, lines in planes:
        for _, evs in lines:
            for s, d, n, st in evs:
                if n == doc["marker"]:
                    marker_t = float(st["t"])
    assert marker_t is not None
    tr = T.DeviceTrace(planes, marker_t, marker_t + 6.7, chips=1)
    assert tr.on_accelerator and len(tr.devices) == 1
    runs = tr.module_runs("verify_kernel_msg32", marker_t, marker_t + 6.7)
    assert len(runs) == 2
    assert all(2.93 < r < 2.94 for r in runs)       # seconds on the chip
    # nothing of another name, and nothing outside the bounds
    assert tr.module_runs("verify_kernel_full", 0, 1e12) == []
    assert tr.module_runs("verify_kernel_msg32", marker_t + 1,
                          marker_t + 6.7) == runs[1:]
    # the cut trace keeps 400 operations: busy is their union, well
    # under the modules' time, and never more than the window
    assert 0 < tr.busy_s < sum(runs) <= tr.window_s
    top = tr.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = tr.idle_gaps(least=0.0)
    assert gaps and gaps[0][1] - gaps[0][0] >= gaps[-1][1] - gaps[-1][0]
    assert abs(sum(e - s for s, e in gaps) + tr.busy_s - tr.window_s) < 1e-6


def test_no_marker_is_an_error(recorded):
    _, planes = recorded
    stripped = [(p, [(ln, [] if not p.startswith("/device:") else evs)
                     for ln, evs in lines]) for p, lines in planes]
    with pytest.raises(RuntimeError):
        T.DeviceTrace(stripped, 0.0, 1.0, chips=1)
