"""Run by hand: `python3 -m pytest benchmarks/tests/test_trace_reduce.py -q`.

The trace reduction, pinned twice: on hand-made events, where the right answer
is plain, and on `data/tables_one_request.xplane.pb`, a trace of ONE request
of `tables-10k-1k.port-sweep` recorded on a TPU v5 lite (my chip run, PR 25),
so that busy time, idle share, device time by operation and the attribution of
idle gaps to `bench.<layer>` spans are computed the same way by every later PR.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import harness, trace_reduce  # noqa: E402

TRACE = os.path.join(HERE, "data", "tables_one_request.xplane.pb")


def test_hand_made_events():
    device = {"/device:TPU:0": [
        ("a", 1.0, 2.0), ("b", 1.5, 2.5),    # overlap: busy 1.0 .. 2.5
        ("a", 4.0, 5.0),
        ("c", 9.5, 11.0),                     # clipped at the window's end
    ]}
    spans = [("bench.window", 0.0, 10.0), ("bench.request", 0.5, 6.0),
             ("bench.evaluate", 0.5, 1.2), ("bench.fetch", 2.4, 6.0)]
    out = trace_reduce.reduce_events(device, spans, window=(0.0, 10.0))
    assert out["busy_s"] == pytest.approx(1.5 + 1.0 + 0.5)
    assert out["window_s"] == 10.0
    assert out["device_ops"][0] == ["a", pytest.approx(2.0)]
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.evaluate"] == pytest.approx(0.5)   # 0.5 .. 1 of the gap 0 .. 1
    assert gaps["bench.fetch"] == pytest.approx(2.5)      # 2.5 .. 4 and 5 .. 6
    assert gaps["bench.window"] == pytest.approx(4.0)     # 0 .. 0.5 and 6 .. 9.5
    assert trace_reduce.busy_inside(device, [("x", 0.0, 2.0)]) == pytest.approx(1.0)
    pieces = trace_reduce._innermost(spans)
    assert trace_reduce._cut(pieces, [p[2] for p in pieces], 9.0, 12.0) == [
        ("bench.window", 9.0, 10.0), (trace_reduce.NO_SPAN, 10.0, 12.0)]


def test_op_names_keep_to_a_name():
    raw = "%select_bitcast_fusion = pred[2,10000,10000]{2,1,0:T(8,128)(4,1)} fusion(...)"
    assert trace_reduce.op_name(raw) == "select_bitcast_fusion_pred_2_10000_10000_"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"


def test_the_recorded_trace():
    device, spans = trace_reduce.read_xplane(TRACE)
    assert list(device) == ["/device:TPU:0"]
    assert len(device["/device:TPU:0"]) == 121
    out = harness.reduce_window(device, spans, "bench.window")
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.960902357, rel=1e-6)
    assert out["busy_s"] == pytest.approx(0.013930348, rel=1e-6)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.98550, abs=1e-4)
    assert out["device_ops"][0][0] == "select_bitcast_fusion_pred_2_10000_10000_"
    assert out["device_ops"][0][1] == pytest.approx(0.003674177, rel=1e-6)
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.fetch"] == pytest.approx(0.914213978, rel=1e-6)
    assert gaps["bench.evaluate"] == pytest.approx(0.032495951, rel=1e-6)
    calls = [s for s in spans if s[0] == "bench.evaluate"]
    assert len(calls) == 1
    assert trace_reduce.busy_inside(device, calls) < 0.001   # dispatch returns at once
