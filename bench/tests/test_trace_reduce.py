"""The reduction from a profiler trace to device busy time, per-operation
time and attributed idle gaps, on a synthetic trace with known answers."""
import pytest

from bench import trace_reduce


def _planes():
    return [
        {
            "name": "/device:TPU:0",
            # two overlapping kernel events, one collective, one event
            # partly outside the window
            "ops": [
                ("_shiftbank_kernel", 1.0, 2.0),
                ("_shiftbank_kernel", 1.5, 2.5),
                ("all-gather.1", 4.0, 4.5),
                ("fusion.3", 9.5, 11.0),
            ],
            "threads": [],
        },
        {
            "name": "/device:TPU:1",
            "ops": [("_shiftbank_kernel", 1.0, 3.0)],
            "threads": [],
        },
        {
            "name": "/host:CPU",
            "ops": [],
            "threads": [
                [("bench.window", 0.0, 10.0), ("bench.train_call", 2.5, 4.0)],
                [("PjitFunction(step)", 5.0, 9.0), ("inner", 6.0, 6.5)],
            ],
        },
    ]


def test_busy_union_ops_and_gaps():
    out = trace_reduce.reduce_planes(_planes(), window_span="bench.window")
    assert out["window_s"] == pytest.approx(10.0)
    # device 0: [1, 2.5] + [4, 4.5] + [9.5, 10] = 2.5; device 1: 2.0
    assert out["busy_s"] == pytest.approx((2.5 + 2.0) / 2)
    assert out["devices"] == 2
    ops = out["op_seconds"]
    assert ops["_shiftbank_kernel"] == pytest.approx(1.0 + 1.0 + 2.0)
    assert ops["all-gather.1"] == pytest.approx(0.5)
    assert ops["fusion.3"] == pytest.approx(0.5)  # clipped at the window's end
    assert trace_reduce.op_seconds_matching(out, r"all-gather|all-reduce") == pytest.approx(0.5)
    assert trace_reduce.op_seconds_matching(out, r"_shiftbank_kernel") == pytest.approx(4.0)
    # device 0's gaps: [0, 1] (only the window span covers it), [2.5, 4]
    # (train call), [4.5, 9.5] (midpoint 7.0: the pjit span, 'inner' ends
    # at 6.5)
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.window"] == pytest.approx(1.0)
    assert gaps["bench.train_call"] == pytest.approx(1.5)
    assert gaps["PjitFunction(step)"] == pytest.approx(5.0)
    assert sum(gaps.values()) == pytest.approx(10.0 - 2.5)
    assert out["device_ops"][0] == ["_shiftbank_kernel", pytest.approx(4.0)]


def test_no_device_plane_reads_nothing():
    planes = [p for p in _planes() if not p["name"].startswith("/device:")]
    assert trace_reduce.reduce_planes(planes, "bench.window") == {}


def test_window_defaults_to_whole_trace():
    planes = [p for p in _planes() if p["name"] != "/host:CPU"]
    out = trace_reduce.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(11.0 - 1.0)
