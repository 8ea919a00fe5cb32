"""trace_reduce on a small trace recorded on a TPU v5e: two jitted
programs run five times each with host sleeps between them, reduced to
busy time, the idle share and the breakdown a traced run reports."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench import trace_reduce  # noqa: E402

DATA = os.path.join(HERE, "data")


def test_reduces_the_recorded_trace():
    red = trace_reduce.reduce(DATA, 1)
    assert 0 < red["busy_s"] < red["window_s"]
    # 10 program runs of tens of microseconds each, 5 sleeps of 10 ms
    assert red["busy_s"] < 1e-3
    assert 0.05 < red["window_s"] < 1.0
    assert len(red["device_ops"]) <= trace_reduce.TOP
    assert red["device_ops"][0][0] == "%fusion fusion"
    secs = [s for _n, s in red["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= red["busy_s"] * 1.0001
    gaps = [s for _n, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert red["idle_gaps"][0][0].endswith("stop_trace")
    assert sum(1 for n, s in red["idle_gaps"]
               if n.endswith("time sleep") and s > 0.01) == 5
    assert red["busy_s"] + sum(gaps) <= red["window_s"] * 1.0001


def test_recorded_trace_gives_the_idle_share_and_breakdown():
    import types
    from chipbench import harness
    red = trace_reduce.reduce(DATA, 1)
    read = harness.metric_reader(os.path.dirname(os.path.dirname(HERE)),
                                 "device_idle_share")
    idle = read(types.SimpleNamespace(trace=red))
    assert idle == 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    assert 99.0 < idle < 100.0     # ten tiny programs in 50 ms of sleeps
    assert all(isinstance(n, str) and s > 0
               for n, s in red["device_ops"] + red["idle_gaps"])


def test_union_and_clip():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace_reduce._clip([(0, 2), (3, 9)], 1, 4) == [(1, 2), (3, 4)]


def test_op_name():
    hlo = ("%convolution_tanh_fusion = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)}"
           " fusion(f32[1024,1024]{1,0:T(8,128)S(1)} %copy-done), "
           "kind=kOutput")
    assert trace_reduce.op_name(hlo) == "%convolution_tanh_fusion fusion"
    assert trace_reduce.op_name("%copy-start = (f32[2]{0}, u32[]) "
                                "copy-start(f32[2]{0} %x)") == \
        "%copy-start copy-start"
