"""The reduction from a profiler trace to busy, idle and kernel time."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


KERNEL = ('%fantastic4_fused_mlp_pallas.3 = f32[8,128]{1,0} custom-call('
          'f32[8,512]{1,0} %x), custom_call_target="tpu_custom_call"')


def ev(name, start, end):
    return trace.Event(name, start, end)


def test_union_and_gaps():
    merged = trace.union([(5, 8), (0, 2), (1, 3), (7, 12)], 0, 10)
    assert merged == [(0, 3), (5, 10)]
    assert trace.gaps(merged, 0, 10) == [(3, 5)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_reduce_synthetic():
    host = [[ev(trace.WINDOW_SPAN, 100, 1100)],
            [ev("Execute", 400, 700), ev("Outer", 0, 2000)]]
    dev = [ev("%fusion.1 = f32[8] fusion(f32[8] %a)", 50, 150),
           ev(KERNEL, 200, 400), ev("%copy.2 = f32[8] copy(%b)", 300, 500),
           ev(KERNEL, 900, 1000)]
    r = trace.reduce(trace.Trace({0: dev}, host))
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,150] + [200,500] + [900,1000] = 450 ns
    assert r["busy_s"] == [pytest.approx(450e-9)]
    assert r["kernel_s"] == [pytest.approx(300e-9)]
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["TPU:0 Execute", pytest.approx(400e-9)]
    assert len(gaps) == 3
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fantastic4_fused_mlp_pallas"] == pytest.approx(300e-9)
    assert ops["copy"] == pytest.approx(200e-9)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace({0: []}, [[ev("other", 0, 1)]]))


def test_reduce_a_trace_recorded_on_the_chip():
    """Three ``plan.run`` calls of MLP-GSC int8 at 8 rows (bucket 8, bound
    to the stream schedule) on one v5e, traced by
    ``tools/trace_sample.py``: one kernel per call."""
    t = trace.load(os.path.join(DATA, "gsc-int8-8rows.xplane.pb"))
    assert sorted(t.devices) == [0]
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(0.004151359)
    assert r["busy_s"] == [pytest.approx(7.3493e-05)]
    assert r["kernel_s"] == [pytest.approx(7.0878e-05)]
    kernels = [e for e in t.devices[0] if trace.is_kernel(e)]
    assert len(kernels) == 3
    assert r["kernel_s"][0] <= r["busy_s"][0] <= r["window_s"]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["fantastic4_fused_mlp_stream_pallas"] == \
        pytest.approx(7.0878e-05)
