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
    assert r["op_s"] == ops


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


def test_op_s_is_not_cut_at_top():
    """Two devices, ``TOP`` + 2 op names: ``op_s`` keeps them all, each
    summed over both devices and clipped to the window, and the breakdown
    still lists the ``TOP`` largest."""
    host = [[ev(trace.WINDOW_SPAN, 0, 10_000)]]
    names = [f"%op{i}.{i} = f32[8] fusion(f32[8] %a)" for i in
             range(trace.TOP + 2)]
    dev = {d: [ev(n, 100 * i, 100 * i + 10 * (i + 1) * (d + 1))
               for i, n in enumerate(names)] for d in (0, 1)}
    dev[0].append(ev(names[0], 9_990, 10_050))
    r = trace.reduce(trace.Trace(dev, host))
    assert len(r["op_s"]) == trace.TOP + 2
    for i in range(trace.TOP + 2):
        want = 10 * (i + 1) * 3 + (10 if i == 0 else 0)
        assert r["op_s"][f"op{i}"] == pytest.approx(want * 1e-9)
    top = r["breakdown"]["device_ops"]
    assert [n for n, _ in top] == [f"op{i}" for i in
                                   range(trace.TOP + 1, 1, -1)]
    assert all(r["op_s"][n] == s for n, s in top)


def test_op_s_on_a_trace_recorded_on_the_chip():
    """On an online chip trace (MLP-GSC int8, 1-128 row buckets) ``op_s``
    holds every op of the breakdown with the same seconds, and the Pallas
    kernels' ops add up to ``kernel_s``."""
    t = trace.load(os.path.join(DATA, "gsc-int8-online-spans.xplane.pb"))
    r = trace.reduce(t)
    ops = r["breakdown"]["device_ops"]
    assert ops
    for name, seconds in ops:
        assert r["op_s"][name] == seconds
    kernels = {trace.op_name(e.name) for evs in t.devices.values()
               for e in evs if trace.is_kernel(e)}
    assert kernels and not kernels & {
        trace.op_name(e.name) for evs in t.devices.values() for e in evs
        if not trace.is_kernel(e)}
    assert sum(r["op_s"][k] for k in kernels) == pytest.approx(
        sum(r["kernel_s"]), rel=1e-12)
