"""The reduction of the serving path's own spans (``harness/spans.py``) on
synthetic events and on traces recorded on the chip, and the spans the
program writes: every launch of a ``ServingFrontend``, traced on the CPU,
holds its phases in order on one thread."""
from cbtest import isolated_autotune  # noqa: F401  (autouse)
import os

import numpy as np
import pytest

from harness import spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PHASES = ["serving.coalesce", "serving.h2d", "serving.enqueue",
          "serving.sync", "serving.d2h", "serving.scatter"]


def ev(name, start, end):
    return trace.Event(name, start, end)


def test_spans_reduce_synthetic():
    window = [ev(trace.WINDOW_SPAN, 100, 1100)]
    dispatch = [
        ev("serving.wait", 100, 200),
        # metadata after a '#' leaves the name matchable
        ev("serving.launch#bucket=8,rows=5,requests=5#", 200, 600),
        ev("serving.take", 200, 220),
        ev("serving.enqueue", 300, 400),
        ev("serving.plan_run", 310, 390),        # nested: counted once
        ev("serving.sync", 400, 500),
        ev("serving.launch", 800, 1000),
        ev("serving.d2h", 850, 900),
        ev("serving.launch", 1100, 1200),        # starts at the window's end
        ev("Execute", 300, 350),                 # not the program's span
    ]
    caller = [ev("serving.submit", 150, 160), ev("serving.submit", 50, 60)]
    dev = [ev("%op.1 = f32[8] fusion(%a)", 100, 250),
           ev("%op.2 = f32[8] fusion(%a)", 450, 850)]
    r = spans.reduce(trace.Trace({0: dev}, [window, dispatch, caller]))
    assert r["spans"]["serving.launch"] == {"n": 2,
                                            "s": pytest.approx(600e-9)}
    assert r["spans"]["serving.submit"] == {"n": 1,
                                            "s": pytest.approx(10e-9)}
    assert r["spans"]["serving.plan_run"]["n"] == 1
    assert "Execute" not in r["spans"]
    assert r["launch_lines"] == [1]
    # children: [200,220] + [300,500] in the first, [850,900] in the second
    assert r["launch_covered_s"] == pytest.approx(270e-9)
    assert spans.mean_ms(r, "serving.launch") == pytest.approx(300e-6)
    assert spans.mean_ms(r, "serving.take") == pytest.approx(20e-6)
    assert spans.mean_ms(r, "serving.nothing") is None
    # idle: [250,450] + [850,1100] = 450 ns; inside a launch (which runs
    # [200,600] and [800,1000]): [250,450] + [850,1000] = 350 ns
    assert r["devices"] == [0]
    assert r["idle_s"] == [pytest.approx(450e-9)]
    assert r["idle_in_launch_s"] == [pytest.approx(350e-9)]


def test_spans_reduce_without_serving_spans():
    """A program without spans, as the parent of this reduction has:
    nothing to read, and nothing raised."""
    host = [[ev(trace.WINDOW_SPAN, 0, 100), ev("Execute", 10, 20)]]
    r = spans.reduce(trace.Trace({0: [ev("%a.1 = f32[8] copy(%b)", 0, 40)]},
                                 host))
    assert r["spans"] == {} and r["launch_lines"] == []
    assert r["launch_covered_s"] == 0.0
    assert r["idle_s"] == [pytest.approx(60e-9)]
    assert r["idle_in_launch_s"] == [0.0]
    assert spans.mean_ms(r, spans.LAUNCH) is None


def test_overlap_and_covered():
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap([], [(0, 1)]) == 0
    # a span that starts before the launch, or ends after it, is not its
    # child
    assert spans.covered([(10, 20)], [(5, 12), (12, 15), (18, 25)]) == 3


def test_trace_reduce_unchanged_on_the_recorded_chip_trace():
    """The chip trace recorded before the program had spans: the existing
    reduction reads what it read, and the span reduction finds no span
    and splits the idle time alone."""
    t = trace.load(os.path.join(DATA, "gsc-int8-8rows.xplane.pb"))
    r = trace.reduce(t)
    assert sorted(r) == ["breakdown", "busy_s", "kernel_s", "window_s"]
    assert r["window_s"] == pytest.approx(0.004151359)
    assert r["busy_s"] == [pytest.approx(7.3493e-05)]
    assert r["kernel_s"] == [pytest.approx(7.0878e-05)]
    assert r["breakdown"]["device_ops"][0] == [
        "fantastic4_fused_mlp_stream_pallas", pytest.approx(7.0878e-05)]
    s = spans.reduce(t)
    assert s["spans"] == {} and s["devices"] == [0]
    assert s["idle_s"][0] == pytest.approx(r["window_s"] - r["busy_s"][0])
    assert s["idle_in_launch_s"] == [0.0]


def test_spans_reduce_a_trace_recorded_on_the_chip():
    """0.3 s of gsc-int8-online on one v5e with the program's spans
    (``tools/span_split.py --out``): each launch's phases cover at least
    90% of it, and the device idles almost only while a launch runs."""
    t = trace.load(os.path.join(DATA, "gsc-int8-online-spans.xplane.pb"))
    r = spans.reduce(t)
    assert r["spans"][spans.LAUNCH] == {"n": 159,
                                        "s": pytest.approx(0.298666279)}
    assert r["spans"]["serving.submit"]["n"] == 1920
    assert set(r["spans"]) == {"serving." + p for p in (
        "submit", "wait", "launch", "take", "coalesce", "h2d", "enqueue",
        "sync", "d2h", "scatter")}
    assert len(r["launch_lines"]) == 1
    assert r["launch_covered_s"] == pytest.approx(0.283572607)
    assert r["launch_covered_s"] >= 0.9 * r["spans"][spans.LAUNCH]["s"]
    assert r["idle_s"] == [pytest.approx(0.297160293)]
    assert r["idle_in_launch_s"] == [pytest.approx(0.292819243)]
    ops = dict(trace.reduce(t)["breakdown"]["device_ops"])
    assert "fantastic4_fused_mlp_stream_pallas" in ops


def _tiny_plan():
    import jax.numpy as jnp
    from repro import serving
    from repro.core import bitplanes as bp
    rng = np.random.default_rng(0)
    k, n = 16, 8
    layer = {"packed": bp.pack_codes_rows(jnp.asarray(
                 rng.integers(0, 16, size=(k, n)).astype(np.uint8))),
             "omega": jnp.asarray(rng.normal(size=4), jnp.float32),
             "alpha1": jnp.ones((n,), jnp.float32),
             "bias": jnp.zeros((n,), jnp.float32),
             "alpha2": jnp.asarray(np.float32(1.0)),
             "shape": (k, n), "activation": None}
    return serving.build_plan({"layers": [layer], "act_bits": None},
                              mode="oracle", max_bucket=8)


@pytest.mark.parametrize("streams", [1, 2])
def test_frontend_launch_spans_nest_in_order(tmp_path, streams):
    """Each ``serving.launch`` holds the phases in order on its own
    thread (``serving.take`` first where the launching thread takes the
    bucket itself), one launch per flush, with its metadata beside the
    name rather than in it."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    from repro import serving
    plan = _tiny_plan()
    rng = np.random.default_rng(1)
    xs = [rng.normal(size=(1, 16)).astype(np.float32) for _ in range(40)]
    fe = serving.ServingFrontend(streams=streams)
    fe.register("m", plan, max_delay=1e-3)
    with fe:
        for b in plan.bucket_sizes:             # compile outside the trace
            np.asarray(plan.entry(b)(np.zeros((b, 16), np.float32)))
        fe.serve("m", xs[:1], timeout=60)
        batcher = fe.registry.batcher("m")
        flushes = batcher.stats["flushes"]
        jax.profiler.start_trace(str(tmp_path))
        try:
            with TraceAnnotation(trace.WINDOW_SPAN):
                for f in [fe.submit("m", x) for x in xs]:
                    f.result(60)
        finally:
            jax.profiler.stop_trace()
        flushes = batcher.stats["flushes"] - flushes
    t = trace.load(str(tmp_path))
    expected = (["serving.take"] if streams == 1 else []) + PHASES
    launches = 0
    for line in t.host:
        mine = sorted((e for e in line
                       if spans.base_name(e.name) == spans.LAUNCH),
                      key=lambda e: e.start)
        for launch in mine:
            inside = sorted((e for e in line
                             if spans.base_name(e.name) in expected
                             and launch.start <= e.start
                             and e.end <= launch.end),
                            key=lambda e: e.start)
            assert [spans.base_name(e.name) for e in inside] == expected
        launches += len(mine)
    assert launches == flushes > 0
    r = spans.reduce(t)
    assert r["spans"][spans.LAUNCH]["n"] == flushes
    assert r["spans"]["serving.submit"]["n"] == len(xs)
    assert len(r["launch_lines"]) >= 1
    assert 0 < r["launch_covered_s"] <= r["spans"][spans.LAUNCH]["s"]
    assert r["devices"] == []                 # no TPU plane on the CPU
    data = ProfileData.from_file(trace.find_xspace(str(tmp_path)))
    meta = [dict(e.stats) for p in data.planes for line in p.lines
            for e in line.events if e.name == spans.LAUNCH]
    assert len(meta) == flushes
    assert sum(m["requests"] for m in meta) == len(xs)
    assert all(m["bucket"] >= m["rows"] >= m["requests"] for m in meta)
