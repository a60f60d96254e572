"""MicroBatcher: padding/coalescing parity (a request served from a padded
bucket must equal serving it alone — bit-identical on the int8 paths),
deadline-based partial flush, FIFO scatter, and the replay simulator."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serving
from test_serving_plans import _rand_pack

DIMS = (33, 129, 71, 7)          # odd-K everywhere
EVEN_DIMS = (64, 96, 10)


def _plan(pack, **kw):
    return serving.build_plan(pack, mode="fused", interpret=True, **kw)


# ------------------------------------------------------- padding parity

@pytest.mark.parametrize("dims", [DIMS, EVEN_DIMS],
                         ids=["oddK", "evenK"])
@pytest.mark.parametrize("act_dtype", ["float32", "int8"])
def test_padded_bucket_parity_vs_alone(dims, act_dtype):
    """Satellite contract: logits for a request served in a padded /
    coalesced bucket are bit-identical (int8) / allclose (fp32) to serving
    the same request alone — including batch=1 and odd-K stacks."""
    pack = _rand_pack(dims, seed=sum(dims))
    calib_x = jnp.asarray(np.random.default_rng(0).normal(size=(16, dims[0])),
                          jnp.float32)
    kw = {}
    if act_dtype == "int8":
        kw = {"act_dtype": "int8",
              "calib": serving.calibrate_act_scales(pack, calib_x)}
    plan = _plan(pack, **kw)

    rng = np.random.default_rng(1)
    reqs = [jnp.asarray(rng.normal(size=(r, dims[0])), jnp.float32)
            for r in (1, 3, 1, 2)]           # 7 rows -> one 8-row bucket

    batcher = serving.MicroBatcher(plan)
    coalesced = batcher.serve(reqs)
    assert batcher.stats["flushes"] == 1
    assert batcher.stats["padded_rows"] == 1

    for req, got in zip(reqs, coalesced):
        alone = serving.MicroBatcher(plan).serve([req])[0]
        if act_dtype == "int8":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(alone))
        else:
            np.testing.assert_allclose(got, alone, atol=1e-5, rtol=1e-5)
        # and the engine result matches the plan run directly (row slice
        # of a padded bucket == the request on its own bucket)
        np.testing.assert_allclose(got, plan.run(req), atol=1e-5, rtol=1e-5)


def test_single_row_bucket1_parity_int8():
    """batch=1: the latency (weight-stationary) bucket through the engine
    equals serving the row alone, bit for bit on int8."""
    pack = _rand_pack(DIMS, seed=2)
    x1 = jnp.asarray(np.random.default_rng(3).normal(size=(1, DIMS[0])),
                     jnp.float32)
    calib = serving.calibrate_act_scales(pack, x1)
    plan = _plan(pack, act_dtype="int8", calib=calib)
    assert plan.path_for(1) == "fused_ws"
    got = serving.MicroBatcher(plan).serve([x1])[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(plan.run(x1)))


# ------------------------------------------------------------- batching

def test_full_tile_flush_and_fifo_scatter():
    pack = _rand_pack(EVEN_DIMS)
    plan = _plan(pack, max_bucket=8)
    oracle = serving.build_plan(pack, mode="oracle")
    b = serving.MicroBatcher(plan, max_delay=1e9, clock=lambda: 0.0)
    rng = np.random.default_rng(5)
    xs = [jnp.asarray(rng.normal(size=(1, EVEN_DIMS[0])), jnp.float32)
          for _ in range(8)]
    rids = [b.submit(x) for x in xs]
    # 8 rows == max bucket: pump flushes exactly one full tile, no deadline
    done = b.pump(now=0.0)
    assert {c.rid for c in done} == set(rids)
    assert b.stats["flushes"] == 1
    assert b.stats["padded_rows"] == 0
    for x, rid in zip(xs, rids):
        np.testing.assert_allclose(b.result(rid).y, oracle.run(x),
                                   atol=1e-3, rtol=1e-4)
    with pytest.raises(KeyError):          # popped: loud, not None
        b.result(rids[0])


def test_deadline_partial_flush():
    plan = _plan(_rand_pack(EVEN_DIMS))
    b = serving.MicroBatcher(plan, max_delay=0.5)
    x = jnp.zeros((1, EVEN_DIMS[0]), jnp.float32)
    rid = b.submit(x, now=10.0)
    assert b.pump(now=10.1) == []          # not due, tile not full: holds
    assert b.pending_rows == 1
    done = b.pump(now=10.6)                # deadline hit: partial flush
    assert [c.rid for c in done] == [rid]
    assert done[0].bucket == 1


def test_queue_wait_counts_a_known_wait():
    """``queue_wait_s`` sums launch start minus arrival over the launched
    requests, on the live clock."""
    plan = _plan(_rand_pack(EVEN_DIMS))
    b = serving.MicroBatcher(plan, max_delay=1.0)
    x = np.zeros((1, EVEN_DIMS[0]), np.float32)
    b.submit(x)
    b.submit(x)
    time.sleep(0.005)
    b.flush()
    assert b.stats["flushed_requests"] == 2
    assert b.stats["flushes"] == 1
    assert 2 * 0.005 <= b.stats["queue_wait_s"] < 2 * 60.0


def test_queue_wait_stays_off_a_virtual_clock():
    """A virtual clock's seconds are not the live ones: the wait is the
    driver's to account, as ``compute_s`` is; the requests still count."""
    plan = _plan(_rand_pack(EVEN_DIMS))
    b = serving.MicroBatcher(plan, max_delay=0.5, clock=None)
    b.submit(np.zeros((1, EVEN_DIMS[0]), np.float32), now=0.0)
    b.flush(now=3.0)
    assert b.stats["flushed_requests"] == 1
    assert b.stats["queue_wait_s"] == 0.0


def test_execute_records_devices_only_when_asked():
    plan = _plan(_rand_pack(EVEN_DIMS))
    b = serving.MicroBatcher(plan)
    x = np.zeros((1, EVEN_DIMS[0]), np.float32)
    b.submit(x)
    t = b.take()
    b.execute(t)
    assert t.devices is None
    b.submit(x)
    t = b.take()
    b.execute(t, record_devices=True)
    here = (str(jax.devices()[0]),)
    assert t.devices == (here, here)


def test_multi_row_requests_stay_contiguous():
    pack = _rand_pack(EVEN_DIMS)
    plan = _plan(pack, max_bucket=4)
    oracle = serving.build_plan(pack, mode="oracle")
    b = serving.MicroBatcher(plan)
    rng = np.random.default_rng(6)
    big = jnp.asarray(rng.normal(size=(3, EVEN_DIMS[0])), jnp.float32)
    small = jnp.asarray(rng.normal(size=(2, EVEN_DIMS[0])), jnp.float32)
    r1, r2 = b.submit(big), b.submit(small)
    b.flush()
    # 3+2 rows > max_bucket 4: the second request must ride a second
    # launch, never be split across buckets
    assert b.stats["flushes"] == 2
    np.testing.assert_allclose(b.result(r1).y, oracle.run(big),
                               atol=1e-3, rtol=1e-4)
    np.testing.assert_allclose(b.result(r2).y, oracle.run(small),
                               atol=1e-3, rtol=1e-4)


def test_oversized_request_runs_alone_at_exact_rows():
    pack = _rand_pack(EVEN_DIMS)
    plan = _plan(pack, max_bucket=4)
    b = serving.MicroBatcher(plan, max_bucket=4)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(9, EVEN_DIMS[0])),
                    jnp.float32)
    rid = b.submit(x)
    b.flush()
    c = b.result(rid)
    assert c.y.shape == (9, EVEN_DIMS[-1])
    np.testing.assert_allclose(
        c.y, serving.build_plan(pack, mode="oracle").run(x),
        atol=1e-3, rtol=1e-4)


def test_bad_request_shape_rejected():
    b = serving.MicroBatcher(_plan(_rand_pack(EVEN_DIMS)))
    with pytest.raises(ValueError):
        b.submit(jnp.zeros((2, 5), jnp.float32))


# --------------------------------------------------------------- replay

def test_replay_work_conserving_and_correct():
    pack = _rand_pack(EVEN_DIMS)
    plan = _plan(pack)
    oracle = serving.build_plan(pack, mode="oracle")
    rng = np.random.default_rng(8)
    xs = [jnp.asarray(rng.normal(size=(1, EVEN_DIMS[0])), jnp.float32)
          for _ in range(12)]
    arrivals = np.cumsum(rng.exponential(1e-4, size=12))
    out = serving.replay(plan, xs, arrivals, service_times={
        b: 1e-3 for b in plan.bucket_sizes})
    for x, y in zip(xs, out["results"]):
        np.testing.assert_allclose(y, oracle.run(x), atol=1e-3, rtol=1e-4)
    assert out["throughput_rps"] > 0
    assert out["stats"]["flushes"] <= 12   # backlog must coalesce
    # with a dense burst and 1ms service, later arrivals must have batched
    assert out["stats"]["flushes"] < 12


def test_replay_naive_equals_bucketed_results():
    pack = _rand_pack(DIMS, seed=4)
    plan = _plan(pack)
    rng = np.random.default_rng(9)
    xs = [jnp.asarray(rng.normal(size=(int(r), DIMS[0])), jnp.float32)
          for r in rng.choice([1, 2, 4], size=10)]
    arrivals = np.sort(rng.uniform(0, 1e-2, size=10))
    a = serving.replay(plan, xs, arrivals, max_bucket=1)
    b = serving.replay(plan, xs, arrivals)
    for ya, yb in zip(a["results"], b["results"]):
        np.testing.assert_allclose(ya, yb, atol=1e-5, rtol=1e-5)
