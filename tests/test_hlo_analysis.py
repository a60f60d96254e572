"""Trip-count-aware HLO walker vs hand counts and XLA cost analysis."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import hlo_analysis as H


def test_scan_flops_scale_with_trip_count():
    def f(x, w):
        def body(c, wi):
            return c @ wi, None
        y, _ = jax.lax.scan(body, x, w)
        return y
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    c = jax.jit(f).lower(x, w).compile()
    r = H.analyze(c.as_text())
    base = 2 * 128 ** 3
    assert 10 * base <= r["flops"] <= 11 * base


def test_loop_free_matches_xla():
    def g(a, b):
        return jnp.tanh(a @ b) @ b
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = jax.jit(g).lower(a, a).compile()
    r = H.analyze(c.as_text())
    xla = c.cost_analysis()
    assert abs(r["flops"] - xla["flops"]) / xla["flops"] < 0.02
    assert abs(r["bytes"] - xla["bytes accessed"]) / xla["bytes accessed"] < 0.2


def test_collectives_counted(tmp_path):
    from conftest import run_with_devices
    run_with_devices("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import hlo_analysis as H
mesh = jax.make_mesh((4,), ("data",))
def f(x):
    return jax.lax.with_sharding_constraint(
        x.sum(0, keepdims=True), NamedSharding(mesh, P(None, None)))
x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
with mesh:
    c = jax.jit(f, in_shardings=NamedSharding(mesh, P("data", None)),
                out_shardings=NamedSharding(mesh, P(None, None))).lower(x).compile()
r = H.analyze(c.as_text())
assert r["collectives"]["total"] > 0, r["collectives"]
print("collective bytes:", r["collectives"]["total"])
""", n_devices=4)
