"""The serving kernels compile for a TPU v5e that is described, not attached.

The interpret-mode tests run the kernel bodies on the CPU, where nothing
checks what only the TPU compiler refuses: casts Mosaic has no lowering
for, blocks off the (8, 128) tiling, more VMEM than a kernel may use.  Each
test here lowers one kernel for one chip of a described ``v5e:2x2`` and
compiles it: the four megakernel schedules at MLP-GSC's widths in fp32 and
int8, the batch-tiled and db ones also over several batch tiles (decoded
once into VMEM scratch), and the per-layer kernel at its heuristic TPU
blocks for the last MLP-GSC layer and an LM FFN's two matmuls
(smollm-360m, 960 <-> 2560).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import fantastic4_fused_mlp as F
from repro.kernels import fantastic4_matmul as MM

GSC = (512, 512, 512, 256, 256, 128, 128, 12)
SHAPES = tuple(zip(GSC[:-1], GSC[1:]))
ACTS = ("relu",) * (len(SHAPES) - 1) + (None,)
ROWS = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_kernel_text(name, fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text          # a Mosaic kernel, compiled
    # under its stable name, whatever jit wraps it: the device trace names
    # each op event by its HLO instruction
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(", text), name
    return text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _per_layer_specs(s):
    """The batch-tiled kernel's per-layer operands at MLP-GSC's widths."""
    return (tuple(s((k // 2, n), jnp.uint8) for k, n in SHAPES),
            tuple(s((4,), jnp.float32) for _ in SHAPES),
            tuple(s((n,), jnp.float32) for _, n in SHAPES),
            tuple(s((n,), jnp.float32) for _, n in SHAPES),
            tuple(s((), jnp.float32) for _ in SHAPES))


@pytest.mark.parametrize("act_dtype", ["float32", "int8"])
@pytest.mark.parametrize("schedule", ["batch_tiled", "db", "ws", "stream"])
def test_megakernel_schedule_compiles(one_chip, schedule, act_dtype):
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    x = s((ROWS, GSC[0]), jnp.float32)
    if schedule in ("ws", "stream"):
        d = F.ws_width(SHAPES)
        n = len(SHAPES)
        stacked = (s((n, d // 2, d), jnp.uint8), s((n, 1, 4), jnp.float32),
                   s((n, 1, d), jnp.float32), s((n, 1, d), jnp.float32),
                   s((n, 1, 4), jnp.float32))
        kernel = (F.fantastic4_fused_mlp_ws_pallas if schedule == "ws"
                  else F.fantastic4_fused_mlp_stream_pallas)
        kw = {} if schedule == "ws" else {"block_m": 32}
        _compiled_kernel_text(
            f"fantastic4_fused_mlp_{schedule}_pallas",
            lambda x, *ops: kernel(x, *ops, shapes=SHAPES, activations=ACTS,
                                   act_dtype=act_dtype, **kw),
            x, *stacked)
        return
    per_layer = _per_layer_specs(s)
    _compiled_kernel_text(
        "fantastic4_fused_mlp_pallas",
        lambda x, *ops: F.fantastic4_fused_mlp_pallas(
            x, *ops, shapes=SHAPES, activations=ACTS, block_m=ROWS,
            act_dtype=act_dtype, double_buffer=schedule == "db"),
        x, *per_layer)


@pytest.mark.parametrize("act_dtype", ["float32", "int8"])
@pytest.mark.parametrize("schedule", ["batch_tiled", "db"])
def test_megakernel_decode_once_compiles(one_chip, schedule, act_dtype):
    """Several batch tiles: the first grid step decodes every layer into
    VMEM scratch and the grid runs in order."""
    block_m = ROWS // 4
    assert F.fused_mlp_decode_once(SHAPES, ROWS, block_m, act_dtype,
                                   double_buffer=schedule == "db")
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    per_layer = _per_layer_specs(s)
    _compiled_kernel_text(
        "fantastic4_fused_mlp_pallas",
        lambda x, *ops: F.fantastic4_fused_mlp_pallas(
            x, *ops, shapes=SHAPES, activations=ACTS, block_m=block_m,
            act_dtype=act_dtype, double_buffer=schedule == "db"),
        s((ROWS, GSC[0]), jnp.float32), *per_layer)


@pytest.mark.parametrize("k,n", [(128, 12), (960, 2560), (2560, 960)])
def test_per_layer_kernel_compiles(one_chip, k, n):
    s = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    cfg = autotune.heuristic_blocks(ROWS, k, n, backend="tpu")
    _compiled_kernel_text(
        "fantastic4_matmul_pallas",
        lambda *a: MM.fantastic4_matmul_pallas(
            *a, activation="relu", block_m=cfg.block_m,
            block_n=cfg.block_n, block_k=cfg.block_k),
        s((ROWS, k), jnp.float32), s((k // 2, n), jnp.uint8),
        s((4,), jnp.float32), s((n,), jnp.float32), s((n,), jnp.float32),
        s((), jnp.float32))
