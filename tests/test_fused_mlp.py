"""Fused serving megakernel vs the chained per-layer oracle.

Every paper stack (MLP-GSC, MLP-HR, LeNet-300-100 — the latter has
odd/unpadded dims: 784 in, 300/100/10 out), batch=1 and odd batches, plus
the VMEM-budget fallback and a trained freeze->serve end-to-end check.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_mlps import MLPS
from repro.core import bitplanes as bp
from repro.kernels import ops, ref
from repro.kernels.fantastic4_fused_mlp import (fused_mlp_decode_once,
                                                fused_mlp_fits,
                                                fused_mlp_vmem_bytes,
                                                stream_mlp_fits,
                                                stream_mlp_vmem_bytes)
from repro.models import mlp as M

# (K, N) chains: the three paper stacks + a deliberately odd/unpadded one.
STACKS = {name: (cfg.d_in,) + tuple(cfg.features) for name, cfg in MLPS.items()}
STACKS["odd"] = (33, 130, 72, 7)


def _rand_pack(dims, seed=0, scale=None):
    """Synthetic frozen pack with BN-realistic magnitudes (activations stay
    O(1), as freeze_mlp's folded constants make them)."""
    rng = np.random.default_rng(seed)
    layers = []
    for i, (k, n) in enumerate(zip(dims[:-1], dims[1:])):
        codes = rng.integers(0, 16, size=(k + (k % 2), n)).astype(np.uint8)
        if k % 2:
            codes[-1] = 0
        s = scale if scale is not None else 1.0 / np.sqrt(k)
        layers.append({
            "packed": bp.pack_codes_rows(jnp.asarray(codes)),
            "omega": jnp.asarray(rng.normal(size=4) * s, jnp.float32),
            "alpha1": jnp.asarray(rng.normal(size=n) * 0.5, jnp.float32),
            "bias": jnp.asarray(rng.normal(size=n) * 0.1, jnp.float32),
            "alpha2": jnp.asarray(np.float32(rng.uniform(0.5, 1.5))),
            "shape": (k, n),
            "activation": "relu" if i < len(dims) - 2 else None,
        })
    return {"layers": layers, "act_bits": None}


def _oracle(pack, x):
    for l in pack["layers"]:
        if l["shape"][0] % 2:
            # odd K: the pack carries one zero code row — mirror it on x
            x = jnp.pad(x, ((0, 0), (0, 1)))
        x = ref.fantastic4_matmul_ref(
            x, l["packed"], l["omega"], bias=l["bias"], alpha1=l["alpha1"],
            alpha2=l["alpha2"], activation=l["activation"],
            out_dtype=jnp.float32)
    return x


@pytest.mark.parametrize("stack", sorted(STACKS))
@pytest.mark.parametrize("batch", [1, 5, 64])
def test_fused_matches_per_layer_oracle(stack, batch):
    dims = STACKS[stack]
    # deterministic seed (hash() varies per interpreter run); rtol covers
    # the occasional pack whose activations drift past O(1), where f32
    # accumulation-order noise exceeds any fixed absolute gate.
    pack = _rand_pack(dims, seed=sorted(STACKS).index(stack) * 100 + batch)
    rng = np.random.default_rng(batch)
    x = jnp.asarray(rng.normal(size=(batch, dims[0])), jnp.float32)
    y_fused = M.mlp_serve(pack, x, use_kernel=True, fused=True,
                          interpret=True)
    y_ref = _oracle(pack, x)
    assert y_fused.shape == (batch, dims[-1])
    np.testing.assert_allclose(y_fused, y_ref, atol=1e-3, rtol=1e-5)


def test_fused_matches_per_layer_kernel_path():
    dims = STACKS["mlp-hr"]
    pack = _rand_pack(dims, seed=7)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, dims[0])),
                    jnp.float32)
    y_fused = M.mlp_serve(pack, x, fused=True, interpret=True)
    y_chain = M.mlp_serve(pack, x, fused=False, interpret=True,
                          block_m=None)
    np.testing.assert_allclose(y_fused, y_chain, atol=1e-3, rtol=1e-4)


def test_odd_k_serves_on_every_path():
    """Odd-K packs work on fused, per-layer-kernel AND oracle mlp_serve
    paths (each mirrors the pack's zero code row with a zero x column)."""
    pack = _rand_pack(STACKS["odd"], seed=11)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(3, 33)),
                    jnp.float32)
    y_ref = _oracle(pack, x)
    for kwargs in ({"fused": True}, {"fused": False},
                   {"use_kernel": False}):
        y = M.mlp_serve(pack, x, interpret=True, **kwargs)
        np.testing.assert_allclose(y, y_ref, atol=1e-3, rtol=1e-4,
                                   err_msg=str(kwargs))


def test_vmem_fallback_triggers_and_matches():
    """A 1-byte budget forces the per-layer fallback; result is unchanged."""
    dims = STACKS["odd"]
    pack = _rand_pack(dims, seed=3)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(4, dims[0])),
                    jnp.float32)
    shapes = tuple(l["shape"] for l in pack["layers"])
    assert fused_mlp_fits(shapes)
    assert not fused_mlp_fits(shapes, budget_bytes=1)
    y_fb = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True,
                                    vmem_budget_bytes=1)
    y_ref = _oracle(pack, x)
    np.testing.assert_allclose(y_fb, y_ref, atol=1e-3, rtol=1e-4)


def test_vmem_estimate_scales_with_stack():
    small = fused_mlp_vmem_bytes(((128, 128),))
    big = fused_mlp_vmem_bytes(((512, 512), (512, 512), (512, 256)))
    assert 0 < small < big
    # all paper stacks fit the default budget at 4 bits/weight
    for dims in STACKS.values():
        shapes = tuple(zip(dims[:-1], dims[1:]))
        assert fused_mlp_fits(shapes), dims


def test_stream_vmem_estimate_scales_with_batch_not_depth():
    """The streaming schedule's defining trade: its working set grows with
    the resident batch but NOT with layer count (one layer per grid
    step), so deep stacks that bust the batch-tiled budget still fit."""
    shapes3 = ((512, 512),) * 3
    shapes9 = ((512, 512),) * 9
    # streamed per-step set: invariant in L ...
    assert stream_mlp_vmem_bytes(shapes3, rows=64) == \
        stream_mlp_vmem_bytes(shapes9, rows=64)
    # ... but grows with the resident batch
    assert stream_mlp_vmem_bytes(shapes3, rows=64) < \
        stream_mlp_vmem_bytes(shapes3, rows=512)
    # batch-tiled grows with L instead
    assert fused_mlp_vmem_bytes(shapes3) < fused_mlp_vmem_bytes(shapes9)
    # a budget between the two admits stream but not batch-tiled
    mid = (stream_mlp_vmem_bytes(shapes9, rows=64)
           + fused_mlp_vmem_bytes(shapes9, block_m=64)) // 2
    assert stream_mlp_fits(shapes9, rows=64, budget_bytes=mid)
    assert not fused_mlp_fits(shapes9, block_m=64, budget_bytes=mid)
    assert not stream_mlp_fits(shapes9, rows=64, budget_bytes=1)
    assert not stream_mlp_fits((), rows=64)
    # the act scratch is charged at the kernel's real whole-tile padding:
    # 264 rows with 256-row tiles allocate a 512-row scratch, not 264
    assert stream_mlp_vmem_bytes(shapes3, rows=264, block_m=256) == \
        stream_mlp_vmem_bytes(shapes3, rows=512, block_m=256)
    assert stream_mlp_vmem_bytes(shapes3, rows=264, block_m=8) < \
        stream_mlp_vmem_bytes(shapes3, rows=264, block_m=256)


def test_stream_schedule_decode_amortized_paths_match():
    """Streaming schedule vs oracle across tile shapes that exercise the
    decode-once/reuse machinery: multiple batch tiles, ragged final tile,
    single-layer stack, odd-K dims."""
    for dims, batch, bm in (
            (STACKS["odd"], 40, 16),       # ragged last tile (40 = 2.5*16)
            (STACKS["lenet-300-100"], 24, 8),
            ((33, 17), 9, 8),              # single layer, odd everything
    ):
        pack = _rand_pack(dims, seed=sum(dims))
        x = jnp.asarray(
            np.random.default_rng(batch).normal(size=(batch, dims[0])),
            jnp.float32)
        y = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True,
                                     schedule="stream", block_m=bm)
        np.testing.assert_allclose(y, _oracle(pack, x), atol=1e-3,
                                   rtol=1e-4, err_msg=str((dims, batch, bm)))


GSC_DIMS = STACKS["mlp-gsc"]


@pytest.mark.parametrize("dims,rows,bm,act_dtype,schedule", [
    (STACKS["odd"], 37, 16, "float32", "batch_tiled"),    # ragged last tile
    (STACKS["lenet-300-100"], 40, 8, "float32", "batch_tiled"),
    (GSC_DIMS, 40, 16, "int8", "batch_tiled"),             # 3 tiles
    (STACKS["odd"], 40, 16, "float32", "db"),              # 16-row tiles
], ids=["ragged", "lenet", "gsc-int8", "db"])
def test_multi_tile_decode_once_is_bitwise_the_per_tile_decode(
        dims, rows, bm, act_dtype, schedule):
    """A call of several batch tiles decodes each layer once into VMEM
    scratch; each tile's rows run as a call of one tile (zero rows fill a
    ragged last tile, as the kernel pads it) take the per-step decode.  W
    holds the same values and meets the same dot, so the logits agree bit
    for bit.  (Against one tile of all rows they need not: XLA's CPU dot
    rounds a row differently at another row count.)"""
    shapes = tuple(zip(dims[:-1], dims[1:]))
    assert fused_mlp_decode_once(shapes, rows, bm, act_dtype,
                                 double_buffer=schedule == "db")
    assert not fused_mlp_decode_once(shapes, bm, bm, act_dtype,
                                     double_buffer=schedule == "db")
    pack = _rand_pack(dims, seed=rows + bm)
    x = jnp.asarray(np.random.default_rng(rows).normal(size=(rows, dims[0])),
                    jnp.float32)
    kw = {"interpret": True, "block_m": bm, "schedule": schedule}
    if act_dtype == "int8":
        kw.update(act_dtype="int8",
                  act_scales=[0.05] * (len(pack["layers"]) - 1))
    y = np.asarray(ops.fantastic4_mlp_fused(x, pack["layers"], **kw))
    tiles = []
    for i in range(0, rows, bm):
        t = x[i:i + bm]
        one = jnp.pad(t, ((0, bm - t.shape[0]), (0, 0)))
        tiles.append(np.asarray(
            ops.fantastic4_mlp_fused(one, pack["layers"], **kw))[:len(t)])
    np.testing.assert_array_equal(y, np.concatenate(tiles))
    # and the result is the stack's, not only self-consistent
    if act_dtype == "float32":
        np.testing.assert_allclose(y, _oracle(pack, x), atol=1e-3,
                                   rtol=1e-5)


def test_decode_once_needs_several_tiles_and_the_decoded_stack_in_vmem():
    for name, rows, bm, act in (("lenet-300-100", 65536, 32, "float32"),
                                ("mlp-gsc", 65536, 128, "int8"),
                                ("mlp-hr", 300, 128, "float32")):
        dims = STACKS[name]
        shapes = tuple(zip(dims[:-1], dims[1:]))
        assert fused_mlp_decode_once(shapes, rows, bm, act), name
        assert fused_mlp_decode_once(shapes, rows, bm, act,
                                     double_buffer=True), name
        # one tile, however the rows fall: the per-step decode
        for one in (1, bm - 3, bm):
            assert not fused_mlp_decode_once(shapes, one, bm, act), name
    # four 1024x1024 layers: 4 MiB decoded each.  The per-step working
    # set fits the budget, all four held at once do not.
    wide = ((1024, 1024),) * 4
    assert fused_mlp_fits(wide, block_m=128)
    assert not fused_mlp_decode_once(wide, 1024, 128)
    assert not fused_mlp_decode_once((), 1024, 128)


def test_gelu_activation_matches_on_every_schedule():
    """gelu epilogues (the transformer FFN's activation) vs the oracle on
    all four kernel schedules — the static-activation paths (batch_tiled,
    db) and the coded-activation paths (ws, stream) alike."""
    dims = (33, 48, 17)
    pack = _rand_pack(dims, seed=21)
    for l in pack["layers"][:-1]:
        l["activation"] = "gelu"
    x = jnp.asarray(np.random.default_rng(3).normal(size=(9, dims[0])),
                    jnp.float32)
    y_ref = _oracle(pack, x)
    for sched in ("batch_tiled", "db", "ws", "stream"):
        y = ops.fantastic4_mlp_fused(x, pack["layers"], interpret=True,
                                     schedule=sched)
        np.testing.assert_allclose(y, y_ref, atol=1e-3, rtol=1e-4,
                                   err_msg=sched)


def test_frozen_pack_serves_fused():
    """freeze_mlp -> mlp_serve(fused) == oracle serve on a real pack."""
    import jax
    from repro.core import qat
    cfg = MLPS["lenet-300-100"]
    params, bn = M.mlp_init(jax.random.PRNGKey(0), cfg)
    qs = qat.build_qstate(params)
    pack = M.freeze_mlp(params, qs, bn, lam=0.02)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(9, cfg.d_in)),
                    jnp.float32)
    y_fused = M.mlp_serve(pack, x, use_kernel=True, fused=True,
                          interpret=True)
    y_oracle = M.mlp_serve(pack, x, use_kernel=False)
    assert float(jnp.max(jnp.abs(y_fused - y_oracle))) < 1e-3
