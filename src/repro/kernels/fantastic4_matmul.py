"""Pallas TPU kernel: packed-int4 ACM matmul with fused §V epilogue.

TPU adaptation of the FantastIC4 ACM engine (DESIGN.md §2): the packed 4-bit
codes travel HBM→VMEM at 4 bits/weight (the paper's data-movement win); a
VMEM tile is decoded to ``W_tile = Σ_i ω_i B_i`` with VPU ops (the 4
"multipliers" of the paper become 4 scalar·mask AXPYs per tile) and consumed
by a single MXU matmul. The per-layer epilogue (×α₁ per-feature, +bias,
ReLU, ×α₂) is fused so the (M,N) output never round-trips HBM between ops —
the software analogue of the paper's pipelined float unit.

Layouts / tiling:
  x       (M, K)     activation tile (bm, bk) — revisited across the N grid
                     (activation-stationary dataflow, §V-C).
  packed  (K//2, N)  two codes per byte along K (sublane interleave unpack).
  omega   (1, 4) f32; bias/alpha1 (1, N) f32; alpha2 (1, 1) f32.
  out     (M, N)     accumulated in an f32 VMEM scratch across the K grid.

Grid: (M/bm, N/bn, K/bk), K innermost ("arbitrary"), M/N parallel.

Relation to the fused serving megakernel (fantastic4_fused_mlp.py): this
kernel fuses *within* one layer, so a served L-layer stack still round-trips
the (M, N) activation through HBM L−1 times:

    per-layer:  HBM ─x─▶ [L₁] ─▶ HBM ─▶ [L₂] ─▶ HBM ─▶ … ─▶ [L_n] ─▶ HBM
    fused:      HBM ─x─▶ [L₁ ▸ L₂ ▸ … ▸ L_n] ─▶ HBM   (acts in VMEM scratch)

The megakernel is the default serving path whenever the whole stack's
packed weights + activation scratch fit the VMEM budget (all paper MLPs
do at 4 bits/weight); this kernel is the fallback for oversized layers and
the building block for everything non-MLP.  Block sizes default to the
shape-aware autotuner (autotune.py) via ops.fantastic4_matmul.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .autotune import LANE, SUBLANE


def decode_tile(packed: jax.Array, omega) -> jax.Array:
    """(k//2, n) uint8 row-pair codes -> (k, n) f32 W = Σ_i ω_i B_i.

    ``omega`` is indexed ``[0, i]`` (a (1, 4) ref or value).  The codes are
    widened to int32 before the bit operations: Mosaic has no uint8 ->
    float32 cast, and the int32 path gives the same bits.
    """
    codes32 = packed.astype(jnp.int32)
    lo = codes32 & 0xF
    hi = (codes32 >> 4) & 0xF
    codes = jnp.stack([lo, hi], axis=1)                   # (k//2, 2, n)
    codes = codes.reshape(packed.shape[0] * 2, packed.shape[1])
    # the 4-multiplier ACM recombination
    w = jnp.zeros(codes.shape, jnp.float32)
    for i in range(4):
        bit = ((codes >> i) & 1).astype(jnp.float32)
        w = w + omega[0, i] * bit
    return w


def lane_dot(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` in f32, one 128-column slab of ``w`` at a time.

    Every kernel multiplies through this, so an output column is always
    the product of the same (rows, K) x (K, 128) dot, whatever width the
    kernel holds: the schedules keep different widths (the per-layer
    blocks, each layer's padded width, the stack's widest layer), and
    XLA's CPU dot rounds a column differently at different widths.  One
    slab is one pass of the 128-wide MXU.  The precision is full f32: the
    decoded weights are f32 sums of the ω codebook, which one bf16 MXU
    pass would round.
    """
    return jnp.concatenate(
        [jnp.dot(x, w[:, j:j + LANE], preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)
         for j in range(0, w.shape[1], LANE)], axis=1)


def _kernel(x_ref, w_ref, omega_ref, alpha1_ref, bias_ref, alpha2_ref,
            o_ref, acc_ref, *, activation: Optional[str], n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_tile = decode_tile(w_ref[...], omega_ref)           # (bk, bn) f32
    x_tile = x_ref[...].astype(jnp.float32)
    acc_ref[...] += lane_dot(x_tile, w_tile)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        y = acc_ref[...]
        y = y * alpha1_ref[...]                           # (1, bn) broadcasts
        y = y + bias_ref[...]
        y = ref.apply_activation(y, activation)
        y = y * alpha2_ref[0, 0]
        o_ref[...] = y.astype(o_ref.dtype)


def trim_padding(out: jax.Array, m: int, n: int, interpret: bool
                 ) -> jax.Array:
    """A kernel's padded result cut to its ``(m, n)`` rows and columns.

    Compiled, the cut is part of the kernel's own program, so a launch is
    one dispatch.  Interpreted, the padded result is returned and the
    caller cuts it outside the jit (``ops.unpad``): traced together with
    the interpreted kernel body, XLA's CPU backend fuses the cut into the
    dot and rounds some columns differently from the other kernels, which
    breaks the int8 megakernel-vs-chain parity.  Mosaic's kernel is opaque
    to XLA, so the compiled cut changes no result.
    """
    return out if interpret else out[:m, :n]


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "out_dtype", "block_m", "block_n",
                     "block_k", "interpret"))
def fantastic4_matmul_pallas(
        x: jax.Array, packed: jax.Array, omega: jax.Array,
        alpha1: jax.Array, bias: jax.Array, alpha2: jax.Array,
        *, activation: Optional[str] = None, out_dtype=None,
        block_m: int = 128, block_n: int = 256, block_k: int = 512,
        interpret: bool = False) -> jax.Array:
    """x:(M,K) f32/bf16/int8 · packed:(K//2,N) uint8 -> (M,N) out_dtype."""
    m, k = x.shape
    k2, n = packed.shape
    assert k == 2 * k2, (x.shape, packed.shape)
    out_dtype = out_dtype or x.dtype

    # pad to whole (8, 128) tiles first, the same padding the fused
    # megakernel applies, so a layer that fits one block runs the very same
    # dot shape on both paths (the int8 bit-exactness contract).
    x = _pad_to(_pad_to(x, 0, SUBLANE), 1, LANE)
    packed = _pad_to(_pad_to(packed, 0, LANE // 2), 1, LANE)
    # blocks are whole tiles too: Mosaic refuses any other block shape
    bm = min(_round_up(block_m, SUBLANE), x.shape[0])
    bn = min(_round_up(block_n, LANE), packed.shape[1])
    bk = min(_round_up(block_k, LANE), x.shape[1])
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(packed, 0, bk // 2), 1, bn)
    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    alpha1 = _pad_to(alpha1.reshape(1, -1).astype(jnp.float32), 1, bn)
    bias = _pad_to(bias.reshape(1, -1).astype(jnp.float32), 1, bn)
    alpha2 = alpha2.reshape(1, 1).astype(jnp.float32)
    omega = omega.reshape(1, 4).astype(jnp.float32)

    out = pl.pallas_call(
        functools.partial(_kernel, activation=activation, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 4), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fantastic4_matmul_pallas",
    )(xp, wp, omega, alpha1, bias, alpha2)
    return trim_padding(out, m, n, interpret)
