"""Pallas TPU kernel: fused ECL assignment + dequantization (QAT hot loop).

Every EC4T training step re-assigns every master weight to one of the 16
subset-sum centroids (cost = squared distance + entropy penalty, §IV-C) and
dequantizes it for the STE forward. Unfused, that is an HBM-bound chain of
~20 elementwise ops over every parameter; fused it is one read of W and one
write each of (codes, w_hat) per element.

Tiling: plain 2-D elementwise grid, (block_r, block_c) VMEM tiles. The 16
candidate costs are an unrolled VPU loop with a running (best_cost,
best_code, best_val) select — no gather, MXU untouched.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, omega_ref, pen_ref, codes_ref, what_ref):
    w = w_ref[...].astype(jnp.float32)
    best_cost = jnp.full(w.shape, jnp.inf, jnp.float32)
    best_code = jnp.zeros(w.shape, jnp.uint8)
    best_val = jnp.zeros(w.shape, jnp.float32)
    for c in range(16):
        v = jnp.zeros((), jnp.float32)
        for i in range(4):
            if (c >> i) & 1:
                v = v + omega_ref[0, i]
        cost = (w - v) ** 2 + pen_ref[0, c]
        take = cost < best_cost
        best_cost = jnp.where(take, cost, best_cost)
        best_code = jnp.where(take, jnp.uint8(c), best_code)
        best_val = jnp.where(take, v, best_val)
    codes_ref[...] = best_code
    what_ref[...] = best_val.astype(what_ref.dtype)


def _pad_to(a, axis, mult):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_c", "interpret"))
def ecl_quant_pallas(w: jax.Array, omega: jax.Array, penalty: jax.Array,
                     *, block_r: int = 256, block_c: int = 512,
                     interpret: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """w:(R,C) -> (codes uint8 (R,C), w_hat f32 (R,C)).

    penalty: (16,) f32 = lam * (-log2 probs), precomputed on host/XLA side.
    """
    r, c = w.shape
    br, bc = min(block_r, r), min(block_c, c)
    wp = _pad_to(_pad_to(w, 0, br), 1, bc)
    rp, cp = wp.shape
    grid = (rp // br, cp // bc)

    omega2 = omega.reshape(1, 4).astype(jnp.float32)
    pen2 = penalty.reshape(1, 16).astype(jnp.float32)

    codes, what = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((1, 4), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 16), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, cp), jnp.uint8),
            jax.ShapeDtypeStruct((rp, cp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ecl_quant_pallas",
    )(wp, omega2, pen2)
    return codes[:r, :c], what[:r, :c]
