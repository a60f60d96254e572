"""Pallas TPU megakernel: a whole FantastIC4 MLP stack in one ``pallas_call``.

The paper's hardware win (§V) is a *pipelined* datapath: activations never
leave the chip between FC layers while the 4-bit weights stream in.  The
per-layer kernel already fuses the epilogue, but chaining L ``pallas_call``s
still round-trips every (M, N) activation through HBM L−1 times.  At 4
bits/weight the paper-shaped stacks fit in VMEM whole (MLP-GSC, the largest,
packs to ~0.4 MiB), so this kernel keeps the *activations* resident instead:

    HBM                      VMEM (one grid step, batch tile i)
    ────                     ──────────────────────────────────────────────
    x[i·bm:(i+1)·bm, :] ───▶ act₀ ─┐
    packed W₁ … W_L ───────▶ (all  │ decode Σωᵢ·Bᵢ → W_l, MXU matmul,
    ω, α₁, b, scale per l ──▶ L at │ epilogue ×α₁ +b ReLU ×scale — result
                              once)│ written to the act scratch, read
    out[i·bm:(i+1)·bm, :] ◀─ act_L ┘ back as the next layer's input

Only the first input tile and the last output tile touch HBM per grid step;
inter-layer activations exist solely as kernel values, which Pallas keeps
on-chip by construction (kernel intermediates cannot spill to HBM), with
the final activation parking in a ``(block_m, max_width)`` VMEM scratch
before the single HBM store.  ``fused_mlp_vmem_bytes`` budgets that
activation working set either way.  The grid is 1-D over batch tiles
(weights use constant index maps, so they are fetched once and revisited).

Where the decode happens depends on the grid alone
(``fused_mlp_decode_once``):

* **one batch tile** — each layer's codes are decoded in the grid step, as
  a kernel value that feeds the layer's matmul (one step, one decode).
* **several batch tiles**, when every layer's decoded W fits the VMEM
  budget at once — the first grid step decodes every layer into its own
  ``(K_l, N_l)`` f32 VMEM scratch (each at its own padded shape), and every
  batch tile multiplies against those scratches: L decodes per call
  instead of L per tile.  The grid then runs in order (``"arbitrary"``
  semantics, since step i>0 reads what step 0 wrote); a one-core v5e loses
  nothing by that, but a two-TensorCore (megacore) part would lose its
  split of the batch tiles across cores on this path.
* several tiles whose decoded stack would bust the budget — the per-step
  decode of the one-tile case, tile after tile (``"parallel"``).

Either way W holds the same values and meets the same ``lane_dot``, so the
outputs are bit for bit the same.

Two orthogonal variants on top of the PR-1 fp32 path:

* ``act_dtype="int8"`` — the paper's §VI-C FPGA configuration (8-bit
  inter-layer activations).  Each non-final layer's epilogue emits
  ``round(y / s_l)`` clipped to [−127, 127] and *cast to int8* before the
  value feeds the next layer's MXU op; the caller folds ``s_{l−1}`` into
  layer l's α₁ exactly as the per-layer ``mlp_serve_int8`` chain does, so
  the two paths agree on the quantized grid bit for bit.  The per-layer
  ``scale`` operand carries the quantization scale s_l instead of α₂
  (which the int8 serving path never uses; the final layer returns raw
  float logits).
* ``n_halves=2`` — double-buffered batch tile, emulating the paper's
  pipelined row processing: the (bm, ·) tile splits into two row groups
  that traverse the stack on a skewed schedule (group 1 runs layer l while
  group 0 runs layer l+1), so decode/MXU work on consecutive layers can
  overlap instead of serialising per layer.  Row groups are independent
  (each output row depends only on its input row), so results are
  unchanged.

Layer dims are zero-padded to ``DIM_ALIGN`` multiples: zero *codes* decode
to zero *weights* (code 0 has no set bit-planes), and padded epilogue
columns carry α₁ = b = 0, so padding is exactly absorbed — layer l+1's
padded K rows meet zero weights, and the final slice drops the rest.  In
int8 mode padded columns quantize to round(0/s) = 0, preserving the
invariant.

``fused_mlp_fits`` estimates the VMEM working set; callers fall back to the
per-layer kernel when a stack exceeds the budget (e.g. a >VMEM embedding
projection) — the software analogue of the paper's "fits the FPGA's on-chip
SRAM" precondition.

A third schedule serves the latency path (batch=1 bucket of the serving
engine): the **weight-stationary** variant
(``fantastic4_fused_mlp_ws_pallas``).  The batch-tiled megakernel above
keeps *all* layer weights VMEM-resident and streams batch tiles past them;
with a single-row batch there is nothing left to stream, so holding the
whole stack on-chip only inflates the working set.  The ws variant flips
the dataflow: the grid runs over *layers* (sequential ``"arbitrary"``
semantics), the tiny activation tile is the resident operand (a VMEM
scratch carried across grid steps), and each grid step fetches exactly one
layer's packed codes — every weight byte crosses HBM→VMEM once per
inference and is the stationary operand of its own step while the
activation hops through the scratch.  Layer operands are stacked into
uniform ``(L, D/2, D)`` / ``(L, 1, D)`` arrays (D = the stack's widest
padded dim) so one ``BlockSpec`` indexed by the layer id can address them;
zero-padded codes decode to zero weights and padded epilogue columns carry
α₁ = b = 0, so the uniform width is exactly absorbed (padded columns stay
0.0 through relu and int8 re-quantization alike).  Per-step VMEM is one
layer's codes + one decoded tile instead of the whole stack, so the ws
schedule also serves stacks whose *total* packed size busts the megakernel
budget, still in one launch.

The fourth schedule — the **decode-amortized streaming** variant
(``fantastic4_fused_mlp_stream_pallas``) — covers the mid-size batches
where neither of the above dominates.  The batch-tiled kernel keeps the
whole stack resident (decoded once per call where the decoded stack fits
VMEM, else once per batch tile); the ws kernel decodes each layer once but
cannot tile the batch at all (the whole batch rides in its scratch and
meets one layer per step).  The streaming grid is ``(layers, batch
tiles)`` ordered layers-outer / batch-tiles-inner: at step (l, 0) layer
l's codes are decoded once into a persistent ``(D, D)`` VMEM scratch, and
every subsequent batch tile of that layer reuses the decoded tile — decode
runs **once per layer per inference batch**, L·T matmuls share L decodes.
The activation ping-pongs through a whole-batch ``(M, D)`` VMEM scratch
(tile i's rows are read and rewritten in place — row ranges are disjoint
across tiles, so no tile ever reads another's output).  Per-step streamed
VMEM is one layer's codes + the decoded tile + one batch tile, so like the
ws schedule it serves stacks whose *total* packed size busts the
batch-tiled budget — but unlike ws it still tiles the batch, which is what
makes it the mid-size/large-batch rescue schedule.  Operands are the same
stacked uniform-width arrays as the ws kernel (``build_ws_operands``), so
the two schedules share their decode + epilogue arithmetic term for term
and the int8 grid is bit-identical across all four schedules.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref
from .fantastic4_matmul import decode_tile, lane_dot, trim_padding

# layer dims are padded to this multiple (f32 lane width) before entering
# the kernel; keeps every in-kernel slice tile-aligned.
DIM_ALIGN = 128
# conservative per-core budget: 16 MiB VMEM minus pipelining headroom.
VMEM_BUDGET_BYTES = 12 << 20
# packed code rows per step of the decode into VMEM scratch: one uint8
# (32, 128) tile of rows.
DECODE_CHUNK = 32


def _round_up(v: int, mult: int) -> int:
    return -(-max(v, 1) // mult) * mult


def padded_shapes(shapes: Sequence[Tuple[int, int]],
                  dim_align: int = DIM_ALIGN) -> Tuple[Tuple[int, int], ...]:
    return tuple((_round_up(k, dim_align), _round_up(n, dim_align))
                 for k, n in shapes)


def fused_mlp_vmem_bytes(shapes: Sequence[Tuple[int, int]],
                         block_m: int = 128,
                         dim_align: int = DIM_ALIGN,
                         act_dtype: str = "float32",
                         double_buffer: bool = False) -> int:
    """Working-set estimate for one grid step (bytes) of the per-step
    decode: the kernel's form for a single batch tile.

    packed codes for all layers + the largest decoded W tile + the x tile,
    activation scratch, output tile and epilogue vectors; ×2 on the
    HBM-fetched operands for the pipeline's double buffering.  int8 mode
    adds the quantized copy of the activation tile (1 byte/elem) that each
    epilogue materialises before the next layer's MXU op; the
    double-buffered schedule keeps up to two decoded W tiles live (layer l
    serves row group 1 one tick after group 0).  Plan resolution and the
    chain fallback decide on this estimate.  A multi-tile call that decodes
    once holds every layer's decoded W instead of the largest
    (``fused_mlp_decode_once``).
    """
    ps = padded_shapes(shapes, dim_align)
    packed = sum(kp // 2 * np_ for kp, np_ in ps)          # uint8
    epilogue = sum(2 * 4 * np_ + 4 * 4 + 4 for _, np_ in ps)
    decoded = max(4 * kp * np_ for kp, np_ in ps)
    if double_buffer:
        decoded *= 2
    max_w = max([ps[0][0]] + [np_ for _, np_ in ps])
    x_tile = 4 * block_m * ps[0][0]
    out_tile = 4 * block_m * ps[-1][1]
    act = 4 * block_m * max_w
    if act_dtype == "int8":
        act += block_m * max_w
    return 2 * (packed + epilogue + x_tile + out_tile) + decoded + act


def fused_mlp_fits(shapes: Sequence[Tuple[int, int]], *,
                   block_m: int = 128,
                   budget_bytes: int = VMEM_BUDGET_BYTES,
                   dim_align: int = DIM_ALIGN,
                   act_dtype: str = "float32",
                   double_buffer: bool = False) -> bool:
    """True when the whole stack's working set fits the VMEM budget."""
    if not shapes:
        return False
    return fused_mlp_vmem_bytes(shapes, block_m, dim_align,
                                act_dtype, double_buffer) <= budget_bytes


def fused_mlp_decode_once(shapes: Sequence[Tuple[int, int]], rows: int,
                          block_m: int = 128,
                          act_dtype: str = "float32",
                          double_buffer: bool = False,
                          budget_bytes: int = VMEM_BUDGET_BYTES,
                          dim_align: int = DIM_ALIGN) -> bool:
    """True when a batch-tiled call over ``rows`` decodes each layer once.

    That is when the grid has more than one batch tile and the working set
    with every layer's decoded W held in VMEM (the sum of 4·K_l·N_l over
    layers in place of the largest tile; twice over on the double-buffered
    schedule, where the TPU compiler takes about one more copy of it) fits
    ``budget_bytes``.  Otherwise the kernel decodes in every grid step.
    ``fantastic4_fused_mlp_pallas`` decides with this at the default
    budget; serving plans report it.
    """
    if not shapes:
        return False
    bm = min(block_m, _round_up(rows, 8))
    if _round_up(rows, bm) // bm < 2:
        return False
    copies = 2 if double_buffer else 1
    sizes = [4 * kp * np_ for kp, np_ in padded_shapes(shapes, dim_align)]
    held = (fused_mlp_vmem_bytes(shapes, bm, dim_align, act_dtype,
                                 double_buffer)
            + copies * (sum(sizes) - max(sizes)))
    return held <= budget_bytes


def _decode_into(w_ref, packed_ref, omega_ref) -> None:
    """``w_ref[...] = decode_tile(packed_ref[...], omega_ref)``, one
    ``DECODE_CHUNK`` of code rows per loop step, so that the decode's int32
    and bit-plane temporaries take one chunk of VMEM, not one layer."""
    rows = packed_ref.shape[0]
    chunk = math.gcd(rows, DECODE_CHUNK)

    def step(i, carry):
        r = pl.multiple_of(i * chunk, chunk)
        w_ref[pl.ds(2 * r, 2 * chunk), :] = decode_tile(
            packed_ref[pl.ds(r, chunk), :], omega_ref)
        return carry

    jax.lax.fori_loop(0, rows // chunk, step, 0)


def _kernel(*refs, activations: Tuple[Optional[str], ...],
            act_dtype: str, n_halves: int, decode_once: bool):
    n_layers = len(activations)
    x_ref = refs[0]
    layer_refs = refs[1:1 + 5 * n_layers]
    o_ref = refs[1 + 5 * n_layers]
    act_ref = refs[2 + 5 * n_layers]          # (bm, max_width) VMEM scratch
    w_refs = refs[3 + 5 * n_layers:]          # per-layer decoded W scratch
    int8_acts = act_dtype == "int8"

    if decode_once:
        # the first batch tile decodes every layer into its scratch, which
        # persists across grid steps: every later tile reads it.
        @pl.when(pl.program_id(0) == 0)
        def _():
            for l, w_ref in enumerate(w_refs):
                _decode_into(w_ref, *layer_refs[5 * l:5 * l + 2])

    # Per-step decode: each layer's weight tile is decoded once and shared
    # across row groups: in the skewed schedule layer l serves group 0 at
    # tick l and group 1 at tick l+1, so the decoded tile stays live for
    # exactly one extra tick (≤2 decoded tiles concurrently) instead of
    # being decoded per group.  The python-level dict is static — the
    # compiler sees one decode_tile per layer either way.
    decoded = {}

    def apply_layer(cur: jax.Array, l: int, last_use: bool) -> jax.Array:
        packed_ref, omega_ref, alpha1_ref, bias_ref, scale_ref = \
            layer_refs[5 * l:5 * l + 5]
        if decode_once:
            w = w_refs[l]     # lane_dot loads one 128-column slab at a time
        else:
            if l not in decoded:
                decoded[l] = decode_tile(packed_ref[...], omega_ref)
            w = decoded[l]
            if last_use:
                del decoded[l]
        y = lane_dot(cur, w)
        y = y * alpha1_ref[...] + bias_ref[...]
        y = ref.apply_activation(y, activations[l])
        if int8_acts:
            if l < n_layers - 1:
                # §VI-C re-quantization: the activation leaves the layer as
                # a true int8 value (the float32 round-trip is exact on the
                # [-127, 127] grid, and mirrors the per-layer chain's math
                # term for term so both paths agree bitwise).
                q = jnp.clip(jnp.round(y / scale_ref[0, 0]), -127.0, 127.0)
                y = q.astype(jnp.int8).astype(jnp.float32)
        else:
            y = y * scale_ref[0, 0]           # fp32 epilogue: ×α₂
        return y

    x = x_ref[...].astype(jnp.float32)
    bm = x.shape[0]
    rows = bm // n_halves
    halves = [x[h * rows:(h + 1) * rows, :] for h in range(n_halves)]
    # Skewed schedule (trivial for n_halves=1): at tick t, row group h runs
    # layer t−h, so group 1 streams through layer l while group 0 is already
    # on layer l+1 — the paper's pipelined rows, §V.
    for t in range(n_layers + n_halves - 1):
        for h in range(n_halves):
            l = t - h
            if 0 <= l < n_layers:
                halves[h] = apply_layer(halves[h], l,
                                        last_use=h == n_halves - 1)
    # the last activation parks in the VMEM scratch before the single HBM
    # store; every earlier one only ever existed as on-chip kernel values
    # (Pallas intermediates cannot spill to HBM).
    width = halves[0].shape[1]
    for h in range(n_halves):
        act_ref[h * rows:(h + 1) * rows, :width] = halves[h]
    o_ref[...] = act_ref[:, :width].astype(o_ref.dtype)


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(a, ((0, rows - a.shape[0]), (0, cols - a.shape[1])))


@functools.partial(
    jax.jit,
    static_argnames=("shapes", "activations", "out_dtype", "block_m",
                     "interpret", "dim_align", "act_dtype", "double_buffer"))
def fantastic4_fused_mlp_pallas(
        x: jax.Array,
        packed: Tuple[jax.Array, ...],
        omega: Tuple[jax.Array, ...],
        alpha1: Tuple[jax.Array, ...],
        bias: Tuple[jax.Array, ...],
        scale: Tuple[jax.Array, ...],
        *, shapes: Tuple[Tuple[int, int], ...],
        activations: Tuple[Optional[str], ...],
        out_dtype=None, block_m: int = 128,
        interpret: bool = False,
        dim_align: int = DIM_ALIGN,
        act_dtype: str = "float32",
        double_buffer: bool = False) -> jax.Array:
    """x:(M, K₀) · per-layer packed codes -> (M, N_L) in one pallas_call.

    ``shapes[l] = (K_l, N_l)`` are the *unpadded* layer dims (``K_{l+1} ==
    N_l``); ``packed[l]`` is ``(ceil(K_l/2), N_l)`` uint8 row-pair codes.

    ``scale[l]`` is a scalar whose meaning depends on ``act_dtype``: the
    fp32 epilogue's α₂ multiplier, or the int8 mode's activation
    quantization scale s_l (the final layer's entry is ignored there — the
    logits stay float).  In int8 mode the caller must already have folded
    s_{l−1} into ``alpha1[l]``, exactly as the per-layer serving chain
    does.  ``double_buffer`` splits the batch tile into two row groups on
    the skewed schedule described in the module docstring (it needs two
    full sublane groups, so it engages only when the tile has ≥16 rows).
    A call of several batch tiles decodes each layer once into VMEM
    scratch where the decoded stack fits (``fused_mlp_decode_once``).
    """
    assert act_dtype in ("float32", "int8"), act_dtype
    n_layers = len(shapes)
    assert n_layers >= 1
    assert len(activations) == n_layers
    m, k0 = x.shape
    assert k0 == shapes[0][0], (x.shape, shapes)
    for l in range(1, n_layers):
        assert shapes[l][0] == shapes[l - 1][1], shapes
    out_dtype = out_dtype or x.dtype

    ps = padded_shapes(shapes, dim_align)
    bm = min(block_m, _round_up(m, 8))
    # two row groups need two whole f32 sublane tiles
    n_halves = 2 if double_buffer and bm % 16 == 0 else 1
    mp = _round_up(m, bm)
    xp = _pad2(x, mp, ps[0][0])

    operands = [xp]
    in_specs = [pl.BlockSpec((bm, ps[0][0]), lambda i: (i, 0))]
    for l, ((kp, np_), (k, n)) in enumerate(zip(ps, shapes)):
        operands += [
            _pad2(packed[l], kp // 2, np_),
            omega[l].reshape(1, 4).astype(jnp.float32),
            _pad2(alpha1[l].reshape(1, -1).astype(jnp.float32), 1, np_),
            _pad2(bias[l].reshape(1, -1).astype(jnp.float32), 1, np_),
            scale[l].reshape(1, 1).astype(jnp.float32),
        ]
        in_specs += [
            pl.BlockSpec((kp // 2, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, 4), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, np_), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ]

    n_last_p = ps[-1][1]
    max_width = max([ps[0][0]] + [np_ for _, np_ in ps])
    decode_once = fused_mlp_decode_once(shapes, m, block_m, act_dtype,
                                        double_buffer, dim_align=dim_align)
    w_scratch = [pltpu.VMEM(p, jnp.float32) for p in ps] if decode_once \
        else []
    out = pl.pallas_call(
        functools.partial(_kernel, activations=tuple(activations),
                          act_dtype=act_dtype, n_halves=n_halves,
                          decode_once=decode_once),
        grid=(mp // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, n_last_p), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, n_last_p), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, max_width), jnp.float32)]
        + w_scratch,
        # tiles after the first read the scratch the first one decoded
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if decode_once
                                 else "parallel",)),
        interpret=interpret,
        name="fantastic4_fused_mlp_pallas",
    )(*operands)
    return trim_padding(out, m, shapes[-1][1], interpret)


# ------------------------------------------------ weight-stationary variant

def ws_width(shapes: Sequence[Tuple[int, int]],
             dim_align: int = DIM_ALIGN) -> int:
    """Uniform stacked-operand width D: the stack's widest padded dim."""
    ps = padded_shapes(shapes, dim_align)
    return max([ps[0][0]] + [np_ for _, np_ in ps])


def ws_mlp_vmem_bytes(shapes: Sequence[Tuple[int, int]], rows: int = 8,
                      dim_align: int = DIM_ALIGN,
                      act_dtype: str = "float32") -> int:
    """Per-grid-step working set of the weight-stationary schedule (bytes).

    One layer's packed (D/2, D) block + its decoded (D, D) tile + the
    resident (rows, D) activation scratch and x/out tiles; ×2 on the
    streamed per-layer operands for pipelining double buffers.  Unlike
    ``fused_mlp_vmem_bytes`` this does not scale with L — the whole point
    of the schedule.
    """
    d = ws_width(shapes, dim_align)
    rp = _round_up(rows, 8)
    packed = d // 2 * d                              # uint8, one layer
    vectors = 2 * 4 * d + 4 * 4 + 4 * 4              # α₁/b + ω + meta
    decoded = 4 * d * d
    act = 4 * rp * d
    x_tile = 4 * rp * d
    out_tile = 4 * rp * d
    if act_dtype == "int8":
        act += rp * d
    return 2 * (packed + vectors) + decoded + act + x_tile + out_tile


def ws_mlp_fits(shapes: Sequence[Tuple[int, int]], *, rows: int = 8,
                budget_bytes: int = VMEM_BUDGET_BYTES,
                dim_align: int = DIM_ALIGN,
                act_dtype: str = "float32") -> bool:
    if not shapes:
        return False
    return ws_mlp_vmem_bytes(shapes, rows, dim_align,
                             act_dtype) <= budget_bytes


def build_ws_operands(packed: Sequence[jax.Array],
                      omega: Sequence[jax.Array],
                      alpha1: Sequence[jax.Array],
                      bias: Sequence[jax.Array],
                      scale: Sequence[jax.Array],
                      *, shapes: Sequence[Tuple[int, int]],
                      activations: Sequence[Optional[str]],
                      act_dtype: str = "float32",
                      dim_align: int = DIM_ALIGN) -> tuple:
    """Stack per-layer operands into the ws kernel's uniform-width arrays.

    Returns ``(packed (L, D/2, D) u8, omega (L, 1, 4), alpha1 (L, 1, D),
    bias (L, 1, D), meta (L, 1, 4))`` where ``meta[l] = [scale_l,
    activation_code, quant_flag, 0]`` (codes per ``ref.ACTIVATION_CODES``:
    0 none, 1 relu, 2 gelu) — the activation/re-quantization choices
    become data so one kernel body can serve every grid step (the layer id
    is a traced ``program_id``).  Do this once per frozen pack, not per
    call: the serving plan caches the result.
    """
    n_layers = len(shapes)
    d = ws_width(shapes, dim_align)
    pk, om, a1, bi, me = [], [], [], [], []
    for l in range(n_layers):
        pk.append(_pad2(packed[l], d // 2, d))
        om.append(omega[l].reshape(1, 4).astype(jnp.float32))
        a1.append(_pad2(alpha1[l].reshape(1, -1).astype(jnp.float32), 1, d))
        bi.append(_pad2(bias[l].reshape(1, -1).astype(jnp.float32), 1, d))
        act_f = float(ref.activation_code(activations[l]))
        quant_f = 1.0 if (act_dtype == "int8" and l < n_layers - 1) else 0.0
        me.append(jnp.asarray(
            [[float(jnp.asarray(scale[l]).reshape(())), act_f, quant_f,
              0.0]], jnp.float32))
    return (jnp.stack(pk), jnp.stack(om), jnp.stack(a1), jnp.stack(bi),
            jnp.stack(me))


def _ws_kernel(x_ref, packed_ref, omega_ref, alpha1_ref, bias_ref, meta_ref,
               o_ref, act_ref, *, act_dtype: str, n_layers: int):
    l = pl.program_id(0)

    @pl.when(l == 0)
    def _():
        act_ref[...] = x_ref[...].astype(jnp.float32)

    cur = act_ref[...]
    w = decode_tile(packed_ref[0], omega_ref[0])
    y = lane_dot(cur, w)
    y = y * alpha1_ref[0] + bias_ref[0]
    # activation/quantization choices are per-layer *data* (meta operand):
    # the layer id is traced, so the branch cannot be a python conditional.
    y = ref.apply_activation_coded(y, meta_ref[0, 0, 1])
    s = meta_ref[0, 0, 0]
    if act_dtype == "int8":
        q = jnp.clip(jnp.round(y / s), -127.0, 127.0)
        yq = q.astype(jnp.int8).astype(jnp.float32)
        y = jnp.where(meta_ref[0, 0, 2] > 0, yq, y)
    else:
        y = y * s
    act_ref[...] = y

    @pl.when(l == n_layers - 1)
    def _():
        o_ref[...] = act_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("shapes", "activations", "out_dtype", "interpret",
                     "dim_align", "act_dtype"))
def fantastic4_fused_mlp_ws_pallas(
        x: jax.Array,
        packed_stack: jax.Array,
        omega_stack: jax.Array,
        alpha1_stack: jax.Array,
        bias_stack: jax.Array,
        meta_stack: jax.Array,
        *, shapes: Tuple[Tuple[int, int], ...],
        activations: Tuple[Optional[str], ...],
        out_dtype=None,
        interpret: bool = False,
        dim_align: int = DIM_ALIGN,
        act_dtype: str = "float32") -> jax.Array:
    """Weight-stationary whole-stack serving: grid over layers, activation
    resident in scratch, one layer's weights fetched per step.

    Operands come pre-stacked from ``build_ws_operands`` (uniform width D).
    The batch is not tiled — the whole (rounded) batch rides in the scratch
    — so this is the latency schedule for small row counts (the serving
    plan selects it for the batch≤8 bucket).  The grid must run in order
    (``"arbitrary"`` semantics): step l reads the activation step l−1
    wrote.
    """
    assert act_dtype in ("float32", "int8"), act_dtype
    n_layers = len(shapes)
    assert n_layers >= 1
    assert packed_stack.shape[0] == n_layers
    m, k0 = x.shape
    assert k0 == shapes[0][0], (x.shape, shapes)
    out_dtype = out_dtype or x.dtype
    d = ws_width(shapes, dim_align)
    mp = _round_up(m, 8)
    xp = _pad2(x, mp, d)

    out = pl.pallas_call(
        functools.partial(_ws_kernel, act_dtype=act_dtype,
                          n_layers=n_layers),
        grid=(n_layers,),
        in_specs=[
            pl.BlockSpec((mp, d), lambda l: (0, 0)),
            pl.BlockSpec((1, d // 2, d), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda l: (l, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda l: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((mp, d), lambda l: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((mp, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fantastic4_fused_mlp_ws_pallas",
    )(xp, packed_stack, omega_stack, alpha1_stack, bias_stack, meta_stack)
    return trim_padding(out, m, shapes[-1][1], interpret)


# ------------------------------------------- decode-amortized streaming variant

def stream_mlp_vmem_bytes(shapes: Sequence[Tuple[int, int]], rows: int,
                          block_m: int = 128,
                          dim_align: int = DIM_ALIGN,
                          act_dtype: str = "float32") -> int:
    """Per-grid-step working set of the streaming schedule (bytes).

    One layer's packed (D/2, D) block + the persistent decoded (D, D)
    scratch + the whole-batch (M, D) activation scratch + one (bm, D)
    x/out tile pair; ×2 on the streamed per-layer operands for pipelining
    double buffers.  Scales with the batch (the activation scratch holds
    every tile so the decode can be amortized across them) but not with L
    — the schedule's defining trade against the batch-tiled kernel.
    """
    d = ws_width(shapes, dim_align)
    rp = _round_up(rows, 8)
    bm = min(_round_up(block_m, 8), rp)
    mp = _round_up(rp, bm)       # the kernel pads the batch to whole tiles
    packed = d // 2 * d                              # uint8, one layer
    vectors = 2 * 4 * d + 4 * 4 + 4 * 4              # α₁/b + ω + meta
    decoded = 4 * d * d                              # persistent W scratch
    act = 4 * mp * d                                 # whole-batch scratch
    x_tile = 4 * bm * d
    out_tile = 4 * bm * d
    return 2 * (packed + vectors + x_tile + out_tile) + decoded + act


def stream_mlp_fits(shapes: Sequence[Tuple[int, int]], *, rows: int,
                    block_m: int = 128,
                    budget_bytes: int = VMEM_BUDGET_BYTES,
                    dim_align: int = DIM_ALIGN,
                    act_dtype: str = "float32") -> bool:
    if not shapes:
        return False
    return stream_mlp_vmem_bytes(shapes, rows, block_m, dim_align,
                                 act_dtype) <= budget_bytes


def _stream_kernel(x_ref, packed_ref, omega_ref, alpha1_ref, bias_ref,
                   meta_ref, o_ref, act_ref, w_ref, *, act_dtype: str,
                   n_layers: int, block_m: int):
    l = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(l == 0)
    def _():
        # first pass over the batch: park the input tiles in the resident
        # whole-batch scratch (later layers never touch x again).
        act_ref[pl.ds(i * block_m, block_m), :] = \
            x_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _():
        # THE amortization: layer l's bit-plane decode runs once per
        # inference batch, at its first batch tile, into a scratch that
        # persists across grid steps — every later tile of this layer
        # reuses it.
        w_ref[...] = decode_tile(packed_ref[0], omega_ref[0])

    cur = act_ref[pl.ds(i * block_m, block_m), :]
    y = lane_dot(cur, w_ref[...])
    y = y * alpha1_ref[0] + bias_ref[0]
    # per-layer activation/quantization choices are data (meta operand),
    # exactly as in the ws kernel — the layer id is traced.
    y = ref.apply_activation_coded(y, meta_ref[0, 0, 1])
    s = meta_ref[0, 0, 0]
    if act_dtype == "int8":
        q = jnp.clip(jnp.round(y / s), -127.0, 127.0)
        yq = q.astype(jnp.int8).astype(jnp.float32)
        y = jnp.where(meta_ref[0, 0, 2] > 0, yq, y)
    else:
        y = y * s
    # in-place ping-pong: tile i's rows are read and rewritten by the same
    # step; row ranges are disjoint across tiles, so no tile reads another
    # tile's freshly written rows.
    act_ref[pl.ds(i * block_m, block_m), :] = y

    @pl.when(l == n_layers - 1)
    def _():
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("shapes", "activations", "out_dtype", "block_m",
                     "interpret", "dim_align", "act_dtype"))
def fantastic4_fused_mlp_stream_pallas(
        x: jax.Array,
        packed_stack: jax.Array,
        omega_stack: jax.Array,
        alpha1_stack: jax.Array,
        bias_stack: jax.Array,
        meta_stack: jax.Array,
        *, shapes: Tuple[Tuple[int, int], ...],
        activations: Tuple[Optional[str], ...],
        out_dtype=None, block_m: int = 128,
        interpret: bool = False,
        dim_align: int = DIM_ALIGN,
        act_dtype: str = "float32") -> jax.Array:
    """Decode-amortized streaming whole-stack serving: grid over
    (layers, batch tiles) with layers outer, each layer decoded once per
    inference batch and reused across every batch tile.

    Operands come pre-stacked from ``build_ws_operands`` (uniform width D)
    — shared with the ws kernel, so decode + epilogue arithmetic is
    identical term for term and the int8 grid stays bit-exact across
    schedules.  The whole (rounded) batch is resident in a VMEM scratch;
    the grid must run in order (``"arbitrary"`` semantics both ways:
    layer l reads what layer l−1 wrote, tile i>0 reads the decode tile
    i=0 wrote).
    """
    assert act_dtype in ("float32", "int8"), act_dtype
    n_layers = len(shapes)
    assert n_layers >= 1
    assert packed_stack.shape[0] == n_layers
    m, k0 = x.shape
    assert k0 == shapes[0][0], (x.shape, shapes)
    out_dtype = out_dtype or x.dtype
    d = ws_width(shapes, dim_align)
    bm = min(block_m, _round_up(m, 8))
    mp = _round_up(m, bm)
    n_tiles = mp // bm
    xp = _pad2(x, mp, d)

    out = pl.pallas_call(
        functools.partial(_stream_kernel, act_dtype=act_dtype,
                          n_layers=n_layers, block_m=bm),
        grid=(n_layers, n_tiles),
        in_specs=[
            # x is only read on the first layer pass; pin the index to
            # tile 0 afterwards so later layers don't re-stream the batch.
            pl.BlockSpec((bm, d),
                         lambda l, i: (jnp.where(l == 0, i, 0), 0)),
            pl.BlockSpec((1, d // 2, d), lambda l, i: (l, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda l, i: (l, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda l, i: (l, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda l, i: (l, 0, 0)),
            pl.BlockSpec((1, 1, 4), lambda l, i: (l, 0, 0)),
        ],
        # only the last layer writes real output tiles; pinning earlier
        # layers to tile 0 keeps the copy-out traffic to one final pass
        # (tile 0's stale flushes are overwritten by its last-layer write).
        out_specs=pl.BlockSpec(
            (bm, d), lambda l, i: (jnp.where(l == n_layers - 1, i, 0), 0)),
        out_shape=jax.ShapeDtypeStruct((mp, d), out_dtype),
        scratch_shapes=[pltpu.VMEM((mp, d), jnp.float32),
                        pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="fantastic4_fused_mlp_stream_pallas",
    )(xp, packed_stack, omega_stack, alpha1_stack, bias_stack, meta_stack)
    return trim_padding(out, m, shapes[-1][1], interpret)
