"""Public jit'd wrappers around the FantastIC4 Pallas kernels.

On a TPU backend the Pallas kernels run natively; on the CPU they execute
in ``interpret=True`` mode (``default_interpret``; any other backend is
refused) so every test validates the actual kernel body against the
pure-jnp oracles in ``ref.py``. ``use_kernel=False``
selects the oracle path (used by the models' default serving path on CPU,
where interpret-mode would be needlessly slow for large layers).

Block sizes left as ``None`` are resolved by the shape-aware autotuner
(``autotune.py``): a timed candidate sweep on a real TPU backend, a pure
heuristic in interpret/CPU mode, both behind a persistent JSON cache — so
every entry point (models, launchers, benchmarks) runs the same tuned
configuration instead of the old hard-coded 128/256/512 defaults.
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from ..memo import MISS, IdentityMemo
from . import autotune, ref
from .ecl_quant import ecl_quant_pallas
from .fantastic4_fused_mlp import (VMEM_BUDGET_BYTES, build_ws_operands,
                                   fantastic4_fused_mlp_pallas,
                                   fantastic4_fused_mlp_stream_pallas,
                                   fantastic4_fused_mlp_ws_pallas,
                                   fused_mlp_fits, stream_mlp_fits,
                                   ws_mlp_fits)
from .fantastic4_matmul import fantastic4_matmul_pallas


def default_interpret() -> bool:
    """Whether the Pallas kernels run in interpret mode: exactly when JAX
    runs on the CPU.  The TPU compiles them; any other backend has no
    Pallas path here and is refused rather than quietly interpreted."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for 'tpu' and are interpreted on "
        f"'cpu'; JAX's default backend is {backend!r}")


def unpad(out: jax.Array, m: int, n: int) -> jax.Array:
    """A kernel result cut to ``(m, n)``: only the interpreter returns it
    padded; a compiled kernel has cut it in its own program
    (``fantastic4_matmul.trim_padding``), and no second dispatch runs."""
    return out if out.shape == (m, n) else out[:m, :n]


def _timeit(fn, repeats: int = 3) -> float:
    """Median wall-clock of ``fn()`` after one warm-up (compile) call.

    A candidate that fails to compile or run raises; the autotuner's sweep
    records the error.  A traced result raises too: ``block_until_ready``
    returns at once on a tracer, so the time would be the trace's."""
    out = fn()
    if any(isinstance(a, jax.core.Tracer)
           for a in jax.tree_util.tree_leaves(out)):
        raise TypeError("timed sweep called under a trace: resolve the "
                        "blocks eagerly before jit/shard_map")
    jax.block_until_ready(out)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def matmul_blocks(m: int, k: int, n: int, *, dtype=jnp.float32,
                  interpret: bool,
                  activation: Optional[str] = None) -> autotune.BlockConfig:
    """The per-layer kernel's blocks for an ``(m, k) @ (k, n)`` layer.

    On the TPU a cache miss times every candidate on zero operands of the
    layer's shape, so this runs eagerly; callers that trace the kernel
    (``serving.sharded``) resolve here first and pass the blocks in.
    Interpret-mode answers are keyed under backend "interpret" so they
    never shadow a real backend's timed sweep for the same shape.
    """
    def _measure(cfg: autotune.BlockConfig) -> float:
        x = jnp.zeros((m, k), dtype)
        packed = jnp.zeros((k // 2, n), jnp.uint8)
        omega = jnp.zeros((4,), jnp.float32)
        vec = jnp.zeros((n,), jnp.float32)
        one = jnp.ones((), jnp.float32)
        return _timeit(lambda: fantastic4_matmul_pallas(
            x, packed, omega, vec, vec, one, activation=activation,
            block_m=cfg.block_m, block_n=cfg.block_n, block_k=cfg.block_k,
            interpret=interpret))

    return autotune.get_block_config(
        m, k, n, dtype=str(jnp.dtype(dtype)), fused=False,
        backend="interpret" if interpret else None,
        measure=None if interpret else _measure)


def fantastic4_matmul(x: jax.Array, packed: jax.Array, omega: jax.Array,
                      bias: Optional[jax.Array] = None,
                      alpha1: Optional[jax.Array] = None,
                      alpha2: Optional[jax.Array] = None,
                      activation: Optional[str] = None,
                      out_dtype=None,
                      use_kernel: bool = True,
                      interpret: Optional[bool] = None,
                      block_m: Optional[int] = None,
                      block_n: Optional[int] = None,
                      block_k: Optional[int] = None) -> jax.Array:
    """Quantized linear y = epilogue(x @ decode(packed, omega)).

    x: (M, K); packed: (K//2, N) uint8 (row-pair packed); omega: (4,).
    bias/alpha1: (N,) or None; alpha2: scalar or None.
    block_*: None -> autotuned per shape (see module docstring).
    """
    n = packed.shape[1]
    if not use_kernel:
        return ref.fantastic4_matmul_ref(
            x, packed, omega, bias=bias, alpha1=alpha1, alpha2=alpha2,
            activation=activation, out_dtype=out_dtype)
    interpret = default_interpret() if interpret is None else interpret
    alpha1 = jnp.ones((n,), jnp.float32) if alpha1 is None else alpha1
    bias = jnp.zeros((n,), jnp.float32) if bias is None else bias
    alpha2 = jnp.ones((), jnp.float32) if alpha2 is None else jnp.asarray(alpha2)

    if None in (block_m, block_n, block_k):
        cfg = matmul_blocks(x.shape[0], x.shape[1], n, dtype=x.dtype,
                            interpret=interpret, activation=activation)
        block_m = block_m or cfg.block_m
        block_n = block_n or cfg.block_n
        block_k = block_k or cfg.block_k
    return unpad(fantastic4_matmul_pallas(
        x, packed, omega, alpha1, bias, alpha2,
        activation=activation, out_dtype=out_dtype or x.dtype,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret), x.shape[0], n)


def fantastic4_mlp_chain(x: jax.Array, layers: Sequence[dict], *,
                         use_kernel: bool = True,
                         interpret: Optional[bool] = None) -> jax.Array:
    """Chained per-layer serving over a frozen pack's layer list (kernel or
    oracle per ``use_kernel``) — the unfused path and the megakernel's
    over-budget fallback."""
    for layer in layers:
        if layer["shape"][0] % 2:
            # odd K: the pack carries one zero code row — mirror it on x
            x = jnp.pad(x, ((0, 0), (0, 1)))
        x = fantastic4_matmul(
            x, layer["packed"], layer["omega"], bias=layer["bias"],
            alpha1=layer["alpha1"], alpha2=layer["alpha2"],
            activation=layer.get("activation"), use_kernel=use_kernel,
            interpret=interpret)
    return x


def fantastic4_mlp_chain_int8(x: jax.Array, layers: Sequence[dict],
                              act_scales: Sequence[float], *,
                              use_kernel: bool = True,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Per-layer int8-activation serving chain (paper §VI-C).

    Layer i emits ``round(y/s_i)`` clipped to int8; layer i+1 folds s_i
    into its alpha1.  This is both ``mlp_serve_int8``'s unfused path and
    the int8 megakernel's over-budget fallback — one implementation, so
    the fused kernel's bit-exactness contract has a single ground truth.
    """
    n = len(layers)
    xq = x.astype(jnp.float32)
    in_scale = 1.0
    for i, layer in enumerate(layers):
        if layer["shape"][0] % 2:
            # odd K: the pack carries one zero code row — mirror it on x
            xq = jnp.pad(xq, ((0, 0), (0, 1)))
        alpha1 = layer["alpha1"] * in_scale      # de-quantize inputs
        y = fantastic4_matmul(
            xq, layer["packed"], layer["omega"], bias=layer["bias"],
            alpha1=alpha1, alpha2=None, activation=layer.get("activation"),
            use_kernel=use_kernel, interpret=interpret)
        if i < n - 1:
            s = act_scales[i]
            xq = jnp.clip(jnp.round(y / s), -127, 127)
            xq = xq.astype(jnp.int8).astype(jnp.float32)
            in_scale = s
        else:
            xq = y
    return xq


# folded int8 serving operands, memoized per (layers, act_scales) identity:
# re-folding alpha1·s and L scalar conversions on every call is exactly the
# per-call wrapper dispatch cost the megakernel path avoids for the pack
# arrays (see the NB in _call_fused).  Identity keying is safe because a
# frozen pack's arrays are never mutated in place (see repro.memo).
_INT8_FOLD_MEMO = IdentityMemo()


def _int8_folded_operands(layers: Sequence[dict],
                          act_scales: Sequence[float]) -> tuple:
    hit = _INT8_FOLD_MEMO.get((layers, act_scales))
    if hit is not MISS:
        return hit
    # fold s_{l-1} into alpha1_l — same expression as the per-layer chain
    # (fantastic4_mlp_chain_int8), so the arrays are bitwise identical on
    # both paths; the per-layer scale operand carries s_l (final layer:
    # sentinel 1.0, logits stay float).
    alpha1s = tuple(
        l["alpha1"] * (1.0 if i == 0 else act_scales[i - 1])
        for i, l in enumerate(layers))
    scales = tuple(
        jnp.asarray(act_scales[i] if i < len(layers) - 1 else 1.0,
                    jnp.float32)
        for i in range(len(layers)))
    _INT8_FOLD_MEMO.put((layers, act_scales), (), (alpha1s, scales))
    return alpha1s, scales


# stacked weight-stationary operands, memoized per (layers, act_scales)
# identity like the int8 fold above: the stacking concat/pad work must run
# once per frozen pack, not once per request.
_WS_OPERAND_MEMO = IdentityMemo()


def _ws_stacked_operands(layers: Sequence[dict], act_dtype: str,
                         act_scales: Optional[Sequence[float]]) -> tuple:
    hit = _WS_OPERAND_MEMO.get((layers, act_scales), (act_dtype,))
    if hit is not MISS:
        return hit
    shapes = tuple(tuple(l["shape"]) for l in layers)
    activations = tuple(l.get("activation") for l in layers)
    if act_dtype == "int8":
        alpha1s, scales = _int8_folded_operands(layers, act_scales)
    else:
        alpha1s = tuple(l["alpha1"] for l in layers)
        scales = tuple(l["alpha2"] for l in layers)
    stacked = build_ws_operands(
        tuple(l["packed"] for l in layers),
        tuple(l["omega"] for l in layers),
        alpha1s,
        tuple(l["bias"] for l in layers),
        scales,
        shapes=shapes, activations=activations, act_dtype=act_dtype)
    _WS_OPERAND_MEMO.put((layers, act_scales), (act_dtype,), stacked)
    return stacked


def forget_pack_operands(layers: Sequence[dict]) -> int:
    """Drop every decoded-operand cache entry keyed on ``layers``' identity
    (folded int8 operands and stacked weight-stationary operands);
    returns how many entries were released.  The serving pack cache and
    ``ModelRegistry.unregister`` call this when a model leaves the hot
    tier — these memos hold strong references to the decoded arrays, so
    without the drop an evicted pack's operands stay resident for the
    process lifetime."""
    return (_INT8_FOLD_MEMO.drop(layers)
            + _WS_OPERAND_MEMO.drop(layers))


def fantastic4_mlp_fused(x: jax.Array, layers: Sequence[dict], *,
                         use_kernel: bool = True,
                         interpret: Optional[bool] = None,
                         out_dtype=None,
                         block_m: Optional[int] = None,
                         act_dtype: str = "float32",
                         act_scales: Optional[Sequence[float]] = None,
                         double_buffer: bool = False,
                         weight_stationary: bool = False,
                         schedule: Optional[str] = None,
                         vmem_budget_bytes: int = VMEM_BUDGET_BYTES
                         ) -> jax.Array:
    """Whole-stack serving: one megakernel launch instead of L.

    ``layers`` is the frozen pack's layer list: each entry carries ``packed``
    (ceil(K/2), N) uint8, ``omega`` (4,), ``alpha1``/``bias`` (N,),
    ``alpha2`` scalar, ``shape`` (K, N) and ``activation``.  Falls back to
    the chained per-layer kernel when the stack's VMEM working set exceeds
    ``vmem_budget_bytes`` (see ``fantastic4_fused_mlp.fused_mlp_fits``).

    ``act_dtype="int8"`` runs the paper's §VI-C configuration end-to-end
    inside the kernel: inter-layer activations are re-quantized to int8 in
    VMEM (``act_scales``, one scale per layer boundary, from
    ``calibrate_act_scales``), with each layer's alpha1 absorbing the
    previous scale — folded here exactly as the per-layer chain folds it,
    so fused and chained int8 agree on the quantized grid bit for bit
    whenever the per-layer kernel accumulates K in a single block (always
    true in interpret/CPU mode, where the heuristic takes whole dims; a
    TPU block_k split of a wide layer can move a sum by one ulp and flip
    a quantization boundary, leaving grid-level-but-not-bitwise
    agreement).

    ``schedule`` names the kernel schedule explicitly — one of
    ``"batch_tiled"`` (default), ``"db"`` (pipelined two-row-group
    batch tile), ``"ws"`` (weight-stationary: grid over layers,
    activation resident — the batch=1 latency path) or ``"stream"``
    (decode-amortized streaming: layers-outer/batch-tiles-inner grid,
    each layer decoded once per inference batch).  The legacy
    ``double_buffer`` / ``weight_stationary`` booleans map onto it and
    remain for callers that predate the serving plans.  Every schedule
    falls back to the per-layer chain past its own VMEM fit.
    """
    if schedule is None:
        schedule = ("ws" if weight_stationary
                    else "db" if double_buffer else "batch_tiled")
    assert schedule in autotune.SCHEDULES, schedule
    shapes = tuple(tuple(l["shape"]) for l in layers)
    activations = tuple(l.get("activation") for l in layers)
    interpret = default_interpret() if interpret is None else interpret
    m, k0 = x.shape
    n_last = shapes[-1][1]

    if act_dtype == "int8":
        if act_scales is None or len(act_scales) < len(layers) - 1:
            raise ValueError("act_dtype='int8' needs act_scales with one "
                             "entry per layer boundary")
        alpha1s, scales = _int8_folded_operands(layers, act_scales)
    else:
        alpha1s = tuple(l["alpha1"] for l in layers)
        scales = tuple(l["alpha2"] for l in layers)

    def _chain_fallback(use_k: bool) -> jax.Array:
        if act_dtype == "int8":
            y = fantastic4_mlp_chain_int8(x, layers, act_scales,
                                          use_kernel=use_k,
                                          interpret=interpret)
        else:
            y = fantastic4_mlp_chain(x, layers, use_kernel=use_k,
                                     interpret=interpret)
        return y.astype(out_dtype or y.dtype)

    if schedule == "ws" and use_kernel:
        if ws_mlp_fits(shapes, rows=m, budget_bytes=vmem_budget_bytes,
                       act_dtype=act_dtype):
            stacked = _ws_stacked_operands(
                layers, act_dtype, act_scales if act_dtype == "int8"
                else None)
            return unpad(fantastic4_fused_mlp_ws_pallas(
                x, *stacked, shapes=shapes, activations=activations,
                out_dtype=out_dtype or x.dtype, interpret=interpret,
                act_dtype=act_dtype), m, n_last)
        # over-budget even per layer: same per-layer-chain fallback as the
        # batch-tiled schedule below.
        return _chain_fallback(True)

    if schedule == "stream" and use_kernel:
        bm = block_m or 128
        if stream_mlp_fits(shapes, rows=m, block_m=bm,
                           budget_bytes=vmem_budget_bytes,
                           act_dtype=act_dtype):
            stacked = _ws_stacked_operands(
                layers, act_dtype, act_scales if act_dtype == "int8"
                else None)
            return unpad(fantastic4_fused_mlp_stream_pallas(
                x, *stacked, shapes=shapes, activations=activations,
                out_dtype=out_dtype or x.dtype, block_m=bm,
                interpret=interpret, act_dtype=act_dtype), m, n_last)
        return _chain_fallback(True)

    def _measure(cfg: autotune.BlockConfig) -> float:
        return _timeit(lambda: _call_fused(cfg.block_m))

    def _call_fused(bm: int) -> jax.Array:
        # NB: no jnp.asarray here — pack entries are already device arrays
        # and per-array asarray dominates the wrapper's dispatch cost.
        return unpad(fantastic4_fused_mlp_pallas(
            x,
            tuple(l["packed"] for l in layers),
            tuple(l["omega"] for l in layers),
            alpha1s,
            tuple(l["bias"] for l in layers),
            scales,
            shapes=shapes, activations=activations,
            out_dtype=out_dtype or x.dtype, block_m=bm,
            interpret=interpret, act_dtype=act_dtype,
            double_buffer=schedule == "db"), m, n_last)

    # fits check first (conservatively at the largest candidate block_m):
    # an over-budget stack must not pay for a fused-candidate sweep whose
    # result would be thrown away.
    fits = fused_mlp_fits(shapes, block_m=block_m or 256,
                          budget_bytes=vmem_budget_bytes,
                          act_dtype=act_dtype,
                          double_buffer=schedule == "db")
    if use_kernel and fits and block_m is None:
        cfg = autotune.get_block_config(
            m, k0, n_last, dtype=str(x.dtype), fused=True,
            backend="interpret" if interpret else None,
            act_dtype=act_dtype,
            # (M, K₀, N_last) alone cannot distinguish two stacks with the
            # same ends (MLP-GSC vs MLP-HR): key the hidden widths too.
            extra="stack" + "x".join(str(n) for _, n in shapes),
            measure=_measure if not interpret else None)
        block_m = cfg.block_m
    if not use_kernel or not fits:
        return _chain_fallback(use_kernel)
    return _call_fused(block_m)


def ecl_quant(w: jax.Array, omega: jax.Array, penalty: jax.Array,
              use_kernel: bool = True,
              interpret: Optional[bool] = None,
              block_r: Optional[int] = None,
              block_c: Optional[int] = None):
    """Fused ECL assign + dequant. Returns (codes uint8, w_hat f32).

    ``block_r/block_c=None`` (default) defer to the autotuner — the same
    cache → timed-sweep → heuristic tiering as the matmul kernels, keyed
    as an elementwise problem so it can never collide with a matmul
    shape's blocks.  Explicit values win.
    """
    if not use_kernel:
        return ref.ecl_quant_ref(w, omega, penalty)
    interpret = default_interpret() if interpret is None else interpret
    squeeze = w.ndim == 1
    w2 = w[None, :] if squeeze else w.reshape(w.shape[0], -1)
    if block_r is None or block_c is None:
        def _measure(cfg: autotune.BlockConfig) -> float:
            return _timeit(lambda: ecl_quant_pallas(
                w2, omega, penalty, block_r=cfg.block_m,
                block_c=cfg.block_n, interpret=interpret))

        cfg = autotune.get_elementwise_config(
            w2.shape[0], w2.shape[1], dtype=str(w2.dtype),
            backend="interpret" if interpret else None,
            measure=_measure if not interpret else None)
        block_r = block_r or cfg.block_m
        block_c = block_c or cfg.block_n
    codes, what = ecl_quant_pallas(w2, omega, penalty,
                                   block_r=block_r, block_c=block_c,
                                   interpret=interpret)
    if squeeze:
        return codes[0], what[0]
    return codes.reshape(w.shape), what.reshape(w.shape)
