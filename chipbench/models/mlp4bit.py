"""FantastIC4 MLPs: the model made from the seed, the serving plan, and the
plain reference.

The benchmark makes the model itself, in the format the program serves:
per layer, row-pair packed 4-bit codes ``(K/2, N)`` uint8 (low nibble = row
2r, high nibble = row 2r+1), a 4-value codebook omega, the folded-BN
epilogue (alpha1, bias) and alpha2; for 8-bit activations also one
activation scale per layer boundary, calibrated here.  Layer l decodes to
``W = sum_i omega_i * B_i`` where ``B_i`` is bit i of each code.  Nothing
the reference uses comes from the program.

The program sees the pack through ``repro.serving.build_plan``.  The
reference is a straightforward forward pass in jax.numpy, its products at
the configuration's ``matmul_precision`` (float32 at ``highest``); the
int8 variant rounds each hidden activation to the int8 grid of its scale,
as the configuration states.  ``control`` computes the same forward one
step below one of the precisions the configuration states: its products
as three bf16 passes (``high``) or one (``default``), or its activations
on the int4 grid (``int4``).
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

CALIB_ROWS = 256
HIGHEST = jax.lax.Precision.HIGHEST
# a row misses when one of its logits is further from the reference than
# this share of the reference's largest logit: far above float32 rounding
# (about 1e-7), far below one activation moved by one step of its grid
MISS_TOL = 1e-4


def shapes(config: dict) -> List[tuple]:
    widths = [int(config["d_in"])] + [int(n) for n in config["features"]]
    return list(zip(widths[:-1], widths[1:]))


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one of the run's random streams (any whole seed)."""
    word = np.random.SeedSequence([seed % 2 ** 64, stream]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]))


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2 ** 64, stream])


@functools.partial(jax.jit, static_argnames=("layer_shapes",))
def _make_layers(key, layer_shapes):
    """Every layer's arrays in one program: uniform 4-bit codes, a
    zero-sum codebook (so W has mean 0) and an alpha1 that keeps each
    layer's output at about unit scale."""
    out = []
    for k, n in layer_shapes:
        key, kp, ko, ka, kb = jax.random.split(key, 5)
        packed = jax.random.bits(kp, (k // 2, n), jnp.uint8)
        o = jax.random.normal(ko, (3,), jnp.float32)
        omega = jnp.concatenate([o, -jnp.sum(o, keepdims=True)])
        gain = jnp.sqrt(2.0 / (k * jnp.sum(omega ** 2) / 4.0))
        alpha1 = gain * jax.random.uniform(ka, (n,), jnp.float32, 0.75, 1.25)
        bias = 0.1 * jax.random.normal(kb, (n,), jnp.float32)
        out.append((packed, omega, alpha1, bias, jnp.ones((), jnp.float32)))
    return out


class Model:
    """The model of one configuration at one seed."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.shapes = shapes(config)
        if any(k % 2 for k, _ in self.shapes):
            raise ValueError("odd K needs a padded code row; not made here")
        self.act_dtype = config["act_dtype"]
        self.d_in = self.shapes[0][0]
        arrays = _make_layers(seed_key(seed, 0), tuple(self.shapes))
        n = len(self.shapes)
        self.layers = [
            {"packed": p, "omega": o, "alpha1": a1, "bias": b, "alpha2": a2,
             "shape": (k, nn), "activation": "relu" if i < n - 1 else None}
            for i, ((p, o, a1, b, a2), (k, nn)) in
            enumerate(zip(arrays, self.shapes))]
        self.act_scales: Optional[List[float]] = None
        if self.act_dtype == "int8":
            x = jax.random.normal(seed_key(seed, 2), (CALIB_ROWS, self.d_in),
                                  jnp.float32)
            self.act_scales = calibrate(self.layers, x)

    def pack(self) -> dict:
        """The serving pack handed to the program."""
        return {"layers": [dict(l) for l in self.layers],
                "act_bits": 8 if self.act_dtype == "int8" else None}

    def plan(self, **kwargs):
        from repro import serving
        calib = None if self.act_scales is None else \
            {"act_scales": list(self.act_scales)}
        return serving.build_plan(self.pack(), mode="auto",
                                  act_dtype=self.act_dtype, calib=calib,
                                  max_bucket=int(self.config.get(
                                      "max_bucket", 256)), **kwargs)

    def reference(self, x, block_rows: int = 8192) -> np.ndarray:
        """Logits of the reference for rows ``x``, in blocks of rows."""
        precision = self.config["matmul_precision"]
        return _blocks(lambda xb: self._forward(xb, precision), x,
                       block_rows)

    def control(self, x, kind: str, block_rows: int = 8192) -> np.ndarray:
        """The reference one step below a precision the configuration
        states: ``kind`` is one of its ``controls``."""
        if kind in ("high", "default"):
            fn = lambda xb: self._forward(xb, kind)
        elif kind == "int4":
            fn = lambda xb: self._forward(
                xb, self.config["matmul_precision"], levels=7)
        else:
            raise ValueError(f"unknown control {kind!r}")
        return _blocks(fn, x, block_rows)

    def _forward(self, x, precision: str, levels: int = 127):
        weights = tuple((l["packed"], l["omega"], l["alpha1"], l["bias"],
                         l["alpha2"]) for l in self.layers)
        acts = tuple(l["activation"] for l in self.layers)
        scales = None if self.act_scales is None else \
            tuple(self.act_scales)
        return _forward(weights, x, scales, acts=acts, precision=precision,
                        levels=levels)


def _blocks(fn, x, block_rows: int) -> np.ndarray:
    x = jnp.asarray(x, jnp.float32)
    outs = [np.asarray(fn(x[i:i + block_rows]))
            for i in range(0, x.shape[0], block_rows)]
    return np.concatenate(outs, axis=0)


def decode(packed: jax.Array, omega: jax.Array) -> jax.Array:
    """(K/2, N) row-pair codes -> (K, N) float32 W = sum_i omega_i B_i."""
    c = packed.astype(jnp.int32)
    codes = jnp.stack([c & 0xF, c >> 4], axis=1).reshape(
        2 * c.shape[0], c.shape[1])
    w = jnp.zeros(codes.shape, jnp.float32)
    for i in range(4):
        w = w + omega[i] * ((codes >> i) & 1).astype(jnp.float32)
    return w


def _split_bf16(a: jax.Array):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def matmul(a: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """``a @ w`` in float32: ``highest`` is full float32; ``high`` is three
    bf16 passes (hi*hi + hi*lo + lo*hi), ``default`` one (hi*hi).  On the
    TPU those are the chip's own ``Precision.HIGH`` and ``DEFAULT`` (XLA
    folds a bf16 round trip written out in float32 away there); other
    backends ignore that setting, so there the passes are written out."""
    if precision == "highest":
        return jnp.matmul(a, w, precision=HIGHEST)
    if precision not in ("high", "default"):
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.matmul(a, w, precision=jax.lax.Precision[
            precision.upper()])
    ah, al = _split_bf16(a)
    wh, wl = _split_bf16(w)
    out = jnp.matmul(ah, wh, precision=HIGHEST)
    if precision == "high":
        out = (out + jnp.matmul(ah, wl, precision=HIGHEST)
               + jnp.matmul(al, wh, precision=HIGHEST))
    return out


@functools.partial(jax.jit, static_argnames=("acts", "precision", "levels"))
def _forward(weights, x, act_scales, *, acts, precision: str,
             levels: int = 127):
    """Plain forward.  With ``act_scales`` each hidden activation leaves
    its layer rounded to the grid ``round(y / s)`` clipped to
    [-levels, levels], on the scale ``s * 127 / levels`` (int8 at 127,
    int4 at 7), and the next layer multiplies its alpha1 by that scale."""
    h = x.astype(jnp.float32)
    in_scale = 1.0
    n = len(weights)
    for i, ((packed, omega, alpha1, bias, alpha2), act) in enumerate(
            zip(weights, acts)):
        w = decode(packed, omega)
        y = matmul(h, w, precision) * (alpha1 * in_scale) + bias
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        elif act is not None:
            raise ValueError(f"reference has no activation {act!r}")
        if act_scales is None:
            y = y * alpha2
        elif i < n - 1:
            s = act_scales[i] * (127.0 / levels)
            y = jnp.clip(jnp.round(y / s), -levels, levels)
            in_scale = s
        h = y
    return h


def calibrate(layers: Sequence[dict], x: jax.Array) -> List[float]:
    """One activation scale per layer boundary: the largest |activation|
    of a float forward over ``x``, over 127."""
    peaks = _calibration_peaks(
        tuple((l["packed"], l["omega"], l["alpha1"], l["bias"])
              for l in layers[:-1]), x)
    return [max(float(p), 1e-6) / 127.0 for p in np.asarray(peaks)]


@jax.jit
def _calibration_peaks(weights, x):
    h = x.astype(jnp.float32)
    peaks = []
    for packed, omega, alpha1, bias in weights:
        y = matmul(h, decode(packed, omega), "highest") * alpha1 + bias
        h = jnp.maximum(y, 0.0)
        peaks.append(jnp.max(jnp.abs(h)))
    return jnp.stack(peaks)


def _rel_gaps(served, ref) -> Optional[np.ndarray]:
    """|served - reference| over the largest |reference|, or None where
    the shapes differ or a served logit is not finite."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape or not np.all(np.isfinite(served)):
        return None
    return np.abs(served - ref) / max(np.max(np.abs(ref)), 1e-30)


def max_rel_err(served: np.ndarray, ref: np.ndarray) -> float:
    """Largest |served - reference| over the largest |reference|."""
    gaps = _rel_gaps(served, ref)
    return float("inf") if gaps is None else float(np.max(gaps))


def miss_count(served: np.ndarray, ref: np.ndarray) -> float:
    """Rows with a logit further than ``MISS_TOL`` of the largest
    |reference| from the reference (every row where a logit is missing or
    not finite)."""
    gaps = _rel_gaps(served, ref)
    if gaps is None:
        return float(np.shape(ref)[0])
    return float(np.sum(np.max(gaps, axis=1) > MISS_TOL))
