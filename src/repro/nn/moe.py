"""Mixture-of-Experts: sort-based capacity routing with expert parallelism.

Two execution paths over identical routing math (tests assert equality):

* :func:`moe_apply` — single logical device / pure GSPMD.  Sort-based
  dispatch (argsort by expert id + scatter into an (E, C, d) buffer), no
  (N, E, C) one-hot tensor is ever materialised.
* :func:`moe_apply_ep` — production path: ``jax.shard_map`` over the full
  mesh.  Tokens are sharded over *all* mesh axes (the model axis included —
  a free re-partition of the replicated activations), each device routes its
  local tokens, and two ``all_to_all`` collectives over the 'model' axis move
  token slots to/from the expert-owning shards.  Expert weights live sharded
  over 'model' (E % tp == 0: deepseek 256e) and are replicated over the data
  axes (their gradient psum is inserted by shard_map's transpose).

Routing variants:

* ``gate="softmax"``  — grok-1 style: softmax over the top-k logits.
* ``gate="sigmoid"``  — deepseek-v3 style: sigmoid scores, selection by
  score + a bias-correction term (aux-loss-free balancing, the bias is a
  slow-updated buffer), weights = selected scores / their sum, scaled by
  ``routed_scaling``.

A Switch-style load-balance auxiliary loss is returned alongside (coefficient
applied by the caller); deepseek runs with coefficient ~0 and relies on the
bias correction.  The router itself stays fp32 and un-quantized (paper's
mixed-precision contribution: sensitive small parameters keep full
precision); expert FFN weights are EC4T-quantized.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import linear, linear_init, subtree
from .module import QuantCtx, materialize


# ------------------------------------------------------------------- init

def moe_init(key, d: int, d_ff: int, n_experts: int, quantize: bool,
             n_shared: int = 0, shared_ff: Optional[int] = None) -> dict:
    """Stacked expert SwiGLU weights (E, ...) + fp32 router (+ shared expert)."""
    kr, ke, ks = jax.random.split(key, 3)
    scale = d ** -0.5

    def expert_bank(k, d_in, d_out):
        w = jax.random.uniform(k, (n_experts, d_in, d_out), jnp.float32,
                               -scale, scale)
        if quantize:
            from ..core import qat
            return qat.make_quant_param(w)
        return w

    k1, k2, k3 = jax.random.split(ke, 3)
    p = {
        "router": {
            "w": jax.random.normal(kr, (d, n_experts), jnp.float32) * 0.02,
            "bias_correction": jnp.zeros((n_experts,), jnp.float32),
        },
        "experts": {
            "gate": expert_bank(k1, d, d_ff),
            "up": expert_bank(k2, d, d_ff),
            "down": expert_bank(k3, d_ff, d),
        },
    }
    if n_shared:
        from .layers import swiglu_init
        p["shared"] = swiglu_init(ks, d, (shared_ff or d_ff) * n_shared,
                                  quantize)
    return p


# ---------------------------------------------------------------- routing

def route(logits: jax.Array, bias_correction: jax.Array, *, top_k: int,
          gate: str, routed_scaling: float = 1.0):
    """(N, E) logits -> (ids (N,k) int32, weights (N,k) f32, aux_loss)."""
    n, e = logits.shape
    if gate == "softmax":
        sel_score = logits
        _, ids = jax.lax.top_k(sel_score, top_k)
        w = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=1), axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
    elif gate == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(scores + bias_correction[None, :], top_k)
        sel = jnp.take_along_axis(scores, ids, axis=1)
        w = routed_scaling * sel / jnp.maximum(sel.sum(-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        raise ValueError(gate)
    # Switch-style load-balance aux loss: E * Σ_e f_e · p_e
    onehot_frac = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / (n * top_k))
    aux = e * jnp.sum(onehot_frac * probs.mean(0))
    return ids.astype(jnp.int32), w.astype(jnp.float32), aux


def _dispatch_indices(flat_ids: jax.Array, n_experts: int, capacity: int):
    """Sort-based slot assignment.  flat_ids: (N*k,) expert of each
    assignment.  Returns (slot (N*k,), keep (N*k,)): slot = e*C + pos within
    expert for kept assignments (earlier tokens win — the paper-standard
    'drop by position' policy), garbage otherwise."""
    order = jnp.argsort(flat_ids, stable=True)            # (A,)
    sorted_ids = flat_ids[order]
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat_ids].add(1)
    starts = jnp.cumsum(counts) - counts                  # (E,)
    pos_in_e = jnp.arange(flat_ids.size, dtype=jnp.int32) - starts[sorted_ids]
    keep_sorted = pos_in_e < capacity
    slot_sorted = sorted_ids * capacity + jnp.minimum(pos_in_e, capacity - 1)
    # scatter back to assignment order
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.size, dtype=order.dtype))
    return slot_sorted[inv], keep_sorted[inv]


def _expert_ffn(experts: dict, q_state: Any, xs: jax.Array,
                ctx: QuantCtx) -> jax.Array:
    """xs: (E, C, d) -> (E, C, d) via per-expert SwiGLU (batched einsum)."""
    def mat(name):
        return materialize(experts[name], subtree(q_state, name), ctx)
    g = jnp.einsum("ecd,edf->ecf", xs, mat("gate"))
    u = jnp.einsum("ecd,edf->ecf", xs, mat("up"))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xs.dtype)
    return jnp.einsum("ecf,efd->ecd", h, mat("down"))


def _capacity(n_assign: int, n_experts: int, factor: float) -> int:
    c = int(-(-n_assign * factor // n_experts))           # ceil
    return max(8, -(-c // 8) * 8)                         # pad to 8


# --------------------------------------------------- single-device / GSPMD

def moe_apply(p: dict, q_state: Any, x: jax.Array, ctx: QuantCtx, *,
              top_k: int, gate: str = "softmax", capacity_factor: float = 1.25,
              routed_scaling: float = 1.0,
              mesh: Optional[jax.sharding.Mesh] = None):
    """MoE forward on (..., d) tokens; returns (y, aux_loss).

    With a mesh, the (E, C, d) dispatch buffer is sharding-constrained:
    capacity over the data axes, FFN width implicitly over 'model' via the
    per-expert-TP weight sharding.  Without the constraint GSPMD replicates
    the scattered buffer and every device runs every token (observed 30×
    FLOP inflation on grok — EXPERIMENTS.md §Perf)."""
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    n = xt.shape[0]
    e = p["router"]["w"].shape[1]

    dp_axes = tuple(a for a in ("pod", "data")
                    if mesh is not None and a in mesh.axis_names)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]

    def constrain(arr, spec):
        if mesh is None or mesh.devices.size == 1:
            return arr
        return jax.lax.with_sharding_constraint(
            arr, jax.sharding.NamedSharding(mesh, spec))

    logits = xt.astype(jnp.float32) @ p["router"]["w"]
    ids, w, aux = route(logits, jax.lax.stop_gradient(
        p["router"]["bias_correction"]), top_k=top_k, gate=gate,
        routed_scaling=routed_scaling)

    cap = _capacity(n * top_k, e, capacity_factor)
    if dp > 1:
        cap = -(-cap // dp) * dp          # capacity divisible by dp shards
    flat_ids = ids.reshape(-1)
    slot, keep = _dispatch_indices(flat_ids, e, cap)

    token_of = jnp.repeat(jnp.arange(n, dtype=jnp.int32), top_k)
    buf = jnp.zeros((e * cap, d), ctx.dtype)
    buf = buf.at[jnp.where(keep, slot, e * cap)].set(
        xt[token_of].astype(ctx.dtype), mode="drop")
    buf = constrain(buf.reshape(e, cap, d),
                    P(None, dp_axes if dp_axes else None, None))

    out_buf = _expert_ffn(p["experts"], subtree(q_state, "experts"),
                          buf, ctx)
    out_buf = constrain(out_buf, P(None, dp_axes if dp_axes else None, None))
    out_buf = out_buf.reshape(e * cap, d)

    gathered = out_buf[slot] * (w.reshape(-1, 1) * keep[:, None]).astype(ctx.dtype)
    y = jnp.zeros((n, d), ctx.dtype).at[token_of].add(gathered)
    y = constrain(y, P(dp_axes if dp_axes else None, None))

    if "shared" in p:
        from .layers import swiglu
        y = y + swiglu(p["shared"], subtree(q_state, "shared"), xt, ctx)
    return y.reshape(shape), aux


# --------------------------------------------------------- shard_map EP

def moe_apply_ep(p: dict, q_state: Any, x: jax.Array, ctx: QuantCtx, *,
                 mesh: jax.sharding.Mesh, top_k: int, gate: str = "softmax",
                 capacity_factor: float = 1.25, routed_scaling: float = 1.0,
                 expert_axis: str = "model"):
    """Expert-parallel MoE over ``mesh``: tokens sharded over every mesh
    axis, experts over ``expert_axis``; two all_to_alls per block.

    Equivalent to :func:`moe_apply` up to capacity-drop boundary effects
    (local capacity is enforced per shard — the deliberate production
    trade-off: no global sort, no global collectives outside the two a2a).
    """
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    all_axes = tuple(mesh.axis_names)
    data_axes = tuple(a for a in all_axes if a != expert_axis)
    ep = mesh.shape[expert_axis]
    e = p["router"]["w"].shape[1]
    assert e % ep == 0, (e, ep)

    # decode-sized batches may not divide over every mesh axis: pad token
    # rows to the device count (zero rows route like any token, their
    # outputs are sliced away; capacity is computed from the padded count,
    # so drops are unaffected to first order)
    n_tok = xt.shape[0]
    n_dev = int(mesh.devices.size)
    pad = (-n_tok) % n_dev
    if pad:
        xt = jnp.concatenate([xt, jnp.zeros((pad, d), xt.dtype)], axis=0)

    def local_moe(xt_l, router_w, bias_corr, gate_w, up_w, down_w):
        n_l = xt_l.shape[0]
        logits = xt_l.astype(jnp.float32) @ router_w
        ids, w, aux = route(logits, jax.lax.stop_gradient(bias_corr),
                            top_k=top_k, gate=gate,
                            routed_scaling=routed_scaling)
        cap = _capacity(n_l * top_k, e, capacity_factor)
        flat_ids = ids.reshape(-1)
        slot, keep = _dispatch_indices(flat_ids, e, cap)
        token_of = jnp.repeat(jnp.arange(n_l, dtype=jnp.int32), top_k)

        buf = jnp.zeros((e * cap, d), ctx.dtype)
        buf = buf.at[jnp.where(keep, slot, e * cap)].set(
            xt_l[token_of].astype(ctx.dtype), mode="drop")
        buf = buf.reshape(e, cap, d)

        # (E, C, d) -> (E_loc, ep*C, d): slots travel to their expert's shard
        buf = jax.lax.all_to_all(buf, expert_axis, split_axis=0,
                                 concat_axis=1, tiled=True)
        out = _expert_ffn({"gate": gate_w, "up": up_w, "down": down_w},
                          0, buf, ctx)
        out = jax.lax.all_to_all(out, expert_axis, split_axis=1,
                                 concat_axis=0, tiled=True).reshape(e * cap, d)

        gathered = out[slot] * (w.reshape(-1, 1) * keep[:, None]).astype(ctx.dtype)
        y = jnp.zeros((n_l, d), ctx.dtype).at[token_of].add(gathered)
        return y, jax.lax.pmean(aux, all_axes)

    # expert weights enter shard_map already materialised (fake-quant runs
    # once, outside, under GSPMD; only the a2a pattern needs manual control)
    eq = subtree(q_state, "experts")
    mats = [materialize(p["experts"][k], subtree(eq, k), ctx)
            for k in ("gate", "up", "down")]

    tok_spec = P(all_axes, None)
    y, aux = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P(None),
                  P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None)),
        out_specs=(tok_spec, P()),
    )(xt, p["router"]["w"], p["router"]["bias_correction"], *mats)
    if pad:
        y = y[:n_tok]
        xt = xt[:n_tok]

    if "shared" in p:
        from .layers import swiglu
        y = y + swiglu(p["shared"], subtree(q_state, "shared"), xt, ctx)
    return y.reshape(shape), aux


# --------------------------------------------- shard_map expert-TP (E < tp)

def moe_apply_tp(p: dict, q_state: Any, x: jax.Array, ctx: QuantCtx, *,
                 mesh: jax.sharding.Mesh, top_k: int, gate: str = "softmax",
                 capacity_factor: float = 1.25, routed_scaling: float = 1.0,
                 expert_axis: str = "model"):
    """Per-expert tensor parallelism for few-expert archs (grok: 8e on a
    16-wide model axis).  Tokens shard over the data axes; every model
    column holds a 1/tp slice of every expert's FFN width.  Dispatch is
    purely *local* (sort + scatter within the shard — no cross-device
    scatter), expert FFNs contract their ff slice, and a single psum over
    'model' reduces the row-sharded down-projection.

    Replaces the GSPMD fallback whose cross-shard scatter lowered to
    per-layer all-reduces of the whole (E·C, d) buffer — 1.5e13 collective
    B/device on grok train (§Perf grok iteration 1)."""
    shape = x.shape
    d = shape[-1]
    xt = x.reshape(-1, d)
    all_axes = tuple(mesh.axis_names)
    data_axes = tuple(a for a in all_axes if a != expert_axis)
    e = p["router"]["w"].shape[1]

    eq = subtree(q_state, "experts")
    mats = [materialize(p["experts"][k], subtree(eq, k), ctx)
            for k in ("gate", "up", "down")]

    def local_moe(xt_l, router_w, bias_corr, gate_w, up_w, down_w):
        n_l = xt_l.shape[0]
        logits = xt_l.astype(jnp.float32) @ router_w
        ids, w, aux = route(logits, jax.lax.stop_gradient(bias_corr),
                            top_k=top_k, gate=gate,
                            routed_scaling=routed_scaling)
        cap = _capacity(n_l * top_k, e, capacity_factor)
        flat_ids = ids.reshape(-1)
        slot, keep = _dispatch_indices(flat_ids, e, cap)
        token_of = jnp.repeat(jnp.arange(n_l, dtype=jnp.int32), top_k)

        buf = jnp.zeros((e * cap, d), ctx.dtype)
        buf = buf.at[jnp.where(keep, slot, e * cap)].set(
            xt_l[token_of].astype(ctx.dtype), mode="drop").reshape(e, cap, d)

        g = jnp.einsum("ecd,edf->ecf", buf, gate_w)      # ff/tp slice
        u = jnp.einsum("ecd,edf->ecf", buf, up_w)
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(buf.dtype)
        out = jnp.einsum("ecf,efd->ecd", h, down_w)      # partial sums
        out = jax.lax.psum(out, expert_axis)             # the one collective
        out = out.reshape(e * cap, d)

        gathered = out[slot] * (w.reshape(-1, 1)
                                * keep[:, None]).astype(ctx.dtype)
        y = jnp.zeros((n_l, d), ctx.dtype).at[token_of].add(gathered)
        # aux is already invariant along 'model' (same tokens per column);
        # only the data axes need the mean
        return y, jax.lax.pmean(aux, data_axes)

    tok_spec = P(data_axes, None)
    y, aux = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(tok_spec, P(None, None), P(None),
                  P(None, None, expert_axis), P(None, None, expert_axis),
                  P(None, expert_axis, None)),
        out_specs=(tok_spec, P()),
    )(xt, p["router"]["w"], p["router"]["bias_correction"], *mats)

    if "shared" in p:
        from .layers import swiglu
        y = y + swiglu(p["shared"], subtree(q_state, "shared"), xt, ctx)
    return y.reshape(shape), aux


def moe_ffn(p, q_state, x, ctx, *, mesh: Optional[jax.sharding.Mesh],
            top_k: int, gate: str = "softmax", capacity_factor: float = 1.25,
            routed_scaling: float = 1.0, use_ep: bool = True):
    """Dispatcher: shard_map EP when experts divide the model axis
    (deepseek 256e), shard_map expert-TP when the FFN width divides instead
    (grok 8e × ff 32768), pure-GSPMD sort dispatch otherwise."""
    e = p["router"]["w"].shape[1]
    gate_bank = p["experts"]["gate"]
    if isinstance(gate_bank, dict):      # quant {"w",...} / frozen {"packed",...}
        gate_bank = gate_bank.get("w", gate_bank.get("packed"))
    ff = gate_bank.shape[-1]
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    multi = mesh is not None and mesh.devices.size > 1
    if use_ep and multi and e % tp == 0:
        return moe_apply_ep(p, q_state, x, ctx, mesh=mesh, top_k=top_k,
                            gate=gate, capacity_factor=capacity_factor,
                            routed_scaling=routed_scaling)
    if use_ep and multi and ff % tp == 0:
        return moe_apply_tp(p, q_state, x, ctx, mesh=mesh, top_k=top_k,
                            gate=gate, capacity_factor=capacity_factor,
                            routed_scaling=routed_scaling)
    return moe_apply(p, q_state, x, ctx, top_k=top_k, gate=gate,
                     capacity_factor=capacity_factor,
                     routed_scaling=routed_scaling, mesh=mesh)
