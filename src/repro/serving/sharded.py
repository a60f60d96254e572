"""Column-sharded serving execution over a ``('data', 'model')`` mesh.

The serving stack so far scales *across* models (one stream per device,
``frontend.ServingFrontend(streams=N)``); this module scales a *single*
pack across devices — the Megatron column split applied to the frozen
FantastIC4 serving pack.  Each layer's packed (⌈K/2⌉, N) bit-plane
tensor splits over its output features on the ``'model'`` axis (the same
``//packed`` column rule the training-side tree uses — see
``runtime.sharding.serving_pack_specs``), so every shard decodes and
multiplies only its N/tp column slice: 4-bit weight bytes, decode work
and the (K, N/tp) matmul all shrink by the model-axis width.  The
epilogue vectors (alpha1 / bias) follow their layer's split; ω and
alpha2 — the paper's full-precision shared parameters — replicate.

Between layers the next matmul needs the *full* activation row, so each
layer ends in one tiled ``all_gather`` of the column blocks over
``'model'`` (N/tp columns moved per device per layer — the only
communication; there is no psum on this path, which is what keeps it
**bit-exact**, see below).  Batch rows shard over ``'data'`` when the
row count divides the axis and replicate otherwise.

Bit-exactness
-------------

Column-splitting never changes a single output column's arithmetic: the
contraction (K) dimension is not partitioned, every column is computed
in full on exactly one shard with the same accumulation order as the
unsharded per-layer chain kernel, and the tiled all-gather merely
re-concatenates the blocks in mesh order.  A row split would end in a
psum — a *re-association* of the fp32 accumulation — and break the int8
grid's bitwise parity contract; the column split preserves it, and the
int8 inter-layer requantization (clip∘round on elementwise-identical
inputs) then reproduces ``kernels.ops.fantastic4_mlp_chain_int8``
bit-for-bit (``tests/test_serving_sharded.py`` pins this on a forced
multi-device host).

Widths that do not divide the model axis **replicate** (the rules'
divisibility guard): that layer computes fully on every shard and skips
the gather — correct everywhere, scale-out where the pack allows it.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ops as kops
from ..runtime.sharding import Rules, serving_pack_specs


class ShardedStack:
    """One frozen pack bound to one mesh: operands placed once at build
    (``device_put`` under the serving-pack partition specs), one jitted
    shard_map program per batch shape.  Callable like a plan entry:
    ``stack(x) -> logits``.  Built by ``ExecutionPlan(mode="sharded")`` —
    use the plan, not this class, from serving code."""

    def __init__(self, pack: dict, mesh: Mesh, *,
                 act_dtype: str = "float32",
                 act_scales: Optional[List[float]] = None,
                 interpret: Optional[bool] = None,
                 use_kernel: bool = True):
        if "model" not in mesh.axis_names or "data" not in mesh.axis_names:
            raise ValueError(
                f"sharded serving needs a ('data', 'model') mesh; got axes "
                f"{tuple(mesh.axis_names)} (build one with "
                "launch.mesh.fit_mesh)")
        if act_dtype == "int8" and act_scales is None:
            raise ValueError("act_dtype='int8' requires act_scales")
        self.mesh = mesh
        self.layers = pack["layers"]
        self.act_dtype = act_dtype
        self.act_scales = list(act_scales) if act_scales else None
        self.interpret = (kops.default_interpret() if interpret is None
                          else interpret)
        self.use_kernel = use_kernel
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.dp = int(axis_sizes.get("data", 1))
        self.tp = int(axis_sizes.get("model", 1))
        rules = Rules(tuple(mesh.axis_names), axis_sizes, cfg=None)
        self.specs = serving_pack_specs(self.layers, rules)
        self.col_sharded: Tuple[bool, ...] = tuple(
            len(s["packed"]) == 2 and s["packed"][1] is not None
            for s in self.specs)
        # operands placed once, under the rules' specs — every later call
        # reuses the resident shards (the plan/operand-cache posture).
        self._operand_specs = tuple(
            (s["packed"], s["omega"], s["alpha1"], s["bias"], s["alpha2"])
            for s in self.specs)
        self._operands = tuple(
            tuple(jax.device_put(
                jnp.asarray(arr, dtype=None), NamedSharding(mesh, spec))
                for arr, spec in zip(
                    (layer["packed"], layer["omega"], layer["alpha1"],
                     layer["bias"],
                     jnp.asarray(1.0 if layer.get("alpha2") is None
                                 else layer["alpha2"], jnp.float32)),
                    self._operand_specs[i]))
            for i, layer in enumerate(self.layers))
        self._fns: Dict[Tuple[int, int], Callable] = {}

    # ----------------------------------------------------------- body

    def _layer_blocks(self, m: int) -> Tuple[Optional[tuple], ...]:
        """Per-layer kernel blocks for an ``m``-row batch, resolved eagerly
        (the TPU sweep times real kernels, which it cannot do on the
        tracers inside ``shard_map``).  Each layer takes the blocks of its
        *unsharded* ``(m, K, N)`` problem — the per-layer chain's own cache
        entry — so every column is summed over the same K blocks as in
        the chain; ``block_n`` is clamped to the shard's width by the
        kernel."""
        if not self.use_kernel:
            return (None,) * len(self.layers)
        out = []
        for layer in self.layers:
            k, n = layer["shape"]
            cfg = kops.matmul_blocks(m, k + k % 2, n,
                                     interpret=self.interpret,
                                     activation=layer.get("activation"))
            out.append(cfg.as_tuple())
        return tuple(out)

    def _stack_body(self, x: jax.Array, operands, blocks) -> jax.Array:
        """Per-shard stack: the per-layer chain with column-local matmuls
        and a tiled gather after each split layer.  Mirrors
        ``fantastic4_mlp_chain`` / ``fantastic4_mlp_chain_int8``
        expression-for-expression — the bitwise parity ground truth."""
        int8 = self.act_dtype == "int8"
        n = len(self.layers)
        xq = x.astype(jnp.float32)
        in_scale = 1.0
        for i, (layer, ops_i) in enumerate(zip(self.layers, operands)):
            packed, omega, alpha1, bias, alpha2 = ops_i
            bm, bn, bk = blocks[i] or (None, None, None)
            if layer["shape"][0] % 2:
                # odd K: the pack carries one zero code row — mirror on x
                xq = jnp.pad(xq, ((0, 0), (0, 1)))
            if int8:
                alpha1 = alpha1 * in_scale     # de-quantize inputs
                alpha2 = None
            y = kops.fantastic4_matmul(
                xq, packed, omega, bias=bias, alpha1=alpha1,
                alpha2=alpha2, activation=layer.get("activation"),
                use_kernel=self.use_kernel, interpret=self.interpret,
                block_m=bm, block_n=bn, block_k=bk)
            if self.col_sharded[i]:
                y = jax.lax.all_gather(y, "model", axis=1, tiled=True)
            if int8 and i < n - 1:
                s = self.act_scales[i]
                y = jnp.clip(jnp.round(y / s), -127, 127)
                y = y.astype(jnp.int8).astype(jnp.float32)
                in_scale = s
            xq = y
        return xq

    # ----------------------------------------------------------- call

    def _fn_for(self, m: int, d: int) -> Callable:
        fn = self._fns.get((m, d))
        if fn is None:
            # batch rows shard over 'data' when they divide the axis; an
            # indivisible batch replicates (every device computes every
            # row — correct, not scaled) instead of failing.
            xspec = P("data", None) if m % self.dp == 0 else P(None, None)
            body = functools.partial(self._stack_body,
                                     blocks=self._layer_blocks(m))
            mapped = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(xspec, self._operand_specs),
                out_specs=xspec,
                # Pallas outputs carry no varying-axes annotation
                check_vma=False)
            fn = jax.jit(mapped)
            self._fns[(m, d)] = fn
        return fn

    def __call__(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x, jnp.float32)
        return self._fn_for(*x.shape)(x, self._operands)

    # ----------------------------------------------------------- report

    def describe(self) -> dict:
        return {
            "mesh": dict(zip(self.mesh.axis_names,
                             (int(s) for s in self.mesh.devices.shape))),
            "n_devices": int(self.mesh.devices.size),
            "col_sharded_layers": [i for i, c in
                                   enumerate(self.col_sharded) if c],
            "replicated_layers": [i for i, c in
                                  enumerate(self.col_sharded) if not c],
        }
