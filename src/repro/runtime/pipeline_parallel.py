"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

For depth-dominated models (grok 64L / deepseek 61L at >512-chip scale) an
extra pipeline axis beats wider TP (which hits ICI latency) — DESIGN.md §4
keeps the default 2-axis mesh for the assigned 256-chip pods, and this
module supplies the third axis when scaling beyond.

Mechanics (``pipeline_apply``): the layer stack (L, ...) is split into
``n_stages`` contiguous stages, one per 'pipe'-axis shard, via shard_map.
Microbatches stream through stages with the canonical rotating schedule:
each of the ``n_micro + n_stages - 1`` ticks runs every stage on its
resident microbatch, then ``collective_permute`` rotates activations to the
next stage.  Bubble fraction = (S-1)/(M+S-1), the GPipe formula — tests
check both the math (vs a single-device reference) and the bubble
accounting.

The per-stage body is an arbitrary ``layer_fn`` (the same scan body the
non-PP path uses), so PP composes with EC4T quantization and with TP on the
trailing 'model' axis unchanged.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_split(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L//S, ...) stage-major."""
    def f(leaf):
        l = leaf.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return leaf.reshape(n_stages, l // n_stages, *leaf.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)


def pipeline_apply(layer_fn: Callable, stage_params: Any, x: jax.Array, *,
                   mesh: Mesh, n_micro: int, axis: str = "pipe") -> jax.Array:
    """Run x (B, ...) through all stages with GPipe microbatching.

    ``layer_fn(stage_local_params, micro_x) -> micro_y`` applies one stage's
    layer block (it may itself scan over the stage's local layers).
    ``stage_params`` leaves are (S, L/S, ...) — stage-sharded over ``axis``.
    B must divide by n_micro.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    micro = x.reshape(n_micro, mb, *x.shape[1:])

    def stage_body(params_local, micro_local):
        # params_local: (1, L/S, ...) this stage's slice
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            buf, out = carry            # buf: (mb, ...) in-flight activation
            # stage 0 ingests microbatch t (when valid)
            take = jnp.clip(t, 0, n_micro - 1)
            fresh = micro_local[take]
            buf = jnp.where(stage_id == 0,
                            jnp.where(t < n_micro, fresh, buf), buf)
            y = layer_fn(params_local, buf)
            # the last stage retires microbatch (t - n_stages + 1)
            retire = t - (n_stages - 1)
            ok = (stage_id == n_stages - 1) & (retire >= 0)
            out = jax.lax.cond(
                ok,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(retire, 0, n_micro - 1), 0),
                lambda o: o, out)
            # rotate stage s -> s+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, out), None

        buf0 = jnp.zeros_like(micro_local[0])
        out0 = jnp.zeros_like(micro_local)
        (_, out), _ = jax.lax.scan(tick, (buf0, out0),
                                   jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast = masked psum
        out = jax.lax.psum(
            jnp.where(stage_id == n_stages - 1, out, jnp.zeros_like(out)),
            axis)
        return out

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P(*([None] * micro.ndim)))
    out = jax.shard_map(stage_body, mesh=mesh, in_specs=in_specs,
                        out_specs=P(), check_vma=False)(stage_params, micro)
    return out.reshape(b, *x.shape[1:])


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: (S-1) / (M + S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
