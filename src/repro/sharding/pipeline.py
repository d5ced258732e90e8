"""Opt-in pipeline parallelism: GPipe-style microbatch streaming.

Stages are laid out on a ``pipe`` mesh axis; each device holds one stage's
parameters (sharded on the leading stage dim). Microbatches stream through
the pipeline with ``jax.lax.ppermute`` ring transfers inside ``shard_map``;
the scan has the classic ``n_micro + n_stages - 1`` fill/drain schedule. The
production 512-chip mesh uses "pod" as outer data-parallel by default;
configuring ``("pipe", "data", "model")`` instead turns this on (e.g. for
cross-DCN pods where pipeline's point-to-point traffic beats all-reduce).

Bubble fraction = (S-1)/(S-1+M): callers pick n_micro >= 4x stages.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages - 1 + n_micro)


def pipeline_apply(
    stage_fn: Callable,                # (stage_params, x) -> x
    stage_params,                      # pytree, leaves [n_stages, ...]
    xs: jnp.ndarray,                   # [n_micro, micro_batch, ...]
    *,
    mesh: Mesh,
    axis_name: str = "pipe",
) -> jnp.ndarray:
    """Run ``n_stages`` sequential stages over ``n_micro`` microbatches.
    Returns [n_micro, micro_batch, ...] — identical to applying the stages
    sequentially (the test asserts this)."""
    n_stages = dict(mesh.shape)[axis_name]
    n_micro = xs.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def run(params, xs_local):
        stage = jax.lax.axis_index(axis_name)
        p = jax.tree.map(lambda a: a[0], params)       # this device's stage
        T = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs_local[0])              # inbound activation
        outs = jnp.zeros_like(xs_local)

        def step(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (while available); other stages
            # consume what arrived over the ring
            inject = xs_local[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(stage == 0, inject, buf)
            y = stage_fn(p, x_in)
            # the LAST stage emits microbatch t-(S-1); everyone else forwards
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (out_idx < n_micro) & (
                stage == n_stages - 1)
            upd = jnp.where(valid, y, outs[jnp.clip(out_idx, 0, n_micro - 1)])
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, upd, jnp.clip(out_idx, 0, n_micro - 1), 0)
            buf = jax.lax.ppermute(y, axis_name, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(T))
        # only the last stage holds real outputs; broadcast over the ring
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    spec_params = jax.tree.map(lambda _: P(axis_name), stage_params)
    smap = jax.shard_map(
        run, mesh=mesh, in_specs=(spec_params, P()), out_specs=P(),
        check_vma=False)
    return jax.jit(smap)(stage_params, xs)
