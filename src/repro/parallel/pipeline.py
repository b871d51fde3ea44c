"""GPipe pipeline parallelism via shard_map + collective_permute.

Stages hold contiguous layer chunks; microbatches stream through the ring:
at tick t, stage s computes microbatch (t - s) and passes its activation to
stage s+1 with ppermute.  Bubble fraction = (S-1)/(T+S-1), reported by
``bubble_fraction`` and validated in tests/test_parallel.py against the
sequential reference (exact equality of outputs).

This is a library feature (the 40-cell dry-run uses DP×TP×FSDP per
DESIGN.md §6); it targets meshes with a 'pipe' axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn, stage_params, x_micro, mesh: Mesh,
                   axis: str = "pipe"):
    """Run ``stage_fn(params_s, x)`` as a GPipe pipeline over mesh axis.

    stage_params: pytree whose leaves have a leading n_stages axis (sharded
      over ``axis``).
    x_micro: (n_micro, mb, ...) microbatched input, replicated.
    Returns (n_micro, mb, ...) outputs (stage S-1's results, replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def body(params, xm):
        # params: leading stage axis sliced to this stage (leading dim 1)
        params = jax.tree.map(lambda p: p[0], params)
        sid = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xm[0])  # current activation for this stage
        outs = jnp.zeros_like(xm)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = t - sid  # which microbatch this stage works on
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            # stage 0 ingests a fresh microbatch
            x_in = jnp.where(
                sid == 0,
                xm[jnp.clip(mb_idx, 0, n_micro - 1)],
                buf,
            )
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage records its finished microbatch
            outs = jnp.where(
                (sid == n_stages - 1) & active,
                outs.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                outs,
            )
            # ring forward: stage s -> s+1 (last wraps to 0, ignored)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # all-reduce over the pipe axis: only the last stage wrote outs
        outs = jax.lax.psum(outs, axis)
        return outs

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_micro)
