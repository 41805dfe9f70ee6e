"""Pipeline parallelism: a GPipe-style stage runner on a mesh axis.

The main training path uses DP/FSDP/TP/EP (scan-over-layers keeps
activations resident, which on TPU pods beats PP for the assigned dense
sizes); this module provides the PP substrate for depth-dominated regimes
(e.g. granite-34b's 88 layers on small-HBM parts): stages are laid out on
a mesh axis and microbatches stream through with `ppermute` handoffs under
shard_map.

Schedule: classic GPipe fill-drain.  For S stages and M microbatches the
loop runs S+M-1 ticks; stage s computes microbatch (t - s) when
0 <= t - s < M.  Bubble fraction = (S-1)/(S+M-1).

The stage function must be shape-preserving (d_model in == d_model out),
which matches this framework's block stacks.
"""
from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_run(mesh: Mesh, axis: str, stage_fn: Callable,
                 stage_params: Any, x_micro: jnp.ndarray) -> jnp.ndarray:
    """Run microbatches through pipeline stages laid out on ``axis``.

    stage_fn(params_slice, x) -> x            (one stage's computation)
    stage_params: pytree with leading dim == num_stages (sharded on axis)
    x_micro: (M, mb, S, D) microbatches (replicated over ``axis``)

    Returns (M, mb, S, D) outputs after all stages.
    """
    n_stages = mesh.shape[axis]
    m = x_micro.shape[0]
    ticks = n_stages + m - 1

    def body(params_l, xs_l):
        # params_l: this stage's params (leading dim 1); xs_l: all micros
        params_me = jax.tree.map(lambda a: a[0], params_l)
        sid = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(xs_l[0])          # current carried activation
        outs = jnp.zeros_like(xs_l)

        def tick(t, state):
            buf, outs = state
            # stage 0 ingests microbatch t; others take the permuted buf
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = jnp.where(sid == 0, 1, 0)
            x_in = jnp.where(inject, xs_l[mb_idx], buf)
            active = (t - sid >= 0) & (t - sid < m)
            y = stage_fn(params_me, x_in)
            y = jnp.where(active, y, buf)
            # last stage writes its finished microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            write = (sid == n_stages - 1) & (t - (n_stages - 1) >= 0)
            outs = jax.lax.cond(
                write,
                lambda o: o.at[out_idx].set(y),
                lambda o: o, outs)
            # hand activations downstream (ring; stage S-1 -> 0 is ignored)
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage wrote into outs (others kept zeros):
        # a psum over the axis broadcasts the finished microbatches
        return jax.lax.psum(outs, axis)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(stage_params, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)
