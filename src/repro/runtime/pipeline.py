"""GPipe-style pipeline parallelism over a mesh axis (designed for "pod").

Cross-pod ICI/DCN links are the slowest; pipeline point-to-point traffic
(one activation tensor per microbatch tick) is the cheapest way to use them.
The layer stack (leading scan axis) is sharded over the pipeline axis via
shard_map; inside, a GPipe schedule runs M microbatches over P stages with
``ppermute`` hops.  The SPMD emulation computes every stage every tick
(bubble = (P-1)/(M+P-1) wasted ticks — the standard GPipe overhead).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



def pipeline_body(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    *,
    axis: str,
    microbatches: int,
):
    """Per-shard GPipe body (call inside shard_map over ``axis``).

    stage_fn(stage_params, xmb) -> ymb applies THIS stage's layer slice.
    x: (B, ...) replicated batch; returns y: (B, ...) replicated.
    """
    nstages = lax.psum(1, axis)
    s = lax.axis_index(axis)
    M = microbatches
    B = x.shape[0]
    assert B % M == 0
    mb = x.reshape(M, B // M, *x.shape[1:])
    ticks = M + nstages - 1
    perm = [(i, i + 1) for i in range(nstages - 1)]

    def tick(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t (or garbage past the end)
        idx = jnp.clip(t, 0, M - 1)
        inp = jnp.where(s == 0, mb[idx], buf)
        out = stage_fn(stage_params, inp)
        # last stage collects microbatch t-(P-1)
        oidx = t - (nstages - 1)
        valid = (s == nstages - 1) & (oidx >= 0)
        outs = lax.cond(
            valid,
            lambda o: o.at[jnp.clip(oidx, 0, M - 1)].set(out),
            lambda o: o,
            outs,
        )
        nxt = lax.ppermute(out, axis, perm)
        return (nxt, outs), None

    buf0 = jnp.zeros_like(mb[0])
    outs0 = jnp.zeros((M,) + mb.shape[1:], x.dtype)
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # broadcast final outputs from the last stage to all stages
    outs = lax.psum(jnp.where(s == nstages - 1, outs, jnp.zeros_like(outs)), axis)
    return outs.reshape(B, *x.shape[1:])


def pipelined_apply(
    stage_fn: Callable,
    params_stacked,
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "pod",
    microbatches: int = 4,
):
    """shard_map wrapper: layer-stack leading dim sharded over ``axis``."""
    pspec = jax.tree.map(lambda _: P(axis), params_stacked)
    body = partial(pipeline_body, stage_fn, axis=axis, microbatches=microbatches)
    return jax.shard_map(
        lambda p, xx: body(p, xx),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x)
