"""Pipeline parallelism (pp mesh axis): GPipe-style microbatched stages.

NEW capability relative to the reference (SURVEY.md §2.5: the reference's
only model parallelism is manual `group2ctx` device placement with
cross-device copies).  TPU-native design: every pp device holds ONE
stage's parameters; a `shard_map` over the pp axis runs the classic
GPipe schedule — M microbatches flow through S stages in M+S-1 ticks,
activations hop stage→stage with `lax.ppermute` over ICI, and the whole
schedule is a single `lax.scan` inside one jitted SPMD program (no
host-side orchestration, unlike GPipe's original executor).

Forward-only utilities here compose with jax.grad: the scan/ppermute
schedule is differentiable, so the backward pipeline (reverse ppermute
schedule) falls out of the same program — the pjit analog of GPipe's
re-forward backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .mesh import mesh_shape


def pipeline_apply(stage_fn, stage_params, x, mesh: Mesh,
                   num_microbatches: int, axis: str = "pp"):
    """Run ``stage_fn`` as a pp-axis pipeline.

    stage_fn(params_i, h) -> h        (same activation shape in/out)
    stage_params: pytree whose leaves have leading dim S == pp size
                  (stage i's params live on pp rank i)
    x: (batch, ...) global input; batch must divide num_microbatches
    Returns stage_{S-1}(...stage_0(x)) exactly, computed GPipe-style.
    """
    S = mesh_shape(mesh).get(axis, 1)
    if S <= 1:
        h = x
        for i in range(jax.tree.leaves(stage_params)[0].shape[0]):
            h = stage_fn(jax.tree.map(lambda p: p[i], stage_params), h)
        return h
    B = x.shape[0]
    if B % num_microbatches:
        raise MXNetError(
            f"pipeline_apply: batch {B} not divisible by "
            f"num_microbatches {num_microbatches}")
    mb = B // num_microbatches
    xm = x.reshape((num_microbatches, mb) + x.shape[1:])

    n_ticks = num_microbatches + S - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params_local, xm_local):
        # params_local: this stage's params (leading dim 1 from sharding)
        params_i = jax.tree.map(lambda p: p[0], params_local)
        idx = lax.axis_index(axis)

        def tick(carry, t):
            incoming, outputs = carry
            # stage 0 feeds itself from the microbatch stream; others use
            # the activation ppermuted from the previous stage
            feed = jnp.where(t < num_microbatches, t, 0)
            h_in = jnp.where(idx == 0, xm_local[feed], incoming)
            h_out = stage_fn(params_i, h_in)
            # last stage records finished microbatches (tick t finishes
            # microbatch t-(S-1))
            done = t - (S - 1)
            write = jnp.where((idx == S - 1) & (done >= 0), 1.0, 0.0)
            slot = jnp.where(done >= 0, done, 0)
            outputs = outputs.at[slot].add(write * h_out)
            nxt = lax.ppermute(h_out, axis, fwd_perm)
            return (nxt, outputs), None

        init_in = jnp.zeros_like(xm_local[0])
        outs0 = jnp.zeros_like(xm_local)
        (_, outputs), _ = lax.scan(
            tick, (init_in, outs0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum broadcasts them
        return lax.psum(outputs, axis)

    pspec_params = P(axis)
    pspec_x = P()        # microbatch stream replicated over pp
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec_params, stage_params),
                  pspec_x),
        out_specs=P(),
        check_vma=False)
    out = fn(stage_params, xm)
    return out.reshape((B,) + x.shape[1:])
