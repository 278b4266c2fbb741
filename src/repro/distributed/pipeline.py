"""Optional GPipe-style pipeline parallelism over a mesh axis.

The assigned production mesh uses DP x TP (+pod DP), so PP is off by default;
this module exists because 1000+-node deployments of deep models want the
option (DESIGN.md §5).  Implementation: shard_map over the stage axis, a
static schedule of T = n_micro + n_stages - 1 ticks, ``lax.ppermute`` moving
activations stage->stage+1 each tick.  Differentiable (ppermute transposes to
the reverse permute), validated against the sequential reference in tests.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves stacked over n_stages on dim 0
    x: jax.Array,  # (n_micro, micro_batch, ...) microbatched input
    mesh: Mesh,
    axis: str = "model",
) -> jax.Array:
    """Runs x through n_stages sequential stages, pipelined over microbatches.

    stage_fn(params_for_one_stage, h) -> h, same shape (the classic GPipe
    restriction).  Returns (n_micro, micro_batch, ...) outputs.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro % 1 == 0 and n_micro >= 1

    def per_stage(params_l, x_l):
        # params_l: this stage's params (leading stage dim of size 1)
        params_l = jax.tree.map(lambda a: a[0], params_l)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_l[0])  # current activation on this stage
        outs = jnp.zeros_like(x_l)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (others ignore feed)
            feed = jax.lax.dynamic_index_in_dim(
                x_l, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0,
                             jnp.where(t < n_micro, 1.0, 0.0), 1.0) * \
                jnp.where(stage == 0, feed, buf)
            h_out = stage_fn(params_l, h_in)
            # last stage emits microbatch (t - n_stages + 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.clip(out_idx, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # shift activations to the next stage
            buf_next = jax.lax.ppermute(
                h_out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (buf_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(n_ticks))
        return outs

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    # check_vma off: the body's ppermute/cond pattern is not something the
    # varying-manual-axes checker can verify
    out = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(axis),  # each stage returns outs; only last is real
        check_vma=False,
    )(stage_params, x)
    # out has a stage-sharded leading dim view: (n_stages*n_micro, ...) after
    # concat; the real outputs live in the last stage's block
    return out.reshape(n_stages, n_micro, *x.shape[1:])[-1]
