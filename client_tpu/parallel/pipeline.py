"""GPipe-style pipeline parallelism over the mesh's ``pp`` axis.

The layer stack is split into S contiguous stages; each device along ``pp``
holds one stage's parameters (stacked with a leading stage dim sharded over
``pp``).  Microbatches flow through the pipeline with ``lax.ppermute``
activation handoffs riding ICI: at step t, stage s processes microbatch
t - s, so after M + S - 1 steps all M microbatches have crossed all stages
and the bubble is the classic (S-1)/(M+S-1) fraction.

Everything runs inside one ``jax.shard_map``-ed, jit-compiled program —
the schedule is a ``lax.scan``, the handoff a collective, nothing is
host-orchestrated.  Backward works by differentiating straight through the
scan + ppermute (grad of a ppermute is the reverse ppermute), which gives
correct full-batch gradients with recomputation — the 1F1B memory schedule
is the production refinement this trades away.

This completes the framework's parallelism portfolio (dp/tp/sp/ep/pp);
the reference client stack has none of it (SURVEY.md §2.4 note).
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def stack_stage_params(layers, n_stages):
    """[L] list of identical per-layer pytrees -> pytree with leading
    [S, L/S] dims, ready to shard over ``pp``."""
    n_layers = len(layers)
    if n_layers % n_stages:
        raise ValueError(
            f"{n_layers} layers not divisible by {n_stages} stages"
        )
    per = n_layers // n_stages
    stage_trees = [
        jax.tree.map(lambda *xs: jnp.stack(xs), *layers[s * per:(s + 1) * per])
        for s in range(n_stages)
    ]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stage_trees)


def pipeline_apply(stage_fn, stage_params, x, mesh, n_microbatches,
                   axis="pp", batch_axis="dp"):
    """Run ``x`` through the S-stage pipeline.

    Args:
      stage_fn: ``(stage_layers, x_mb) -> y_mb`` applying ONE stage's layer
        block to one microbatch; ``stage_layers`` leaves have a leading
        [L/S] dim (scan over it inside).  Must preserve the microbatch
        shape (activations hand off between stages unchanged).
      stage_params: pytree from :func:`stack_stage_params`, leaves
        [S, L/S, ...], laid out (or laid out by this call) over ``axis``.
      x: [B, ...] batch, B divisible by n_microbatches.
      mesh: mesh containing ``axis``.
      batch_axis: mesh axis the per-microbatch batch dim shards over
        (data parallelism *inside* the pipeline region); each dp slice
        pipelines its own microbatch shard.  Pass None to replicate.

    Within the pipeline region the non-stage dims of ``stage_params`` are
    replicated: tensor-parallel sharding inside a shard_map body needs
    hand-written collectives in ``stage_fn``, which this GPipe layer does
    not do — tp/ep compose only outside the region (embed / lm_head).

    Returns [B, ...] outputs, replicated over ``axis``, sharded over
    ``batch_axis``.
    """
    n_stages = mesh.shape[axis]
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by {n_microbatches} microbatches"
        )
    mb = batch // n_microbatches
    if batch_axis is not None and mesh.shape[batch_axis] > 1:
        if mb % mesh.shape[batch_axis]:
            raise ValueError(
                f"microbatch size {mb} not divisible by "
                f"{batch_axis}={mesh.shape[batch_axis]}"
            )
    else:
        batch_axis = None
    x_micro = x.reshape(n_microbatches, mb, *x.shape[1:])
    x_spec = P(None, batch_axis)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)

    def body(local_params, x_all):
        # local leaves are [1, L/S, ...]: drop the sharded stage dim
        local_params = jax.tree.map(lambda a: a[0], local_params)
        stage = lax.axis_index(axis)
        n_steps = n_microbatches + n_stages - 1
        state = jnp.zeros(x_all.shape[1:], x_all.dtype)  # inflight activation
        outputs = jnp.zeros_like(x_all)
        if hasattr(lax, "pcast"):
            # the scan body makes both carries pp-varying (stage params are
            # sharded over pp) — and dp-varying when the batch is sharded;
            # the zero-initialized carries must match.  `outputs` inherits
            # the batch variance from zeros_like(x_all); `state` is fresh.
            vary = (axis,) if batch_axis is None else (axis, batch_axis)
            state = lax.pcast(state, vary, to="varying")
            outputs = lax.pcast(outputs, (axis,), to="varying")

        def step(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clamped: past-the-end steps feed
            # a stale microbatch whose output is never collected)
            feed = lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, n_microbatches - 1), keepdims=False
            )
            current = jnp.where(stage == 0, feed, state)
            y = stage_fn(local_params, current)
            # the last stage's step-t output is microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            idx = jnp.clip(out_idx, 0, n_microbatches - 1)
            zeros = (0,) * y.ndim
            old = lax.dynamic_slice(outputs, (idx,) + zeros, (1,) + y.shape)
            outputs = lax.dynamic_update_slice(
                outputs, jnp.where(valid, y[None], old), (idx,) + zeros
            )
            # hand the activation to the next stage
            state = lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (state, outputs), None

        (state, outputs), _ = lax.scan(
            step, (state, outputs), jnp.arange(n_steps)
        )
        # only the last stage holds real outputs (zeros elsewhere): the psum
        # broadcasts them to every stage, making the result replicated
        return lax.psum(outputs, axis)

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
    )
    out = fn(stage_params, x_micro)
    return out.reshape(batch, *x.shape[1:])
