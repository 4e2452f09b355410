"""Pipeline parallelism: microbatched SPMD pipeline over the "pipe" axis.

The reference has no in-tree pipeline engine (SURVEY.md §2.4 "PP:
Absent"); this fills that row TPU-natively. Instead of a torch-style
scheduler object issuing forward/backward ops per rank, the whole
pipeline is ONE spmd program: stage params are sharded over the "pipe"
mesh axis, the forward is a fori_loop whose per-tick activation hand-off
is a lax.ppermute ring shift, and jax AD differentiates through the loop
— the reversed ppermutes ARE the backward pipeline, and XLA schedules
both (the compiler-scheduled equivalent of a hand-written 1F1B; same
math, same per-stage memory scaling in n_micro).

Cost model: T = n_micro + n_stages - 1 ticks; every stage computes every
tick, so utilization is n_micro / T — the standard pipeline bubble.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

from ray_tpu.parallel.mesh import AXIS_PIPE


def make_pipeline_fn(stage_fn: Callable[[Any, Any], Any],
                     n_stages: int, n_micro: int, mesh,
                     loss_fn: Optional[Callable[[Any, Any], Any]] = None):
    """Build pipelined(params_stacked, x_micro, y_micro) -> mean loss.

    stage_fn(stage_params, x) -> x'   (one stage's chunk of layers)
    params_stacked: pytree whose leaves have leading dim n_stages (the
    "layers"→"pipe" sharded stack). x_micro: [n_micro, mb, ...] inputs.
    loss_fn(final_out, y) -> per-microbatch scalar (required: the
    pipeline's product is the scalar objective to differentiate; per-
    microbatch outputs never leave the last stage). The mean over
    microbatches is returned, identical to running the unpipelined model.
    """
    if loss_fn is None:
        raise ValueError("make_pipeline_fn requires loss_fn: the pipeline "
                         "returns the differentiable scalar objective")
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, x_micro, y_micro, extras):
        # params: this stage's pytree (leading stage dim stripped by
        # shard_map's P(AXIS_PIPE, ...) spec → local leaves [1, ...]).
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(AXIS_PIPE)
        n_ticks = n_micro + n_stages - 1

        def apply_loss(out, y):
            # Traced arrays must enter the shard_map explicitly (closure
            # capture would broadcast with an auto-mesh sharding, which
            # manual-mode rejects); `extras` is that explicit door for
            # loss params (final norm / lm head / ...).
            if extras is not None:
                return loss_fn(out, y, extras)
            return loss_fn(out, y)

        def tick(t, carry):
            buf, losses = carry
            # stage 0 ingests microbatch t (garbage after the last one —
            # masked out because its results fall past the drain window)
            feed = x_micro[jnp.minimum(t, n_micro - 1)]
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(params, inp)
            # last stage finishes microbatch m = t - (n_stages - 1)
            m = t - (n_stages - 1)
            valid = jnp.logical_and(stage == n_stages - 1,
                                    jnp.logical_and(m >= 0, m < n_micro))
            y = y_micro[jnp.clip(m, 0, n_micro - 1)]
            losses = losses + jnp.where(valid, apply_loss(out, y), 0.0)
            nxt = jax.lax.ppermute(out, AXIS_PIPE, fwd_perm)
            return (nxt, losses)

        # carry shape/dtype via eval_shape — an actual x*0.0 application
        # would cost one extra stage computation per invocation (XLA can't
        # fold float x*0 because of NaN/Inf semantics)
        out_shape = jax.eval_shape(stage_fn, params, x_micro[0])
        buf0 = jnp.zeros(out_shape.shape, out_shape.dtype)
        losses0 = jnp.zeros(())
        buf, losses = jax.lax.fori_loop(0, n_ticks, tick, (buf0, losses0))
        # total loss lives on the last stage; share it with every stage
        total = jax.lax.psum(losses, AXIS_PIPE) / n_micro
        return total[None]

    def run(params_stacked, x_micro, y_micro, extras=None):
        """extras: optional replicated pytree handed to
        loss_fn(out, y, extras) — pass loss-side parameters here, never
        via closure (see apply_loss)."""
        if extras is None:
            # bind extras=None statically so the shard_map sees 3 inputs
            fn = functools.partial(per_stage, extras=None)
            in_specs = (P(AXIS_PIPE), P(), P())
            args = (params_stacked, x_micro, y_micro)
        else:
            fn = per_stage
            in_specs = (P(AXIS_PIPE), P(), P(), P())
            args = (params_stacked, x_micro, y_micro, extras)
        pipelined = shard_map(fn, mesh=mesh, in_specs=in_specs,
                              out_specs=P(AXIS_PIPE), check_vma=False)
        out = pipelined(*args)
        return out.mean()  # identical replicated per-stage values

    return run


def stack_stage_params(per_stage_params: list) -> Any:
    """[stage0_tree, stage1_tree, ...] → one tree with leading stage dim
    (shard it ("layers", ...) → pipe)."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
