"""The vocab head: hidden states to logits, and to the token cross-entropy.

The one module that knows the head's weight and its orientation (a tied
`[vocab, d]` embedding contracted as `vd`, an untied `[d, vocab]`
`lm_head`), the logits einsum with its f32 accumulation (divided by
`cfg.logit_divisor` where a model publishes one), the cross-entropy
(`logsumexp` less the gold logit), and how a training step keeps the
`[B, T, vocab]` f32 logits out of HBM: T is scanned in `cfg.loss_chunk`
slices, each chunk's gradient is taken in that same scan while its logits
exist (a `custom_vjp`), and where the mesh splits only the batch the chunks
run per chip under `shard_map`.

`logits` is for `Transformer.apply`; everything that trains takes `nll_sum`
of `weight`. The projection is scope `head`, the cross-entropy and the
chunking around it `loss` (models/transformer.py's vocabulary).
"""

from __future__ import annotations

from typing import Optional

from ray_tpu.models.configs import TransformerConfig
from ray_tpu.parallel.sharding import (ShardingRules, logical_sharding,
                                       with_logical_constraint)


def weight(params, cfg: TransformerConfig):
    """The head's parameter as `logits` and `nll_sum` contract it: the
    embedding itself when tied ("vd": no `[d, vocab]` transpose is
    materialized each step), else `lm_head` ("dv")."""
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


def _cast(w, dtype):
    import jax

    with jax.named_scope("head"):
        return w.astype(dtype)


def _project(head, x, cfg: TransformerConfig, seq_axis, mesh, rules):
    """x [b, c, d] x head -> f32 logits [b, c, vocab]; `seq_axis` is the
    logical name of c: "seq" for whole sequences, None for a chunk."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("head"):
        eq = "bcd,vd->bcv" if cfg.tie_embeddings else "bcd,dv->bcv"
        logits = jnp.einsum(eq, x, head, preferred_element_type=jnp.float32)
        if cfg.logit_divisor != 1.0:   # muP's `logits_scaling`
            logits = logits / cfg.logit_divisor
        return with_logical_constraint(
            logits, ("batch", seq_axis, "act_vocab"), mesh=mesh, rules=rules)


def logits(params, x, cfg: TransformerConfig, *, mesh=None,
           rules: Optional[ShardingRules] = None):
    """hidden states [B, T, d] -> f32 logits [B, T, vocab]."""
    return _project(_cast(weight(params, cfg), x.dtype), x, cfg, "seq",
                    mesh, rules)


def _token_nll(head, x, targets, cfg, seq_axis, mesh, rules):
    """The token cross-entropy of x [b, t, d] against targets [b, t]:
    f32 [b, t]."""
    import jax
    import jax.numpy as jnp

    logits = _project(head, x, cfg, seq_axis, mesh, rules)
    with jax.named_scope("loss"):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return logz - gold


def _token_nll_sum(head, x, targets, mask, weights, cfg, seq_axis, mesh,
                   rules):
    """`_token_nll` summed under `mask` and `weights` (each [b, t] or
    None): the f32 scalar, and with `weights` the pair of it and the
    tokens' own f32 [b, t] cross-entropy, which is d sum / d weights under
    the mask."""
    import jax
    import jax.numpy as jnp

    nll = _token_nll(head, x, targets, cfg, seq_axis, mesh, rules)
    with jax.named_scope("loss"):
        if mask is not None:
            nll = nll * mask
        if weights is None:
            return jnp.sum(nll)
        return jnp.sum(nll * weights), nll


def _chunked_nll_sum(chunk_nll_sum, head, x, targets, mask, weights,
                     chunk, unroll):
    """Sum of `chunk_nll_sum(head, x_c, t_c, m_c, w_c)` over the
    `chunk`-token slices of these sequences, so only one [b, chunk, vocab]
    f32 logits block lives in HBM at a time. Each chunk's gradient is
    taken in that same scan (`nll_sum_fwd`), while its logits exist:
    nothing is saved for, or computed again in, the backward pass. A
    custom_vjp: reverse mode only (nothing in the tree takes a
    forward-mode or a second derivative of the loss).

    `mask` gets no cotangent (`None`: it is data). `weights` [b, t] do:
    d sum / d weights is the tokens' own (masked) cross-entropy, which the
    forward scan has in hand, so with `weights` the scan stacks that f32
    [b, t] array besides, the function returns it beside the sum (a
    reading: its own cotangent is dropped), and the backward pass hands
    `weights` the sum's cotangent times it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = x.shape[1] // chunk
    weighted = weights is not None

    def split(a):  # [b, t, ...] -> [n, b, chunk, ...]
        return jnp.swapaxes(
            a.reshape(a.shape[0], n, chunk, *a.shape[2:]), 0, 1)

    def join(a):   # back
        return jnp.swapaxes(a, 0, 1).reshape(
            a.shape[1], n * chunk, *a.shape[3:])

    def scan_chunks(step, init, x, targets, mask, weights):
        """`step(carry, (x_c, t_c, m_c, w_c))` over the n chunks; m_c and
        w_c are None for a batch without them"""
        ms = None if mask is None else split(mask)
        ws = None if weights is None else split(weights)
        return lax.scan(step, init, (split(x), split(targets), ms, ws),
                        unroll=unroll)

    @jax.custom_vjp
    def nll_sum(head, x, targets, mask, weights):
        def step(total, xtmw):
            out = chunk_nll_sum(head, *xtmw)
            if weighted:
                return total + out[0], out[1]
            return total + out, None
        total, nll = scan_chunks(step, jnp.zeros((), jnp.float32),
                                 x, targets, mask, weights)
        return (total, join(nll)) if weighted else total

    def nll_sum_fwd(head, x, targets, mask, weights):
        # the sum's incoming cotangent is one scalar, so d head and dx
        # are complete here but for that factor; d head is carried in
        # the head's dtype, as autodiff's backward scan carried it
        def step(carry, xtmw):
            total, d_head = carry
            out, (dh_c, dx_c) = jax.value_and_grad(
                chunk_nll_sum, argnums=(0, 1), has_aux=weighted)(
                    head, *xtmw)
            if weighted:
                return (total + out[0], d_head + dh_c), (dx_c, out[1])
            return (total + out, d_head + dh_c), dx_c
        (total, d_head), out = scan_chunks(
            step, (jnp.zeros((), jnp.float32), jnp.zeros_like(head)),
            x, targets, mask, weights)
        if weighted:
            nll = join(out[1])
            return (total, nll), (d_head, join(out[0]), nll)
        return total, (d_head, join(out))

    def nll_sum_bwd(res, g):
        with jax.named_scope("loss"):
            if weighted:
                g = g[0]   # the reading's own cotangent is dropped
            d_head, dx = ((g * r).astype(r.dtype) for r in res[:2])
            d_weights = g * res[2] if weighted else None
        return d_head, dx, None, None, d_weights

    nll_sum.defvjp(nll_sum_fwd, nll_sum_bwd)

    # the chunking itself (slicing the hidden states, stacking their
    # gradients, the running sums) is "loss"; the projection inside
    # chunk_nll_sum names itself "head"
    with jax.named_scope("loss"):
        return nll_sum(head, x, targets, mask, weights)


def nll_sum(w, x, targets, cfg: TransformerConfig, *, mask=None,
            weights=None, mesh=None,
            rules: Optional[ShardingRules] = None):
    """Sum over the tokens of hidden states x [B, T, d] of the (masked)
    negative log-likelihood of targets [B, T] under the head `w`
    (`weight`): f32 scalar. Chunked over T where `cfg.loss_chunk` divides
    a longer T, else plain autodiff through whole-sequence logits.

    `mask` [B, T] is data: it multiplies each token's term and gets no
    cotangent. `weights` [B, T] f32 are part of the model (a looped
    stack's exit distribution): they multiply each token's (masked) term,
    the result is the pair (the weighted sum, the tokens' own masked f32
    [B, T] cross-entropy under a stopped gradient: a reading for
    metrics), and the weights' cotangent is the sum's times that
    cross-entropy, chunked or not."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rules = rules or ShardingRules()
    head = _cast(w, x.dtype)
    if mask is not None:
        mask = mask.astype(jnp.float32)
    b, t = targets.shape
    chunk = cfg.loss_chunk

    def with_reading(out):
        if weights is None:
            return out
        return out[0], lax.stop_gradient(out[1])

    if not (chunk and t > chunk and t % chunk == 0):
        return with_reading(_token_nll_sum(
            head, x, targets, mask, weights, cfg, "seq", mesh, rules))

    # GSPMD cannot carry an unreduced sum through a loop: it reduces the
    # whole [vocab, d] dW, and gathers the head, once per chunk. So where
    # the mesh splits only the batch, the chunks run per chip (shard_map).
    per_chip = False
    if mesh is not None:
        batch_axes, = logical_sharding(("batch",), mesh, rules, (b,)).spec
        head_spec = logical_sharding(
            ("vocab", "embed") if cfg.tie_embeddings else ("embed", "vocab"),
            mesh, rules, head.shape).spec
        per_chip = batch_axes is not None and all(
            size == 1 for a, size in mesh.shape.items()
            if a not in batch_axes)
    # inside the map the chip owns its layout: no GSPMD constraint
    c_mesh = None if per_chip else mesh

    def chunk_nll_sum(head, x_c, t_c, m_c, w_c):
        return _token_nll_sum(head, x_c, t_c, m_c, w_c, cfg, None, c_mesh,
                              rules)

    # what the batch has of mask and weights, by name, after x and targets
    given = [name for name, a in (("mask", mask), ("weights", weights))
             if a is not None]

    def local_nll_sum(head, x, targets, *rest):
        rest = dict(zip(given, rest))
        return _chunked_nll_sum(
            chunk_nll_sum, head, x, targets, rest.get("mask"),
            rest.get("weights"), chunk, cfg.scan_unroll > 1)

    args = (x, targets) + tuple(
        a for a in (mask, weights) if a is not None)
    if not per_chip:
        return with_reading(local_nll_sum(head, *args))

    def per_chip_nll_sum(head, *local):
        with jax.named_scope("head"):
            for dim, axes in enumerate(head_spec):
                if axes is not None:
                    head = lax.all_gather(head, axes, axis=dim, tiled=True)
        out = local_nll_sum(head, *local)
        with jax.named_scope("loss"):
            if weights is None:
                return lax.psum(out, batch_axes)
            return lax.psum(out[0], batch_axes), out[1]

    from jax.sharding import PartitionSpec as P
    # check_vma=False as in Transformer._make_attention: the checker types
    # the gathered head as varying and puts a psum of dW in every chunk
    return with_reading(jax.shard_map(
        per_chip_nll_sum, mesh=mesh,
        in_specs=(head_spec,) + (P(batch_axes),) * len(args),
        out_specs=P() if weights is None else (P(), P(batch_axes)),
        check_vma=False)(head, *args))
