"""A configuration's programs, compiled once a process.

Called eagerly, `Transformer.loss` traces and compiles its scans anew on
every call (a second seed, a second test, a second pass at the same
configuration pays the first one's 10-20 s again), and a plain reference of
`benchmark/reference/` compiles every op of every layer alone (three times
what one program costs). The whole-model tests read both sides through
these instead: one `jax.jit` each of the logits, the loss with its metrics
and the loss's gradients, kept by the (hashable, frozen) configuration, so
that only a new configuration or new sizes compile. The references are
called as they are, under a trace: their files are not edited.
"""

import functools
import types

import jax

from ray_tpu.models import Transformer


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """`.logits(params, tokens)`, `.loss(params, batch)` -> (loss, metrics)
    and `.grads(params, batch)` -> (loss, d loss / d params) at `cfg`."""
    return types.SimpleNamespace(
        logits=jax.jit(lambda params, tokens: Transformer.apply(
            params, tokens, cfg)),
        loss=jax.jit(lambda params, batch: Transformer.loss(
            params, batch, cfg, with_metrics=True)),
        grads=jax.jit(jax.value_and_grad(lambda params, batch:
                                         Transformer.loss(params, batch, cfg))))


@functools.lru_cache(maxsize=None)
def reference(ref, published, cfg, **forward_kw):
    """The reference module `ref` at `published(cfg)`, the config.json keys
    it reads: `.forward(weights, *inputs)` (with `forward_kw`) and
    `.loss_and_grads(weights, *inputs)`, each under one `jax.jit`."""
    model = published(cfg)
    return types.SimpleNamespace(
        forward=jax.jit(lambda w, *inputs: ref.forward(
            w, *inputs, model, **forward_kw)),
        loss_and_grads=jax.jit(lambda w, *inputs: ref.loss_and_grads(
            w, *inputs, model)))


@functools.lru_cache(maxsize=None)
def value_and_grads(fn):
    """`fn(*operands)` and the gradients of its sum under `probe`, one
    program: `(probe, *operands) -> (output, gradients)`. What the kernel
    files hold a kernel, the XLA path and the recurrence to each other
    by."""
    def both(probe, *operands):
        out, pull = jax.vjp(fn, *operands)
        return out, pull(probe.astype(out.dtype))

    return jax.jit(both)
