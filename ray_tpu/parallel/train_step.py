"""Sharded training-step factory.

TPU-native replacement for the reference's DDP/ZeRO wrapping
(reference: rllib/core/learner/torch/torch_learner.py:378-390 wraps modules
in TorchDDPRLModule; train/examples/deepspeed/deepspeed_torch_trainer.py
configures ZeRO stages). Here there is no wrapper object: the train step is
a single jitted function whose in/out shardings place params per the
logical rules (FSDP/TP/…) and whose gradient reduction is whatever XLA
derives from those shardings — DP gradients all-reduce, FSDP gradients
reduce-scatter, automatically.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

from ray_tpu.parallel.sharding import ShardingRules, shard_pytree


def make_train_step(
        loss_fn: Callable[[Any, Dict[str, Any]], Any],
        param_specs: Any,
        mesh,
        *,
        optimizer=None,
        rules: Optional[ShardingRules] = None,
        # Input arrays are sharded batch-only by default: token ids are
        # tiny, and [B, T+1] next-token batches aren't divisible by the
        # seq axis — the model's activation constraints reshard onto
        # "seq" right after embedding. Long-context callers with
        # seq-divisible inputs can pass ("batch", "seq").
        batch_logical: Tuple[Optional[str], ...] = ("batch", None),
        donate: bool = True,
        frozen: Any = None,
) -> Tuple[Callable, Callable]:
    """Build (init_state, train_step), both jitted with explicit shardings.

    loss_fn(params, batch) -> scalar loss, or (loss, aux dict) whose
    entries join the step's metrics beside `loss`, `grad_norm` and `step`
    (`Transformer.loss(..., with_metrics=True)`: a MoE model's per-expert
    token counts reach the loop from the step's own forward pass).
    init_state(params) -> state dict; train_step(state, batch) ->
    (state, metrics); train_step.lower(state, batch) -> jax Lowered.

    `frozen`: a tree of bools shaped like the params, True for a leaf that
    is a buffer and not a parameter (`Transformer.frozen(cfg)`: a sigmoid
    router's choice bias). Such a leaf leaves a step bit for bit as it
    entered it, whatever its gradient and the optimizer's weight decay.
    """
    import jax
    import jax.numpy as jnp
    import optax

    rules = rules or ShardingRules()
    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)

    p_shardings = shard_pytree(param_specs, mesh, rules)
    replicated = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    batch_sharding = jax.sharding.NamedSharding(
        mesh, rules.spec(batch_logical))

    def _opt_shardings(params_shape, fitted_p_shardings):
        # optax states are pytrees whose array leaves either mirror the
        # param tree (momenta: the leaf path *ends with* the param's path,
        # e.g. (0, 'mu', 'layers', 'wq') for param ('layers', 'wq')) or
        # are scalars/globals (counts -> replicated). Match by key-path
        # suffix — never by shape, which collides when two params share a
        # shape (e.g. w_gate (d, f) vs w_down (f, d) with d == f).
        from jax.tree_util import tree_flatten_with_path

        def path_key(path):
            return tuple(str(k) for k in path)

        p_leaves = tree_flatten_with_path(fitted_p_shardings)[0]
        by_path = {path_key(path): sh for path, sh in p_leaves}
        max_len = max((len(k) for k in by_path), default=0)

        opt_shape = jax.eval_shape(
            lambda p: optimizer.init(p), params_shape)
        opt_leaves, opt_treedef = tree_flatten_with_path(opt_shape)
        out = []
        for path, leaf in opt_leaves:
            key = path_key(path)
            sh = replicated
            for n in range(min(len(key), max_len), 0, -1):
                hit = by_path.get(key[-n:])
                if hit is not None:
                    sh = hit
                    break
            out.append(sh)
        return jax.tree.unflatten(opt_treedef, out)

    def _init(params):
        return {
            "params": params,
            "opt_state": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def _step(state, batch):
        def wrapped(p):
            out = loss_fn(p, batch)
            if isinstance(out, tuple):
                return out
            return out, {}

        (loss, aux), grads = jax.value_and_grad(
            wrapped, has_aux=True)(state["params"])
        # the last name of the models' scope vocabulary
        # (models/transformer.py): everything after the gradient
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state["opt_state"], state["params"])
            params = optax.apply_updates(state["params"], updates)
            if frozen is not None:
                params = jax.tree.map(
                    lambda new, old, keep: old if keep else new,
                    params, state["params"], frozen)
            gnorm = optax.global_norm(grads)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": new_state["step"], **aux}
        return new_state, metrics

    def _fit(sharding, leaf):
        # degrade non-dividing spec entries to replicated (e.g. kv_heads
        # narrower than the tensor axis); same rule as the constraint
        # path (sharding.fit_spec_to_shape)
        from ray_tpu.parallel.sharding import fit_spec_to_shape
        new = fit_spec_to_shape(sharding.spec,
                                getattr(leaf, "shape", ()), mesh)
        return jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*new))

    def make_state_shardings(params):
        params_shape = jax.eval_shape(lambda x: x, params)
        fitted = jax.tree.map(_fit, p_shardings, params_shape)
        return {
            "params": fitted,
            "opt_state": _opt_shardings(params_shape, fitted),
            "step": replicated,
        }

    def init_state(params):
        state_shardings = make_state_shardings(params)
        return jax.jit(_init, out_shardings=state_shardings)(params)

    _cache: Dict[Any, Callable] = {}

    def _jitted(state):
        key = jax.tree.structure(state)
        fn = _cache.get(key)
        if fn is None:
            state_shardings = make_state_shardings(state["params"])
            fn = jax.jit(
                _step,
                in_shardings=(state_shardings, batch_sharding),
                out_shardings=(state_shardings, None),
                donate_argnums=(0,) if donate else ())
            _cache[key] = fn
        return fn

    def train_step(state, batch):
        from ray_tpu._private import spans
        from ray_tpu.util import jax_sentinel
        with jax_sentinel.step_region("train.step"):
            # the dispatch, in the flight recorder and (spans.traced) on
            # the host line of a device trace, with the loop thread's CPU
            # time and preemptions since its last step (cpu_s, ivcsw)
            with spans.traced("train.step", **spans.thread_usage()):
                return _jitted(state)(state, batch)

    # like jit's own .lower: ahead-of-time lowering of the same program
    # (arrays or ShapeDtypeStructs), to read what the step compiles to
    train_step.lower = lambda state, batch: _jitted(state).lower(
        state, batch)

    return init_state, train_step
