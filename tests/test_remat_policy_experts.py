"""`remat_policy="attention"`, the default, against `"full"` on one tiny
configuration of each kind of expert cell (`tests/test_moe_routing_residuals`'
`KINDS`): the same loss, routing record and gradients. In a file of its
own: op by op each kind compiles its few hundred ops one at a time, half a
minute a kind, which `--dist loadfile` can then run beside
`tests/test_models.py` and not after it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import Transformer

from tests.test_models import _rel_l2
from tests.test_moe_routing_residuals import BATCH, KINDS, weights


@pytest.mark.parametrize("compiled", [False, True],
                         ids=["op_by_op", "jit"])
@pytest.mark.parametrize("kind", ["every_expert_softmax",
                                  "held_share_sigmoid",
                                  "held_below_picked_latent"])
def test_remat_policy_matches_full_with_experts(kind, compiled):
    """The default keeps an expert layer's routing where "full" makes
    it again (`ops/moe.ROUTING_RESIDUALS`): the same loss, the same
    routing record and the same gradients, to the bit. Op by op every
    leaf is equal; under `jit` XLA:CPU fuses the forward it runs
    again in its own way (there "full" itself is 2-6e-7 from the
    program without remat), so the gradients are held to rounding:
    1e-6, and 2e-6 in the latent kind. The largest leaf of six seeds
    (PR 60; `jax.random.key(s + 1000 i)` for weights and tokens), the
    parent's tree then PR 60's: every expert held 2.2e-7 both, a held
    share 3.4e-7 and 3.7e-7, the latent kind 8.2, 4.2, 0.3, 0, 1.6
    and 0 e-7 then 10.8, 4.5, 0.3, 0, 1.9 and 0 e-7. Its largest is
    this test's own seed on both trees, on the first mixer's `A_log`,
    one entry a head, where "full" itself is 1.3e-6 from the program
    without remat: PR 60's bounded run takes the token sums out of
    remat's forward, and what XLA:CPU fuses around them moved."""
    cfg = KINDS[kind]
    params = weights(cfg)

    def run(policy):
        c = cfg.replace(remat_policy=policy)
        step = jax.value_and_grad(
            lambda p: Transformer.loss(p, BATCH, c, with_metrics=True),
            has_aux=True)
        if compiled:
            return jax.jit(step)(params)
        with jax.disable_jit():
            return step(params)

    (ref_loss, ref_record), ref_grads = run("full")
    (loss, record), grads = run("attention")
    assert float(loss) == float(ref_loss)
    assert set(record) >= {"moe_tokens_per_expert", "moe_dropped",
                           "moe_aux_loss"}
    jax.tree.map(np.testing.assert_array_equal, record, ref_record)
    if compiled:
        errs = jax.tree.map(_rel_l2, grads, ref_grads)
        limit = 2e-6 if kind == "held_below_picked_latent" else 1e-6
        assert all(e < limit for e in jax.tree.leaves(errs)), errs
    else:
        jax.tree.map(np.testing.assert_array_equal, grads, ref_grads)
    routers = [g for path, g in jax.tree_util.tree_leaves_with_path(grads)
               if path[-1].key == "w_router"]
    assert routers and all(float(jnp.abs(g).max()) > 0 for g in routers)
