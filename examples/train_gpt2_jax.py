"""JaxTrainer: sharded GPT-2-class training with checkpoints.

Runs a tiny decoder on the available mesh (data+fsdp+tensor axes) via
the Train worker-group machinery: gang-scheduled workers, jax
coordinator bootstrap, session report/checkpoint flow.
"""

import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig


def train_loop(config):
    import jax
    import optax

    import ray_tpu.train as train
    from ray_tpu.models import TINY, Transformer
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.parallel.train_step import make_train_step

    cfg = TINY
    mesh = make_mesh(MeshConfig(data=-1))
    params = Transformer.init(jax.random.PRNGKey(0), cfg)
    init_state, step = make_train_step(
        lambda p, b: Transformer.loss(p, b, cfg, mesh=mesh),
        Transformer.param_specs(cfg), mesh,
        optimizer=optax.adamw(3e-4))
    state = init_state(params)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (8, cfg.max_seq_len + 1), 0,
        cfg.vocab_size)
    for i in range(config.get("steps", 10)):
        state, metrics = step(state, {"tokens": tokens})
        train.report({"step": i, "loss": float(metrics["loss"])})


def main():
    ray_tpu.init(num_cpus=4)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"steps": 10},
        # On a TPU host give the worker the chip(s) it trains on — the
        # driver stays off JAX, and the worker checks at set-up that it
        # sees that many TPU devices (chip_smoke.py drives this variant
        # at the 125M size):
        #   resources_per_worker={"CPU": 1, "TPU": 1}
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker={"CPU": 1}),
        run_config=RunConfig(name="gpt2_tiny_demo"))
    result = trainer.fit()
    print("final:", result.metrics)
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
