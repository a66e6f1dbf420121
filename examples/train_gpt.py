"""Train the flagship GPT with JaxTrainer: gang of workers, mesh from
ScalingConfig axes, AIR checkpoints.

Run: JAX_PLATFORMS=cpu python examples/train_gpt.py
(toy sizes on a virtual CPU mesh; chip_smoke.py drives the same entry
points on the chip at the 737M widths, with ScalingConfig(use_tpu=True))
"""

import ray_tpu
from ray_tpu.air import Checkpoint, ScalingConfig, session
from ray_tpu.train.jax import JaxConfig, JaxTrainer


def train_loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=256, d_model=64, n_heads=4,
                        n_layers=2, d_ff=128, max_seq=64,
                        dtype=jnp.float32, remat=False)
    mesh = session.get_mesh()  # built from ScalingConfig axes
    opt = optax.adamw(1e-3)
    key = jax.random.PRNGKey(0)
    state, _ = gpt.make_train_state(cfg, key, mesh=mesh, optimizer=opt)
    step = gpt.make_train_step(cfg, mesh=mesh, optimizer=opt,
                               donate=False)
    tokens = jax.random.randint(key, (8, 33), 0, cfg.vocab_size)
    for epoch in range(config["epochs"]):
        state, metrics = step(state, tokens)
        session.report(
            {"loss": float(metrics["loss"]), "epoch": epoch},
            checkpoint=Checkpoint.from_pytree({"params": state["params"]})
            if epoch == config["epochs"] - 1 else None)


def main():
    ray_tpu.init(num_cpus=4)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"epochs": 10},
        jax_config=JaxConfig(use_distributed=False, virtual_cpu_devices=8),
        scaling_config=ScalingConfig(num_workers=1, dp=2, tp=2, fsdp=2),
    )
    result = trainer.fit()
    print("final loss:", result.metrics["loss"])
    print("checkpoint keys:", list(result.checkpoint.to_pytree()))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
