"""PPO on CartPole with the fluent AlgorithmConfig builder.

Run: JAX_PLATFORMS=cpu python examples/rllib_ppo.py
"""

import ray_tpu
from ray_tpu.rllib.algorithms.ppo import PPOConfig


def main():
    ray_tpu.init(num_cpus=4)
    algo = (PPOConfig()
            .environment("CartPole-v1")
            .rollouts(num_rollout_workers=2, rollout_fragment_length=200)
            .training(train_batch_size=800, lr=3e-4,
                      num_sgd_iter=6)).build()
    for i in range(5):
        result = algo.train()
        print(f"iter {i}: episode_reward_mean="
              f"{result['episode_reward_mean']:.1f}")
    algo.stop()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
