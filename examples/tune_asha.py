"""Hyperparameter sweep with ASHA early stopping + a TimeoutStopper
safety net.

Run: JAX_PLATFORMS=cpu python examples/tune_asha.py
"""

import ray_tpu
from ray_tpu import tune
from ray_tpu.air import RunConfig
from ray_tpu.tune import Tuner, TuneConfig
from ray_tpu.tune.schedulers import ASHAScheduler


def objective(config):
    acc = 0.0
    for step in range(30):
        acc += config["lr"] * (1.0 - acc)  # toy convergence curve
        tune.report({"accuracy": acc})


def main():
    ray_tpu.init(num_cpus=4)
    results = Tuner(
        objective,
        param_space={"lr": tune.loguniform(1e-3, 0.5)},
        tune_config=TuneConfig(
            metric="accuracy", mode="max", num_samples=8,
            scheduler=ASHAScheduler(metric="accuracy", mode="max",
                                    max_t=30, grace_period=3)),
        run_config=RunConfig(stop=tune.TimeoutStopper(300)),
    ).fit()
    best = results.get_best_result()
    print("best lr: %.4f  accuracy: %.3f"
          % (best.config["lr"], best.metrics["accuracy"]))
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
