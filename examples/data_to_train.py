"""Data pipeline -> Train ingest: read files, preprocess, shard to a
training gang (reference: the AIR "data + train" quickstart shape).

Run: JAX_PLATFORMS=cpu python examples/data_to_train.py
"""

import os
import tempfile

import numpy as np
import pandas as pd

import ray_tpu
from ray_tpu import data
from ray_tpu.air import ScalingConfig, session
from ray_tpu.data.preprocessors import StandardScaler
from ray_tpu.train.jax import JaxConfig, JaxTrainer


def train_loop(config):
    import jax
    import jax.numpy as jnp

    shard = session.get_dataset_shard("train")
    w = jnp.zeros((2,))

    @jax.jit
    def sgd(w, x, y):
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 0.1 * g, l

    for epoch in range(config["epochs"]):
        for batch in shard.iter_batches(batch_size=32,
                                        batch_format="numpy"):
            x = jnp.stack([jnp.asarray(batch["a"], jnp.float32),
                           jnp.asarray(batch["b"], jnp.float32)], axis=1)
            y = jnp.asarray(batch["y"], jnp.float32)
            w, l = sgd(w, x, y)
        session.report({"loss": float(l), "epoch": epoch})


def main():
    ray_tpu.init(num_cpus=4)

    # 1. Write some CSV shards, read them back as a Dataset.
    tmp = tempfile.mkdtemp()
    rng = np.random.default_rng(0)
    for i in range(4):
        a, b = rng.normal(size=100), rng.normal(size=100)
        pd.DataFrame({"a": a, "b": b, "y": 3 * a - 2 * b}).to_csv(
            os.path.join(tmp, f"part{i}.csv"), index=False)
    ds = data.read_csv(tmp)
    print("read", ds.count(), "rows from", len(ds.input_files()), "files")

    # 2. Train with a fitted preprocessor; "train" auto-splits per rank.
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"epochs": 3},
        datasets={"train": ds},
        preprocessor=StandardScaler(columns=["a", "b"]),
        jax_config=JaxConfig(use_distributed=False),
        scaling_config=ScalingConfig(num_workers=2),
    )
    result = trainer.fit()
    print("final loss:", result.metrics["loss"])
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
