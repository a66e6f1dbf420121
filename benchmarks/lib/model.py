"""From a configuration file to the program's model: what the
configuration's architecture (`archs/<arch>`: `build`, `init`) makes of
its sizes, and weights made on the device from the seed in ONE jitted
call, in the type they are kept in (bf16 for a serving replica, fp32
sharded over the mesh for training).  Runs inside the worker that holds
the chip, which finds the architecture by the registry's directory
(`bench_dir`); the driver never imports jax through here.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.registry import arch_of


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def serving_loader(config: Dict[str, Any], seed: int, platform: str,
                   bench_dir: str):
    """The zero-argument `model_loader` an LLMServer replica calls."""
    def load():
        import jax

        from ray_tpu._private.jax_utils import device_facts

        facts = device_facts()
        if facts["platform"] != platform:
            raise RuntimeError(f"this replica computes on "
                               f"{facts['platform']!r}, not {platform!r}")
        arch = arch_of(config, bench_dir)
        cfg = arch.build(config, config["serving"]["engine"]["max_seq"],
                         remat=False)
        params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
            seed_key(seed))
        return params, cfg
    return load


def train_state(arch, config: Dict[str, Any], job: Dict[str, Any],
                seed: int, mesh):
    """(state, optimizer, cfg): fp32 params born sharded over `mesh`
    (the architecture's `param_specs`), adamw state initialised from
    them on the device."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    cfg = arch.build(config, job["seq"], remat=bool(job["remat"]))
    opt = optax.adamw(job["learning_rate"],
                      mu_dtype=getattr(jnp, job["mu_dtype"]))
    shardings = None
    if mesh is not None:
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), arch.param_specs(cfg))
    params = jax.jit(lambda key: arch.init(cfg, key, jnp.float32),
                     out_shardings=shardings)(seed_key(seed))
    # opt.init is called eagerly, as the program's own make_train_state
    # does: zeros made from a sharded array keep its sharding, where a
    # jitted init would give back replicated moments (10.6 GiB a chip
    # here).
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    return state, opt, cfg
