"""From a configuration file to the program's model: the LlamaConfig the
repo's `models/llama.py` / `models/decode.py` run, and weights made on
the device from the seed in ONE jitted call, in the type they are kept
in (bf16 for a serving replica, fp32 sharded over the mesh for
training).  Runs inside the worker that holds the chip; the driver
never imports jax through here.
"""

from __future__ import annotations

from typing import Any, Dict


def llama_config(c: Dict[str, Any], max_seq: int, remat: bool):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    if c["head_dim"] * c["num_attention_heads"] != c["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden/heads; "
                         "this configuration needs another")
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        n_layers=c["num_hidden_layers"], d_ff=c["intermediate_size"],
        max_seq=max_seq, rope_theta=float(c["rope_theta"]),
        dtype=getattr(jnp, c["torch_dtype"]), remat=remat)


def seed_key(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def _init(cfg, key, dtype):
    """Same shapes and scales as llama.init_params, in one traced
    function, drawn directly in `dtype`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, D, H, Hk, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)
    s = 0.02
    so = s / np.sqrt(2 * L)
    k = iter(jax.random.split(key, 8))

    def nrm(shape, scale):
        return (scale * jax.random.normal(next(k), shape, jnp.float32)
                ).astype(dtype)

    ones = lambda shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    return {
        "wte": nrm((cfg.vocab_size, D), s),
        "blocks": {
            "ln1": ones((L, D)), "wq": nrm((L, D, H, Dh), s),
            "wkv": nrm((L, D, 2, Hk, Dh), s), "wo": nrm((L, H, Dh, D), so),
            "ln2": ones((L, D)), "w_gate": nrm((L, D, F), s),
            "w_up": nrm((L, D, F), s), "w_down": nrm((L, F, D), so)},
        "ln_f": ones((D,)),
        "wlm": nrm((D, cfg.vocab_size), s),
    }


def serving_loader(config: Dict[str, Any], seed: int, platform: str):
    """The zero-argument `model_loader` an LLMServer replica calls."""
    def load():
        import jax

        from ray_tpu._private.jax_utils import device_facts

        facts = device_facts()
        if facts["platform"] != platform:
            raise RuntimeError(f"this replica computes on "
                               f"{facts['platform']!r}, not {platform!r}")
        cfg = llama_config(config, config["serving"]["engine"]["max_seq"],
                           remat=False)
        params = jax.jit(lambda key: _init(cfg, key, cfg.dtype))(
            seed_key(seed))
        return params, cfg
    return load


def train_state(config: Dict[str, Any], job: Dict[str, Any], seed: int,
                mesh):
    """(state, optimizer, cfg): fp32 params born sharded over `mesh`
    (llama.param_specs), adamw state initialised from them on the
    device."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu.models import llama

    cfg = llama_config(config, job["seq"], remat=bool(job["remat"]))
    opt = optax.adamw(job["learning_rate"],
                      mu_dtype=getattr(jnp, job["mu_dtype"]))
    shardings = None
    if mesh is not None:
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), llama.param_specs(cfg))
    params = jax.jit(lambda key: _init(cfg, key, jnp.float32),
                     out_shardings=shardings)(seed_key(seed))
    # opt.init is called eagerly, as llama.make_train_state does: zeros
    # made from a sharded array keep its sharding, where a jitted init
    # would give back replicated moments (10.6 GiB a chip here).
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    return state, opt, cfg
