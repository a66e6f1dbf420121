"""The architecture `llama`: the dense GQA decoder (RoPE, SwiGLU,
RMSNorm, untied head) that `ray_tpu.models.llama.LlamaConfig`,
`models/decode.py` and the engine run.  A configuration with no `arch`
key is one of these.

What the harness asks an architecture for, all by these names
(`c` is the configuration file's dict, `cfg` what `build` returned):

    build(c, max_seq, remat) -> cfg     the program's config object
    init(cfg, key, dtype) -> params     weights in the program's layout,
                                        one traced function
    reference(params, tokens, c)        plain float32 logits [T, V]
    matmul_params, total_params, kv_bytes_per_token, decode_tick,
    prefill_chunk, train_flops_per_token    the yardstick (costs.py)
    param_specs(cfg), make_train_step(cfg, mesh, optimizer),
    batch_axes()                        training; a serve-only
                                        architecture leaves them out
    check_logits(engine, seed, prompt_len, n_decode, c, reference)
                                        optional: the serving cell's
                                        logits check, for a body whose
                                        step is not the default's

Every function imports jax inside itself: the driver process loads this
module for the yardstick alone and must not start a backend.

Without a `check_logits` (this module has none) the check is
`lib/checks.default`: the prompt through `engine._prefill_chunk` chunk
by chunk, then `engine._paged_tick` one token a row a tick, against one
full forward of `reference`.  A module that brings its own composes it
from `lib/checks.py` (`seeded_prompt`, `borrowed_pages`, `prefill`,
`tick_by_tick`, `full_forward`, `compare`) and owes the harness this:

(a) it runs the engine's own jitted programs, the ones the timed window
    runs, through the engine's own pool and rows, on the worker thread
    with the engine idle (`engine.run_on_worker`), at the widths the
    engine was built with;
(b) it compares logits, never tokens, at every position it produced,
    teacher-forced on what the program itself chose, with `reference`;
(c) it returns `checks.compare`'s dictionary, so the numbers `run.py`
    judges are the harness's arithmetic and not the architecture's;
(d) it names the programs it ran under `programs`, as a device trace
    shows them (`checks.program_name`).

`replica.probe_check_logits` holds the result to that by name
(`checks.hold`) and adds `procedure`; a traced run prints which of
`programs` its trace did not hold (`check_programs_not_in_trace`).
"""

from __future__ import annotations

from typing import Any, Dict

from .costs import (decode_tick, kv_bytes_per_token,  # noqa: F401
                    layer_matmul_params, matmul_params, prefill_chunk,
                    total_params, train_flops_per_token)
from .reference import forward as reference  # noqa: F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
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


def init(cfg, key, dtype):
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


def param_specs(cfg):
    from ray_tpu.models import llama
    return llama.param_specs(cfg)


def make_train_step(cfg, mesh, optimizer):
    from ray_tpu.models import llama
    return llama.make_train_step(cfg, mesh=mesh, optimizer=optimizer,
                                 donate=True)


def batch_axes():
    """Mesh axes the batch dimension of a training batch is split over."""
    from ray_tpu.models.gpt import BATCH_AXES
    return BATCH_AXES
