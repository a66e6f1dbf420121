"""The architecture `minicpm_sala`: MiniCPM-SALA's two kinds of layer in
their fixed order — block-sparse GQA attention over chosen pages
(`minicpm4`) and lightning linear attention with a per-row recurrent
state (`lightning-attn`) — under the MiniCPM family's muP scaling, as
`ray_tpu.models.minicpm_sala` and the engine run it.  It serves only:
no `param_specs`, `make_train_step` or `batch_axes`.

What the harness asks an architecture for is listed in
`archs/llama/__init__.py`.  Every function imports jax inside itself:
the driver loads this module for the yardstick alone and must not start
a backend.  The module refuses to load, by name, on a program that
lacks the model: a parent commit fails in the driver, at once.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

if importlib.util.find_spec("ray_tpu.models.minicpm_sala") is None:
    raise ImportError(
        "the architecture 'minicpm_sala' needs ray_tpu.models.minicpm_sala, "
        "which this checkout of the program does not have")

from .costs import (decode_tick, kv_bytes_per_token,  # noqa: E402,F401
                    layer_matmul_params, lightning_chunk, lightning_step,
                    matmul_params, prefill_chunk, sparse_attend,
                    sparse_score, total_params, train_flops_per_token)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala

    if len(c["layer_mixers"]) != c["num_hidden_layers"]:
        raise ValueError("layer_mixers must name every layer run")
    if c["lightning_nkv"] != c["lightning_nh"]:
        raise ValueError("the lightning layers have as many KV heads as "
                         "heads")
    sp = c["sparse_config"]
    return minicpm_sala.SalaConfig(
        mixer_types=tuple(c["layer_mixers"]), max_seq=max_seq,
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], lin_heads=c["lightning_nh"],
        lin_head_dim=c["lightning_head_dim"],
        rope_theta=float(c["rope_theta"]), scale_emb=float(c["scale_emb"]),
        scale_depth=float(c["scale_depth"]),
        mup_depth=c["published"]["num_hidden_layers"],
        dim_model_base=c["dim_model_base"], block=sp["block_size"],
        kernel=sp["kernel_size"], stride=sp["kernel_stride"],
        init_blocks=sp["init_blocks"],
        local_blocks=sp["window_size"] // sp["block_size"],
        topk=sp["topk"], dense_len=sp["dense_len"],
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded q . k / sqrt(head_dim) of the `minicpm4` layers has this
# standard deviation (1 with the norms' weights at one, which is near
# uniform attention over thousands of keys: the chosen blocks then do
# not matter and no comparison can see the selection).  At 4 a handful
# of keys hold most of a head's weight, as in a trained model, and a
# block wrongly chosen or dropped loses them.
SEEDED_ATTN_LOGIT_STD = 4.0


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with the q and k norms of the attention layers
    scaled so that seeded attention is peaked: a test holds everything
    else equal to `minicpm_sala.init_params`."""
    from ray_tpu.models import minicpm_sala
    params = minicpm_sala.init_params(cfg, key, dtype)
    gain = SEEDED_ATTN_LOGIT_STD ** 0.5
    runs = tuple(
        dict(run, qn=run["qn"] * gain, kn=run["kn"] * gain)
        if kind == minicpm_sala.ATTN else run
        for (kind, _, _), run in zip(cfg.runs, params["runs"]))
    return dict(params, runs=runs)
