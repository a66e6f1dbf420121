"""The architecture `jamba`: Mamba-1 layers (a selective-scan state and
a convolution tail a decode row) with one attention layer every
`attn_layer_period` (one key-value head, no positional encoding, paged),
a SwiGLU after every mixer, embedding and head tied, as
`ray_tpu.models.jamba` and the engine run it.  It serves only: no
`param_specs`, `make_train_step` or `batch_axes`.

What the harness asks an architecture for is listed in
`archs/llama/__init__.py`.  Every function imports jax inside itself:
the driver loads this module for the yardstick alone and must not start
a backend.  The module refuses to load, by name, on a program that
lacks the model: a parent commit fails in the driver, at once.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

if importlib.util.find_spec("ray_tpu.models.jamba") is None:
    raise ImportError(
        "the architecture 'jamba' needs ray_tpu.models.jamba, which this "
        "checkout of the program does not have")

from .costs import (attention_params, attn_nope,  # noqa: E402,F401
                    decode_tick, ffn_params, kv_bytes_per_token,
                    layer_matmul_params, mamba_matmul_params,
                    mamba_other_params, matmul_params, prefill_chunk,
                    ssm_conv, ssm_scan, ssm_step, state_bytes_per_row,
                    total_params, train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    if c["num_experts"] != 1 or c["num_experts_per_tok"] != 1:
        raise ValueError("the model is written for one feed-forward a "
                         "layer (num_experts 1)")
    if c["mamba_proj_bias"] or not c["mamba_conv_bias"] \
            or c["hidden_act"] != "silu" or not c["tie_word_embeddings"]:
        raise ValueError("the mixer is written for a biased convolution, "
                         "unbiased projections, silu and a tied head")
    if c.get("sliding_window") is not None:
        raise ValueError("the attention layers attend to everything")
    return jamba.JambaConfig(
        max_seq=max_seq, n_layers=c["num_hidden_layers"],
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"],
        attn_layer_period=c["attn_layer_period"],
        attn_layer_offset=c["attn_layer_offset"],
        mamba_expand=c["mamba_expand"], d_state=c["mamba_d_state"],
        d_conv=c["mamba_d_conv"], dt_rank=c["mamba_dt_rank"],
        rms_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded q . k / sqrt(head_dim) has this standard deviation (1 as the
# program draws W_q and W_k: attention over hundreds of keys is then
# near uniform, and positions wrongly applied move no logit a comparison
# could see: the trap PR 28 found in MiniCPM-SALA's seeded attention).
# At 4 a handful of keys hold most of a head's weight, as in a trained
# model.
SEEDED_ATTN_LOGIT_STD = 4.0


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with W_q and W_k of the attention layers
    scaled so that seeded attention is peaked (there is no q or k norm
    to carry the gain): a test holds everything else equal to
    `jamba.init_params`."""
    from ray_tpu.models import jamba
    params = jamba.init_params(cfg, key, dtype)
    gain = SEEDED_ATTN_LOGIT_STD ** 0.5

    def peaked(run):
        return dict(run, wq=run["wq"] * gain,
                    wkv=run["wkv"].at[:, :, 0].multiply(gain))
    return dict(params, runs=tuple(
        peaked(run) if kind == jamba.ATTN else run
        for (kind, _, _), run in zip(cfg.runs, params["runs"])))
