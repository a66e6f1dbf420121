"""The architecture `zaya`: ZAYA1's decoder, every layer one compressed
convolutional attention sublayer (queries and keys mixed over time by
two short convolutions in a latent half the stream's width, half the
value heads one token late: pages of 2 heads x 128 AND three tails per
decode row in EVERY layer) and one expert sublayer (top-1 of 16 SwiGLU
experts under a router MLP whose 256-wide state runs from layer to
layer), the embedding tied to the head, as `ray_tpu.models.zaya` and the
engine run it.  It serves only: no `param_specs`, `make_train_step` or
`batch_axes`.

What the harness asks an architecture for is listed in
`archs/llama/__init__.py`.  Every function imports jax inside itself:
the driver loads this module for the yardstick alone and must not start
a backend.  The module refuses to load, by name, on a program that
lacks the model: a parent commit fails in the driver, at once.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

if importlib.util.find_spec("ray_tpu.models.zaya") is None:
    raise ImportError(
        "the architecture 'zaya' needs ray_tpu.models.zaya, which this "
        "checkout of the program does not have")

from .costs import (attention_params, attn_latent,  # noqa: E402,F401
                    attn_latent_chunk, cca_mix, decode_tick, expert_params,
                    experts_touched, kv_bytes_per_token, matmul_params,
                    moe_experts, moe_route, prefill_chunk,
                    row_state_bytes_per_row, total_params,
                    train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import zaya

    L = c["num_hidden_layers"]
    if set(c["layer_types"][:L]) != {"hybrid"}:
        raise ValueError("every layer run is 'hybrid': one attention "
                         "sublayer and one expert sublayer")
    if (c["cca_time0"], c["cca_time1"]) != (2, 2):
        raise ValueError("both convolutions are written for two taps")
    if c["attention_bias"] or c["lm_head_bias"] \
            or not c["tie_word_embeddings"] or c["sliding_window"]:
        raise ValueError("the model is written without biases in "
                         "attention or head, with a tied head and no "
                         "window")
    if c["hidden_act"] != "silu":
        raise ValueError("the experts are SwiGLU")
    rope = c["rope_parameters"]["hybrid"]
    if rope["partial_rotary_factor"] != c["partial_rotary_factor"] \
            or rope["rope_type"] != "default":
        raise ValueError("rope_parameters.hybrid is read as the one RoPE")
    return zaya.ZayaConfig(
        max_seq=max_seq, n_layers=L, vocab_size=c["vocab_size"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        rotary_dim=int(c["partial_rotary_factor"] * c["head_dim"]),
        rope_theta=float(rope["rope_theta"]),
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        router_dim=c["router_hidden_size"],
        rms_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, c["torch_dtype"]))


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`): `zaya.init_params` draws the taps, the
    temperature, the residual scales and the router so that every
    mechanism moves the logits, and says how."""
    from ray_tpu.models import zaya
    return zaya.init_params(cfg, key, dtype)
