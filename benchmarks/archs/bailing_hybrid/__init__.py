"""The architecture `bailing_hybrid`: Ling-3.0-flash's decoder, groups
of five Kimi-Delta-Attention layers (a delta-rule state of 32 x 128 x
128 float32 and three convolution tails a decode row, no keys) and one
latent-attention layer (deepseek_v2's cached row, paged), a dense SwiGLU
in the leading layers and then one shared + 512 sigmoid-routed experts
top-8 through a bias and a group limit, an untied head, as
`ray_tpu.models.bailing_hybrid` and the engine run it.  It serves only:
no `param_specs`, `make_train_step` or `batch_axes`.

What the harness asks an architecture for is listed in
`archs/llama/__init__.py`.  Every function imports jax inside itself:
the driver loads this module for the yardstick alone and must not start
a backend.  The module refuses to load, by name, on a program that
lacks the model: a parent commit fails in the driver, at once.

The serving check is `lib/checks.default`: the prompt is whole chunks,
so the default's chunk by chunk (row 0, every token real) then tick by
tick (row 0 alone live) is this body's contract too.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

if importlib.util.find_spec("ray_tpu.models.bailing_hybrid") is None:
    raise ImportError(
        "the architecture 'bailing_hybrid' needs "
        "ray_tpu.models.bailing_hybrid, which this checkout of the program "
        "does not have")

from .costs import (decode_tick, dims, expert_params,  # noqa: E402,F401
                    experts_touched, fixed_matmul_params, kda_chunk,
                    kda_conv, kda_params, kda_step, kv_bytes_per_token,
                    matmul_params, mla_absorb_attend, mla_expand_attend,
                    mla_params, moe_experts, moe_route, prefill_chunk,
                    state_bytes_per_row, total_params,
                    train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import bailing_hybrid

    if c["score_function"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
            or not c["norm_topk_prob"] \
            or not c["moe_router_enable_expert_bias"]:
        raise ValueError("the router is written for sigmoid scores, a "
                         "selection bias, the group limit of noaux_tc and "
                         "renormalised weights")
    if c["q_lora_rank"] is not None or c["rope_scaling"] is not None \
            or not c["rope_interleave"] or c["use_mla_nope"]:
        raise ValueError("latent attention is written for one full-rank "
                         "query projection and interleaved RoPE with no "
                         "scaling")
    if c["num_key_value_heads"] != c["num_attention_heads"] \
            or c["qk_head_dim"] != c["qk_nope_head_dim"] \
            + c["qk_rope_head_dim"] or c["rotary_dim"] \
            != c["qk_rope_head_dim"]:
        raise ValueError("latent attention has as many KV heads as heads, "
                         "and rotates the 64 beside the 128")
    if not c["no_kda_lora"] or c["use_kda_lora"] or not c["kda_safe_gate"] \
            or not c["linear_silu"] or c["group_norm_size"] != 1 \
            or c["value_norm"] or c["num_kv_heads_for_linear_attn"]:
        raise ValueError("the delta-rule layers are written for full-rank "
                         "gates under the bounded decay, SiLU after the "
                         "convolution, a norm a head and as many key-value "
                         "heads as heads")
    if c["use_bias"] or c["use_qkv_bias"] or c["tie_word_embeddings"] \
            or c["hidden_act"] != "silu" or c["num_shared_experts"] != 1 \
            or c["use_nGPT"] or c["up_proj_norm"] \
            or c["scale_router_input"]:
        raise ValueError("the model is written without biases, with an "
                         "untied head, SwiGLU and one shared expert")
    first, L = c.get("layer_offset", 0), c["num_hidden_layers"]
    limits = c["expert_swiglu_limit_list"][first:first + L] \
        + c["share_expert_swiglu_limit_list"][first:first + L]
    if any(limits):
        raise ValueError("the layers run here clamp no SwiGLU (their "
                         "swiglu limits are 0); a clamp is not written")
    return bailing_hybrid.BailingHybridConfig(
        max_seq=max_seq, n_layers=L, layer_offset=first,
        layer_group_size=c["layer_group_size"], vocab_size=c["vocab_size"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        head_dim=c["head_dim"], conv_kernel=c["short_conv_kernel_size"],
        kda_lower_bound=float(c["kda_lower_bound"]),
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["num_experts"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        experts_held=c["num_experts"],
        expert_offset=c.get("expert_offset", 0),
        rms_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, c["torch_dtype"]))


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`): `bailing_hybrid.init_params` draws the decay's
    gates, the write strength, the router's bias and latent attention's
    query so that every mechanism moves the logits, and says how."""
    from ray_tpu.models import bailing_hybrid
    return bailing_hybrid.init_params(cfg, key, dtype)
