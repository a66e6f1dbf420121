"""The architecture `glm_moe_dsa`: GLM-5's decoder, DeepSeek-V2's latent
attention in every layer with a learned 32-head indexer beside it (a
second, 128-wide key a token beside the latent page, one block table)
whose queries choose 2,048 keys a token, a dense SwiGLU in the leading
layers and then one shared + 256 sigmoid-routed experts top-8 of which
this chip HOLDS A SHARE (`n_routed_experts` of the file is the count
held, `expert_offset` the first; `published.n_routed_experts` is what
the router scores), an untied head, as `ray_tpu.models.glm_moe_dsa` and
the engine run it.  It serves only: no `param_specs`, `make_train_step`
or `batch_axes`.

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

if importlib.util.find_spec("ray_tpu.models.glm_moe_dsa") is None:
    raise ImportError(
        "the architecture 'glm_moe_dsa' needs ray_tpu.models.glm_moe_dsa, "
        "which this checkout of the program does not have")

from .costs import (decode_tick, dims, dsa_attend,  # noqa: E402,F401
                    dsa_index, dsa_select, expert_params, experts_touched,
                    fixed_matmul_params, kv_bytes_per_token, matmul_params,
                    mixer_params, moe_experts, moe_route, prefill_chunk,
                    total_params, train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import glm_moe_dsa

    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
            or not c["norm_topk_prob"] or c["n_group"] != 1 \
            or c["topk_group"] != 1 or c["moe_layer_freq"] != 1:
        raise ValueError("the router is written for sigmoid scores, a "
                         "selection bias, one group, renormalised weights "
                         "and an expert layer after every dense one")
    if c["rope_parameters"]["rope_type"] != "default" \
            or c["attention_bias"] or c["tie_word_embeddings"] \
            or c["hidden_act"] != "silu":
        raise ValueError("the model is written for RoPE with no scaling, "
                         "no biases, an untied head and SwiGLU")
    if not (c["rope_interleave"] and c["indexer_rope_interleave"]):
        raise ValueError("RoPE pairs are (2i, 2i + 1), in the attention "
                         "and in the indexer")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("latent attention has as many KV heads as heads")
    if c["qk_head_dim"] != c["qk_nope_head_dim"] + c["qk_rope_head_dim"] \
            or c["head_dim"] != c["qk_rope_head_dim"]:
        raise ValueError("a query head is its nope and rope parts; the "
                         "config's head_dim is the rotary width")
    if c["num_nextn_predict_layers"]:
        raise ValueError("the multi-token-prediction layer is not written")
    return glm_moe_dsa.GlmMoeDsaConfig(
        max_seq=max_seq, n_layers=c["num_hidden_layers"],
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        index_n_heads=c["index_n_heads"],
        index_head_dim=c["index_head_dim"], index_topk=c["index_topk"],
        d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        experts_held=c["n_routed_experts"],
        expert_offset=c.get("expert_offset", 0),
        rms_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, c["torch_dtype"]))


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`): `glm_moe_dsa.init_params` draws the
    attention's queries, the indexer and the router's bias so that every
    mechanism moves the logits, and says how."""
    from ray_tpu.models import glm_moe_dsa
    return glm_moe_dsa.init_params(cfg, key, dtype)
