"""The architecture `deepseek_v2`: DeepSeek-V2's multi-head latent
attention over a latent page, a leading dense layer, then layers of
shared + routed SwiGLU experts of which this chip HOLDS A SHARE
(`n_routed_experts` of the file is the count held, `expert_offset` the
first; `published.n_routed_experts` is what the router scores), as
`ray_tpu.models.deepseek_v2` and the engine run it.  It serves only: no
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

if importlib.util.find_spec("ray_tpu.models.deepseek_v2") is None:
    raise ImportError(
        "the architecture 'deepseek_v2' needs ray_tpu.models.deepseek_v2, "
        "which this checkout of the program does not have")

from .costs import (attention_params, decode_tick,  # noqa: E402,F401
                    expert_params, experts_touched, kv_bytes_per_token,
                    latent_bytes_per_token, layer_matmul_params,
                    matmul_params, mla_absorb_attend, mla_expand_attend,
                    moe_experts, moe_route, prefill_chunk, total_params,
                    train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v2

    if c["norm_topk_prob"] or c["scoring_func"] != "softmax" \
            or c["topk_method"] != "group_limited_greedy" \
            or c["moe_layer_freq"] != 1 or c["attention_bias"]:
        raise ValueError("the model is written for softmax scores, "
                         "group-limited greedy top-k without "
                         "renormalisation, an expert layer in every layer "
                         "past the dense ones, and no attention bias")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("latent attention has as many KV heads as heads")
    rs = c["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling {rs['type']!r}: only yarn is "
                         f"written")
    return deepseek_v2.DeepseekV2Config(
        max_seq=max_seq, n_layers=c["num_hidden_layers"],
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"], n_group=c["n_group"],
        topk_group=c["topk_group"], top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        experts_held=c["n_routed_experts"],
        expert_offset=c.get("expert_offset", 0),
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_orig_max=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded attention logits (scaled q . k over the 128 + 64 dimensions)
# have this standard deviation.  With every norm's weight at one it is
# 1.1: attention over thousands of keys is then near uniform, and
# dropping YaRN's factor from the softmax scale (x 1.59) moves no logit
# a comparison could see (the trap PR 28 found in MiniCPM-SALA's seeded
# attention).  At 4 a handful of keys hold most of a head's weight, as
# in a trained model.
SEEDED_ATTN_LOGIT_STD = 4.0


def seeded_attn_logit_std(cfg) -> float:
    """The standard deviation of a seeded attention logit with every
    norm's weight at one: unit-RMS inputs through matrices of std 0.02."""
    var = 0.02 ** 2
    q = var * cfg.q_lora_rank                  # a component of q
    k_nope = var * cfg.kv_lora_rank            # ...of k_nope = c_kv Wk_b
    k_pe = var * cfg.d_model                   # ...of k_pe = x Wkv_a
    return (cfg.qk_nope_head_dim * q * k_nope
            + cfg.qk_rope_head_dim * q * k_pe) ** 0.5 * cfg.softmax_scale


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with the query latent's norm scaled so that
    seeded attention is peaked: a test holds everything else equal to
    `deepseek_v2.init_params`.  The router is the program's: std 0.02
    on a unit-RMS input of 5120 gives router logits a standard
    deviation of 1.43, a softmax over 160 whose best six hold ~4-12 %
    each.  Scaling it changes no choice (same order, same groups) and,
    measured, no share of near ties either: the rounding that swaps the
    sixth and seventh expert scales with the logits (PERF.md section 4)."""
    from ray_tpu.models import deepseek_v2
    params = deepseek_v2.init_params(cfg, key, dtype)
    gain = SEEDED_ATTN_LOGIT_STD / seeded_attn_logit_std(cfg)
    return dict(params, layers=tuple(
        dict(lp, q_norm=lp["q_norm"] * gain) for lp in params["layers"]))
