"""The architecture `mimo_v2_flash`: MiMo-V2-Flash's GQA decoder with
full layers (pages of 4 heads, keys 192 wide and values 128) beside
window layers (a ring of `sliding_window` tokens a decode row, 8 heads,
a learned sink in every softmax), partial RoPE with a theta a kind, a
leading dense layer, then layers of routed SwiGLU experts under a
sigmoid router of which this chip HOLDS A SHARE (`n_routed_experts` of
the file is the count held, `expert_offset` the first;
`published.n_routed_experts` is what the router scores), as
`ray_tpu.models.mimo_v2_flash` and the engine run it.  It serves only:
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

if importlib.util.find_spec("ray_tpu.models.mimo_v2_flash") is None:
    raise ImportError(
        "the architecture 'mimo_v2_flash' needs "
        "ray_tpu.models.mimo_v2_flash, which this checkout of the "
        "program does not have")

from .costs import (attention_params, attn_global,  # noqa: E402,F401
                    attn_global_chunk, attn_window, attn_window_chunk,
                    decode_tick, expert_params, experts_touched,
                    kv_bytes_per_token, matmul_params, moe_experts,
                    moe_route, prefill_chunk, ring_bytes_per_row,
                    token_layer_bytes, total_params, train_flops_per_token,
                    weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2_flash

    if not c["norm_topk_prob"] or c["scoring_func"] != "sigmoid" \
            or c["n_group"] != 1 or c["topk_group"] != 1 \
            or c["n_shared_experts"]:
        raise ValueError("the model is written for sigmoid scores, top-k "
                         "over all experts (one group), weights "
                         "renormalised over the chosen and no shared "
                         "expert")
    L = c["num_hidden_layers"]
    dense = c["moe_layer_freq"][:L].count(0)
    if c["moe_layer_freq"][:L] != [0] * dense + [1] * (L - dense):
        raise ValueError("moe_layer_freq: dense layers lead, expert "
                         "layers follow")
    if c["add_full_attention_sink_bias"] \
            or not c["add_swa_attention_sink_bias"]:
        raise ValueError("a sink is written for the window layers' "
                         "softmax and not for the full layers'")
    if c["attention_bias"] or c["swa_head_dim"] != c["head_dim"] \
            or c["swa_v_head_dim"] != c["v_head_dim"] \
            or c["swa_num_attention_heads"] != c["num_attention_heads"]:
        raise ValueError("both kinds of layer share the query heads and "
                         "the head widths, and no projection has a bias")
    if not c["sliding_window"] == c["sliding_window_size"] \
            == c["attention_chunk_size"]:
        raise ValueError("sliding_window, sliding_window_size and "
                         "attention_chunk_size are read as the one window")
    return mimo_v2_flash.MimoV2FlashConfig(
        max_seq=max_seq, n_layers=L, vocab_size=c["vocab_size"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        swa_n_kv_heads=c["swa_num_key_value_heads"],
        head_dim=c["head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], first_k_dense=dense,
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["n_routed_experts"],
        top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"] or 1.0),
        experts_held=c["n_routed_experts"],
        expert_offset=c.get("expert_offset", 0),
        hybrid_layer_pattern=tuple(c["hybrid_layer_pattern"][:L]),
        window=c["sliding_window"], rope_theta=float(c["rope_theta"]),
        swa_rope_theta=float(c["swa_rope_theta"]),
        rotary_dim=int(c["partial_rotary_factor"] * c["head_dim"]),
        v_scale=float(c["attention_value_scale"]),
        rms_eps=float(c["layernorm_epsilon"]),
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded q . k / sqrt(head_dim) has this standard deviation.  The model
# has no q or k norm: u = rms(x) has unit components, so with W_q and
# W_k drawn at 0.02 a seeded score has a standard deviation of 0.02^2 x
# hidden_size (1.64 at 4096: attention over thousands of keys is then
# near uniform, and a window, a theta or a rotary share that was wrong
# moves no logit a comparison could see: the trap PR 28 found in
# MiniCPM-SALA's seeded attention).  At 4 a handful of keys hold most of
# a head's weight, as in a trained model.
SEEDED_ATTN_LOGIT_STD = 4.0
# A seeded sink: normal with this standard deviation about the largest
# score a head expects among its window's keys (SEEDED_ATTN_LOGIT_STD x
# sqrt(2 ln window)) less 2.  A sink drawn about ZERO takes 0.02 % of a
# softmax whose strongest of 128 keys scores ~12: leaving it out would
# move nothing.  About the strongest key's score less 2 it takes ~40 %
# on average, from nearly nothing in some heads to nearly all in others.
SEEDED_SINK_STD = 2.0


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with W_q and W_k scaled so that seeded
    attention is peaked (each by the square root of
    SEEDED_ATTN_LOGIT_STD / (0.02^2 x d_model)) and the window layers'
    sinks drawn, not zero.  A test holds everything else equal to
    `mimo_v2_flash.init_params`."""
    import math

    import jax

    from ray_tpu.models import mimo_v2_flash
    params = mimo_v2_flash.init_params(cfg, key, dtype)
    gain = (SEEDED_ATTN_LOGIT_STD / (0.02 ** 2 * cfg.d_model)) ** 0.5
    sink_mean = SEEDED_ATTN_LOGIT_STD \
        * math.sqrt(2 * math.log(cfg.window)) - 2.0

    def seeded(l, lp):
        lp = dict(lp, wq=(lp["wq"] * gain).astype(lp["wq"].dtype),
                  wk=(lp["wk"] * gain).astype(lp["wk"].dtype))
        if "sink" in lp:
            lp["sink"] = sink_mean + SEEDED_SINK_STD * jax.random.normal(
                jax.random.fold_in(key, 1000 + l), lp["sink"].shape)
        return lp
    return dict(params, layers=tuple(
        seeded(l, lp) for l, lp in enumerate(params["layers"])))
