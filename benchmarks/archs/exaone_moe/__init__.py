"""The architecture `exaone_moe`: K-EXAONE's GQA decoder with window
layers (a ring of `sliding_window` tokens a decode row) beside global
layers (pages), a leading dense layer, then layers of one shared +
routed SwiGLU experts under a sigmoid router of which this chip HOLDS A
SHARE (`num_experts` of the file is the count held, `expert_offset` the
first; `published.num_experts` is what the router scores), as
`ray_tpu.models.exaone_moe` and the engine run it.  It serves only: no
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

if importlib.util.find_spec("ray_tpu.models.exaone_moe") is None:
    raise ImportError(
        "the architecture 'exaone_moe' needs ray_tpu.models.exaone_moe, "
        "which this checkout of the program does not have")

from .costs import (attention_params, attn_global,  # noqa: E402,F401
                    attn_global_chunk, attn_window, attn_window_chunk,
                    decode_tick, expert_params, experts_touched,
                    kv_bytes_per_token, layer_matmul_params, matmul_params,
                    moe_experts, moe_route, prefill_chunk,
                    ring_bytes_per_row, token_layer_bytes, total_params,
                    train_flops_per_token, weight_bytes)
from .reference import forward as reference  # noqa: E402,F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import exaone_moe

    if not c["norm_topk_prob"] or c["scoring_func"] != "sigmoid" \
            or c["n_group"] != 1 or c["topk_group"] != 1:
        raise ValueError("the model is written for sigmoid scores, top-k "
                         "over all experts (one group) and weights "
                         "renormalised over the chosen")
    L = c["num_hidden_layers"]
    kinds = ["sparse" if i >= c["first_k_dense_replace"] else "dense"
             for i in range(L)]
    if c["mlp_layer_types"][:L] != kinds:
        raise ValueError("mlp_layer_types: dense layers lead, expert "
                         "layers follow")
    if [w > 0 for w in c["sliding_windows"][:L]] != [
            t == "sliding_attention" for t in c["layer_types"][:L]] \
            or {w for w in c["sliding_windows"] if w} \
            != {c["sliding_window"]}:
        raise ValueError("sliding_windows must be sliding_window in the "
                         "sliding_attention layers and 0 elsewhere")
    if c["rope_parameters"]["rope_type"] != "default":
        raise ValueError("only the default RoPE is written")
    return exaone_moe.ExaoneMoeConfig(
        max_seq=max_seq, n_layers=L, vocab_size=c["vocab_size"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["published"]["num_experts"],
        n_shared_experts=c["num_shared_experts"],
        top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        experts_held=c["num_experts"],
        expert_offset=c.get("expert_offset", 0),
        sliding_windows=tuple(c["sliding_windows"][:L]),
        rope_theta=float(c["rope_parameters"]["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded q . k / sqrt(head_dim) has this standard deviation (1 with the
# q and k norms' weights at one: attention over thousands of keys is
# then near uniform, and a window that was ignored moves no logit a
# comparison could see: the trap PR 28 found in MiniCPM-SALA's seeded
# attention).  At 4 a handful of keys hold most of a head's weight, as
# in a trained model.
SEEDED_ATTN_LOGIT_STD = 4.0


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with the q and k norms scaled so that seeded
    attention is peaked: a normed q and k have unit components, so
    q . k / sqrt(head_dim) has a standard deviation of 1, and of
    `SEEDED_ATTN_LOGIT_STD` with both norms' weights at its square
    root.  A test holds everything else equal to
    `exaone_moe.init_params`."""
    from ray_tpu.models import exaone_moe
    params = exaone_moe.init_params(cfg, key, dtype)
    gain = SEEDED_ATTN_LOGIT_STD ** 0.5
    return dict(params, layers=tuple(
        dict(lp, qn=lp["qn"] * gain, kn=lp["kn"] * gain)
        for lp in params["layers"]))
