"""A second architecture, for the tests alone: the repo's GPT block
(`ray_tpu.models.gpt.GPTConfig`: learned positions, one fused `wqkv`, as
many KV heads as heads, a two-matrix GELU feed-forward), which the
engine serves through the other arm of `models/decode.py`.  The tests
copy this directory into a temporary root's `archs/`; the benchmark
itself has no such architecture, cell or configuration.  It serves only:
no `param_specs`, `make_train_step` or `batch_axes`.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.lib.costs import BF16

from .reference import forward as reference  # noqa: F401


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("GPTConfig has as many KV heads as heads")
    return gpt.GPTConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"], max_seq=max_seq,
        dtype=getattr(jnp, c["torch_dtype"]), remat=remat)


def init(cfg, key, dtype):
    """The layout of gpt.init_params, drawn in one traced function."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, D, H, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim,
                      cfg.d_ff)
    s = 0.02
    so = s / np.sqrt(2 * L)
    k = iter(jax.random.split(key, 7))

    def nrm(shape, scale):
        return (scale * jax.random.normal(next(k), shape, jnp.float32)
                ).astype(dtype)

    ones = lambda shape: jnp.ones(shape, jnp.float32)  # noqa: E731
    return {
        "wte": nrm((cfg.vocab_size, D), s),
        "wpe": nrm((cfg.max_seq, D), s),
        "blocks": {
            "ln1": ones((L, D)), "wqkv": nrm((L, D, 3, H, Dh), s),
            "wo": nrm((L, H, Dh, D), so), "ln2": ones((L, D)),
            "w1": nrm((L, D, F), s), "w2": nrm((L, F, D), so)},
        "ln_f": ones((D,)),
        "wlm": nrm((D, cfg.vocab_size), s),
    }


# -- the yardstick: a layer is four attention matrices of D x D and TWO
# feed-forward matrices of D x F --------------------------------------

def layer_matmul_params(c: Dict) -> int:
    D, F = c["hidden_size"], c["intermediate_size"]
    return 4 * D * c["num_attention_heads"] * c["head_dim"] + 2 * D * F


def matmul_params(c: Dict) -> int:
    return c["num_hidden_layers"] * layer_matmul_params(c) \
        + c["hidden_size"] * c["vocab_size"]


def total_params(c: Dict) -> int:
    D = c["hidden_size"]
    return matmul_params(c) + (c["vocab_size"]
                               + c["serving"]["engine"]["max_seq"]) * D \
        + (2 * c["num_hidden_layers"] + 1) * D


def kv_bytes_per_token(c: Dict) -> int:
    return c["num_hidden_layers"] * 2 * c["num_attention_heads"] \
        * c["head_dim"] * BF16


def _attn_flops(c: Dict, pairs: float) -> float:
    """Scores and weighted values for `pairs` (query, key) pairs."""
    return 4 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * pairs


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    return {"flops": 2 * matmul_params(c) * rows
            + _attn_flops(c, context_tokens),
            "bytes": matmul_params(c) * BF16
            + 2 * rows * c["hidden_size"] * BF16     # wte and wpe rows
            + kv_bytes_per_token(c) * (context_tokens + rows)}


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    layers = c["num_hidden_layers"] * layer_matmul_params(c)
    head = c["hidden_size"] * c["vocab_size"] if with_head else 0
    return {"flops": 2 * layers * tokens + 2 * head
            + _attn_flops(c, tokens * (context_tokens + (tokens + 1) / 2)),
            "bytes": (layers + head) * BF16
            + 2 * tokens * c["hidden_size"] * BF16
            + kv_bytes_per_token(c) * (context_tokens + tokens)}


def train_flops_per_token(c: Dict, seq: int) -> float:
    return 6 * matmul_params(c) + 3 * _attn_flops(c, seq / 2)
