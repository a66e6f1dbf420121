"""Operations and bytes a call of Jamba NEEDS, from shapes alone: the
yardstick of every roofline share the benchmark prints for it.  A
configuration is the dict of its file (the catalog's key names).

Counted as needed: every matmul weight read once a call in the served
type (bf16; the embedding is tied, so the head reads it and a lookup
reads `tokens` rows of it); per decode row and Mamba layer the scan
state read AND written once (float32) and the convolution's tail read
and written once (bf16); in an attention layer the one key and the one
value a token of the whole context once a call (512 B a token and
layer).  The scan's operations are counted as what they are: per token,
channel and state one `exp`, three multiplies and two adds (6), held
against the chip's ONE published peak (the MXU's bf16 rate, which a
vector unit cannot reach): a share of that roofline can understate and
never passes 100 %.  NOT counted: spans gathered past a row's position,
float32 temporaries, the output head on the positions of a chunk whose
logits nobody reads, a chunk's pads.

One function per kernel, named as the program's `named_scope`s
(`ssm_conv`, `ssm_scan`, `ssm_step`, `attn_nope`); `decode_tick` and
`prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4
SCAN_OPS = 6       # exp, 3 multiplies, 2 adds a (token, channel, state)


def dims(c: Dict) -> Dict[str, int]:
    L = c["num_hidden_layers"]
    A = sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
            for i in range(L))
    return {"L": L, "A": A, "M": L - A, "D": c["hidden_size"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "Dh": c["hidden_size"] // c["num_attention_heads"],
            "F": c["intermediate_size"], "V": c["vocab_size"],
            "E": c["mamba_expand"] * c["hidden_size"],
            "N": c["mamba_d_state"], "K": c["mamba_d_conv"],
            "R": c["mamba_dt_rank"]}


def ffn_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["F"]


def attention_params(c: Dict) -> int:
    """q, o of H heads; k, v of the one head."""
    d = dims(c)
    return 2 * d["D"] * d["H"] * d["Dh"] + 2 * d["D"] * d["G"] * d["Dh"]


def mamba_matmul_params(c: Dict) -> int:
    """in_proj, x_proj, dt_proj, out_proj."""
    d = dims(c)
    return d["D"] * 2 * d["E"] + d["E"] * (d["R"] + 2 * d["N"]) \
        + d["R"] * d["E"] + d["E"] * d["D"]


def mamba_other_params(c: Dict) -> int:
    """The convolution's taps and bias, b_dt, A_log, D (float32 as
    served) and the dt / B / C norms."""
    d = dims(c)
    return d["E"] * (d["K"] + 1) + d["E"] + d["E"] * d["N"] + d["E"] \
        + d["R"] + 2 * d["N"]


def layer_matmul_params(c: Dict, kind: str) -> int:
    mixer = attention_params(c) if kind == "attention" \
        else mamba_matmul_params(c)
    return mixer + ffn_params(c)


def layers_matmul_params(c: Dict) -> int:
    d = dims(c)
    return d["A"] * layer_matmul_params(c, "attention") \
        + d["M"] * layer_matmul_params(c, "mamba")


def matmul_params(c: Dict) -> int:
    """Parameters that sit in a matmul on every token: the layers and
    the tied head."""
    d = dims(c)
    return layers_matmul_params(c) + d["D"] * d["V"]


def total_params(c: Dict) -> int:
    """The embedding is the head: counted once."""
    d = dims(c)
    return matmul_params(c) + d["M"] * mamba_other_params(c) \
        + (2 * d["L"] + 1) * d["D"]


def weight_bytes(c: Dict) -> int:
    """Resident weights: the matrices bf16; what feeds the scan (the
    convolution, b_dt, A_log, D) and the norms float32."""
    d = dims(c)
    return matmul_params(c) * BF16 \
        + (d["M"] * mamba_other_params(c) + (2 * d["L"] + 1) * d["D"]) * F32


def kv_bytes_per_token(c: Dict) -> int:
    """K and V of the attention layers alone, what a token occupies in
    pages: a Mamba layer keeps nothing a token."""
    d = dims(c)
    return d["A"] * 2 * d["G"] * d["Dh"] * BF16


def state_bytes_per_row(c: Dict) -> int:
    """Scan state (float32) and convolution tail (bf16) of every Mamba
    layer: what a decode row holds whatever its context."""
    d = dims(c)
    return d["M"] * (d["E"] * d["N"] * F32 + d["E"] * (d["K"] - 1) * BF16)


# -- the kernels ------------------------------------------------------


def ssm_step(c: Dict, rows: float) -> Dict:
    """One token a row through every Mamba layer's recurrence: the
    state read and written once."""
    d = dims(c)
    cells = d["M"] * d["E"] * d["N"]
    return {"flops": SCAN_OPS * cells * rows,
            "bytes": 2 * cells * F32 * rows}


def ssm_scan(c: Dict, tokens: float) -> Dict:
    """A chunk of one row through every Mamba layer's recurrence: the
    state read and written once, delta, the convolution's output and y
    (float32, [tokens, E]) once each."""
    d = dims(c)
    cells = d["M"] * d["E"] * d["N"]
    return {"flops": SCAN_OPS * cells * tokens,
            "bytes": 2 * cells * F32 + d["M"] * 3 * d["E"] * F32 * tokens}


def ssm_conv(c: Dict, tokens: float, rows: float = 1) -> Dict:
    """The depthwise convolution of every Mamba layer over `tokens`
    tokens in all (`rows` rows' tails read and written): K multiplies
    and adds a channel and a silu; input bf16 in, float32 out."""
    d = dims(c)
    return {"flops": d["M"] * (2 * d["K"] + 4) * d["E"] * tokens,
            "bytes": d["M"] * d["E"] * ((BF16 + F32) * tokens
                                        + 2 * (d["K"] - 1) * BF16 * rows)}


def attn_nope(c: Dict, pairs: float, keys: float) -> Dict:
    """Score-and-attend of every attention layer: `pairs` (query, key)
    pairs scored and weighed by every head, `keys` keys and values of
    the one key-value head read."""
    d = dims(c)
    return {"flops": d["A"] * 2 * d["H"] * 2 * d["Dh"] * pairs,
            "bytes": kv_bytes_per_token(c) * keys}


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the two programs -------------------------------------------------


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` tokens of context in total."""
    d = dims(c)
    if not rows:
        return {"flops": 0, "bytes": matmul_params(c) * BF16}
    keys = context_tokens + rows
    weights = {"flops": 2 * matmul_params(c) * rows,
               "bytes": matmul_params(c) * BF16 + rows * d["D"] * BF16
               + kv_bytes_per_token(c) * rows}
    return _sum(weights, ssm_conv(c, rows, rows), ssm_step(c, rows),
                attn_nope(c, keys, keys))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The output head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    d = dims(c)
    head = d["D"] * d["V"] if with_head else 0
    weights = {"flops": 2 * layers_matmul_params(c) * tokens + 2 * head,
               "bytes": (layers_matmul_params(c) + head) * BF16
               + tokens * d["D"] * BF16 + kv_bytes_per_token(c) * tokens}
    return _sum(weights, ssm_conv(c, tokens), ssm_scan(c, tokens),
                attn_nope(c, tokens * (context_tokens + (tokens + 1) / 2),
                          context_tokens + tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "jamba serves only: the selective scan has no backward pass here, "
        "and at 16 B a parameter one period of the model and its "
        "embedding (25.6 GB) fit no chip of this benchmark")
