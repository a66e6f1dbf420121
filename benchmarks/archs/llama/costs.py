"""Operations and bytes a call of the dense GQA decoder NEEDS, from
shapes alone — the yardstick of every roofline share and MFU the
benchmark prints for it.  A configuration is the dict of its file
(Hugging Face key names); `intermediate_size` is THE feed-forward width
of every token, three matrices a layer.

Counted as needed: every matmul weight read once per call in the served
type (bf16), the embedding rows actually looked up, and the K/V of the
context actually attended to.  NOT counted: the virtual-width gather
the paged step makes today (ROADMAP S1(b)), fp32 temporaries, recompute
under remat — that is the waste a roofline share is there to show.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16


def dims(c: Dict) -> Dict[str, int]:
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "Hk": c["num_key_value_heads"],
            "Dh": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def layer_matmul_params(c: Dict) -> int:
    d = dims(c)
    attn = d["D"] * d["H"] * d["Dh"] * 2 + d["D"] * 2 * d["Hk"] * d["Dh"]
    return attn + 3 * d["D"] * d["F"]


def matmul_params(c: Dict) -> int:
    """Parameters that sit in a matmul on every token: the layers and
    the untied output head (the embedding table is a lookup)."""
    d = dims(c)
    return d["L"] * layer_matmul_params(c) + d["D"] * d["V"]


def total_params(c: Dict) -> int:
    d = dims(c)
    return matmul_params(c) + d["V"] * d["D"] + (2 * d["L"] + 1) * d["D"]


def kv_bytes_per_token(c: Dict) -> int:
    d = dims(c)
    return d["L"] * 2 * d["Hk"] * d["Dh"] * BF16


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    attending to `context_tokens` cached tokens in total."""
    d = dims(c)
    flops = 2 * matmul_params(c) * rows \
        + 4 * d["L"] * d["H"] * d["Dh"] * context_tokens
    byts = matmul_params(c) * BF16 + rows * d["D"] * BF16 \
        + kv_bytes_per_token(c) * (context_tokens + rows)
    return {"flops": flops, "bytes": byts}


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens that attends to
    `context_tokens` earlier tokens (plus itself, causally).  The output
    head is needed only by a prompt's last chunk (`with_head`)."""
    d = dims(c)
    mm = d["L"] * layer_matmul_params(c) + (d["D"] * d["V"] if with_head
                                            else 0)
    head_tokens = 1 if with_head else 0
    flops = 2 * d["L"] * layer_matmul_params(c) * tokens \
        + 2 * d["D"] * d["V"] * head_tokens \
        + 4 * d["L"] * d["H"] * d["Dh"] * tokens \
        * (context_tokens + (tokens + 1) / 2)
    byts = mm * BF16 + tokens * d["D"] * BF16 \
        + kv_bytes_per_token(c) * (context_tokens + tokens)
    return {"flops": flops, "bytes": byts}


def train_flops_per_token(c: Dict, seq: int) -> float:
    """Forward + backward FLOPs one trained token requires: 6 per matmul
    parameter, and causal attention over `seq` (mean context seq / 2).
    Recomputed operations (remat) do not count."""
    d = dims(c)
    attn_fwd = 4 * d["L"] * d["H"] * d["Dh"] * (seq / 2)
    return 6 * matmul_params(c) + 3 * attn_fwd
