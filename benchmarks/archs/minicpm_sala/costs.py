"""Operations and bytes a call of MiniCPM-SALA NEEDS, from shapes alone:
the yardstick of every roofline share the benchmark prints for it.  A
configuration is the dict of its file (the catalog's key names, the
`sparse_config` group for the block scorer).

Counted as needed: every matmul weight read once per call in the served
type (bf16); per decode row the lightning layers' state read and written
once (float32); the keys and values of the pages a query attends to
(all it holds below `dense_len`, `topk` blocks above it); the
compressed keys the scorer reads (float32, one per 16 tokens of
context).  NOT counted: the half of the tick's 128-page gather that a
selecting row masks, the chunk's per-token copies of pages that many of
its tokens share (a page a chunk needs is counted once), float32
temporaries, the output head on the 511 positions of a chunk whose
logits nobody reads.

One function per new kernel, named as the program's `named_scope`s
(`sparse_score`, `sparse_attend`, `lightning_chunk`, `lightning_step`);
`decode_tick` and `prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4
ATTN = "minicpm4"
LIGHTNING_SUB = 128     # the chunked scan's sub-chunk (models/minicpm_sala.py)


def dims(c: Dict) -> Dict[str, int]:
    kinds = c["layer_mixers"]
    sp = c["sparse_config"]
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "Dh": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"], "Hl": c["lightning_nh"],
            "Dl": c["lightning_head_dim"],
            "A": sum(k == ATTN for k in kinds),
            "N": sum(k != ATTN for k in kinds),
            "block": sp["block_size"], "stride": sp["kernel_stride"],
            "topk": sp["topk"], "dense_len": sp["dense_len"]}


def layer_matmul_params(c: Dict, kind: str) -> int:
    d = dims(c)
    ffn = 3 * d["D"] * d["F"]
    if kind == ATTN:     # q, gate, o of H heads; k, v of G heads
        return 3 * d["D"] * d["H"] * d["Dh"] + 2 * d["D"] * d["G"] * d["Dh"] \
            + ffn
    return 5 * d["D"] * d["Hl"] * d["Dl"] + ffn          # q, k, v, gate, o


def layers_matmul_params(c: Dict) -> int:
    return sum(layer_matmul_params(c, k) for k in c["layer_mixers"])


def matmul_params(c: Dict) -> int:
    """Parameters that sit in a matmul on every token: the layers and
    the untied output head (the embedding table is a lookup)."""
    d = dims(c)
    return layers_matmul_params(c) + d["D"] * d["V"]


def total_params(c: Dict) -> int:
    d = dims(c)
    norms = (2 * d["L"] + 1) * d["D"] + 2 * d["A"] * d["Dh"] \
        + d["N"] * (2 * d["Dl"] + d["Hl"] * d["Dl"])
    return matmul_params(c) + d["V"] * d["D"] + norms


def kv_bytes_per_token(c: Dict) -> int:
    """K and V of the attention layers alone: the lightning layers keep
    no per-token state."""
    d = dims(c)
    return d["A"] * 2 * d["G"] * d["Dh"] * BF16


def state_bytes_per_row(c: Dict) -> int:
    d = dims(c)
    return d["N"] * d["Hl"] * d["Dl"] * d["Dl"] * F32


# -- the kernels ------------------------------------------------------


def lightning_step(c: Dict, rows: float) -> Dict:
    """One token a row through every lightning layer: S = lambda S +
    k^T v (3 operations an element), o = q S (2), state read and
    written once."""
    d = dims(c)
    cells = d["N"] * d["Hl"] * d["Dl"] * d["Dl"]
    return {"flops": 5 * cells * rows,
            "bytes": 2 * state_bytes_per_row(c) * rows}


def lightning_chunk(c: Dict, tokens: float) -> Dict:
    """A chunk of one row through every lightning layer, in sub-chunks
    of LIGHTNING_SUB: q S and k^T v against the state (2 Dl^2 each a
    token and head) and the causal half of q k^T and of its product
    with v inside a sub-chunk; state read and written once."""
    d = dims(c)
    per_token_head = 4 * d["Dl"] * d["Dl"] \
        + 2 * d["Dl"] * (LIGHTNING_SUB + 1)
    return {"flops": d["N"] * d["Hl"] * per_token_head * tokens,
            "bytes": 2 * state_bytes_per_row(c)}


def sparse_score(c: Dict, scored_tokens: float, read_tokens: float) -> Dict:
    """The block scorer of every attention layer.  `scored_tokens`: the
    context of each query, summed over queries (one compressed key per
    `stride` tokens, every head scores it); `read_tokens`: context
    whose compressed keys are read (a tick reads a row's once, a chunk
    once for all its tokens)."""
    d = dims(c)
    return {"flops": d["A"] * 2 * d["H"] * d["Dh"]
            * scored_tokens / d["stride"],
            "bytes": d["A"] * d["G"] * d["Dh"] * F32
            * read_tokens / d["stride"]}


def sparse_attend(c: Dict, attended_keys: float, read_keys: float) -> Dict:
    """Attention of every attention layer over the pages attended to.
    `attended_keys`: keys each query attends to, summed over queries;
    `read_keys`: keys whose K and V are read."""
    d = dims(c)
    return {"flops": d["A"] * 4 * d["H"] * d["Dh"] * attended_keys,
            "bytes": kv_bytes_per_token(c) * read_keys}


def keys_attended(c: Dict, context: float) -> float:
    """Keys one query attends to with `context` tokens before it."""
    d = dims(c)
    if context < d["dense_len"]:
        return context + 1
    return min(context + 1, d["topk"] * d["block"])


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the two programs -------------------------------------------------


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` cached tokens in total (the selection is
    taken at the rows' mean context)."""
    d = dims(c)
    if not rows:
        return {"flops": 0, "bytes": matmul_params(c) * BF16}
    mean = context_tokens / rows
    selecting = mean >= d["dense_len"]
    keys = keys_attended(c, mean) * rows
    weights = {"flops": 2 * matmul_params(c) * rows,
               "bytes": matmul_params(c) * BF16 + rows * d["D"] * BF16
               + kv_bytes_per_token(c) * rows}
    score = sparse_score(c, context_tokens, context_tokens) if selecting \
        else {"flops": 0, "bytes": 0}
    return _sum(weights, lightning_step(c, rows), score,
                sparse_attend(c, keys, keys))


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
    held = context_tokens + tokens
    if context_tokens >= d["dense_len"]:
        score = sparse_score(c, tokens * (context_tokens + (tokens + 1) / 2),
                             held)
        attended = tokens * d["topk"] * d["block"]
        attend = sparse_attend(c, attended, min(attended, held))
    else:
        score = {"flops": 0, "bytes": 0}
        attend = sparse_attend(
            c, tokens * (context_tokens + (tokens + 1) / 2), held)
    return _sum(weights, lightning_chunk(c, tokens), score, attend)


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "minicpm_sala serves only: at 16 B a parameter its training "
        "state fits no chip of this benchmark")
