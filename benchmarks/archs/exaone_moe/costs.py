"""Operations and bytes a call of K-EXAONE (`exaone_moe`) NEEDS, from
shapes alone: the yardstick of every roofline share the benchmark prints
for it.  A configuration is the dict of its file (the catalog's key
names; `num_experts` is the count HELD here, `published` the router's).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16; the router float32); of the routed
experts the EXPECTED NUMBER OF DISTINCT HELD EXPERTS that the call's
tokens choose under the published top-k of the published count,
`held x (1 - (1 - k / E)^tokens)`, whatever implements the layer; of
the routed (token, expert) pairs the share whose expert is held,
`k x held / E` a token; in a GLOBAL layer the keys and values of the
whole context once a call (4,096 B a token and layer); in a WINDOW layer
the keys and values of `min(context, window)` tokens a row — what the
mechanism needs: a window layer that kept and masked a full-length cache
would read more, and is not the yardstick.  NOT counted: tiles of the
grouped matmul past a group's rows, spans gathered past a row's
position, float32 temporaries, the output head on the positions of a
chunk whose logits nobody reads.

One function per kernel, named as the program's `named_scope`s
(`attn_global`, `attn_window`, `moe_route`, `moe_experts`);
`decode_tick` and `prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4


def dims(c: Dict) -> Dict[str, int]:
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    windows = c["sliding_windows"][:L]
    return {"L": L, "Ld": dense, "Lm": L - dense, "D": c["hidden_size"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "Dh": c["head_dim"], "F": c["intermediate_size"],
            "Fm": c["moe_intermediate_size"], "held": c["num_experts"],
            "E": c["published"]["num_experts"],
            "k": c["num_experts_per_tok"], "ns": c["num_shared_experts"],
            "V": c["vocab_size"], "W": max(windows),
            "Lw": sum(w > 0 for w in windows),
            "Lg": sum(w == 0 for w in windows)}


def attention_params(c: Dict) -> int:
    """q, k, v, o of one layer."""
    d = dims(c)
    return 2 * d["D"] * d["H"] * d["Dh"] + 2 * d["D"] * d["G"] * d["Dh"]


def expert_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def layer_matmul_params(c: Dict, kind: str) -> int:
    """`dense`: a leading layer; `moe`: an expert layer WITHOUT its
    routed experts (attention, the shared expert, the router)."""
    d = dims(c)
    if kind == "dense":
        return attention_params(c) + 3 * d["D"] * d["F"]
    return attention_params(c) + d["ns"] * expert_params(c) + d["D"] * d["E"]


def fixed_matmul_params(c: Dict, with_head: bool = True) -> int:
    """What every token passes through: all but the routed experts."""
    d = dims(c)
    return d["Ld"] * layer_matmul_params(c, "dense") \
        + d["Lm"] * layer_matmul_params(c, "moe") \
        + (d["D"] * d["V"] if with_head else 0)


def matmul_params(c: Dict) -> int:
    """Resident parameters that sit in a matmul (the embedding table is
    a lookup)."""
    d = dims(c)
    return fixed_matmul_params(c) + d["Lm"] * d["held"] * expert_params(c)


def _small_params(c: Dict) -> int:
    """Norm weights (two a layer, two a head width, the last one) and
    the router's selection bias: float32."""
    d = dims(c)
    return d["L"] * (2 * d["D"] + 2 * d["Dh"]) + d["D"] + d["Lm"] * d["E"]


def total_params(c: Dict) -> int:
    d = dims(c)
    return matmul_params(c) + d["V"] * d["D"] + _small_params(c)


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16, the router and norms float32."""
    d = dims(c)
    f32 = d["Lm"] * d["D"] * d["E"] + _small_params(c)
    return (total_params(c) - f32) * BF16 + f32 * F32


def token_layer_bytes(c: Dict) -> int:
    """A token's key and value in one layer."""
    d = dims(c)
    return 2 * d["G"] * d["Dh"] * BF16


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token occupies IN PAGES: the global layers' keys
    and values.  The window layers hold nothing a token."""
    return dims(c)["Lg"] * token_layer_bytes(c)


def ring_bytes_per_row(c: Dict) -> int:
    """What a decode row holds in the window layers, whatever its
    context: `window` tokens a layer."""
    d = dims(c)
    return d["Lw"] * d["W"] * token_layer_bytes(c)


def experts_touched(c: Dict, tokens: float) -> float:
    """Expected distinct HELD experts among the choices of `tokens`
    tokens, each choosing k of E."""
    d = dims(c)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the kernels ------------------------------------------------------


def moe_route(c: Dict, tokens: float) -> Dict:
    """Router of every expert layer: scores over all E experts in
    float32; its weights once."""
    d = dims(c)
    return {"flops": d["Lm"] * 2 * d["D"] * d["E"] * tokens,
            "bytes": d["Lm"] * (d["D"] * d["E"] * F32
                                + tokens * (d["D"] * BF16 + d["E"] * F32))}


def moe_experts(c: Dict, tokens: float) -> Dict:
    """The routed experts of every expert layer: the pairs whose expert
    is held, the distinct held experts' weights once, a pair's input and
    output rows."""
    d = dims(c)
    pairs = tokens * d["k"] * d["held"] / d["E"]
    return {"flops": d["Lm"] * 2 * expert_params(c) * pairs,
            "bytes": d["Lm"] * (experts_touched(c, tokens)
                                * expert_params(c) * BF16
                                + pairs * 2 * d["D"] * BF16)}


def _attend(c: Dict, layers: int, pairs: float, keys: float) -> Dict:
    """`pairs` (query, key) pairs scored and weighed by every head, and
    `keys` keys and values read, in each of `layers` layers."""
    d = dims(c)
    return {"flops": layers * 2 * d["H"] * 2 * d["Dh"] * pairs,
            "bytes": layers * token_layer_bytes(c) * keys}


def attn_global(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in the global layers: every row's whole
    context and its own token."""
    keys = context_tokens + rows
    return _attend(c, dims(c)["Lg"], keys, keys)


def attn_window(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in the window layers: the last `window`
    tokens of each row (its own among them), from the rows' MEAN context
    (exact when every row is past the window, as under a mix whose
    shortest prompt is longer)."""
    d = dims(c)
    keys = rows * min(context_tokens / rows + 1, d["W"]) if rows else 0
    return _attend(c, d["Lw"], keys, keys)


def attn_global_chunk(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention in the global layers: each query over the
    context and the chunk's tokens up to itself."""
    return _attend(c, dims(c)["Lg"],
                   tokens * (context_tokens + (tokens + 1) / 2),
                   context_tokens + tokens)


def attn_window_chunk(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """...in the window layers: each query over at most `window` keys;
    the `window - 1` tokens before the chunk and its own are read."""
    d = dims(c)
    W = d["W"]
    first = min(context_tokens, W - 1)     # keys before the first query
    # query i sees min(first + i + 1, W) keys
    ramp = max(0, min(tokens, W - first))
    pairs = ramp * first + ramp * (ramp + 1) / 2 + (tokens - ramp) * W
    return _attend(c, d["Lw"], pairs, first + tokens)


# -- the two programs -------------------------------------------------


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` tokens of context in total."""
    d = dims(c)
    fixed = fixed_matmul_params(c)
    if not rows:
        return {"flops": 0, "bytes": fixed * BF16}
    weights = {"flops": 2 * fixed * rows,
               "bytes": fixed * BF16 + rows * d["D"] * BF16
               + d["L"] * token_layer_bytes(c) * rows}
    # the router's share of `fixed` is counted by moe_route
    weights["flops"] -= moe_route(c, rows)["flops"]
    weights["bytes"] -= d["Lm"] * d["D"] * d["E"] * BF16
    return _sum(weights, moe_route(c, rows), moe_experts(c, rows),
                attn_global(c, rows, context_tokens),
                attn_window(c, rows, context_tokens))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The output head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    d = dims(c)
    body = fixed_matmul_params(c, with_head=False)
    head = d["D"] * d["V"] if with_head else 0
    weights = {"flops": 2 * body * tokens + 2 * head
               - moe_route(c, tokens)["flops"],
               "bytes": (body + head - d["Lm"] * d["D"] * d["E"]) * BF16
               + tokens * d["D"] * BF16
               + d["L"] * token_layer_bytes(c) * tokens}
    return _sum(weights, moe_route(c, tokens), moe_experts(c, tokens),
                attn_global_chunk(c, tokens, context_tokens),
                attn_window_chunk(c, tokens, context_tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "exaone_moe serves only: at 16 B a parameter even the floors of "
        "this model's cut (2.5 B parameters, 40 GB) fit no chip of this "
        "benchmark")
