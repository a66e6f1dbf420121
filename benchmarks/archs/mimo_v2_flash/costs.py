"""Operations and bytes a call of MiMo-V2-Flash (`mimo_v2_flash`) NEEDS,
from shapes alone: the yardstick of every roofline share the benchmark
prints for it.  A configuration is the dict of its file (the catalog's
key names; `n_routed_experts` is the count HELD here, `published` the
router's).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16; the router float32); of the routed
experts the EXPECTED NUMBER OF DISTINCT HELD EXPERTS that the call's
tokens choose under the published top-k of the published count,
`held x (1 - (1 - k / E)^tokens)`, whatever implements the layer; of
the routed (token, expert) pairs the share whose expert is held,
`k x held / E` a token; in a FULL layer the keys and values of the whole
context once a call (4 heads x (192 + 128) x 2 B = 2,560 B a token and
layer); in a WINDOW layer the keys and values of `min(context, window)`
tokens a row (8 heads: 5,120 B a token and layer) — what the mechanism
needs: a window layer that kept and masked a full-length cache would
read more, and is not the yardstick.  A score costs 2 x 192 operations
and a weighed value 2 x 128; the sink is one score a head and costs
nothing worth counting.  NOT counted: tiles of the grouped matmul past a
group's rows, spans gathered past a row's position, float32 temporaries,
the output head on the positions of a chunk whose logits nobody reads.

One function per kernel, named as the program's `named_scope`s
(`attn_global`, `attn_window`, `moe_route`, `moe_experts`);
`decode_tick` and `prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4


def dims(c: Dict) -> Dict[str, int]:
    L = c["num_hidden_layers"]
    pattern = c["hybrid_layer_pattern"][:L]
    Lm = sum(c["moe_layer_freq"][:L])
    return {"L": L, "Ld": L - Lm, "Lm": Lm, "D": c["hidden_size"],
            "H": c["num_attention_heads"], "Gf": c["num_key_value_heads"],
            "Gw": c["swa_num_key_value_heads"], "Dh": c["head_dim"],
            "Dv": c["v_head_dim"], "F": c["intermediate_size"],
            "Fm": c["moe_intermediate_size"], "held": c["n_routed_experts"],
            "E": c["published"]["n_routed_experts"],
            "k": c["num_experts_per_tok"], "V": c["vocab_size"],
            "W": c["sliding_window"], "Lw": sum(pattern),
            "Lf": L - sum(pattern)}


def attention_params(c: Dict, windowed: bool) -> int:
    """q, k, v, o of one layer of a kind."""
    d = dims(c)
    G = d["Gw"] if windowed else d["Gf"]
    return d["D"] * d["H"] * (d["Dh"] + d["Dv"]) \
        + d["D"] * G * (d["Dh"] + d["Dv"])


def expert_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def _attention_all(c: Dict) -> int:
    d = dims(c)
    return d["Lf"] * attention_params(c, False) \
        + d["Lw"] * attention_params(c, True)


def fixed_matmul_params(c: Dict, with_head: bool = True) -> int:
    """What every token passes through: all but the routed experts
    (attention, the dense feed-forward, the routers, the head)."""
    d = dims(c)
    return _attention_all(c) + d["Ld"] * 3 * d["D"] * d["F"] \
        + d["Lm"] * d["D"] * d["E"] \
        + (d["D"] * d["V"] if with_head else 0)


def matmul_params(c: Dict) -> int:
    """Resident parameters that sit in a matmul (the embedding table is
    a lookup)."""
    d = dims(c)
    return fixed_matmul_params(c) + d["Lm"] * d["held"] * expert_params(c)


def _small_params(c: Dict) -> int:
    """Norm weights (two a layer, the last one), the window layers'
    sinks and the routers' selection biases: float32."""
    d = dims(c)
    return d["L"] * 2 * d["D"] + d["D"] + d["Lw"] * d["H"] \
        + d["Lm"] * d["E"]


def total_params(c: Dict) -> int:
    d = dims(c)
    return matmul_params(c) + d["V"] * d["D"] + _small_params(c)


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16, the router and norms float32."""
    d = dims(c)
    f32 = d["Lm"] * d["D"] * d["E"] + _small_params(c)
    return (total_params(c) - f32) * BF16 + f32 * F32


def token_layer_bytes(c: Dict, windowed: bool) -> int:
    """A token's key and value in one layer of a kind."""
    d = dims(c)
    return (d["Gw"] if windowed else d["Gf"]) * (d["Dh"] + d["Dv"]) * BF16


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token occupies IN PAGES: the full layers' keys and
    values (k and v pages summed: a value is two thirds of a key).  The
    window layers hold nothing a token."""
    return dims(c)["Lf"] * token_layer_bytes(c, False)


def ring_bytes_per_row(c: Dict) -> int:
    """What a decode row holds in the window layers, whatever its
    context: `window` tokens a layer."""
    d = dims(c)
    return d["Lw"] * d["W"] * token_layer_bytes(c, True)


def _written_bytes(c: Dict) -> int:
    """A new token's keys and values, written in every layer."""
    d = dims(c)
    return d["Lf"] * token_layer_bytes(c, False) \
        + d["Lw"] * token_layer_bytes(c, True)


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


def _attend(c: Dict, windowed: bool, pairs: float, keys: float) -> Dict:
    """`pairs` (query, key) pairs scored and weighed by every head, and
    `keys` keys and values read, in each layer of a kind."""
    d = dims(c)
    layers = d["Lw"] if windowed else d["Lf"]
    return {"flops": layers * 2 * d["H"] * (d["Dh"] + d["Dv"]) * pairs,
            "bytes": layers * token_layer_bytes(c, windowed) * keys}


def attn_global(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in the full layers: every row's whole context
    and its own token."""
    keys = context_tokens + rows
    return _attend(c, False, keys, keys)


def attn_window(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in the window layers: the last `window`
    tokens of each row (its own among them), from the rows' MEAN context
    (exact when every row is past the window, as under a mix whose
    shortest prompt is longer)."""
    d = dims(c)
    keys = rows * min(context_tokens / rows + 1, d["W"]) if rows else 0
    return _attend(c, True, keys, keys)


def attn_global_chunk(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention in the full layers: each query over the
    context and the chunk's tokens up to itself."""
    return _attend(c, False,
                   tokens * (context_tokens + (tokens + 1) / 2),
                   context_tokens + tokens)


def attn_window_chunk(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """...in the window layers: each query over at most `window` keys;
    the `window - 1` tokens before the chunk and its own are read."""
    W = dims(c)["W"]
    first = min(context_tokens, W - 1)     # keys before the first query
    # query i sees min(first + i + 1, W) keys
    ramp = max(0, min(tokens, W - first))
    pairs = ramp * first + ramp * (ramp + 1) / 2 + (tokens - ramp) * W
    return _attend(c, True, pairs, first + tokens)


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
               + _written_bytes(c) * rows}
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
               + tokens * d["D"] * BF16 + _written_bytes(c) * tokens}
    return _sum(weights, moe_route(c, tokens), moe_experts(c, tokens),
                attn_global_chunk(c, tokens, context_tokens),
                attn_window_chunk(c, tokens, context_tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "mimo_v2_flash serves only: at 16 B a parameter even the floors "
        "of this model's cut (2.2 B parameters, 35.6 GB) fit no chip of "
        "this benchmark")
