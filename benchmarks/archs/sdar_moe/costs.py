"""Operations and bytes a call of SDAR-MoE (`sdar_moe`) NEEDS, from
shapes alone: the yardstick of every roofline share the benchmark prints
for it.  A configuration is the dict of its file (the catalog's key
names; every expert and head is held here).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16; the router float32); of the routed
experts the EXPECTED NUMBER OF DISTINCT EXPERTS that the call's tokens
choose under top-k of the published count, `E x (1 - (1 - k / E)^tokens)`,
whatever implements the layer; every routed (token, expert) pair; the
keys and values of a row's whole context and of its block once a call
(2 x 4 heads x 128 x 2 B = 2,048 B a token and layer); a score and a
weighed value 2 x 128 operations each, under the block mask: a query
sees its own block whole and every block before it.  A BLOCK STEP is
counted as the program runs the schedule: B columns a live row, the
block's keys written once a step, the head on every column (the logits
of a position already fixed are not needed, and a writing forward needs
none: they are arithmetic under a step the bytes bound, so the share is
not moved by them).  NOT counted: tiles of the grouped matmul past a
group's rows, spans gathered past a row's position, float32
temporaries, and the output head in a prefill chunk, of which a block
body reads nothing.

One function per kernel, named as the program's `named_scope`s
(`block_attn`, `attn_global` for a chunk's, `moe_route`, `moe_experts`);
`block_step` and `prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4


def dims(c: Dict) -> Dict[str, int]:
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "Dh": c["head_dim"], "Fm": c["moe_intermediate_size"],
            "E": c["num_experts"], "k": c["num_experts_per_tok"],
            "V": c["vocab_size"], "B": int(c["assumed"]["block_length"])}


def attention_params(c: Dict) -> int:
    """q, k, v, o of one layer."""
    d = dims(c)
    return 2 * d["D"] * d["H"] * d["Dh"] + 2 * d["D"] * d["G"] * d["Dh"]


def expert_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def fixed_matmul_params(c: Dict, with_head: bool = True) -> int:
    """What every token passes through: all but the routed experts."""
    d = dims(c)
    return d["L"] * (attention_params(c) + d["D"] * d["E"]) \
        + (d["D"] * d["V"] if with_head else 0)


def matmul_params(c: Dict) -> int:
    """Resident parameters that sit in a matmul (the embedding table is
    a lookup)."""
    d = dims(c)
    return fixed_matmul_params(c) + d["L"] * d["E"] * expert_params(c)


def _small_params(c: Dict) -> int:
    """Norm weights (two a layer, two a head width, the last one):
    float32."""
    d = dims(c)
    return d["L"] * (2 * d["D"] + 2 * d["Dh"]) + d["D"]


def total_params(c: Dict) -> int:
    d = dims(c)
    return matmul_params(c) + d["V"] * d["D"] + _small_params(c)


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16, the routers and norms float32."""
    d = dims(c)
    f32 = d["L"] * d["D"] * d["E"] + _small_params(c)
    return (total_params(c) - f32) * BF16 + f32 * F32


def token_layer_bytes(c: Dict) -> int:
    """A token's key and value in one layer."""
    d = dims(c)
    return 2 * d["G"] * d["Dh"] * BF16


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token occupies in pages: every layer's."""
    return dims(c)["L"] * token_layer_bytes(c)


def experts_touched(c: Dict, tokens: float) -> float:
    """Expected distinct experts among the choices of `tokens` tokens,
    each choosing k of E."""
    d = dims(c)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the kernels ------------------------------------------------------


def moe_route(c: Dict, tokens: float) -> Dict:
    """Router of every layer: scores over all E experts in float32; its
    weights once."""
    d = dims(c)
    return {"flops": d["L"] * 2 * d["D"] * d["E"] * tokens,
            "bytes": d["L"] * (d["D"] * d["E"] * F32
                               + tokens * (d["D"] * BF16 + d["E"] * F32))}


def moe_experts(c: Dict, tokens: float) -> Dict:
    """The routed experts of every layer: every pair, the distinct
    experts' weights once, a pair's input and output rows."""
    d = dims(c)
    pairs = tokens * d["k"]
    return {"flops": d["L"] * 2 * expert_params(c) * pairs,
            "bytes": d["L"] * (experts_touched(c, tokens)
                               * expert_params(c) * BF16
                               + pairs * 2 * d["D"] * BF16)}


def _attend(c: Dict, pairs: float, keys: float) -> Dict:
    """`pairs` (query, key) pairs scored and weighed by every head, and
    `keys` keys and values read, in every layer."""
    d = dims(c)
    return {"flops": d["L"] * 2 * d["H"] * 2 * d["Dh"] * pairs,
            "bytes": d["L"] * token_layer_bytes(c) * keys}


def block_attn(c: Dict, columns: float, context_tokens: float) -> Dict:
    """A block step's attention: each of a row's B columns over the
    row's context and its whole block; a row's keys read once."""
    keys = context_tokens + columns
    return _attend(c, dims(c)["B"] * keys, keys)


def attn_global(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention: each query over the context and the chunk's
    blocks through its own, whole."""
    B = dims(c)["B"]
    return _attend(c, tokens * (context_tokens + (tokens + B) / 2),
                   context_tokens + tokens)


# -- the two programs -------------------------------------------------


def _weights(c: Dict, tokens: float, with_head: bool) -> Dict:
    """The weights outside the experts and the router (whose share is
    `moe_route`'s), a token's embedding row, its keys and values
    written."""
    d = dims(c)
    fixed = fixed_matmul_params(c, with_head) - d["L"] * d["D"] * d["E"]
    return {"flops": 2 * fixed * tokens,
            "bytes": fixed * BF16 + tokens * d["D"] * BF16
            + kv_bytes_per_token(c) * tokens}


def block_step(c: Dict, rows: float, columns: float,
               context_tokens: float) -> Dict:
    """One block step: `rows` live rows run `columns` columns in all (B
    a row) over `context_tokens` tokens of context held before their
    blocks, in total."""
    if not rows:
        return {"flops": 0, "bytes": fixed_matmul_params(c) * BF16}
    return _sum(_weights(c, columns, True), moe_route(c, columns),
                moe_experts(c, columns),
                block_attn(c, columns, context_tokens))


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """What the harness calls a decode call of `rows` rows: a block
    step of B columns a row (this body runs no one-token tick)."""
    return block_step(c, rows, rows * dims(c)["B"], context_tokens)


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  A block body's prefill yields no
    token, so the head is needed in no chunk, whatever `with_head`."""
    return _sum(_weights(c, tokens, False), moe_route(c, tokens),
                moe_experts(c, tokens),
                attn_global(c, tokens, context_tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "sdar_moe serves only: at 16 B a parameter the six layers of "
        "this cut (4.36 B parameters, 70 GB) fit no chip of this "
        "benchmark, and the mechanism is a way of generating")
