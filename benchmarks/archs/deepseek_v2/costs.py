"""Operations and bytes a call of DeepSeek-V2 NEEDS, from shapes alone:
the yardstick of every roofline share the benchmark prints for it.  A
configuration is the dict of its file (the catalog's key names;
`n_routed_experts` is the count HELD here, `published` the router's).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16; the router float32); of the routed
experts the EXPECTED NUMBER OF DISTINCT HELD EXPERTS that the call's
tokens choose under the published top-k of the published count,
`held x (1 - (1 - k / E)^tokens)`, whatever implements the layer; of
the routed (token, expert) pairs the share whose expert is held,
`k x held / E` a token; the cached latents of the context once a call
(1,152 B a token and layer: 512 + 64 bf16 numbers, not the 1,280 they
occupy once the 64 is padded to a tile); a decode tick's attention in
the ABSORBED form (per query, head and key 2 x (576 + 512) operations),
a prefill chunk's in the EXPANDED form (keys and values formed from the
context's latents once a call, then 2 x (192 + 128) a query, head and
key).  NOT counted: tiles of the grouped matmul past a group's rows,
spans gathered past a row's position, float32 temporaries, the output
head on the positions of a chunk whose logits nobody reads.

One function per new kernel, named as the program's `named_scope`s
(`mla_absorb_attend`, `mla_expand_attend`, `moe_route`, `moe_experts`);
`decode_tick` and `prefill_chunk` sum them with the weights.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4
TILE = 128      # lanes of the chip's tile: a minor dimension pads to it


def dims(c: Dict) -> Dict[str, int]:
    dense = c["first_k_dense_replace"]
    return {"L": c["num_hidden_layers"], "Ld": dense,
            "Lm": c["num_hidden_layers"] - dense, "D": c["hidden_size"],
            "H": c["num_attention_heads"], "qr": c["q_lora_rank"],
            "kr": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "F": c["intermediate_size"], "Fm": c["moe_intermediate_size"],
            "held": c["n_routed_experts"],
            "E": c["published"]["n_routed_experts"],
            "k": c["num_experts_per_tok"], "ns": c["n_shared_experts"],
            "V": c["vocab_size"]}


def attention_params(c: Dict) -> int:
    """q_a, q_b, kv_a, kv_b (both halves), o of one layer."""
    d = dims(c)
    return d["D"] * d["qr"] + d["qr"] * d["H"] * (d["dn"] + d["dr"]) \
        + d["D"] * (d["kr"] + d["dr"]) \
        + d["kr"] * d["H"] * (d["dn"] + d["dv"]) + d["H"] * d["dv"] * d["D"]


def expert_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def layer_matmul_params(c: Dict, kind: str) -> int:
    """`dense`: a leading layer; `moe`: an expert layer WITHOUT its
    routed experts (attention, the shared experts, the router)."""
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


def total_params(c: Dict) -> int:
    d = dims(c)
    norms = d["L"] * (2 * d["D"] + d["qr"] + d["kr"]) + d["D"]
    return matmul_params(c) + d["V"] * d["D"] + norms


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16, the router and norms float32."""
    d = dims(c)
    f32 = d["Lm"] * d["D"] * d["E"] \
        + d["L"] * (2 * d["D"] + d["qr"] + d["kr"]) + d["D"]
    return (total_params(c) - f32) * BF16 + f32 * F32


def latent_bytes_per_token(c: Dict) -> int:
    """What a cached token IS: 512 + 64 bf16 numbers a layer."""
    d = dims(c)
    return d["L"] * (d["kr"] + d["dr"]) * BF16


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token OCCUPIES on the device: the 64-wide rotary
    part is padded to the tile's 128 lanes (1,280 B a layer)."""
    d = dims(c)
    pad = lambda n: -(-n // TILE) * TILE  # noqa: E731
    return d["L"] * (pad(d["kr"]) + pad(d["dr"])) * BF16


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


def mla_absorb_attend(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in every layer, absorbed: per query, head and
    key (kr + dr) to score and kr to weigh; the query into the latent
    space and the output out of it (wk_b, wv_b: in the weights); the
    context's latents read once."""
    d = dims(c)
    keys = context_tokens + rows
    return {"flops": d["L"] * 2 * d["H"] * (2 * d["kr"] + d["dr"]) * keys,
            "bytes": latent_bytes_per_token(c) * keys}


def mla_expand_attend(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention in every layer, expanded: k_nope and v of
    every key it holds formed once from the latents, then (dn + dr) to
    score and dv to weigh per query, head and causal key."""
    d = dims(c)
    held = context_tokens + tokens
    attended = tokens * (context_tokens + (tokens + 1) / 2)
    return {"flops": d["L"] * 2 * d["H"] * (
                held * d["kr"] * (d["dn"] + d["dv"])
                + attended * (d["dn"] + d["dr"] + d["dv"])),
            "bytes": latent_bytes_per_token(c) * held}


# -- the two programs -------------------------------------------------


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` cached tokens in total."""
    d = dims(c)
    fixed = fixed_matmul_params(c)
    if not rows:
        return {"flops": 0, "bytes": fixed * BF16}
    weights = {"flops": 2 * fixed * rows,
               "bytes": fixed * BF16 + rows * d["D"] * BF16
               + latent_bytes_per_token(c) * rows}
    # the router's share of `fixed` is counted by moe_route
    weights["flops"] -= moe_route(c, rows)["flops"]
    weights["bytes"] -= d["Lm"] * d["D"] * d["E"] * BF16
    return _sum(weights, moe_route(c, rows), moe_experts(c, rows),
                mla_absorb_attend(c, rows, context_tokens))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The output head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    d = dims(c)
    body = fixed_matmul_params(c, with_head=False)
    head = d["D"] * d["V"] if with_head else 0
    # wk_b / wv_b are applied to keys, not to queries: mla_expand_attend
    # counts their use
    kv_b = d["L"] * d["kr"] * d["H"] * (d["dn"] + d["dv"])
    weights = {"flops": 2 * (body - kv_b) * tokens + 2 * head
               - moe_route(c, tokens)["flops"],
               "bytes": (body + head - d["Lm"] * d["D"] * d["E"]) * BF16
               + tokens * d["D"] * BF16 + latent_bytes_per_token(c) * tokens}
    return _sum(weights, moe_route(c, tokens), moe_experts(c, tokens),
                mla_expand_attend(c, tokens, context_tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "deepseek_v2 serves only: at 16 B a parameter one expert layer's "
        "share of training state fits no chip of this benchmark")
