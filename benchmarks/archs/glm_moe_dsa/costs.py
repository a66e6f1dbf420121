"""Operations and bytes a call of GLM-5 (`glm_moe_dsa`) NEEDS, from
shapes alone: the yardstick of every roofline share the benchmark prints
for it.  A configuration is the dict of its file (the catalog's key
names; `n_routed_experts` is the count HELD here, `published` the
router's).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16 matrices; the router, its bias, the
indexer key's LayerNorm and the norms float32); of the routed experts
the EXPECTED NUMBER OF DISTINCT HELD EXPERTS that the call's tokens
choose under the published top-k of the published count, `held x (1 -
(1 - k / E)^tokens)`, whatever implements the layer; of the routed
(token, expert) pairs the share whose expert is held, `k x held / E` a
token.  In every layer, what the WORK needs whatever implements it: the
indexer reads each key its queries see once a call (128 bf16 numbers)
and scores it with 32 heads of 128 (`dsa_index`); the choice reads those
scores once (`dsa_select`: a comparison a score, no multiply); attention
weighs `min(keys seen, index_topk)` keys a query and NOT the context
(`dsa_attend`): a tick gathers that many latent rows a row (1,280 B as
they lie) and attends ABSORBED, (2 x kv_lora_rank + qk_rope_head_dim)
multiply-adds a head and key; a chunk reads each latent its row holds
once, EXPANDS it (rank x (dn + dv) a key and head) and weighs its
chosen pairs at head width (dn + dr + dv a head and pair).  NOT counted:
tiles of the grouped matmul past a group's rows, spans scored past a
row's position, slots gathered for nothing, the pairs a masked chunk
scores and throws away, float32 temporaries, the output head on the
positions of a chunk whose logits nobody reads.

One function per scope, named as the program's `named_scope`s
(`dsa_index`, `dsa_select`, `dsa_attend`, `moe_route`, `moe_experts`);
`decode_tick` and `prefill_chunk` sum them with what lies outside the
scopes (the projections, the dense and shared feed-forwards, the head,
the embedding's rows).
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4
TILE = 128      # lanes of the chip's tile: a minor dimension pads to it


def dims(c: Dict) -> Dict:
    L = c["num_hidden_layers"]
    dense = min(L, c["first_k_dense_replace"])
    return {"L": L, "Ld": dense, "Lm": L - dense, "D": c["hidden_size"],
            "H": c["num_attention_heads"], "rq": c["q_lora_rank"],
            "rk": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "Hi": c["index_n_heads"], "di": c["index_head_dim"],
            "topk": c["index_topk"], "F": c["intermediate_size"],
            "Fm": c["moe_intermediate_size"],
            "Fs": c["n_shared_experts"] * c["moe_intermediate_size"],
            "held": c["n_routed_experts"],
            "E": c["published"]["n_routed_experts"],
            "k": c["num_experts_per_tok"], "V": c["vocab_size"]}


def _row(d: Dict) -> int:
    """Numbers a cached latent row occupies: rank + rope up to tiles."""
    return -(-(d["rk"] + d["dr"]) // TILE) * TILE


def mixer_params(c: Dict) -> Dict[str, int]:
    """One layer's mixer: q_a, q_b, kv_a, kv_b (both halves) and o, the
    indexer's queries' projection, its key's and the heads' weights
    (bf16); the two latents' norms and the indexer key's LayerNorm gain
    and bias (float32)."""
    d = dims(c)
    D = d["D"]
    return {"bf16": D * d["rq"] + d["rq"] * d["H"] * (d["dn"] + d["dr"])
            + D * (d["rk"] + d["dr"]) + d["rk"] * d["H"] * (d["dn"] + d["dv"])
            + d["H"] * d["dv"] * D
            + d["rq"] * d["Hi"] * d["di"] + D * d["di"] + D * d["Hi"],
            "f32": d["rq"] + d["rk"] + 2 * d["di"]}


def expert_params(c: Dict) -> int:
    """One routed expert (SwiGLU: gate, up, down)."""
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def _f32_params(c: Dict) -> int:
    """Everything served in float32: the mixers' small vectors, two
    norms a layer, the routers and their biases, the last norm."""
    d = dims(c)
    return d["L"] * (mixer_params(c)["f32"] + 2 * d["D"]) \
        + d["Lm"] * (d["D"] * d["E"] + d["E"]) + d["D"]


def fixed_matmul_params(c: Dict, with_head: bool = True) -> int:
    """bf16 parameters every call reads whatever it routes: the mixers,
    the dense and shared feed-forwards and, `with_head`, the head."""
    d = dims(c)
    return d["L"] * mixer_params(c)["bf16"] \
        + d["Ld"] * 3 * d["D"] * d["F"] + d["Lm"] * 3 * d["D"] * d["Fs"] \
        + (d["D"] * d["V"] if with_head else 0)


def matmul_params(c: Dict) -> int:
    """Resident parameters that sit in a matmul (the embedding is a
    lookup)."""
    d = dims(c)
    return fixed_matmul_params(c) + d["Lm"] * (
        d["D"] * d["E"] + d["held"] * expert_params(c))


def total_params(c: Dict) -> int:
    d = dims(c)
    return fixed_matmul_params(c) + d["Lm"] * d["held"] * expert_params(c) \
        + d["V"] * d["D"] + _f32_params(c)


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16, the small vectors and the
    routers float32."""
    f32 = _f32_params(c)
    return (total_params(c) - f32) * BF16 + f32 * F32


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token occupies in pages: in every layer one latent
    row of 512 + 64 up to whole tiles (640 numbers) and one indexer key
    (128).  (What it needs: 576 + 128.)"""
    d = dims(c)
    return d["L"] * (_row(d) + d["di"]) * BF16


def experts_touched(c: Dict, tokens: float) -> float:
    """Expected distinct HELD experts among the choices of `tokens`
    tokens, each choosing k of the published E."""
    d = dims(c)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the scopes -------------------------------------------------------


def dsa_index(c: Dict, queries: float, pairs: float, keys: float) -> Dict:
    """The indexer's scores in every layer: `pairs` (query, key) pairs,
    each 32 heads x 128 multiply-adds and the 32-term weighted sum;
    `keys` distinct cached keys read once (bf16), the queries and their
    heads' weights in, a float32 score a pair out."""
    d = dims(c)
    return {"flops": d["L"] * pairs * d["Hi"] * (2 * d["di"] + 2),
            "bytes": d["L"] * (keys * d["di"] * BF16
                               + queries * d["Hi"] * (d["di"] * BF16 + F32)
                               + pairs * F32)}


def dsa_select(c: Dict, pairs: float, chosen: float) -> Dict:
    """The choice in every layer: each score read once and compared (one
    operation a score), a mark or an index a chosen key written."""
    d = dims(c)
    return {"flops": d["L"] * pairs,
            "bytes": d["L"] * (pairs + chosen) * F32}


def dsa_attend(c: Dict, queries: float, chosen: float,
               expanded: float = 0) -> Dict:
    """Attention over the chosen keys in every layer: `chosen` (query,
    key) pairs.  A tick (`expanded` 0): each a gathered latent row
    (1,280 B as it lies) scored by 64 heads over rank + rope and weighed
    over the rank, ABSORBED; the absorbed queries in, the weighted
    latents out.  A chunk: `expanded` distinct latent rows read once and
    formed into keys and values of head width, each pair scored and
    weighed at head width."""
    d = dims(c)
    if expanded:
        return {"flops": d["L"] * 2 * d["H"] * (
                    expanded * d["rk"] * (d["dn"] + d["dv"])
                    + chosen * (d["dn"] + d["dr"] + d["dv"])),
                "bytes": d["L"] * (expanded * _row(d) * BF16
                                   + queries * d["H"] * (d["dn"] + d["dr"]
                                                         + d["dv"]) * BF16)}
    return {"flops": d["L"] * 2 * d["H"] * (2 * d["rk"] + d["dr"]) * chosen,
            "bytes": d["L"] * (chosen * _row(d) * BF16
                               + queries * d["H"] * (_row(d) + d["rk"])
                               * BF16)}


def moe_route(c: Dict, tokens: float) -> Dict:
    """Router of every expert layer: scores over all E experts in
    float32; its weights and bias once."""
    d = dims(c)
    return {"flops": d["Lm"] * 2 * d["D"] * d["E"] * tokens,
            "bytes": d["Lm"] * ((d["D"] + 1) * d["E"] * F32
                                + tokens * (d["D"] * BF16 + d["E"] * F32))}


def moe_experts(c: Dict, pairs: float, touched: float) -> Dict:
    """The routed experts of every expert layer: `pairs` (token, held
    expert) pairs a layer, `touched` distinct held experts' weights once
    a layer, a pair's input and output rows."""
    d = dims(c)
    return {"flops": d["Lm"] * 2 * expert_params(c) * pairs,
            "bytes": d["Lm"] * (touched * expert_params(c) * BF16
                                + pairs * 2 * d["D"] * BF16)}


def _routed(c: Dict, tokens: float) -> Dict:
    d = dims(c)
    return moe_experts(c, tokens * d["k"] * d["held"] / d["E"],
                       experts_touched(c, tokens))


# -- the two programs -------------------------------------------------


def _outside(c: Dict, tokens: float, head_tokens: float,
             skip_kv_b: bool) -> Dict:
    """What no scope's function holds: every fixed matrix applied to
    `tokens` tokens (the head to `head_tokens`), the float32 vectors,
    the embedding's rows in and the cached rows out.  A chunk applies
    wk_b / wv_b to keys, not to queries (`skip_kv_b`): dsa_attend counts
    their use."""
    d = dims(c)
    body = fixed_matmul_params(c, with_head=False)
    head = d["D"] * d["V"] if head_tokens else 0
    kv_b = d["L"] * d["rk"] * d["H"] * (d["dn"] + d["dv"]) \
        if skip_kv_b else 0
    small = _f32_params(c) - d["Lm"] * (d["D"] + 1) * d["E"]
    return {"flops": 2 * ((body - kv_b) * tokens + head * head_tokens),
            "bytes": (body + head) * BF16 + small * F32
            + tokens * (d["D"] * BF16 + kv_bytes_per_token(c))}


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` cached tokens in total."""
    if not rows:
        return {"flops": 0, "bytes": fixed_matmul_params(c) * BF16}
    d = dims(c)
    seen = context_tokens + rows
    chosen = min(seen, rows * d["topk"])
    return _sum(_outside(c, rows, rows, False),
                dsa_index(c, rows, seen, seen), dsa_select(c, seen, chosen),
                dsa_attend(c, rows, chosen),
                moe_route(c, rows), _routed(c, rows))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The output head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    d = dims(c)

    def pairs(limit=None):
        """(query, key) pairs: query j of the chunk sees context + j + 1
        keys, at most `limit`."""
        if limit is None or context_tokens + tokens <= limit:
            return tokens * (context_tokens + (tokens + 1) / 2)
        if context_tokens + 1 >= limit:
            return tokens * limit
        under = limit - context_tokens - 1        # queries under the limit
        return under * (context_tokens + (under + 1) / 2) \
            + (tokens - under) * limit

    held = context_tokens + tokens
    return _sum(_outside(c, tokens, 1 if with_head else 0, True),
                dsa_index(c, tokens, pairs(), held),
                dsa_select(c, pairs(), pairs(d["topk"])),
                dsa_attend(c, tokens, pairs(d["topk"]), expanded=held),
                moe_route(c, tokens), _routed(c, tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "glm_moe_dsa serves only: at 16 B a parameter no cut of this "
        "model that keeps the floors fits a chip")
