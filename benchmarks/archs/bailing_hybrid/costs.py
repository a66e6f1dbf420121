"""Operations and bytes a call of Ling-3.0-flash (`bailing_hybrid`)
NEEDS, from shapes alone: the yardstick of every roofline share the
benchmark prints for it.  A configuration is the dict of its file (the
catalog's key names; `num_experts` is the count HELD here, `published`
the router's).

Counted as needed: every weight outside the routed experts read once a
call in the served type (bf16 matrices; the router, its bias, the
convolution's taps, the decay's `A_log` and `dt_bias` and the norms
float32); of the routed experts the EXPECTED NUMBER OF DISTINCT HELD
EXPERTS that the call's tokens choose under the published top-k of the
published count, `held x (1 - (1 - k / E)^tokens)`, whatever implements
the layer; of the routed (token, expert) pairs the share whose expert is
held, `k x held / E` a token; a KDA layer's state ONCE IN AND ONCE OUT
for every row a call moves (32 x 128 x 128 x 4 B = 2 MiB a row and
layer: a tick's rows, a chunk's one) and the delta rule AS THE
RECURRENCE states it (per token and head: the decay d^2, two reads of
the state 2 x 2 d^2, the write 2 d^2 = 7 d^2), whatever chunked form
computes it; the convolution's tails once in and once out; an MLA
layer's cached latents of the context once a call (1,280 B a token as
they lie: 512 + 64 bf16 numbers and the 64 that pad them to the chip's
tile, which the one-array pool makes real bytes), a tick's attention in
the ABSORBED form, a chunk's in the EXPANDED form.  NOT counted: tiles
of the grouped matmul past a group's rows, blocks copied past a row's
position, the state of rows a step touches and does not move, the
chunked form's triangular solve and pairwise decays, float32
temporaries, the output head on the positions of a chunk whose logits
nobody reads.

One function per kernel, named as the program's `named_scope`s
(`kda_conv`, `kda_step`, `kda_chunk`, `mla_absorb_attend`,
`mla_expand_attend`, `moe_route`, `moe_experts`); `decode_tick` and
`prefill_chunk` sum them with what lies outside the scopes (the
projections, the gates, the dense and shared feed-forwards, the head,
the embedding's rows).
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4
TILE = 128      # lanes of the chip's tile: a minor dimension pads to it


def dims(c: Dict) -> Dict[str, int]:
    L, first = c["num_hidden_layers"], c.get("layer_offset", 0)
    mla = sum((first + i + 1) % c["layer_group_size"] == 0
              for i in range(L))
    dense = c["first_k_dense_replace"]
    H, d = c["num_attention_heads"], c["head_dim"]
    return {"L": L, "La": mla, "Lk": L - mla, "Ld": dense, "Lm": L - dense,
            "D": c["hidden_size"], "H": H, "d": d, "Ek": H * d,
            "K": c["short_conv_kernel_size"], "kr": c["kv_lora_rank"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "F": c["intermediate_size"],
            "Fm": c["moe_intermediate_size"],
            "Fs": c["num_shared_experts"]
            * c["moe_shared_expert_intermediate_size"],
            "held": c["num_experts"], "E": c["published"]["num_experts"],
            "k": c["num_experts_per_tok"], "V": c["vocab_size"]}


def kda_params(c: Dict) -> Dict[str, int]:
    """One KDA mixer: q, k, v, the decay gate, the output gate and the
    output projection at D x 4096 each and beta's D x 32 (bf16); the
    three streams' taps, A_log, dt_bias and the heads' norm (float32)."""
    d = dims(c)
    return {"bf16": 6 * d["D"] * d["Ek"] + d["D"] * d["H"],
            "f32": d["K"] * 3 * d["Ek"] + d["H"] + d["Ek"] + d["d"]}


def mla_params(c: Dict) -> Dict[str, int]:
    """One MLA mixer: the query's one projection, kv_a, kv_b (both
    halves), the gate a head and o (bf16); the latent's norm (float32)."""
    d = dims(c)
    return {"bf16": d["D"] * d["H"] * (d["dn"] + d["dr"])
            + d["D"] * (d["kr"] + d["dr"])
            + d["kr"] * d["H"] * (d["dn"] + d["dv"]) + d["D"] * d["H"]
            + d["H"] * d["dv"] * d["D"],
            "f32": d["kr"]}


def expert_params(c: Dict) -> int:
    """One routed expert (SwiGLU: gate, up, down)."""
    d = dims(c)
    return 3 * d["D"] * d["Fm"]


def _f32_params(c: Dict) -> int:
    """Everything served in float32: the mixers' small vectors, two
    norms a layer, the routers and their biases, the last norm."""
    d = dims(c)
    return d["Lk"] * kda_params(c)["f32"] + d["La"] * mla_params(c)["f32"] \
        + d["L"] * 2 * d["D"] + d["Lm"] * (d["D"] * d["E"] + d["E"]) \
        + d["D"]


def fixed_matmul_params(c: Dict, with_head: bool = True) -> int:
    """bf16 parameters every call reads whatever it routes: the mixers,
    the dense and shared feed-forwards and, `with_head`, the head."""
    d = dims(c)
    return d["Lk"] * kda_params(c)["bf16"] + d["La"] * mla_params(c)["bf16"] \
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
    """What a cached token occupies in pages: one latent row of 512 +
    64 up to whole tiles (640 numbers) in each MLA layer."""
    d = dims(c)
    return d["La"] * -(-(d["kr"] + d["dr"]) // TILE) * TILE * BF16


def state_bytes_per_row(c: Dict) -> int:
    """What a decode row holds beside its pages, whatever its context:
    the delta-rule state (float32) and the last K - 1 inputs of the
    three convolved streams (bf16), in every KDA layer."""
    d = dims(c)
    return d["Lk"] * (d["H"] * d["d"] * d["d"] * F32
                      + (d["K"] - 1) * 3 * d["Ek"] * BF16)


def experts_touched(c: Dict, tokens: float) -> float:
    """Expected distinct HELD experts among the choices of `tokens`
    tokens, each choosing k of the published E."""
    d = dims(c)
    return d["held"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the kernels ------------------------------------------------------


def kda_conv(c: Dict, tokens: float, rows: float) -> Dict:
    """The three streams' convolution and SiLU in every KDA layer: the
    taps once, the streams in (bf16) and out (float32), the tails of
    `rows` decode rows read and written (a chunk: one row)."""
    d = dims(c)
    width = 3 * d["Ek"]
    return {"flops": d["Lk"] * 2 * d["K"] * width * tokens,
            "bytes": d["Lk"] * (d["K"] * width * F32
                                + tokens * width * (BF16 + F32)
                                + 2 * rows * (d["K"] - 1) * width * BF16)}


def _delta_rule(c: Dict, tokens: float, rows: float) -> Dict:
    """`tokens` tokens of the recurrence over the states of `rows` rows,
    in every KDA layer: the state once in and once out; q, k, v and the
    log-decay in, o out (float32), beta."""
    d = dims(c)
    state = d["H"] * d["d"] * d["d"] * F32
    return {"flops": d["Lk"] * 7 * d["H"] * d["d"] * d["d"] * tokens,
            "bytes": d["Lk"] * (2 * rows * state
                                + tokens * (5 * d["Ek"] + d["H"]) * F32)}


def kda_step(c: Dict, rows: float) -> Dict:
    """A tick's step: one token of each of `rows` rows."""
    return _delta_rule(c, rows, rows)


def kda_chunk(c: Dict, tokens: float) -> Dict:
    """A chunk: `tokens` tokens of one row."""
    return _delta_rule(c, tokens, 1)


def mla_absorb_attend(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention in every MLA layer, absorbed: per query, head
    and key (kr + dr) to score and kr to weigh (the query into the
    latent space and the output out of it are wk_b and wv_b, in the
    weights); the context's latents read once."""
    d = dims(c)
    keys = context_tokens + rows
    return {"flops": d["La"] * 2 * d["H"] * (2 * d["kr"] + d["dr"]) * keys,
            "bytes": kv_bytes_per_token(c) * keys}


def mla_expand_attend(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention in every MLA layer, expanded: k_nope and v of
    every key it holds formed once from the latents, then (dn + dr) to
    score and dv to weigh per query, head and causal key."""
    d = dims(c)
    held = context_tokens + tokens
    attended = tokens * (context_tokens + (tokens + 1) / 2)
    return {"flops": d["La"] * 2 * d["H"] * (
                held * d["kr"] * (d["dn"] + d["dv"])
                + attended * (d["dn"] + d["dr"] + d["dv"])),
            "bytes": kv_bytes_per_token(c) * held}


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
    """What no kernel's function holds: every fixed matrix applied to
    `tokens` tokens (the head to `head_tokens`), the float32 vectors,
    the embedding's rows in and the latent rows out.  A chunk applies
    wk_b / wv_b to keys, not to queries (`skip_kv_b`):
    mla_expand_attend counts their use."""
    d = dims(c)
    body = fixed_matmul_params(c, with_head=False)
    head = d["D"] * d["V"] if head_tokens else 0
    kv_b = d["La"] * d["kr"] * d["H"] * (d["dn"] + d["dv"]) \
        if skip_kv_b else 0
    small = _f32_params(c) - d["Lm"] * (d["D"] + 1) * d["E"] \
        - d["Lk"] * d["K"] * 3 * d["Ek"]
    return {"flops": 2 * ((body - kv_b) * tokens + head * head_tokens),
            "bytes": (body + head) * BF16 + small * F32
            + tokens * (d["D"] * BF16 + kv_bytes_per_token(c))}


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` cached tokens in total."""
    if not rows:
        return {"flops": 0, "bytes": fixed_matmul_params(c) * BF16}
    return _sum(_outside(c, rows, rows, False), kda_conv(c, rows, rows),
                kda_step(c, rows),
                mla_absorb_attend(c, rows, context_tokens),
                moe_route(c, rows), _routed(c, rows))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The output head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    return _sum(_outside(c, tokens, 1 if with_head else 0, True),
                kda_conv(c, tokens, 1), kda_chunk(c, tokens),
                mla_expand_attend(c, tokens, context_tokens),
                moe_route(c, tokens), _routed(c, tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "bailing_hybrid serves only: at 16 B a parameter no cut of this "
        "model that keeps the floors fits a chip, and the delta rule has "
        "no backward pass here")
