"""Operations and bytes a call of ZAYA1 (`zaya`) NEEDS, from shapes
alone: the yardstick of every roofline share the benchmark prints for
it.  A configuration is the dict of its file (the catalog's key names).

Counted as needed: every weight outside the experts read once a call in
the served type (bf16 matrices; the router, the convolutions' taps and
biases, the temperatures, the norms and the residual scales float32); of
the experts the EXPECTED NUMBER OF DISTINCT EXPERTS that the call's
tokens choose, `E x (1 - (1 - k / E)^tokens)`, whatever implements the
layer; every (token, expert) pair (all experts are held); the keys and
values of the whole context once a call in EVERY layer (2 heads x (128 +
128) x 2 B = 1,024 B a token and layer); a decode row's three tails read
and written once a layer.  A score costs 2 x 128 operations and a
weighed value as many.  NOT counted: tiles of the grouped matmul past a
group's rows, blocks copied past a row's position, float32 temporaries
(a tick's [rows, vocabulary] logits among them), the tied head on the
positions of a chunk whose logits nobody reads.

One function per kernel, named as the program's `named_scope`s
(`cca_mix`, `attn_latent`, `moe_route`, `moe_experts`); `decode_tick`
and `prefill_chunk` sum them with what lies outside the scopes (the
output projection, the head, the embedding's rows).
"""

from __future__ import annotations

from typing import Dict

from benchmarks.lib.costs import BF16

F32 = 4


def dims(c: Dict) -> Dict[str, int]:
    return {"L": c["num_hidden_layers"], "D": c["hidden_size"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "d": c["head_dim"], "F": c["moe_intermediate_size"],
            "E": c["num_experts"], "k": c["num_experts_per_tok"],
            "R": c["router_hidden_size"], "V": c["vocab_size"]}


def _latent(c: Dict) -> int:
    """Channels the convolutions mix: query heads + key heads."""
    d = dims(c)
    return (d["H"] + d["G"]) * d["d"]


def attention_params(c: Dict) -> int:
    """W_q, W_k, W_v1, W_v2 and W_o of one layer."""
    d = dims(c)
    return d["D"] * (_latent(c) + d["G"] * d["d"]) + d["H"] * d["d"] * d["D"]


def conv_params(c: Dict) -> Dict[str, int]:
    """One layer's two convolutions: the second one's taps are matrices
    (bf16), the rest float32."""
    d = dims(c)
    return {"bf16": 2 * (d["H"] + d["G"]) * d["d"] * d["d"],
            "f32": 2 * _latent(c) + 2 * _latent(c)}


def router_params(c: Dict, layer: int = 1) -> int:
    """W_d, b_d, the carry's gamma (not in layer 0), the norm, two hidden
    layers with biases, the last layer and the balancing bias: float32."""
    d = dims(c)
    R = d["R"]
    return d["D"] * R + R + (R if layer else 0) + R + 2 * (R * R + R) \
        + R * d["E"] + d["E"]


def _router_matmul(c: Dict) -> int:
    d = dims(c)
    return d["D"] * d["R"] + 2 * d["R"] * d["R"] + d["R"] * d["E"]


def expert_params(c: Dict) -> int:
    d = dims(c)
    return 3 * d["D"] * d["F"]


def _small_params(c: Dict) -> int:
    """Float32 outside the router, all layers: two norms, two sets of
    four residual vectors and the temperatures a layer, the
    convolutions' taps and biases, the last norm."""
    d = dims(c)
    return d["L"] * (2 * d["D"] + 8 * d["D"] + d["G"]
                     + conv_params(c)["f32"]) + d["D"]


def _routers(c: Dict) -> int:
    return sum(router_params(c, l) for l in range(dims(c)["L"]))


def matmul_params(c: Dict) -> int:
    """Resident parameters that sit in a matmul; the tied embedding is
    the head's matrix."""
    d = dims(c)
    return d["L"] * (attention_params(c) + conv_params(c)["bf16"]
                     + _router_matmul(c) + d["E"] * expert_params(c)) \
        + d["D"] * d["V"]


def total_params(c: Dict) -> int:
    d = dims(c)
    return d["L"] * (attention_params(c) + conv_params(c)["bf16"]
                     + d["E"] * expert_params(c)) + d["D"] * d["V"] \
        + _routers(c) + _small_params(c)


def weight_bytes(c: Dict) -> int:
    """Resident weights as served: bf16; the router and the small
    vectors float32; the embedding held once."""
    f32 = _routers(c) + _small_params(c)
    return (total_params(c) - f32) * BF16 + f32 * F32


def token_layer_bytes(c: Dict) -> int:
    """A token's keys and values in one layer's pages."""
    d = dims(c)
    return 2 * d["G"] * d["d"] * BF16


def kv_bytes_per_token(c: Dict) -> int:
    """What a cached token occupies in pages: every layer's."""
    return dims(c)["L"] * token_layer_bytes(c)


def row_state_bytes_per_row(c: Dict) -> int:
    """What a decode row holds beside its pages, whatever its context:
    the latents before and after the first convolution and the late
    value head of its last token, in every layer."""
    d = dims(c)
    return d["L"] * (2 * _latent(c) + d["d"]) * BF16


def experts_touched(c: Dict, tokens: float) -> float:
    """Expected distinct experts among the choices of `tokens` tokens,
    each choosing k of E."""
    d = dims(c)
    return d["E"] * (1.0 - (1.0 - d["k"] / d["E"]) ** tokens)


def _sum(*parts: Dict) -> Dict:
    return {"flops": sum(p["flops"] for p in parts),
            "bytes": sum(p["bytes"] for p in parts)}


# -- the kernels ------------------------------------------------------


def cca_mix(c: Dict, tokens: float, rows: float = 0) -> Dict:
    """Projections into the latent, both convolutions, the mean, the norm
    and the rotation of every layer, for `tokens` tokens: the weights
    once, the stream in, a token's keys and values out, and the tails of
    `rows` decode rows read and written (a chunk: one row)."""
    d = dims(c)
    proj = d["D"] * (_latent(c) + d["G"] * d["d"])
    conv = conv_params(c)
    return {"flops": d["L"] * 2 * tokens * (proj + conv["bf16"]),
            "bytes": d["L"] * ((proj + conv["bf16"]) * BF16
                               + (conv["f32"] + d["G"] + d["D"]) * F32
                               + tokens * (d["D"] * BF16
                                           + token_layer_bytes(c)))
            + 2 * rows * row_state_bytes_per_row(c)}


def _attend(c: Dict, pairs: float, keys: float) -> Dict:
    """`pairs` (query, key) pairs scored and weighed by every head, and
    `keys` keys and values read, in each layer."""
    d = dims(c)
    return {"flops": d["L"] * 2 * d["H"] * 2 * d["d"] * pairs,
            "bytes": d["L"] * token_layer_bytes(c) * keys}


def attn_latent(c: Dict, rows: float, context_tokens: float) -> Dict:
    """A tick's attention: every row's whole context and its own token."""
    keys = context_tokens + rows
    return _attend(c, keys, keys)


def attn_latent_chunk(c: Dict, tokens: float, context_tokens: float) -> Dict:
    """A chunk's attention: each query over the context and the chunk's
    tokens up to itself."""
    return _attend(c, tokens * (context_tokens + (tokens + 1) / 2),
                   context_tokens + tokens)


def moe_route(c: Dict, tokens: float) -> Dict:
    """The router MLP of every layer, float32: its weights once, the
    normed stream in, the state read and written."""
    d = dims(c)
    return {"flops": d["L"] * 2 * _router_matmul(c) * tokens,
            "bytes": _routers(c) * F32
            + d["L"] * tokens * (d["D"] * BF16 + 2 * d["R"] * F32
                                 + d["E"] * F32)}


def moe_experts(c: Dict, tokens: float) -> Dict:
    """The experts of every layer: every pair, the distinct chosen
    experts' weights once, a pair's input and output rows."""
    d = dims(c)
    pairs = tokens * d["k"]
    return {"flops": d["L"] * 2 * expert_params(c) * pairs,
            "bytes": d["L"] * (experts_touched(c, tokens)
                               * expert_params(c) * BF16
                               + pairs * 2 * d["D"] * BF16)}


# -- the two programs -------------------------------------------------


def _outside(c: Dict, tokens: float, head_tokens: float) -> Dict:
    """What no scope holds: the embedding's rows, the output projection,
    the norms and residual scales, and the tied head on `head_tokens`
    positions."""
    d = dims(c)
    wo = d["H"] * d["d"] * d["D"]
    head = d["D"] * d["V"] if head_tokens else 0
    return {"flops": 2 * (d["L"] * wo * tokens + head * head_tokens),
            "bytes": (d["L"] * wo + head) * BF16
            + (d["L"] * 9 * d["D"] + d["D"]) * F32
            + tokens * d["D"] * BF16}


def decode_tick(c: Dict, rows: float, context_tokens: float) -> Dict:
    """One decode tick: `rows` active rows, each emitting one token,
    holding `context_tokens` tokens of context in total."""
    if not rows:
        return {"flops": 0, "bytes": (matmul_params(c) - dims(c)["L"]
                                      * dims(c)["E"] * expert_params(c))
                * BF16}
    return _sum(_outside(c, rows, rows), cca_mix(c, rows, rows),
                attn_latent(c, rows, context_tokens), moe_route(c, rows),
                moe_experts(c, rows))


def prefill_chunk(c: Dict, tokens: int, context_tokens: float,
                  with_head: bool) -> Dict:
    """One single-row prefill chunk of `tokens` tokens after
    `context_tokens` earlier ones.  The tied head is needed only by a
    prompt's last chunk (`with_head`), for one position."""
    return _sum(_outside(c, tokens, 1 if with_head else 0),
                cca_mix(c, tokens, 1),
                attn_latent_chunk(c, tokens, context_tokens),
                moe_route(c, tokens), moe_experts(c, tokens))


def train_flops_per_token(c: Dict, seq: int) -> float:
    raise NotImplementedError(
        "zaya serves only: at 16 B a parameter even the floors of this "
        "model's cut (0.69 B parameters, 11.1 GB) leave a 16 GB chip "
        "under 5 GB for activations, and the repo's train step is "
        "written for the dense decoder alone")
