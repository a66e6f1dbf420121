"""The architecture `sdar_moe`: SDAR-MoE's decoder (a Qwen3-MoE layer:
GQA with q/k norm and RoPE, a softmax router over routed SwiGLU experts
whose top-k weights are renormalised, every expert held here) that
GENERATES BY DIFFUSION OVER BLOCKS: the mask is causal between blocks of
`assumed.block_length` positions and full inside one, the logits at a
position are of the token standing there, and a decode step is a block
of columns a row of which the engine fixes one position a step, as
`ray_tpu.models.sdar_moe` and the engine's `_paged_block_step` run it.
It serves only: no `param_specs`, `make_train_step` or `batch_axes`.

What the harness asks an architecture for is listed in
`archs/llama/__init__.py`.  This one brings its own `check_logits` (the
default's is a token a row a tick).  Every function imports jax inside
itself: the driver loads this module for the yardstick alone and must
not start a backend.  The module refuses to load, by name, on a program
that lacks the model: a parent commit fails in the driver, at once.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Callable, Dict

if importlib.util.find_spec("ray_tpu.models.sdar_moe") is None:
    raise ImportError(
        "the architecture 'sdar_moe' needs ray_tpu.models.sdar_moe, which "
        "this checkout of the program does not have")

from . import reference as _reference  # noqa: E402
from .costs import (attention_params, attn_global,  # noqa: E402,F401
                    block_attn, block_step, decode_tick, expert_params,
                    experts_touched, kv_bytes_per_token, matmul_params,
                    moe_experts, moe_route, prefill_chunk,
                    token_layer_bytes, total_params, train_flops_per_token,
                    weight_bytes)

reference = _reference.forward


def build(c: Dict[str, Any], max_seq: int, remat: bool):
    """`remat` is a training option: this architecture serves only."""
    import jax.numpy as jnp

    from ray_tpu.models import sdar_moe

    if not c["norm_topk_prob"] or c["decoder_sparse_step"] != 1 \
            or c["mlp_only_layers"]:
        raise ValueError("the model is written for an expert layer in "
                         "every layer and top-k weights renormalised "
                         "over the chosen")
    if c["attention_bias"] or c["rope_scaling"] or c["sliding_window"] \
            or c["use_sliding_window"] or c["tie_word_embeddings"]:
        raise ValueError("no projection has a bias, RoPE is not scaled, "
                         "no layer has a window and the head is untied")
    a = c["assumed"]
    if a["denoising_steps"] != a["block_length"] \
            or a["remasking"] != "low_confidence_static":
        raise ValueError("the engine's schedule is static: one position "
                         "a step, the most confident of those masked")
    return sdar_moe.SdarMoeConfig(
        max_seq=max_seq, n_layers=c["num_hidden_layers"],
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        moe_d_ff=c["moe_intermediate_size"],
        n_routed_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["rms_norm_eps"]),
        block_length=int(a["block_length"]),
        mask_token_id=int(a["mask_token_id"]),
        dtype=getattr(jnp, c["torch_dtype"]))


# Seeded q . k / sqrt(head_dim) has this standard deviation (1 with the
# q and k norms' weights at one: attention over thousands of keys is
# then near uniform, and a mask that was causal inside a block, or a
# block whose final keys were never written, moves no logit a
# comparison could see: the trap PR 28 found in MiniCPM-SALA's seeded
# attention).  At 4 a handful of keys hold most of a head's weight, as
# in a trained model.
SEEDED_ATTN_LOGIT_STD = 4.0


def init(cfg, key, dtype):
    """The program's own seeded weights (one traced function, drawn
    directly in `dtype`), with the q and k norms scaled so that seeded
    attention is peaked: a normed q and k have unit components, so
    q . k / sqrt(head_dim) has a standard deviation of 1, and of
    `SEEDED_ATTN_LOGIT_STD` with both norms' weights at its square
    root.  A test holds everything else equal to
    `sdar_moe.init_params`."""
    from ray_tpu.models import sdar_moe
    params = sdar_moe.init_params(cfg, key, dtype)
    gain = SEEDED_ATTN_LOGIT_STD ** 0.5
    return dict(params, layers=tuple(
        dict(lp, qn=lp["qn"] * gain, kn=lp["kn"] * gain)
        for lp in params["layers"]))


# -- the logits check ---------------------------------------------------


def prefill(engine, prompt, bt_row):
    """`prompt` through `engine._prefill_chunk`, chunk by chunk at the
    engine's width into `bt_row`'s pages, each chunk told how many of
    its tokens are real (`valid`), as admission tells it; each chunk's
    real rows of logits."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import engine as engine_mod

    rows = []
    for start in range(0, len(prompt), engine.prefill_chunk):
        width = min(engine.prefill_chunk, engine._s_virt - start)
        real = prompt[start:start + width]
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :len(real)] = real
        logits, engine._cache = engine_mod._prefill_chunk(
            engine.params, jnp.asarray(chunk), jnp.int32(start),
            engine._cache, jnp.asarray(bt_row[None, :]), engine.cfg,
            **engine._row_args(0, len(real)))
        rows.append(np.asarray(logits[0, :len(real)]))
    return rows


def block_by_block(engine, prompt, n_blocks: int, bt_row,
                   skip_writing: bool = False):
    """`n_blocks` blocks of row 0 through `engine._paged_block_step` at
    the engine's width, the other rows idle, under the engine's own
    schedule: the first block opens with the prompt's last `L mod B`
    tokens fixed; a block is stepped until nothing is masked, then once
    more (the forward that writes its final keys).  Returns a list, one
    entry a forward: (the final tokens before the block, the block as
    the step saw it [B], the step's logits [B, V]).  `skip_writing`
    leaves the writing forward out: a control for tools/sdar_limits.py
    and the tests, never the check."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import engine as engine_mod

    S, B = engine.num_slots, engine._block
    mask_id = engine._body.mask_token
    bt = np.zeros_like(engine._block_tables)
    bt[0] = bt_row
    pos = np.zeros((S,), np.int32)
    host_tok = np.zeros((S, B), np.int32)
    host_masked = np.zeros((S, B), bool)
    take = np.zeros((S,), bool)
    take[0] = True
    idle = (jnp.zeros((S, B), jnp.int32), jnp.zeros((S, B), bool))
    start = len(prompt) // B * B
    fixed = len(prompt) - start
    cur = np.full((B,), mask_id, np.int32)
    cur[:fixed] = prompt[start:]
    masked = np.arange(B) >= fixed
    prefix = [int(t) for t in prompt[:start]]
    out = []
    for _ in range(n_blocks):
        while True:
            writing = not masked.any()
            if writing and skip_writing:
                break
            pos[0], host_tok[0], host_masked[0] = start, cur, masked
            tokens, left, logits, engine._cache = \
                engine_mod._paged_block_step(
                    engine.params, *idle, jnp.asarray(host_tok),
                    jnp.asarray(host_masked), jnp.asarray(take),
                    jnp.asarray(pos), engine._cache, jnp.asarray(bt),
                    engine.cfg, with_logits=True)
            out.append((list(prefix), cur.copy(), np.asarray(logits[0])))
            cur, masked = np.asarray(tokens[0]), np.asarray(left[0])
            if writing:
                break
        prefix += [int(t) for t in cur]
        start += B
        cur, masked = np.full((B,), mask_id, np.int32), np.ones((B,), bool)
    return out


def reference_rows(params, c, prompt, forwards, head_rows: int = 256,
                   round_to=None):
    """The plain reference's logits for what `prefill` and
    `block_by_block` produced: the prompt's positions from one forward
    of the prompt, then each forward's B positions from one forward of
    the final prefix + the block as that step saw it (padded behind the
    block to one length: a later block is seen by no earlier position).
    The head is taken `head_rows` positions at a time.  `round_to` is
    tools/sdar_limits.py's (the reference in a lower precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = _reference
    B = ref.block_length(c)
    hidden = jax.jit(lambda p, t: ref.hidden(p, t, c, round_to=round_to))
    head = jax.jit(lambda p, x: ref.head(p, x, round_to))
    x = hidden(params, jnp.asarray(np.asarray(prompt, np.int32)))
    rows = [np.asarray(head(params, x[i:i + head_rows]))
            for i in range(0, len(prompt), head_rows)]
    longest = max((len(pre) for pre, _, _ in forwards), default=0) + B
    for pre, block, _ in forwards:
        seq = np.zeros((longest,), np.int32)
        seq[:len(pre)] = pre
        seq[len(pre):len(pre) + B] = block
        x = hidden(params, jnp.asarray(seq))
        rows.append(np.asarray(head(params, x[len(pre):len(pre) + B])))
    return np.concatenate(rows)


def sequences(seed: int, vocab: int, prompt_len: int, n_decode: int,
              c: Dict[str, Any]):
    """The check's sequences, [(prompt, blocks)]: `prompt_len` seeded
    tokens and `n_decode` blocks after them and, where the
    configuration's check has a `short` entry, a second, short prompt
    followed by many blocks.  The first holds the chunks to the
    reference over a deep context; in the second nearly every key a
    block reads was written by a block step, so a step that left a
    block's final keys unwritten moves the logits of every later block
    (behind 2,046 prompt tokens one such key in 2,050 moves them by less
    than the served type's rounding does: tools/sdar_limits.py)."""
    from benchmarks.lib import checks

    out = [(checks.seeded_prompt(seed, vocab, prompt_len), n_decode)]
    short = c["serving"]["check"].get("short")
    if short:
        out.append((checks.seeded_prompt(seed + 1, vocab,
                                         short["prompt_len"]),
                    short["blocks"]))
    return out


def prompts_first(parts, prompt_lens):
    """Rows of several sequences, each its prompt's rows and then its
    forwards', as one array with every prompt's rows first: the order
    `checks.compare` takes."""
    import numpy as np

    return np.concatenate([p[:n] for p, n in zip(parts, prompt_lens)]
                          + [p[n:] for p, n in zip(parts, prompt_lens)])


def check_logits(engine, seed: int, prompt_len: int, n_decode: int,
                 c: Dict[str, Any], reference: Callable) -> Dict[str, Any]:
    """The serving cell's logits check for a block body, under the
    contract (a)-(d) of `archs/llama/__init__.py`: each of `sequences`
    through `engine._prefill_chunk` with `valid`, then its BLOCKS through
    `engine._paged_block_step` at the engine's width; the logits of
    every forward, denoising steps (masks standing in the input) and
    writing forwards alike, are compared with the reference's forward
    of the same tokens, teacher-forced on what the program itself
    fixed.  `reference` is this module's `forward`; the check takes its
    two halves (`hidden`, `head`) so that the head runs a block of
    positions at a time."""
    import numpy as np

    from benchmarks.lib import checks
    from ray_tpu.serve.llm import engine as engine_mod

    B = engine._block
    todo = sequences(seed, engine.cfg.vocab_size, prompt_len, n_decode, c)

    def through_the_engine():
        out = []
        for prompt, blocks in todo:
            with checks.borrowed_pages(
                    engine, len(prompt) // B * B + B * blocks) as bt_row:
                out.append((prefill(engine, prompt, bt_row),
                            block_by_block(engine, prompt, blocks, bt_row)))
        return out

    ran = engine.run_on_worker(through_the_engine, timeout=900.0)
    lens = [len(prompt) for prompt, _ in todo]
    got = prompts_first(
        [np.concatenate(rows + [lg for _, _, lg in forwards])
         for rows, forwards in ran], lens)
    ref = prompts_first(
        [reference_rows(engine.params, c, prompt, forwards)
         for (prompt, _), (_, forwards) in zip(todo, ran)], lens)
    return {**checks.compare(got, ref, sum(lens)),
            "block_forwards": sum(len(forwards) for _, forwards in ran),
            "programs": [checks.program_name(engine_mod._prefill_chunk),
                         checks.program_name(engine_mod._paged_block_step)]}
