"""A third architecture, for the tests alone: `gpt` (the package beside
this file) with a logits check of its own, as an architecture whose
step is not the default's would bring one.  The prompt goes through
`checks.prefill`; the decode loop is this file's and drives another of
the engine's programs, `_paged_verify`, at two columns a row: column 0
is the token, column 1 a draft nobody reads (the next call overwrites
its keys before any unmasked read).  The program hands back column 0's
logits alone, so a call advances one position.  Everything else is
`gpt`'s, found the way the harness finds it.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict

from benchmarks.lib import checks
from benchmarks.lib.registry import find_module

_gpt = find_module("archs", "gpt", (os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))),))
globals().update({name: getattr(_gpt, name) for name in (
    "build", "init", "reference", "layer_matmul_params", "matmul_params",
    "total_params", "kv_bytes_per_token", "decode_tick", "prefill_chunk",
    "train_flops_per_token")})

COLUMNS = 2


def check_logits(engine, seed: int, prompt_len: int, n_decode: int,
                 config: Dict[str, Any], reference: Callable
                 ) -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.serve.llm import engine as engine_mod

    prompt = checks.seeded_prompt(seed, engine.cfg.vocab_size, prompt_len)

    def through_the_engine():
        # the last call's draft column lies one past the last token
        with checks.borrowed_pages(
                engine, prompt_len + n_decode + COLUMNS - 1) as bt_row:
            rows = checks.prefill(engine, prompt, bt_row)
            tokens = [int(rows[-1][-1].argmax())]
            bt = np.zeros_like(engine._block_tables)
            bt[0] = bt_row
            pos = np.zeros((engine.num_slots,), np.int32)
            chunk = np.zeros((engine.num_slots, COLUMNS), np.int32)
            for i in range(n_decode):
                pos[0], chunk[0, :] = prompt_len + i, tokens[-1]
                preds, logits0, engine._cache = engine_mod._paged_verify(
                    engine.params, jnp.asarray(chunk), jnp.asarray(pos),
                    engine._cache, jnp.asarray(bt), engine.cfg,
                    with_logits=True)
                rows.append(np.asarray(logits0[:1]))
                tokens.append(int(np.asarray(preds)[0, 0]))
            return np.concatenate(rows), tokens

    got, tokens = engine.run_on_worker(through_the_engine, timeout=900.0)
    ref = checks.full_forward(
        reference, engine.params,
        np.concatenate([prompt, tokens[:n_decode]]), config)
    return {**checks.compare(got, ref, prompt_len),
            "programs": [checks.program_name(engine_mod._prefill_chunk),
                         checks.program_name(engine_mod._paged_verify)]}
