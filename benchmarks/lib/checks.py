"""The comparison that decides a serving cell's `correct`, in the pieces
an architecture's own procedure reuses.

`replica.probe_check_logits` runs `arch.check_logits` where the
configuration's architecture module defines one and `default` where it
does not, always as `(engine, seed, prompt_len, n_decode, config,
reference)`, inside the process that holds the chip.  What a
`check_logits` owes the harness, (a) to (d), is written where an
architecture's author reads it: `archs/llama/__init__.py`.

`hold` is the contract by name; a result that lacks a key raises there
and never reads as `correct: false`.  jax is imported inside functions:
the driver process imports `benchmarks.lib` and starts no backend.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

COMPARED = ("positions", "prefill_positions", "decode_positions", "finite",
            "max_abs_diff", "max_abs_diff_prefill", "max_abs_diff_decode",
            "mean_abs_diff", "reference_logit_std", "argmax_equal")


def seeded_prompt(seed: int, vocab: int, n: int) -> np.ndarray:
    """The check's `n` prompt tokens of `seed`, in 1..vocab-1."""
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    return rng.integers(1, vocab, size=n)


def program_name(jitted: Callable) -> str:
    """A jitted function's name as a device trace shows its program."""
    return "jit_" + jitted.__name__


@contextlib.contextmanager
def borrowed_pages(engine, n_tokens: int):
    """Row 0's block-table row over pages for `n_tokens`, taken from the
    idle engine's allocator (the prefix cache evicted as admission
    would) and given back on the way out.  Worker thread only."""
    if any(r is not None for r in engine._slots):
        raise RuntimeError("the engine is not idle")
    n_pages = -(-n_tokens // engine.page_size)
    pages = engine._alloc.alloc(n_pages)
    if pages is None and engine._prefix is not None:
        engine._prefix.evict(n_pages)      # as admission would
        pages = engine._alloc.alloc(n_pages)
    if pages is None:
        raise RuntimeError("no free pages for the logits check")
    try:
        bt_row = np.zeros((engine._max_blocks,), np.int32)
        bt_row[:n_pages] = pages
        yield bt_row
    finally:
        for p in pages:
            engine._alloc.decref(p)


def prefill(engine, prompt: Sequence[int], bt_row: np.ndarray
            ) -> List[np.ndarray]:
    """`prompt` through `engine._prefill_chunk`, chunk by chunk at the
    engine's width into `bt_row`'s pages; each chunk's real rows of
    logits."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import engine as engine_mod

    rows = []
    for start in range(0, len(prompt), engine.prefill_chunk):
        width = min(engine.prefill_chunk, engine._s_virt - start)
        real = prompt[start:start + width]
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :len(real)] = real
        logits, engine._cache = engine_mod._prefill_chunk(
            engine.params, jnp.asarray(chunk), jnp.int32(start),
            engine._cache, jnp.asarray(bt_row[None, :]), engine.cfg)
        rows.append(np.asarray(logits[0, :len(real)]))
    return rows


def tick_by_tick(engine, first_token: int, prompt_len: int, n_decode: int,
                 bt_row: np.ndarray) -> Tuple[List[np.ndarray], List[int]]:
    """`n_decode` greedy calls of `engine._paged_tick` at the engine's
    batch, row 0 alone live, from `first_token` at position
    `prompt_len`: each call's row of logits `[1, V]`, and every token
    (the first, then what each call chose; the first `n_decode` were
    fed)."""
    import jax.numpy as jnp

    from ray_tpu.serve.llm import engine as engine_mod

    rows, tokens = [], [int(first_token)]
    bt = np.zeros_like(engine._block_tables)
    bt[0] = bt_row
    pos = np.zeros((engine.num_slots,), np.int32)
    tok = np.zeros((engine.num_slots,), np.int32)
    for i in range(n_decode):
        pos[0], tok[0] = prompt_len + i, tokens[-1]
        sampled, logits, engine._cache = engine_mod._paged_tick(
            engine.params, jnp.asarray(tok), jnp.asarray(pos),
            engine._cache, jnp.asarray(bt), engine.cfg, with_logits=True)
        rows.append(np.asarray(logits[:1]))
        tokens.append(int(np.asarray(sampled)[0]))
    return rows, tokens


def full_forward(reference: Callable, params, tokens: Sequence[int],
                 config: Dict[str, Any]) -> np.ndarray:
    """One jitted forward of the plain reference over `tokens`: logits
    `[T, V]`, beside the replica on its device."""
    import jax
    import jax.numpy as jnp

    return np.asarray(jax.jit(
        lambda params, tokens: reference(params, tokens, config))(
            params, jnp.asarray(np.asarray(tokens, np.int32))))


def compare(got: np.ndarray, ref: np.ndarray, prefill_rows: int
            ) -> Dict[str, Any]:
    """What `correct` is decided from.  `got` and `ref` are rows of
    logits `[n, V]`, the first `prefill_rows` of them the prompt's and
    the rest the decode's; `positions` counts rows compared (a
    procedure that compares a position at several of its steps hands
    in a row a step)."""
    if got.shape != ref.shape or not 0 <= prefill_rows <= len(got):
        raise ValueError(f"rows {got.shape} against {ref.shape}, "
                         f"{prefill_rows} of them the prompt's")
    diff = np.abs(got - ref)
    return {"positions": int(len(got)),
            "prefill_positions": int(prefill_rows),
            "decode_positions": int(len(got) - prefill_rows),
            "finite": bool(np.isfinite(got).all()
                           and np.isfinite(ref).all()),
            "max_abs_diff": float(diff.max()),
            "max_abs_diff_prefill": float(diff[:prefill_rows].max()),
            "max_abs_diff_decode": float(diff[prefill_rows:].max()),
            "mean_abs_diff": float(diff.mean()),
            "reference_logit_std": float(ref.std()),
            "argmax_equal": int((got.argmax(-1) == ref.argmax(-1)).sum())}


def default(engine, seed: int, prompt_len: int, n_decode: int,
            config: Dict[str, Any], reference: Callable) -> Dict[str, Any]:
    """Prefill `prompt_len` seeded tokens chunk by chunk, then decode
    `n_decode` greedy tokens tick by tick, one token a row a tick;
    compare every position's logits with one full forward of the
    reference over the same tokens."""
    from ray_tpu.serve.llm import engine as engine_mod

    prompt = seeded_prompt(seed, engine.cfg.vocab_size, prompt_len)

    def through_the_engine():
        with borrowed_pages(engine, prompt_len + n_decode) as bt_row:
            rows = prefill(engine, prompt, bt_row)
            ticks, tokens = tick_by_tick(
                engine, int(rows[-1][-1].argmax()), prompt_len, n_decode,
                bt_row)
            return np.concatenate(rows + ticks), tokens

    got, tokens = engine.run_on_worker(through_the_engine, timeout=900.0)
    ref = full_forward(reference, engine.params,
                       np.concatenate([prompt, tokens[:n_decode]]), config)
    return {**compare(got, ref, prompt_len),
            "programs": [program_name(engine_mod._prefill_chunk),
                         program_name(engine_mod._paged_tick)]}


def hold(result: Any, procedure: Callable, arch: str) -> Dict[str, Any]:
    """`result` held to the contract by name, and stamped with the
    procedure that gave it."""
    name = f"{__name__}.default" if procedure is default \
        else f"archs.{arch}.{procedure.__qualname__}"
    who = f"{name} (architecture {arch!r})"
    if not isinstance(result, dict):
        raise TypeError(f"{who} returned {type(result).__name__}, not "
                        f"checks.compare's dictionary")
    for key in COMPARED + ("programs",):
        if key not in result:
            raise KeyError(f"{who} returned no {key!r}: a check_logits "
                           f"returns checks.compare's dictionary and "
                           f"`programs`")
    if result["positions"] != (result["prefill_positions"]
                               + result["decode_positions"]) \
            or result["decode_positions"] <= 0:
        raise ValueError(
            f"{who}: positions {result['positions']} of which "
            f"{result['prefill_positions']} prefill and "
            f"{result['decode_positions']} decode")
    if not result["programs"]:
        raise ValueError(f"{who} names no program")
    return {**result, "procedure": name}
