"""The two programs of `sdar-30b-a3b-pp8-d6` timed outside the engine,
on the chip, at the configuration's sizes: what the configuration's
`num_slots` and `prefill_chunk` were chosen from, and where the step
table of PERF.md section 5 comes from.

    python3 benchmarks/tools/sdar_steps.py --seed 2147498001 \
        [--rows 64,96] [--depth 0.5,2] [--chunk 256,1024]

The state is the cell's: decode rows whose positions are the `blockgen`
mix's prompt lengths plus half an output, rounded down to a block (mean
~1.0k), each row on pages of its own, every row's block half masked; a
chunk is timed after 0 / 1,024 / 3,072 tokens of context.  `--rows`
times the block step at other widths, `--depth` at the cell's rows with
every position scaled so (what attention's share of a step is), and
`--chunk` the chunk at other widths.  Every line is one JSON object,
with the call's least time from `archs/sdar_moe/costs.py` beside it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import traffic  # noqa: E402
from benchmarks.lib.costs import min_time  # noqa: E402
from benchmarks.lib.model import seed_key  # noqa: E402
from benchmarks.lib.peaks import peaks_for  # noqa: E402
from benchmarks.lib.registry import Registry, arch_of  # noqa: E402

STARTS = (0, 1024, 3072)


def say(**row):
    print(json.dumps(row), flush=True)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decode
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="sdar-30b-a3b-pp8-d6")
    p.add_argument("--rows", default="")
    p.add_argument("--depth", default="")
    p.add_argument("--chunk", default="")
    p.add_argument("--calls", type=int, default=12)
    args = p.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    B = cfg.block_length
    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    psz = e["page_size"]
    nblk = -(-e["max_seq"] // psz)
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic("blockgen")
    cache = decode.init_paged_cache(cfg, e["kv_pages"] + 1, psz,
                                    e["num_slots"])

    def state(rows, scale=1.0):
        """(positions, block tables) of `rows` decode rows, each as deep
        as one of the mix's prompts plus half an output (times `scale`,
        within the table) and on pages of its own."""
        prompts = np.asarray(traffic.quantile_grid(mix["prompt_len"], rows))
        outs = np.asarray(traffic.quantile_grid(mix["output_len"], rows))
        pos = (rng.permutation(prompts) + rng.permutation(outs) / 2) * scale
        pos = np.minimum(pos, e["max_seq"] - B).astype(np.int32) // B * B
        need = (pos + B + psz - 1) // psz
        if need.sum() > e["kv_pages"]:
            raise ValueError(f"{need.sum()} pages for {rows} rows")
        bt = np.zeros((rows, nblk), np.int32)
        first = 1 + np.concatenate([[0], np.cumsum(need)[:-1]])
        for r in range(rows):
            bt[r, :need[r]] = first[r] + np.arange(need[r])
        return pos, bt

    def run_step(n, pos, bt):
        nonlocal cache
        rows = len(pos)
        tok = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(rows, B)),
                          jnp.int32)
        masked = jnp.asarray(np.arange(B)[None, :] >= B // 2
                             ) & jnp.ones((rows, 1), bool)
        take = jnp.ones((rows,), bool)
        for _ in range(n):
            out, _, _, cache = engine._paged_block_step(
                params, tok, masked, tok, masked, take, jnp.asarray(pos),
                cache, jnp.asarray(bt), cfg, with_logits=False)
        out.block_until_ready()

    def run_chunk(n, width, start, bt):
        nonlocal cache
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, width)),
                           jnp.int32)
        for _ in range(n):
            out, cache = engine._prefill_chunk(
                params, toks, jnp.int32(start), cache, jnp.asarray(bt), cfg,
                slot=jnp.int32(0), valid=jnp.int32(width))
        out.block_until_ready()

    def timed(fn, *a):
        t0 = time.perf_counter()
        fn(2, *a)                                   # compile + warm
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        fn(args.calls, *a)
        return (time.perf_counter() - t0) / args.calls * 1e3, first

    def step_line(rows, scale=1.0):
        pos, bt = state(rows, scale)
        least = min_time(arch.block_step(c, rows, rows * B,
                                         float(pos.sum())), peaks)
        ms, first = timed(run_step, pos, bt)
        say(what="block_step", rows=rows, columns=rows * B,
            depth_scale=scale, mean_pos=float(pos.mean()),
            max_pos=int(pos.max()), ms=ms, first_two_s=first,
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            # a row's forward fixes ONE position, and one forward in
            # five of a whole block fixes none
            tokens_per_s_at_1_25=rows / 1.25 / ms * 1e3)

    say(what="state", device=kind, weight_gb=arch.weight_bytes(c) / 1e9,
        pool_gb=(e["kv_pages"] + 1) * psz * arch.kv_bytes_per_token(c) / 1e9)
    step_line(e["num_slots"])
    for scale in [float(x) for x in args.depth.split(",") if x]:
        step_line(e["num_slots"], scale)
    for n in ints(args.rows):
        step_line(n)
    deep = np.zeros((1, nblk), np.int32)
    deep[0] = 1 + np.arange(nblk)                   # pages enough
    for width in [e["prefill_chunk"]] + ints(args.chunk):
        for start in STARTS:
            if start + width > e["max_seq"]:
                continue
            least = min_time(arch.prefill_chunk(c, width, start, False),
                             peaks)
            ms, first = timed(run_chunk, width, start, deep)
            say(what="chunk", width=width, start=start, ms=ms,
                first_two_s=first, least_ms=least["seconds"] * 1e3,
                bound=least["bound"], tokens_per_s=width / ms * 1e3)
    return 0


if __name__ == "__main__":
    sys.exit(main())
