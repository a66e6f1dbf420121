"""The two programs of `zaya1-8b-pp2-d20` timed outside the engine, on
the chip, at the configuration's sizes: what the configuration's
`num_slots` and `prefill_chunk` and the ragged kernel's block on a 1 KB
token were chosen from, and where the kernel table of PERF.md section 5
comes from.

    python3 benchmarks/tools/zaya_steps.py --seed 2147498001 \
        [--rows 48,96] [--block-keys 384,640,960,1920] \
        [--chunk 256,512] [--scopes]

The state is the cell's: decode rows whose positions are the
`reason_short` mix's prompt lengths plus half an output (mean ~1.2k,
deepest ~3.8k), each row on pages of its own; a chunk is timed after 0 /
1,024 / 3,072 tokens of context.  Every line is one JSON object.
`--block-keys` times the tick with the ragged kernel's block at so many
keys (`ops/paged_attention._BLOCK_BYTES` set to that many of this
model's 1,024 B tokens).  `--scopes` traces a few calls of each program
as the configuration stands and sums device time by the program's
`named_scope`s (`cca_mix`, `attn_latent`, `moe_route`, `moe_experts`;
the rest is `other`), beside each kernel's least time from
`archs/zaya/costs.py`: an instruction of the trace is found in the
compiled program's text by its name, and counted under the scope its
`op_name` carries (a fusion under its root's).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import traffic, trace_reduce  # noqa: E402
from benchmarks.lib.costs import min_time  # noqa: E402
from benchmarks.lib.model import seed_key  # noqa: E402
from benchmarks.lib.peaks import peaks_for  # noqa: E402
from benchmarks.lib.registry import Registry, arch_of  # noqa: E402

SCOPES = ("cca_mix", "attn_latent", "moe_route", "moe_experts")
STARTS = (0, 1024, 3072)


def say(**row):
    print(json.dumps(row), flush=True)


def scope_of_instruction(hlo_text: str) -> dict:
    """{instruction name: scope} from a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out[m.group(1)] = next(
            (s for s in SCOPES if op and s in op.group(1)), "other")
    return out


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decode, zaya
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="zaya1-8b-pp2-d20")
    p.add_argument("--rows", default="")
    p.add_argument("--block-keys", default="")
    p.add_argument("--chunk", default="")
    p.add_argument("--scopes", action="store_true")
    p.add_argument("--calls", type=int, default=12)
    args = p.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    psz = e["page_size"]
    nblk = -(-e["max_seq"] // psz)
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic("reason_short")

    def state(rows):
        """(cache, positions, block tables, tokens) of `rows` decode
        rows, each as deep as one of the mix's prompts plus half an
        output and on pages of its own."""
        cache = decode.init_paged_cache(cfg, e["kv_pages"] + 1, psz, rows)
        prompts = np.asarray(traffic.quantile_grid(mix["prompt_len"], rows))
        outs = np.asarray(traffic.quantile_grid(mix["output_len"], rows))
        pos = (rng.permutation(prompts) + rng.permutation(outs) / 2
               ).astype(np.int32)
        need = (pos + 2 + psz - 1) // psz
        if need.sum() > e["kv_pages"]:
            raise ValueError(f"{need.sum()} pages for {rows} rows")
        bt = np.zeros((rows, nblk), np.int32)
        first = 1 + np.concatenate([[0], np.cumsum(need)[:-1]])
        for r in range(rows):
            bt[r, :need[r]] = first[r] + np.arange(need[r])
        tok = rng.integers(1, cfg.vocab_size, size=rows).astype(np.int32)
        return cache, pos, bt, tok

    rows = e["num_slots"]
    cache, pos, bt, tok = state(rows)
    say(what="state", device=kind, rows=rows, mean_pos=float(pos.mean()),
        max_pos=int(pos.max()), weight_gb=arch.weight_bytes(c) / 1e9)

    def run_tick(n):
        nonlocal cache
        for _ in range(n):
            out, _, cache = engine._paged_tick(
                params, jnp.asarray(tok), jnp.asarray(pos), cache,
                jnp.asarray(bt), cfg, with_logits=False)
        out.block_until_ready()

    def run_chunk(n, width, start):
        nonlocal cache
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, width)),
                           jnp.int32)
        deep = jnp.asarray(bt[int(np.argmax(pos))][None])   # pages enough
        for _ in range(n):
            out, cache = engine._prefill_chunk(
                params, toks, jnp.int32(start), cache, deep, cfg,
                slot=jnp.int32(0), valid=jnp.int32(width))
        out.block_until_ready()

    def timed(fn, *a):
        t0 = time.perf_counter()
        fn(2, *a)                                   # compile + warm
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        fn(args.calls, *a)
        return (time.perf_counter() - t0) / args.calls * 1e3, first

    def fresh():
        engine._paged_tick.clear_cache()
        engine._prefill_chunk.clear_cache()

    def tick_line(**knob):
        least = min_time(arch.decode_tick(c, len(pos), float(pos.sum())),
                         peaks)
        gathered = zaya.attn_keys_gathered(cfg, pos, psz, nblk)
        ms, first = timed(run_tick)
        say(what="tick", rows=len(pos), mean_pos=float(pos.mean()),
            max_pos=int(pos.max()), knobs=knob, ms=ms, first_two_s=first,
            block_pages=pa.block_pages(psz, nblk, cfg.token_bytes),
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            gathered_over_held=gathered / zaya.attn_keys(cfg, pos)[1])

    tick_line()
    for keys in ints(args.block_keys):
        was, pa._BLOCK_BYTES = pa._BLOCK_BYTES, keys * cfg.token_bytes
        fresh()
        tick_line(block_keys=keys)
        pa._BLOCK_BYTES = was
    for n in ints(args.rows):
        fresh()
        cache = None
        cache, pos, bt, tok = state(n)
        tick_line()
    if ints(args.rows):
        cache = None
        cache, pos, bt, tok = state(rows)
    fresh()
    for width in ints(args.chunk) or [e["prefill_chunk"]]:
        fresh()
        for start in STARTS:
            least = min_time(arch.prefill_chunk(c, width, start, False),
                             peaks)
            ms, first = timed(run_chunk, width, start)
            say(what="chunk", width=width, start=start, ms=ms,
                first_two_s=first, least_ms=least["seconds"] * 1e3,
                bound=least["bound"])
    if not args.scopes:
        return 0

    # -- device time by named scope ----------------------------------
    fresh()
    width, start = e["prefill_chunk"], STARTS[1]
    run_tick(2)
    run_chunk(2, width, start)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    texts = {
        "jit__paged_tick": engine._paged_tick.lower(
            shaped(params), i32(rows), i32(rows), shaped(cache),
            i32(rows, nblk), cfg, with_logits=False).compile().as_text(),
        "jit__prefill_chunk": engine._prefill_chunk.lower(
            shaped(params), i32(1, width), i32(), shaped(cache),
            i32(1, nblk), cfg, slot=i32(), valid=i32()).compile().as_text()}
    trace_dir = tempfile.mkdtemp(prefix="zaya-steps-")
    n_tick, n_chunk = 6, 4
    jax.profiler.start_trace(trace_dir)
    run_tick(n_tick)
    run_chunk(n_chunk, width, start)
    jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))["planes"]
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    runs = [(n.split("(")[0], t0, t0 + d) for n, t0, d in lines["XLA Modules"]]
    calls = {"jit__paged_tick": n_tick, "jit__prefill_chunk": n_chunk}
    ctx = float(pos.sum())
    costs = {
        "jit__paged_tick": {
            "cca_mix": arch.cca_mix(c, rows, rows),
            "attn_latent": arch.attn_latent(c, rows, ctx),
            "moe_route": arch.moe_route(c, rows),
            "moe_experts": arch.moe_experts(c, rows)},
        "jit__prefill_chunk": {
            "cca_mix": arch.cca_mix(c, width, 1),
            "attn_latent": arch.attn_latent_chunk(c, width, start),
            "moe_route": arch.moe_route(c, width),
            "moe_experts": arch.moe_experts(c, width)}}
    for program, text in texts.items():
        scope_of = scope_of_instruction(text)
        spans = [(a, b) for n, a, b in runs if n == program][-calls[program]:]
        by_scope, unknown = {}, 0.0
        for name, t0, dur in lines["XLA Ops"]:
            if trace_reduce.CONTAINER.match(name) \
                    or not any(a <= t0 < b for a, b in spans):
                continue
            if name not in scope_of:
                unknown += dur
            scope = scope_of.get(name, "other")
            by_scope[scope] = by_scope.get(scope, 0.0) + dur
        n = calls[program]
        least = {k: min_time(v, peaks) for k, v in costs[program].items()}
        ms = {k: v / n / 1e6 for k, v in sorted(by_scope.items())}
        say(what="scopes", program=program, calls=n, width=width,
            start=start, program_ms=sum(b - a for a, b in spans) / n / 1e6,
            unknown_ms=unknown / n / 1e6, ms=ms,
            least={k: dict(v, ms=v["seconds"] * 1e3)
                   for k, v in least.items()},
            roofline_pct={k: 100 * v["seconds"] * 1e3 / ms[k]
                          for k, v in least.items() if ms.get(k)})
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for program, text in texts.items():
        with open(os.path.join(out_dir, "zaya." + program + ".hlo.txt"),
                  "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
