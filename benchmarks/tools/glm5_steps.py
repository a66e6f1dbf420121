"""The two programs of `glm-5-ep16-d5` timed outside the engine, on the
chip, at the configuration's sizes: where the scope table of PERF.md
section 5 comes from.

    python3 benchmarks/tools/glm5_steps.py --seed 2147566001 \
        [--rows 24,48] [--chunk 512] [--starts 0,8192] [--scopes] \
        [--span 128,512]

The state is the cell's: decode rows whose positions are the
`long_context` mix's prompt lengths plus half an output (median ~8.6k,
deepest ~33k), each row on pages of its own, as many of the rows as the
pool holds (the engine admits by the same pages); a chunk is timed after
0 / 2,048 / 8,192 / 16,384 / 32,256 tokens of context.  Every line is
one JSON object.  `--span` times the chunk again with
`deepseek_v2._CHUNK_SPAN_KEYS` set to each value (a measurement only:
the program keeps the constant it has).
`--scopes` traces a few calls of each program as the configuration
stands and sums device time by the program's `named_scope`s (SCOPES; the
rest is `other`), beside each scope's least time from
`archs/glm_moe_dsa/costs.py`: an instruction of the trace is found in the
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

SCOPES = ("dsa_index", "dsa_select", "dsa_attend", "moe_route",
          "moe_experts", "lm_head")
STARTS = (0, 2048, 8192, 16384, 32256)
# operations that only contain others (a `lax.cond` or `lax.switch` is
# `cond.<n>` or `cond.<n>.clone` in a trace, which
# lib/trace_reduce.CONTAINER does not name)
CONTAINER = re.compile(r"^(while|conditional|cond|call)(\.\d+)?(\.clone)*$")


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

    from ray_tpu.models import decode
    from ray_tpu.models import deepseek_v2
    from ray_tpu.models import glm_moe_dsa as gm
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="glm-5-ep16-d5")
    p.add_argument("--rows", default="")
    p.add_argument("--chunk", default="")
    p.add_argument("--starts", default="")
    p.add_argument("--scopes", action="store_true")
    p.add_argument("--scope-start", type=int, default=8192)
    p.add_argument("--span", default="")
    p.add_argument("--slots", type=int, default=0)
    p.add_argument("--pages", type=int, default=0)
    p.add_argument("--calls", type=int, default=12)
    args = p.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731

    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    e = dict(c["serving"]["engine"])
    e["num_slots"] = args.slots or e["num_slots"]
    e["kv_pages"] = args.pages or e["kv_pages"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind)
    psz = e["page_size"]
    nblk = -(-e["max_seq"] // psz)
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic("long_context")

    def timed(fn, *a):
        t0 = time.perf_counter()
        fn(2, *a)                                   # compile + warm
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        fn(args.calls, *a)
        return (time.perf_counter() - t0) / args.calls * 1e3, first

    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))

    def state(rows):
        """(cache, positions, block tables, tokens) of `rows` decode
        rows, each as deep as one of the mix's prompts plus half an
        output and on pages of its own."""
        cache = decode.paged_body(cfg).init_paged_cache(
            cfg, e["kv_pages"] + 1, psz, e["num_slots"])
        prompts = np.asarray(traffic.quantile_grid(mix["prompt_len"], rows))
        outs = np.asarray(traffic.quantile_grid(mix["output_len"], rows))
        pos = (rng.permutation(prompts) + rng.permutation(outs) / 2
               ).astype(np.int32)
        pos = np.concatenate([pos, np.zeros(e["num_slots"] - rows,
                                            np.int32)])      # idle rows
        need = np.where(pos > 0, (pos + 2 + psz - 1) // psz, 0)
        if need.sum() > e["kv_pages"]:
            raise ValueError(f"{need.sum()} pages for {rows} rows")
        rows = e["num_slots"]
        bt = np.zeros((rows, nblk), np.int32)
        first = 1 + np.concatenate([[0], np.cumsum(need)[:-1]])
        for r in range(rows):
            bt[r, :need[r]] = first[r] + np.arange(need[r])
        tok = rng.integers(1, cfg.vocab_size, size=rows).astype(np.int32)
        return cache, pos, bt, tok

    rows = e["num_slots"]
    live_rows = (ints(args.rows) or [rows * 3 // 4])[0]
    cache, pos, bt, tok = state(live_rows)
    say(what="state", device=kind, rows=rows, live=int((pos > 0).sum()),
        mean_pos=float(pos[pos > 0].mean()), max_pos=int(pos.max()),
        weight_gb=arch.weight_bytes(c) / 1e9)

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

    def fresh():
        engine._paged_tick.clear_cache()
        engine._prefill_chunk.clear_cache()

    def tick_line():
        live = int((pos > 0).sum())
        least = min_time(arch.decode_tick(c, live, float(pos.sum())), peaks)
        gathered = gm.attn_keys_gathered(cfg, pos, psz, nblk)
        ms, first = timed(run_tick)
        say(what="tick", rows=len(pos), live=live,
            mean_pos=float(pos[pos > 0].mean()),
            max_pos=int(pos.max()), ms=ms, first_two_s=first,
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            roofline_pct=100 * least["seconds"] * 1e3 / ms,
            gathered_over_held=gathered / gm.attn_keys(cfg, pos[pos > 0])[1],
            counters=gm.read_counters(cache, cfg))

    tick_line()
    for n in ints(args.rows)[1:]:
        cache = None
        cache, pos, bt, tok = state(n)
        tick_line()
    starts = ints(args.starts) or STARTS
    for width in ints(args.chunk) or [e["prefill_chunk"]]:
        fresh()
        for start in starts:
            least = min_time(arch.prefill_chunk(c, width, start, False),
                             peaks)
            ms, first = timed(run_chunk, width, start)
            say(what="chunk", width=width, start=start, ms=ms,
                first_two_s=first, least_ms=least["seconds"] * 1e3,
                bound=least["bound"],
                roofline_pct=100 * least["seconds"] * 1e3 / ms)
    for span in ints(args.span):
        fresh()
        kept, deepseek_v2._CHUNK_SPAN_KEYS = \
            deepseek_v2._CHUNK_SPAN_KEYS, span
        try:
            for start in starts:
                ms, first = timed(run_chunk, e["prefill_chunk"], start)
                say(what="chunk_span", span=span, start=start, ms=ms,
                    first_two_s=first)
        finally:
            deepseek_v2._CHUNK_SPAN_KEYS = kept
    if not args.scopes:
        return 0

    # -- device time by named scope ----------------------------------
    fresh()
    width, start = e["prefill_chunk"], args.scope_start
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
    trace_dir = tempfile.mkdtemp(prefix="glm5-steps-")
    n_tick, n_chunk = 6, 4
    jax.profiler.start_trace(trace_dir)
    run_tick(n_tick)
    run_chunk(n_chunk, width, start)
    jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))["planes"]
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    runs = [(n.split("(")[0], t0, t0 + d) for n, t0, d in lines["XLA Modules"]]
    calls = {"jit__paged_tick": n_tick, "jit__prefill_chunk": n_chunk}
    live = int((pos > 0).sum())
    seen = float(pos.sum()) + live
    d = arch.dims(c)
    local = d["k"] * d["held"] / d["E"]
    chosen = float(np.minimum(pos[pos > 0] + 1, d["topk"]).sum())
    # a chunk's (query, key) pairs: every key it sees; the chosen
    every = width * (start + (width + 1) / 2)
    kept = sum(min(start + j + 1, d["topk"]) for j in range(width))
    costs = {
        "jit__paged_tick": {
            "dsa_index": arch.dsa_index(c, live, seen, seen),
            "dsa_select": arch.dsa_select(c, seen, chosen),
            "dsa_attend": arch.dsa_attend(c, live, chosen),
            "moe_route": arch.moe_route(c, live),
            "moe_experts": arch.moe_experts(
                c, live * local, arch.experts_touched(c, live))},
        "jit__prefill_chunk": {
            "dsa_index": arch.dsa_index(c, width, every, start + width),
            "dsa_select": arch.dsa_select(c, every, kept),
            "dsa_attend": arch.dsa_attend(c, width, kept,
                                          expanded=start + width),
            "moe_route": arch.moe_route(c, width),
            "moe_experts": arch.moe_experts(
                c, width * local, arch.experts_touched(c, width))}}
    for program, text in texts.items():
        scope_of = scope_of_instruction(text)
        spans = [(a, b) for n, a, b in runs if n == program][-calls[program]:]
        by_scope, by_op, unknown = {}, {}, 0.0
        for name, t0, dur in lines["XLA Ops"]:
            if CONTAINER.match(name) \
                    or not any(a <= t0 < b for a, b in spans):
                continue
            if name not in scope_of:
                unknown += dur
            scope = scope_of.get(name, "other")
            by_scope[scope] = by_scope.get(scope, 0.0) + dur
            by_op[name] = by_op.get(name, 0.0) + dur
        n = calls[program]
        least = {k: min_time(v, peaks) for k, v in costs[program].items()}
        ms = {k: v / n / 1e6 for k, v in sorted(by_scope.items())}
        say(what="scopes", program=program, calls=n, width=width,
            start=start, program_ms=sum(b - a for a, b in spans) / n / 1e6,
            unknown_ms=unknown / n / 1e6, ms=ms,
            least={k: dict(v, ms=v["seconds"] * 1e3)
                   for k, v in least.items()},
            roofline_pct={k: 100 * v["seconds"] * 1e3 / ms[k]
                          for k, v in least.items() if ms.get(k)},
            heaviest=[[k, v / n / 1e6] for k, v in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:14]])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for program, text in texts.items():
        with open(os.path.join(out_dir, "glm5." + program + ".hlo.txt"),
                  "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
