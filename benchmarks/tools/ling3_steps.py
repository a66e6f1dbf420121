"""The two programs of `ling-3.0-flash-ep8-d6` timed outside the engine,
on the chip, at the configuration's sizes: what the configuration's
`prefill_chunk` was chosen beside, and where the kernel table of PERF.md
section 5 comes from.

    python3 benchmarks/tools/ling3_steps.py --seed 2147563001 \
        [--rows 128,256] [--chunk 512,1024] [--kernels] [--scopes]

The state is the cell's: decode rows whose positions are the
`longtail_chat` mix's prompt lengths plus half an output (mean ~2.2k,
deepest ~17k), each row on pages of its own; a chunk is timed after 0 /
1,024 / 8,192 tokens of context.  Every line is one JSON object.
`--kernels` times `ops/kda.py` alone: the tick's step in its two forms
(the Pallas kernel in place, plain XLA) at the configuration's rows,
with every row live and with half of them idle, and the chunk's form at
both chunk widths, each beside its least time from
`archs/bailing_hybrid/costs.py` (one layer's).  `--scopes` traces a few
calls of each program as the configuration stands and sums device time
by the program's `named_scope`s (SCOPES; the rest is `other`), beside
each kernel's least time: an instruction of the trace is found in the
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

SCOPES = ("kda_conv", "kda_gate", "kda_chunk", "kda_step",
          "mla_expand_attend", "mla_absorb_attend", "moe_route",
          "moe_experts", "lm_head")
STARTS = (0, 1024, 8192)


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

    from ray_tpu.models import bailing_hybrid as bh
    from ray_tpu.models import decode
    from ray_tpu.ops import kda
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="ling-3.0-flash-ep8-d6")
    p.add_argument("--rows", default="")
    p.add_argument("--chunk", default="")
    p.add_argument("--kernels", action="store_true")
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
    psz = e["page_size"]
    nblk = -(-e["max_seq"] // psz)
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic("longtail_chat")

    def timed(fn, *a):
        t0 = time.perf_counter()
        fn(2, *a)                                   # compile + warm
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        fn(args.calls, *a)
        return (time.perf_counter() - t0) / args.calls * 1e3, first

    if args.kernels:
        f32 = jnp.float32
        B, H, d = e["num_slots"], cfg.n_heads, cfg.head_dim
        ks = jax.random.split(seed_key(args.seed), 8)
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1,  # noqa: E731
                                             keepdims=True)

        def draws(n):
            return (unit(jax.random.normal(ks[0], (n, H, d), f32)) * d ** -.5,
                    unit(jax.random.normal(ks[1], (n, H, d), f32)),
                    jax.random.normal(ks[2], (n, H, d), f32),
                    -5 * jax.nn.sigmoid(
                        jax.random.normal(ks[3], (n, H, d), f32) * 2 - 6),
                    jax.nn.sigmoid(jax.random.normal(ks[4], (n, H), f32)))
        one = dict(c, num_hidden_layers=1, layer_offset=0,
                   first_k_dense_replace=1)         # one KDA layer's cost
        for form, fn in (("pallas", kda.step_pallas), ("xla", None)):
            for idle in (0, B // 2):
                states = jnp.zeros((cfg.n_kda, B, H, d, d), f32)
                active = jnp.arange(B) >= idle
                if fn is None:
                    step = jax.jit(lambda q, k, v, a, b, s, l, act: (
                        lambda o, S: (o, s.at[l].set(S)))(
                        *kda.step_xla(q, k, v, a, b, s[l], act)),
                        donate_argnums=5)
                else:
                    step = jax.jit(fn, donate_argnums=5)
                vec = draws(B)

                def run(n):
                    nonlocal states
                    for _ in range(n):
                        o, states = step(*vec, states, jnp.int32(2), active)
                    o.block_until_ready()
                ms, first = timed(run)
                least = min_time(arch.kda_step(one, B - idle), peaks)
                say(what="kda_step", form=form, rows=B, idle=idle, ms=ms,
                    first_two_s=first, least_ms=least["seconds"] * 1e3,
                    roofline_pct=100 * least["seconds"] * 1e3 / ms)
                states = None
        for T in ints(args.chunk) or [512, 1024]:
            S0 = jnp.zeros((H, d, d), f32)
            vec = draws(T)
            chunk = jax.jit(kda.kda_chunk)

            def run(n):
                for _ in range(n):
                    o, S = chunk(*vec, S0)
                o.block_until_ready()
            ms, first = timed(run)
            least = min_time(arch.kda_chunk(one, T), peaks)
            say(what="kda_chunk", tokens=T, ms=ms, first_two_s=first,
                least_ms=least["seconds"] * 1e3, bound=least["bound"],
                roofline_pct=100 * least["seconds"] * 1e3 / ms)
        return 0

    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))

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
        max_pos=int(pos.max()), weight_gb=arch.weight_bytes(c) / 1e9,
        row_state_gb=arch.state_bytes_per_row(c) * rows / 1e9)

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
        least = min_time(arch.decode_tick(c, len(pos), float(pos.sum())),
                         peaks)
        gathered = bh.attn_keys_gathered(cfg, pos, psz, nblk)
        ms, first = timed(run_tick)
        say(what="tick", rows=len(pos), mean_pos=float(pos.mean()),
            max_pos=int(pos.max()), ms=ms, first_two_s=first,
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            roofline_pct=100 * least["seconds"] * 1e3 / ms,
            gathered_over_held=gathered / bh.attn_keys(cfg, pos)[1],
            counters=bh.read_counters(cache, cfg))

    tick_line()
    for n in ints(args.rows):
        fresh()
        cache = None
        cache, pos, bt, tok = state(n)
        tick_line()
    if ints(args.rows):
        cache = None
        cache, pos, bt, tok = state(rows)
    for width in ints(args.chunk) or [e["prefill_chunk"]]:
        fresh()
        for start in STARTS:
            least = min_time(arch.prefill_chunk(c, width, start, False),
                             peaks)
            ms, first = timed(run_chunk, width, start)
            say(what="chunk", width=width, start=start, ms=ms,
                first_two_s=first, least_ms=least["seconds"] * 1e3,
                bound=least["bound"],
                roofline_pct=100 * least["seconds"] * 1e3 / ms)
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
    trace_dir = tempfile.mkdtemp(prefix="ling3-steps-")
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
    d = arch.dims(c)
    local = d["k"] * d["held"] / d["E"]
    costs = {
        "jit__paged_tick": {
            "kda_conv": arch.kda_conv(c, rows, rows),
            "kda_step": arch.kda_step(c, rows),
            "mla_absorb_attend": arch.mla_absorb_attend(c, rows, ctx),
            "moe_route": arch.moe_route(c, rows),
            "moe_experts": arch.moe_experts(
                c, rows * local, arch.experts_touched(c, rows))},
        "jit__prefill_chunk": {
            "kda_conv": arch.kda_conv(c, width, 1),
            "kda_chunk": arch.kda_chunk(c, width),
            "mla_expand_attend": arch.mla_expand_attend(c, width, start),
            "moe_route": arch.moe_route(c, width),
            "moe_experts": arch.moe_experts(
                c, width * local, arch.experts_touched(c, width))}}
    for program, text in texts.items():
        scope_of = scope_of_instruction(text)
        spans = [(a, b) for n, a, b in runs if n == program][-calls[program]:]
        by_scope, by_op, unknown = {}, {}, 0.0
        for name, t0, dur in lines["XLA Ops"]:
            if trace_reduce.CONTAINER.match(name) \
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
        with open(os.path.join(out_dir, "ling3." + program + ".hlo.txt"),
                  "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
