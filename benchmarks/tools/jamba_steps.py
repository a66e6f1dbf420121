"""The two programs of `jamba2-3b` timed outside the engine, on the chip,
at the configuration's sizes: what the form of the chunk's scan, the
model's own constants (the spans of the attention layers' softmax) and
the configuration's `num_slots` and `prefill_chunk` were chosen from,
and where the scope table of PERF.md section 6 comes from.

    python3 benchmarks/tools/jamba_steps.py --seed 2147498001 \
        [--scan] [--rows 64,128] [--tick-span 128,256,512] \
        [--chunk 256,512] [--chunk-span 256,512] [--scopes]

`--scan` times ONE layer's selective scan over a chunk in both forms
(`ops/ssm.py`: the Pallas kernel at 1, 2 and 4 tokens a trip, the
blocked `associative_scan` at sub-chunks of 16, 32 and 64) on seeded
inputs of the mixer's own magnitudes, and holds each against the
kernel's first reading.  The state is the cell's: decode rows whose
positions are the mix's prompt lengths plus half an output, each row on
pages of its own; a chunk is timed after 0 / 1,024 / 2,048 tokens of
context.  Every line is one JSON object.  `--scopes` traces a few calls
of each program as the configuration stands and sums device time by the
program's `named_scope`s (`ssm_conv`, `ssm_scan`, `ssm_step`,
`attn_nope`; the rest is `other`: the projections, the feed-forward and
the head), beside each kernel's least time from `archs/jamba/costs.py`:
an instruction of the trace is found in the compiled program's text by
its name, and counted under the scope its `op_name` carries (a fusion
under its root's).
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

SCOPES = ("ssm_conv", "ssm_scan", "ssm_step", "attn_nope")
STARTS = (0, 1024, 2048)


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
        scope = next((s for s in SCOPES if op and s in op.group(1)), "other")
        out[m.group(1)] = scope
    return out


def time_scans(ssm, cfg, T, args, peaks, arch, c) -> None:
    """Every Mamba layer's scan over a chunk of T tokens, both forms:
    ONE jitted call walks the layers' inputs (a call of one layer is
    shorter than its own dispatch), so a reading is device time."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    E, N, M = cfg.d_inner, cfg.d_state, cfg.n_mamba
    ks = jax.random.split(seed_key(args.seed), 6)
    delta = jnp.exp(jax.random.uniform(ks[0], (M, T, E), jnp.float32,
                                       np.log(3e-4), np.log(3e-1)))
    x = jax.nn.silu(jax.random.normal(ks[1], (M, T, E), jnp.float32))
    Bm = jax.random.normal(ks[2], (M, T, N), jnp.float32)
    Cm = jax.random.normal(ks[3], (M, T, N), jnp.float32)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, E))
    h0 = 0.05 * jax.random.normal(ks[4], (M, N, E), jnp.float32)
    least = min_time(arch.ssm_scan(c, T), peaks)
    forms = [("pallas", {"unroll": u}, functools.partial(
        ssm.scan_pallas, unroll=u)) for u in (1, 2, 4, 8)]
    forms += [("blocked", {"sub": s}, functools.partial(
        ssm.scan_blocked, sub=s)) for s in (16, 32, 64)]
    first = None
    for name, knob, fn in forms:
        f = jax.jit(lambda d, u, b, cc, h, fn=fn: lax.map(
            lambda a: fn(a[0], a[1], a[2], a[3], A, a[4]), (d, u, b, cc, h)))
        y, h = f(delta, x, Bm, Cm, h0)
        y.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.calls):
            y, h = f(delta, x, Bm, Cm, h0)
        y.block_until_ready()
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        got = (np.asarray(y), np.asarray(h))
        first = first or got
        say(what="scan", form=name, knobs=knob, tokens=T, channels=E,
            states=N, layers=M, ms_all_layers=ms, ms_a_layer=ms / M,
            least_ms_all_layers=least["seconds"] * 1e3, bound=least["bound"],
            y_std=float(got[0].std()),
            max_diff_y=float(np.abs(got[0] - first[0]).max()),
            max_diff_h=float(np.abs(got[1] - first[1]).max()))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decode
    from ray_tpu.models import jamba as em
    from ray_tpu.ops import ssm
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="jamba2-3b")
    p.add_argument("--tick-span", default="")
    p.add_argument("--rows", default="")
    p.add_argument("--chunk", default="")
    p.add_argument("--chunk-span", default="")
    p.add_argument("--scopes", action="store_true")
    p.add_argument("--scan", action="store_true")
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
    if args.scan:
        time_scans(ssm, cfg, e["prefill_chunk"], args, peaks, arch, c)
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic("ssm_chat")

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
        for _ in range(n):
            out, cache = engine._prefill_chunk(
                params, toks, jnp.int32(start), cache, jnp.asarray(bt[:1]),
                cfg, slot=jnp.int32(0), valid=jnp.int32(width))
        out.block_until_ready()

    def timed(fn, *a):
        fn(2, *a)                                   # compile + warm
        t0 = time.perf_counter()
        fn(args.calls, *a)
        return (time.perf_counter() - t0) / args.calls * 1e3

    def fresh():
        engine._paged_tick.clear_cache()
        engine._prefill_chunk.clear_cache()

    def tick_line(**knob):
        least = min_time(arch.decode_tick(c, len(pos), float(pos.sum())),
                         peaks)
        say(what="tick", rows=len(pos), mean_pos=float(pos.mean()),
            max_pos=int(pos.max()),
            knobs=dict(knobs, **knob), ms=timed(run_tick),
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            gathered_over_held=em.attn_keys_gathered(cfg, pos, psz, nblk)
            / em.attn_keys(cfg, pos)[1])

    knobs = {"_TICK_SPAN_KEYS": em._TICK_SPAN_KEYS,
             "_CHUNK_SPAN_KEYS": em._CHUNK_SPAN_KEYS}
    tick_line()
    for v in ints(args.tick_span):
        was, em._TICK_SPAN_KEYS = em._TICK_SPAN_KEYS, v
        fresh()
        tick_line(_TICK_SPAN_KEYS=v)
        em._TICK_SPAN_KEYS = was
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
        for span in ints(args.chunk_span) or [em._CHUNK_SPAN_KEYS]:
            was, em._CHUNK_SPAN_KEYS = em._CHUNK_SPAN_KEYS, span
            fresh()
            for start in STARTS:
                least = min_time(arch.prefill_chunk(c, width, start, False),
                                 peaks)
                say(what="chunk", width=width, start=start,
                    knobs=dict(knobs, _CHUNK_SPAN_KEYS=span),
                    ms=timed(run_chunk, width, start),
                    least_ms=least["seconds"] * 1e3, bound=least["bound"])
            em._CHUNK_SPAN_KEYS = was
    if not args.scopes:
        return 0

    # -- device time by named scope ----------------------------------
    fresh()
    width = e["prefill_chunk"]
    run_tick(2)
    run_chunk(2, width, STARTS[-1])
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
    trace_dir = tempfile.mkdtemp(prefix="jamba-steps-")
    n_tick, n_chunk = 6, 4
    jax.profiler.start_trace(trace_dir)
    run_tick(n_tick)
    run_chunk(n_chunk, width, STARTS[-1])
    jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(trace_dir))["planes"]
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    runs = [(n.split("(")[0], t0, t0 + d) for n, t0, d in lines["XLA Modules"]]
    calls = {"jit__paged_tick": n_tick, "jit__prefill_chunk": n_chunk}
    keys = float(pos.sum()) + rows
    costs = {
        "jit__paged_tick": {
            "ssm_conv": arch.ssm_conv(c, rows, rows),
            "ssm_step": arch.ssm_step(c, rows),
            "attn_nope": arch.attn_nope(c, keys, keys)},
        "jit__prefill_chunk": {
            "ssm_conv": arch.ssm_conv(c, width),
            "ssm_scan": arch.ssm_scan(c, width),
            "attn_nope": arch.attn_nope(
                c, width * (STARTS[-1] + (width + 1) / 2),
                STARTS[-1] + width)}}
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
        say(what="scopes", program=program, calls=n, width=width,
            program_ms=sum(b - a for a, b in spans) / n / 1e6,
            unknown_ms=unknown / n / 1e6,
            ms={k: v / n / 1e6 for k, v in sorted(by_scope.items())},
            least={k: dict(min_time(v, peaks), ms=min_time(
                v, peaks)["seconds"] * 1e3) for k, v in
                costs[program].items()})
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for program, text in texts.items():
        path = os.path.join(out_dir, "jamba." + program + ".hlo.txt")
        with open(path, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
