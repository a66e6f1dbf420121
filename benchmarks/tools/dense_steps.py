"""The dense body's tick (`models/decode.py`: `mistral-7b-v0.3-d16`,
`internlm2-1.8b`) timed outside the engine, on the chip, at a cell's
shapes, and cut into its attention and the rest: where the dense rows of
PERF.md section 5 and the block sizes of section 6 (PR 60) come from.

    python3 benchmarks/tools/dense_steps.py --seed 2147560001 \
        --config internlm2-1.8b --mix batch [--live 2] \
        [--block-keys 128,256,384] [--calls 32]

The state is the cell's: `--live` decode rows (default: all of the
configuration's `num_slots`) whose positions are the mix's prompt
lengths plus a part of an output drawn uniformly (a row is met anywhere
in its generation), each on pages of its own; every other row
idle as the engine leaves one (position 0, its table on the trash page).
Every line is one JSON object.  A `tick` line holds the host-clocked
tick, its least time from `archs/llama/costs.py`, and what a trace of a
few ticks says of its parts: device time of the program, of its
attention (the instructions whose `op_name` carries the scope
`dense_attn`, or, in a program from before PR 60, that lie in the loop
inside the layer scan: the span loop) and, where there is a span loop,
that loop's own events whole.  `--block-keys` times the tick again with
the kernel's block held at so many keys (`ops/paged_attention.py`'s two
constants patched for the experiment; a program whose tick does not call
the kernel reads the same at each).
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

SCOPE = "dense_attn"
# a loop inside the layer scan's body (whatever call lies between)
SPAN_LOOP = re.compile(r"/while/body/(?:.*/)?while(/|$)")


def say(**row):
    print(json.dumps(row), flush=True)


def attention_instructions(hlo_text: str):
    """({names of the attention's instructions}, {names of the span
    loop's own `while`s}) of a compiled tick's text."""
    attn, loops = set(), set()
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not m or not op:
            continue
        inner = SPAN_LOOP.search(op.group(1))
        if SCOPE in op.group(1) or inner:
            attn.add(m.group(1))
        if " while(" in line and inner and not inner.group(1):
            loops.add(m.group(1))
    return attn, loops


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import decode
    from ray_tpu.ops import paged_attention as pa
    from ray_tpu.serve.llm import engine

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="internlm2-1.8b")
    p.add_argument("--mix", default="batch")
    p.add_argument("--live", type=int, default=0)
    p.add_argument("--block-keys", default="")
    p.add_argument("--calls", type=int, default=32)
    p.add_argument("--traced", type=int, default=8)
    args = p.parse_args()

    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    e = c["serving"]["engine"]
    cfg = arch.build(c, e["max_seq"], remat=False)
    kind = jax.devices()[0].device_kind
    peaks = peaks_for(kind)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    psz, rows = e["page_size"], e["num_slots"]
    nblk = -(-e["max_seq"] // psz)
    live = args.live or rows
    rng = np.random.default_rng(args.seed)
    mix = reg.traffic(args.mix)

    prompts = np.asarray(traffic.quantile_grid(mix["prompt_len"], live))
    outs = np.asarray(traffic.quantile_grid(mix["output_len"], live))
    pos = np.zeros(rows, np.int32)
    pos[:live] = rng.permutation(prompts) \
        + rng.uniform(size=live) * rng.permutation(outs)
    need = np.where(pos > 0, (pos + 2 + psz - 1) // psz, 0)
    bt = np.zeros((rows, nblk), np.int32)
    first = 1 + np.concatenate([[0], np.cumsum(need)[:-1]])
    for r in range(rows):
        bt[r, :need[r]] = first[r] + np.arange(need[r])
    tok = rng.integers(1, cfg.vocab_size, size=rows).astype(np.int32)
    cache = decode.init_paged_cache(cfg, e["kv_pages"] + 1, psz, rows)
    held = pos[:live]
    say(what="state", device=kind, config=args.config, mix=args.mix,
        rows=rows, live=live, mean_pos=float(held.mean()),
        max_pos=int(held.max()), min_pos=int(held.min()))

    def run_tick(n):
        nonlocal cache
        for _ in range(n):
            out, _, cache = engine._paged_tick(
                params, jnp.asarray(tok), jnp.asarray(pos), cache,
                jnp.asarray(bt), cfg, with_logits=False)
        out.block_until_ready()

    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    shaped = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)

    def tick_line(**knob):
        engine._paged_tick.clear_cache()
        run_tick(2)                                 # compile + warm
        t0 = time.perf_counter()
        run_tick(args.calls)
        ms = (time.perf_counter() - t0) / args.calls * 1e3
        text = engine._paged_tick.lower(
            shaped(params), i32(rows), i32(rows), shaped(cache),
            i32(rows, nblk), cfg, with_logits=False).compile().as_text()
        attn, loops = attention_instructions(text)
        trace_dir = tempfile.mkdtemp(prefix="dense-steps-")
        jax.profiler.start_trace(trace_dir)
        run_tick(args.traced)
        jax.profiler.stop_trace()
        plane = trace_reduce.load(
            trace_reduce.find_xplane(trace_dir))["planes"][0]
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        spans = [(t0, t0 + d) for n, t0, d in lines["XLA Modules"]
                 if n.split("(")[0] == "jit__paged_tick"][-args.traced:]
        inside = lambda t: any(a <= t < b for a, b in spans)  # noqa: E731
        n = len(spans)
        attn_ns = sum(d for name, t0, d in lines["XLA Ops"]
                      if name in attn and inside(t0)
                      and not trace_reduce.CONTAINER.match(name))
        loop_ns = sum(d for name, t0, d in lines["XLA Ops"]
                      if name in loops and inside(t0))
        least = min_time(arch.decode_tick(c, live, float(held.sum())), peaks)
        gathered = decode.DENSE_BODY.attn_keys_gathered(cfg, pos, psz, nblk)
        say(what="tick", knobs=knob, ms=ms,
            device_ms=sum(b - a for a, b in spans) / n / 1e6,
            attention_ms=attn_ns / n / 1e6,
            span_loop_ms=loop_ns / n / 1e6 if loops else None,
            least_ms=least["seconds"] * 1e3, bound=least["bound"],
            gathered_over_held=gathered
            / decode.DENSE_BODY.attn_keys(cfg, held)[1])
        return text

    text = tick_line()
    for keys in [int(x) for x in args.block_keys.split(",") if x]:
        was = pa._BLOCK_KEYS, pa._BLOCK_BYTES
        pa._BLOCK_KEYS, pa._BLOCK_BYTES = keys, 0
        tick_line(block_keys=keys)
        pa._BLOCK_KEYS, pa._BLOCK_BYTES = was
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"dense.{args.config}.tick.hlo.txt"),
              "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
