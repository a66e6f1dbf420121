"""The second reading a limit of `sdar-30b-a3b-pp8-d6`'s check is set
from: how far the plain reference moves, in the check's own two numbers
and over the check's own rows, when it is computed wrong in a way the
check must catch.  Run on the chip (the reference alone, seeded weights
and tokens as the check draws them, no engine):

    python3 benchmarks/tools/sdar_limits.py --seed 2147498101 \
        [--config sdar-30b-a3b-pp8-d6] \
        [--variants bf16,fp8,causal,nowrite,shift,top7,norenorm,noqknorm]

  bf16      every weight matmul's inputs rounded to bfloat16: the
            configuration's own precision, the noise a limit must clear
  fp8       ...to float8_e4m3fn: the nearest precision below it
  causal    the mask causal INSIDE a block too (the plain decoder's)
  nowrite   a block's writing forward skipped: later blocks read keys
            computed while the block's last position was still a mask
            (the forwards' prefixes hold the mask token there)
  shift     the logits shifted by one (position i answers for i + 1)
  top7      seven experts a token, not eight
  norenorm  the chosen experts' weights not renormalised
  noqknorm  the norms of q and k left out

The rows are the check's (`arch.sequences`: a long prompt and a few
blocks, a short prompt and many): every prompt's positions, then the
four positions of every forward of the blocks that the float32
reference's own generation runs (denoising steps with masks standing,
and writing forwards).  Each line: the variant's largest and mean
absolute difference from the float32 reference over all those rows,
and over the forwards' rows alone, beside the limits in the file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib.model import seed_key  # noqa: E402
from benchmarks.lib.registry import Registry, arch_of  # noqa: E402

VARIANTS = {
    "bf16": ({}, "bfloat16"),
    "fp8": ({}, "float8_e4m3fn"),
    "causal": ({"_causal_in_block": True}, None),
    "nowrite": ({}, None),
    "shift": ({"_shift_logits": True}, None),
    "top7": ({"_top_k": 7}, None),
    "norenorm": ({"_no_renorm": True}, None),
    "noqknorm": ({"_no_qk_norm": True}, None),
}


def generated(arch, params, c, prompt, n_blocks):
    """The forwards of `n_blocks` blocks of the reference's own
    generation from `prompt`, as `arch.block_by_block` lists the
    engine's: (final prefix, the block as the step saw it, None), and
    for each the column that was fixed LAST in the block before it
    (None in the first)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = arch._reference
    B = ref.block_length(c)
    mask_id = int(c["assumed"]["mask_token_id"])
    longest = len(prompt) // B * B + B * n_blocks
    hidden = jax.jit(lambda p, t: ref.hidden(p, t, c))
    head = jax.jit(lambda p, x: ref.head(p, x))
    start = len(prompt) // B * B
    fixed = len(prompt) - start
    cur = np.full((B,), mask_id, np.int32)
    cur[:fixed] = prompt[start:]
    masked = np.arange(B) >= fixed
    prefix = [int(t) for t in prompt[:start]]
    out, last_fixed = [], []
    for _ in range(n_blocks):
        at = None
        while True:
            writing = not masked.any()
            out.append((list(prefix), cur.copy(), None))
            if writing:
                break
            seq = np.zeros((longest,), np.int32)
            seq[:start] = prefix
            seq[start:start + B] = cur
            z = np.asarray(head(params, hidden(params, jnp.asarray(seq))
                                [start:start + B])).astype(np.float64)
            z = z - z.max(-1, keepdims=True)
            prob = 1.0 / np.exp(z).sum(-1)
            at = int(np.argmax(np.where(masked, prob, -np.inf)))
            cur[at] = int(np.argmax(z[at]))
            masked[at] = False
        last_fixed.append(at)
        prefix += [int(t) for t in cur]
        start += B
        cur, masked = np.full((B,), mask_id, np.int32), np.ones((B,), bool)
    return out, last_fixed


def main() -> int:
    import jax
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="sdar-30b-a3b-pp8-d6")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--prompt-len", type=int, default=None)
    p.add_argument("--blocks", type=int, default=None)
    args = p.parse_args()
    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    ref = arch._reference
    check = c["serving"]["check"]
    prompt_len = args.prompt_len or check["prompt_len"]
    n_blocks = args.blocks or check["decode_tokens"]
    B = ref.block_length(c)
    mask_id = int(c["assumed"]["mask_token_id"])
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    todo = arch.sequences(args.seed, cfg.vocab_size, prompt_len, n_blocks,
                          c)
    lens = [len(prompt) for prompt, _ in todo]
    ran = [generated(arch, params, c, prompt, blocks)
           for prompt, blocks in todo]

    def unwritten(forwards, last_fixed, first_block):
        """The forwards as they would stand had no block's writing
        forward run: in every prefix, each generated block's position
        that was fixed last holds the mask token still."""
        out = []
        for pre, block, _ in forwards:
            pre = list(pre)
            for n, at in enumerate(last_fixed):
                col = first_block + n * B + at
                if col < len(pre):
                    pre[col] = mask_id
            out.append((pre, block, None))
        return out

    def rows(conf, round_to=None, skip_writing=False):
        return arch.prompts_first(
            [arch.reference_rows(
                params, conf, prompt,
                unwritten(forwards, last, len(prompt) // B * B)
                if skip_writing else forwards, round_to=round_to)
             for (prompt, _), (forwards, last) in zip(todo, ran)], lens)

    truth = rows(c)
    n_prompt = sum(lens)
    print(json.dumps({"variant": "float32", "positions": len(truth),
                      "prefill_positions": n_prompt,
                      "block_forwards": [len(f) for f, _ in ran],
                      "logit_std": float(truth.std()),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        switches, round_to = VARIANTS[name]
        got = rows(dict(c, **switches), round_to, name == "nowrite")
        diff = np.abs(got - truth)
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "max_abs_diff_decode": float(diff[n_prompt:].max()),
            "mean_abs_diff_decode": float(diff[n_prompt:].mean()),
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
