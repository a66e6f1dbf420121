"""The second reading a limit of `mimo-v2-flash-ep16-d7`'s check is set
from: how far the plain reference moves, in the check's own two numbers,
when it is computed wrong in a way the check must catch.  Run on the
chip (the reference alone, seeded weights and tokens as the check draws
them, no engine):

    python3 benchmarks/tools/mimo_limits.py --seed 2147498101 \
        [--config mimo-v2-flash-ep16-d7] [--logit-std 4] \
        [--variants bf16,fp8,nosink,novscale,thetas,fullrotary,window127,
                    top7]

  bf16        every weight matmul's inputs rounded to bfloat16: the
              configuration's own precision, the noise a limit must clear
  fp8         ...to float8_e4m3fn: the nearest precision below it
  nosink      the window softmaxes' sinks left out
  novscale    attention_value_scale left out (values not scaled)
  thetas      the two RoPE thetas swapped between the kinds of layer
  fullrotary  RoPE over the whole head of 192, not its first 64
  window127   a window of 127, not 128
  top7        one expert fewer a token than published

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions, beside the limits in the file,
and the share of (token, expert layer) whose chosen experts are the
float32 reference's (as sets).
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


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="mimo-v2-flash-ep16-d7")
    p.add_argument("--variants",
                   default="bf16,fp8,nosink,novscale,thetas,fullrotary,"
                           "window127,top7")
    p.add_argument("--positions", type=int, default=None)
    p.add_argument("--logit-std", type=float, default=None,
                   help="another SEEDED_ATTN_LOGIT_STD than the "
                        "architecture's, to see what the limits rest on")
    args = p.parse_args()
    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    if args.logit_std is not None:
        arch.SEEDED_ATTN_LOGIT_STD = args.logit_std
    check = c["serving"]["check"]
    T = args.positions or check["prompt_len"] + check["decode_tokens"]
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    rng = np.random.default_rng([int(args.seed), 0xC0FFEE])
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=T), jnp.int32)
    k = c["num_experts_per_tok"]
    variants = {
        "bf16": (c, {"round_to": "bfloat16"}),
        "fp8": (c, {"round_to": "float8_e4m3fn"}),
        "nosink": (dict(c, _no_sink=True), {}),
        "novscale": (dict(c, _no_v_scale=True), {}),
        "thetas": (dict(c, _thetas_swapped=True), {}),
        "fullrotary": (dict(c, _rotary_dim=c["head_dim"]), {}),
        "window127": (dict(c, _window=c["sliding_window"] - 1), {}),
        "top7": (dict(c, _top_k=k - 1), {}),
    }

    def run(conf, kw):
        logits, routes = jax.jit(lambda prm, tok: arch.reference(
            prm, tok, conf, with_routes=True, **kw))(params, tokens)
        return np.asarray(logits), np.sort(np.asarray(routes), axis=-1)

    truth, chosen = run(c, {})
    held = (chosen >= c.get("expert_offset", 0)) \
        & (chosen < c.get("expert_offset", 0) + c["n_routed_experts"])
    print(json.dumps({"variant": "float32", "positions": T,
                      "attn_logit_std": arch.SEEDED_ATTN_LOGIT_STD,
                      "logit_std": float(truth.std()),
                      "expert_local_share": float(held.mean()),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        conf, kw = variants[name]
        got, routes = run(conf, kw)
        diff = np.abs(got - truth)
        same = (routes == chosen).all(-1).mean() \
            if routes.shape == chosen.shape else None
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean()),
            "expert_sets_equal": None if same is None else float(same)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
