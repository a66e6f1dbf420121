"""The second reading a limit of `glm-5-ep16-d5`'s check is set
from: how far the plain reference moves, in the check's own two numbers,
when it is computed wrong in a way the check must catch.  Run on the
chip (the reference alone, seeded weights and tokens as the check draws
them, no engine):

    python3 benchmarks/tools/glm5_limits.py --seed 2147566101 \
        [--config glm-5-ep16-d5] [--variants bf16,noselect,...]

  bf16        every weight matmul's inputs rounded to bfloat16: the
              configuration's own precision, the noise a limit must
              clear (routing and selection swaps included)
  fp8         ...to float8_e4m3fn: the nearest precision below it
  noselect    no selection: a layer attends to every key it sees
  topk/2      index_topk halved
  topk-1      index_topk - 1 keys chosen
  w1          the indexer's head weights w set to 1
  norelu      the indexer's products summed without the ReLU
  nobias      the router's selection bias dropped
  scale1      routed_scaling_factor 1 for 2.5
  top7        seven experts a token for eight
  noidxbias   the indexer key's LayerNorm bias dropped
  noidxrope   the indexer's queries and keys not rotated
  norope      RoPE dropped everywhere

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions, beside the limits in the file, the
share of (token, expert layer) whose chosen experts are the float32
reference's, and the share of (position, layer) whose chosen keys
are.
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

LESS, HALF = "one fewer than the file's", "half the file's"
VARIANTS = {
    "bf16": ({}, {"round_to": "bfloat16"}),
    "fp8": ({}, {"round_to": "float8_e4m3fn"}),
    "noselect": ({"_no_selection": True}, {}),
    "topk/2": ({"_index_topk": HALF}, {}),
    "topk-1": ({"_index_topk": LESS}, {}),
    "w1": ({"_head_weights_one": True}, {}),
    "norelu": ({"_no_relu": True}, {}),
    "nobias": ({"_no_router_bias": True}, {}),
    "scale1": ({"_routed_scaling_factor": 1.0}, {}),
    "top7": ({"_top_k": LESS}, {}),
    "noidxbias": ({"_no_index_bias": True}, {}),
    "noidxrope": ({"_no_index_rope": True}, {}),
    "norope": ({"_no_rope": True}, {}),
}
FILE_KEY = {"_index_topk": "index_topk", "_top_k": "num_experts_per_tok"}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="glm-5-ep16-d5")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--positions", type=int, default=None)
    args = p.parse_args()
    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    check = c["serving"]["check"]
    T = args.positions or check["prompt_len"] + check["decode_tokens"]
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    rng = np.random.default_rng([int(args.seed), 0xC0FFEE])
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=T), jnp.int32)

    def run(switches, kw):
        conf = dict(c, **{k: c[FILE_KEY[k]] - 1 if v == LESS
                          else c[FILE_KEY[k]] // 2 if v == HALF else v
                          for k, v in switches.items()})
        logits, routes, masks = jax.jit(lambda prm, tok: arch.reference(
            prm, tok, conf, with_routes=True, **kw))(params, tokens)
        return np.asarray(logits), np.sort(np.asarray(routes), -1), \
            np.asarray(masks)

    truth, chosen, keys = run({}, {})
    print(json.dumps({"variant": "float32", "positions": T,
                      "logit_std": float(truth.std()),
                      "held_share_of_pairs": float(
                          (chosen < c["n_routed_experts"]).mean()),
                      "keys_a_query": float(keys.sum(-1).mean()),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        got, routes, masks = run(*VARIANTS[name])
        diff = np.abs(got - truth)
        same = (routes == chosen).all(-1).mean() \
            if routes.shape == chosen.shape else None
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean()),
            "experts_equal": None if same is None else float(same),
            "keys_equal": float((masks == keys).all(-1).mean()),
            "keys_overlap": float((masks & keys).sum()
                                  / max(1, keys.sum()))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
