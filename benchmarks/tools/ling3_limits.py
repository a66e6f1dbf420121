"""The second reading a limit of `ling-3.0-flash-ep8-d6`'s check is set
from: how far the plain reference moves, in the check's own two numbers,
when it is computed wrong in a way the check must catch.  Run on the
chip (the reference alone, seeded weights and tokens as the check draws
them, no engine):

    python3 benchmarks/tools/ling3_limits.py --seed 2147563101 \
        [--config ling-3.0-flash-ep8-d6] [--variants bf16,nodecay,...]

  bf16        every weight matmul's inputs rounded to bfloat16: the
              configuration's own precision, the noise a limit must clear
  fp8         ...to float8_e4m3fn: the nearest precision below it
  nodecay     the decay dropped (alpha = 1)
  nodelta     the delta term dropped (S = diag(alpha) S + beta k v^T)
  beta1       beta = 1
  nocarry     the state not carried across a chunk boundary (zeroed at
              every multiple of the engine's prefill_chunk)
  notail      the convolution's tail not carried across one
  nol2        the l2 norms of q and k dropped
  nogate      KDA's output gate dropped
  bf16state   the delta-rule state rounded to bfloat16 after every token
  nobias      the router's selection bias dropped
  nogroup     the group limit off
  top7        seven experts a token for eight
  scale1      routed_scaling_factor 2.5 dropped
  noheadgate  MLA's gate a head dropped
  norope      RoPE dropped

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions, beside the limits in the file, the
share of (token, expert layer) whose chosen set is the float32
reference's, and the mean decay.
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

CHUNK = "the engine's prefill_chunk"
VARIANTS = {
    "bf16": ({}, {"round_to": "bfloat16"}),
    "fp8": ({}, {"round_to": "float8_e4m3fn"}),
    "nodecay": ({"_no_decay": True}, {}),
    "nodelta": ({"_no_delta": True}, {}),
    "beta1": ({"_beta_one": True}, {}),
    "nocarry": ({"_state_reset_every": CHUNK}, {}),
    "notail": ({"_tail_reset_every": CHUNK}, {}),
    "nol2": ({"_no_l2norm": True}, {}),
    "nogate": ({"_no_out_gate": True}, {}),
    "bf16state": ({"_state_dtype": "bfloat16"}, {}),
    "nobias": ({"_no_router_bias": True}, {}),
    "nogroup": ({"_no_group_limit": True}, {}),
    "top7": ({"_top_k": 7}, {}),
    "scale1": ({"_routed_scale": 1.0}, {}),
    "noheadgate": ({"_no_head_gate": True}, {}),
    "norope": ({"_no_rope": True}, {}),
}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="ling-3.0-flash-ep8-d6")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--positions", type=int, default=None)
    args = p.parse_args()
    reg = Registry(ROOT)
    c = reg.config(args.config)
    arch = arch_of(c, reg.dir)
    check = c["serving"]["check"]
    width = c["serving"]["engine"]["prefill_chunk"]
    T = args.positions or check["prompt_len"] + check["decode_tokens"]
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    rng = np.random.default_rng([int(args.seed), 0xC0FFEE])
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=T), jnp.int32)

    def run(switches, kw):
        conf = dict(c, **{k: width if v == CHUNK else v
                          for k, v in switches.items()})
        logits, routes, decay = jax.jit(lambda prm, tok: arch.reference(
            prm, tok, conf, with_routes=True, **kw))(params, tokens)
        return np.asarray(logits), np.sort(np.asarray(routes), -1), \
            float(decay)

    truth, chosen, decay = run({}, {})
    print(json.dumps({"variant": "float32", "positions": T,
                      "logit_std": float(truth.std()), "decay_mean": decay,
                      "held_share_of_pairs": float(
                          (chosen < c["num_experts"]).mean()),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        got, routes, decay = run(*VARIANTS[name])
        diff = np.abs(got - truth)
        same = (routes == chosen).all(-1).mean() \
            if routes.shape == chosen.shape else None
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean()),
            "experts_equal": None if same is None else float(same),
            "decay_mean": decay}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
