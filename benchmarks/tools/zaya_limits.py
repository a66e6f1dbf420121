"""The second reading a limit of `zaya1-8b-pp2-d20`'s check is set from:
how far the plain reference moves, in the check's own two numbers, when
it is computed wrong in a way the check must catch.  Run on the chip
(the reference alone, seeded weights and tokens as the check draws them,
no engine):

    python3 benchmarks/tools/zaya_limits.py --seed 2147498101 \
        [--config zaya1-8b-pp2-d20] \
        [--variants bf16,fp8,noconv,conv1dw,nomean,vnow,tau1,fullrotary,
                    nocarry,top2,gate1,nojoin]

  bf16        every weight matmul's inputs rounded to bfloat16: the
              configuration's own precision, the noise a limit must clear
  fp8         ...to float8_e4m3fn: the nearest precision below it
  noconv      both convolutions left out (q, k = latent + mean)
  conv1dw     the second convolution depthwise (its taps' diagonals)
  nomean      the q-k mean left out
  vnow        the late value head taken from this token
  tau1        the keys' temperature 1
  fullrotary  RoPE over the whole head of 128, not its first 64
  nocarry     depth averaging left out (no gamma x the last layer's
              router state)
  top2        two experts a token, not one
  gate1       the chosen expert's weight replaced by 1 (a top-1 that was
              renormalised)
  nojoin      residual scaling left out (plain x + f)

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions, beside the limits in the file, the
share of (token, layer) whose chosen expert is the float32 reference's,
and the mean weight a chosen expert got.
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
    "bf16": ({}, {"round_to": "bfloat16"}),
    "fp8": ({}, {"round_to": "float8_e4m3fn"}),
    "noconv": ({"_no_conv": True}, {}),
    "conv1dw": ({"_conv1_depthwise": True}, {}),
    "nomean": ({"_no_mean": True}, {}),
    "vnow": ({"_v_now": True}, {}),
    "tau1": ({"_tau_one": True}, {}),
    "fullrotary": ({"_rotary_dim": None}, {}),     # the head's width
    "nocarry": ({"_no_carry": True}, {}),
    "top2": ({"_top_k": 2}, {}),
    "gate1": ({"_gate_one": True}, {}),
    "nojoin": ({"_no_join": True}, {}),
}


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="zaya1-8b-pp2-d20")
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
        conf = dict(c, **{k: c["head_dim"] if v is None else v
                          for k, v in switches.items()})
        logits, routes, gates = jax.jit(lambda prm, tok: arch.reference(
            prm, tok, conf, with_routes=True, **kw))(params, tokens)
        return np.asarray(logits), np.asarray(routes), float(gates.mean())

    truth, chosen, gate = run({}, {})
    print(json.dumps({"variant": "float32", "positions": T,
                      "logit_std": float(truth.std()),
                      "gate_weight_mean": gate,
                      "experts_chosen": np.bincount(
                          chosen.reshape(-1),
                          minlength=c["num_experts"]).tolist(),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        got, routes, gate = run(*VARIANTS[name])
        diff = np.abs(got - truth)
        same = (routes == chosen).all(-1).mean() \
            if routes.shape == chosen.shape else None
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean()),
            "expert_equal": None if same is None else float(same),
            "gate_weight_mean": gate}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
