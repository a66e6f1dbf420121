"""The second reading a limit of `jamba2-3b`'s check is set from: how far
the plain reference moves, in the check's own two numbers, when it is
computed wrong in a way the check must catch.  Run on the chip (the
reference alone, seeded weights and tokens as the check draws them, no
engine):

    python3 benchmarks/tools/jamba_limits.py --seed 2147498101 \
        [--config jamba2-3b] [--logit-std 4] \
        [--variants bf16,fp8,nocarry,notail,state16,nonorms,noD,
                    nosoftplus,lindecay,rope]

  bf16        every weight matmul's inputs rounded to bfloat16: the
              configuration's own precision, the noise a limit must clear
  fp8         ...to float8_e4m3fn: the nearest precision below it
  nocarry     the scan state not carried across a chunk boundary (zero
              at every multiple of the configuration's prefill_chunk)
  notail      the convolution's tail not carried across it
  state16     the scan state rounded to bfloat16 after every token
  nonorms     the RMSNorms of dt, B and C dropped
  noD         the D skip dropped
  nosoftplus  delta without softplus (the bare projection's magnitude)
  lindecay    the decay 1 + delta A for exp(delta A)
  rope        RoPE (theta 10,000) applied in the attention layers

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions (not finite: the variant diverged,
which fails any limit), beside the limits in the file.
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

ALL = "bf16,fp8,nocarry,notail,state16,nonorms,noD,nosoftplus,lindecay,rope"


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--config", default="jamba2-3b")
    p.add_argument("--variants", default=ALL)
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
    chunk = c["serving"]["engine"]["prefill_chunk"]
    T = args.positions or check["prompt_len"] + check["decode_tokens"]
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    params = jax.jit(lambda key: arch.init(cfg, key, cfg.dtype))(
        seed_key(args.seed))
    rng = np.random.default_rng([int(args.seed), 0xC0FFEE])
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, size=T), jnp.int32)
    variants = {
        "bf16": (c, {"round_to": "bfloat16"}),
        "fp8": (c, {"round_to": "float8_e4m3fn"}),
        "nocarry": (dict(c, _state_reset_every=chunk), {}),
        "notail": (dict(c, _tail_reset_every=chunk), {}),
        "state16": (dict(c, _state_dtype="bfloat16"), {}),
        "nonorms": (dict(c, _no_dtbc_norms=True), {}),
        "noD": (dict(c, _no_D=True), {}),
        "nosoftplus": (dict(c, _no_softplus=True), {}),
        "lindecay": (dict(c, _linear_decay=True), {}),
        "rope": (dict(c, _rope=10000.0), {}),
    }

    def run(conf, kw):
        return np.asarray(jax.jit(lambda prm, tok: arch.reference(
            prm, tok, conf, **kw))(params, tokens))

    truth = run(c, {})
    print(json.dumps({"variant": "float32", "positions": T,
                      "attn_logit_std": arch.SEEDED_ATTN_LOGIT_STD,
                      "logit_std": float(truth.std()),
                      "tolerance": check["tolerance"]}), flush=True)
    for name in args.variants.split(","):
        conf, kw = variants[name]
        got = run(conf, kw)
        diff = np.abs(got - truth)
        finite = bool(np.isfinite(got).all())
        print(json.dumps({
            "variant": name, "finite": finite,
            "max_abs_diff": float(diff.max()) if finite else None,
            "mean_abs_diff": float(diff.mean()) if finite else None,
            "argmax_equal": float((got.argmax(-1)
                                   == truth.argmax(-1)).mean())}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
