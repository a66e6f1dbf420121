"""The second reading a limit of `minicpm-sala-d16`'s check is set
from: how far the plain reference moves, in the check's own two numbers,
when it is computed wrong in a way the check must catch.  Run on the
chip (the reference alone, seeded weights and tokens as the check
draws them, no engine):

    python3 benchmarks/tools/sala_limits.py --seed 2147488001 \
        [--config minicpm-sala-d16] [--logit-std 4] \
        [--variants bf16,fp8,dense,unforced,nodecay]

  bf16      every weight matmul's inputs rounded to bfloat16: the
            configuration's own precision, the noise a limit must clear
  fp8       every weight matmul's inputs rounded to float8_e4m3fn: the
            nearest precision below the configuration's bf16
  dense     selection switched off (`dense_len` out of reach)
  unforced  no forced blocks (no initial block, a local window of one)
  nodecay   the lightning layers' decay dropped (lambda = 1): a
            configuration with `lightning_no_decay` set, which only
            this tool writes

Each line: the variant's largest and mean absolute difference from the
float32 reference over all positions, beside the limits in the file.
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
    p.add_argument("--config", default="minicpm-sala-d16")
    p.add_argument("--variants",
                   default="bf16,fp8,dense,unforced,nodecay")
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
    sp = c["sparse_config"]
    variants = {
        "bf16": (c, {"round_to": "bfloat16"}),
        "fp8": (c, {"round_to": "float8_e4m3fn"}),
        "dense": (dict(c, sparse_config=dict(sp, dense_len=10 ** 9)), {}),
        "unforced": (dict(c, sparse_config=dict(
            sp, init_blocks=0, window_size=sp["block_size"])), {}),
        "nodecay": (dict(c, lightning_no_decay=True), {}),
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
        diff = np.abs(run(conf, kw) - truth)
        print(json.dumps({
            "variant": name, "max_abs_diff": float(diff.max()),
            "mean_abs_diff": float(diff.mean()),
            "max_abs_diff_past_dense_len": float(
                diff[sp["dense_len"]:].max()) if T > sp["dense_len"]
            else None,
            "mean_abs_diff_past_dense_len": float(
                diff[sp["dense_len"]:].mean()) if T > sp["dense_len"]
            else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
