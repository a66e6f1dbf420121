"""The one general traffic generator: a mix file in, a schedule out.

Stratified: each distribution of a mix is sampled on a FIXED quantile
grid of `block` points, so every block of `block` requests holds the
same multiset of prompt lengths, output lengths and inter-arrival gaps
whatever the seed.  `--seed` chooses only the order inside each block
(an independent permutation per quantity per block) and the token ids.
A window that spans whole blocks is therefore offered identical work in
every run.

Mix file keys (JSON):
  kind            "serve"
  loop            "open" (arrivals on a schedule) | "closed" (`clients`
                  callers, each sending its next request when the last
                  completes)
  rate_rps        open loop: offered rate (requests per second)
  clients         closed loop: number of callers
  blocks_per_window   open loop: the window is cut into this many
                  blocks, so block = round(rate * seconds / blocks)
  block           closed loop: requests per block
  warmup_blocks   open loop: blocks sent before the window opens
  warmup_first_tokens  closed loop: the window opens when this many
                  requests have received a first token
  prompt_len / output_len   {"dist": "lognormal", "median", "sigma",
                  "min", "max"} | {"dist": "fixed", "value"}
  gaps            {"dist": "exponential"} | {"dist": "fixed"}
  order_seed      optional: the orders inside the blocks are drawn from
                  this number instead of `--seed`, so every run offers
                  its requests in ONE order and `--seed` chooses the
                  token ids and the weights alone.  For a mix whose
                  window holds so few requests that the order is the
                  work (`longdoc`: ~15 requests a window)
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Any, Dict, List

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_grid(spec: Dict[str, Any], n: int) -> List[float]:
    """The n mid-quantile points (i + 0.5) / n of a distribution."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "fixed":
        return [float(spec["value"])] * n
    if dist == "lognormal":
        mu, sigma = math.log(spec["median"]), spec["sigma"]
        return [min(spec["max"], max(spec["min"],
                math.exp(mu + sigma * _NORMAL.inv_cdf(q)))) for q in qs]
    if dist == "exponential":      # unit mean; the caller scales by rate
        raw = [-math.log(1.0 - q) for q in qs]
        scale = n / sum(raw)       # block sums to exactly n mean gaps
        return [r * scale for r in raw]
    raise ValueError(f"unknown distribution {dist!r}")


def block_size(mix: Dict[str, Any], seconds: float) -> int:
    if mix["loop"] == "open":
        return max(1, round(mix["rate_rps"] * seconds
                            / mix["blocks_per_window"]))
    return int(mix["block"])


def schedule(mix: Dict[str, Any], seed: int, seconds: float,
             n_blocks: int) -> List[Dict[str, Any]]:
    """`n_blocks` blocks of requests: [{"index", "due" (seconds from the
    start of load; None in a closed loop), "prompt_len", "max_new"}].
    In an open loop a block lasts exactly seconds / blocks_per_window."""
    n = block_size(mix, seconds)
    rng = random.Random(mix.get("order_seed", seed))
    prompts = [int(round(x)) for x in quantile_grid(mix["prompt_len"], n)]
    outputs = [int(round(x)) for x in quantile_grid(mix["output_len"], n)]
    open_loop = mix["loop"] == "open"
    if open_loop:
        block_s = seconds / mix["blocks_per_window"]
        gaps = [g * block_s / n
                for g in quantile_grid(mix.get("gaps",
                                               {"dist": "exponential"}), n)]
    out, t = [], 0.0
    for b in range(n_blocks):
        order = list(range(n))
        rng.shuffle(order)
        p = [prompts[i] for i in order]
        rng.shuffle(order)
        o = [outputs[i] for i in order]
        g = None
        if open_loop:
            g = gaps[:]
            rng.shuffle(g)
            t = b * block_s        # no drift from summing floats
        for i in range(n):
            due = None
            if open_loop:
                # a request is due half a gap into its slot, so a block's
                # arrivals all fall inside the block
                due = t + g[i] / 2
                t += g[i]
            out.append({"index": b * n + i, "due": due,
                        "prompt_len": p[i], "max_new": o[i]})
    return out


def prompt_tokens(seed: int, index: int, length: int, vocab: int
                  ) -> List[int]:
    """Token ids of request `index`: distinct per request and per seed,
    so no prompt shares a prefix page with another."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(1, vocab, size=length).tolist()


def totals(requests: List[Dict[str, Any]]) -> Dict[str, int]:
    return {"requests": len(requests),
            "prompt_tokens": sum(r["prompt_len"] for r in requests),
            "output_tokens": sum(r["max_new"] for r in requests)}
