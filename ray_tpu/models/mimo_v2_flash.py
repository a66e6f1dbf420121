"""MiMo-V2-Flash (`mimo_v2_flash`): a GQA decoder whose attention layers
are of two kinds that differ in more than what they see.  FULL layers
(one in six, and layer 0) attend to everything with 4 key-value heads
and a RoPE theta of 5e6; WINDOW layers attend to the last `window`
tokens with 8 key-value heads, a theta of 1e4 and a learned SINK per
head: a score that joins the softmax's denominator and has no value, so
a head's weights sum to less than 1.  In both kinds a key is 192 wide
and a value 128, RoPE turns the first 64 of a head's 192 and leaves the
rest, and values are scaled by `v_scale` before they are cached.  Layer 0
is a dense SwiGLU; every later layer holds routed SwiGLU experts under a
sigmoid router that renormalises its top-k, with no shared expert.

This module is the model as the serving engine runs it: a config object
and seeded weights.  Everything that computes is `models/exaone_moe.py`'s
(K-EXAONE: window rings beside pages, the same router), which reads one
`AttnKind` a kind of layer from the config's `kind`: its paged step for a
prefill chunk and for a decode tick, the cache it declares, the counters.
The config names exaone_moe's declared body (`exaone_moe.BODY`) as its
own, so the engine's two jitted programs (`engine._prefill_chunk`,
`engine._paged_tick`) run it as they run every model.

The cache (one pytree, `engine._cache`) is four arrays of four shapes:

  k   [n_full, P, page, 4 x 192]    pages of the FULL layers only: they
  v   [n_full, P, page, 4 x 128]    are all the pool; a token is
                                    2 layers x 4 x (192 + 128) x 2 B
  wk  [n_window, B, W, 8 x 192]     a RING per decode row and window
  wv  [n_window, B, W, 8 x 128]     layer (exaone_moe's: position p at
                                    p mod W, keys already rotated)
                                    (a token's heads side by side:
                                    exaone_moe._kept says why)
  moe [7, 2], sink [2, 2] int32     the expert layers' counters and the
                                    sinks' (the share of their softmaxes
                                    they took; the softmaxes counted)

A ring is state per decode row (`row_state_keys`): the prefix cache, tiers,
kv_export / kv_import, migration, session checkpoints and speculation
refuse this model by name, as they refuse K-EXAONE.

What the published config does not pin, and what was taken (the
benchmark's configuration file argues each): pre-norm blocks; no norm on
q or k; the rotary dimensions first and paired (i, i + 32); the value
scale applied before attention; a window that counts the query's own
position.  The multi-token-prediction layers are not here.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

from ray_tpu.models import exaone_moe as _em

AttnKind = _em.AttnKind


@dataclasses.dataclass(frozen=True)
class MimoV2FlashConfig:
    """Published MiMo-V2-Flash sizes by default; `experts_held`,
    `expert_offset`, `vocab_size` and `n_layers` say the share this chip
    holds.  `hybrid_layer_pattern` is the published per-layer list (0: a
    full layer, 1: a window layer), of which the first `n_layers`
    entries are run.  Hashable: the engine passes it as a static
    argument."""
    max_seq: int
    n_layers: int = 48
    vocab_size: int = 152576
    d_model: int = 4096
    n_heads: int = 64
    n_kv_heads: int = 4               # full layers
    swa_n_kv_heads: int = 8           # window layers
    head_dim: int = 192               # a query's and a key's width
    v_head_dim: int = 128
    d_ff: int = 16384                 # the leading dense layers
    first_k_dense: int = 1
    moe_d_ff: int = 2048
    n_routed_experts: int = 256       # what the router scores: never cut
    top_k: int = 8
    routed_scaling_factor: float = 1.0
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0) \
        + (1, 1, 1, 1, 1, 0) * 7
    window: int = 128
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    rotary_dim: int = 64              # int(0.334 x 192)
    v_scale: float = 0.707
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        object.__setattr__(self, "hybrid_layer_pattern",
                           tuple(self.hybrid_layer_pattern)[:self.n_layers])
        if len(self.hybrid_layer_pattern) != self.n_layers \
                or set(self.hybrid_layer_pattern) - {0, 1}:
            raise ValueError("hybrid_layer_pattern must name every layer "
                             "run, 0 (full) or 1 (window)")
        if self.window < 1:
            raise ValueError("window must be >= 1")
        if self.top_k > self.n_routed_experts:
            raise ValueError("top_k exceeds the routed experts")
        if self.expert_offset < 0 or self.experts_held < 1 \
                or self.expert_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError("the held experts must lie among the routed")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense must be 0..n_layers")
        if self.n_heads % self.n_kv_heads \
                or self.n_heads % self.swa_n_kv_heads:
            raise ValueError("n_heads must be a multiple of both kinds' "
                             "key-value heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary_dim must be even and within a head")

    @property
    def sliding_windows(self) -> Tuple[int, ...]:
        """Per layer run: the window, or 0 for a full layer (what
        exaone_moe's layer walk reads)."""
        return tuple(self.window if p else 0
                     for p in self.hybrid_layer_pattern)

    @property
    def n_window(self) -> int:
        return sum(self.hybrid_layer_pattern)

    @property
    def n_global(self) -> int:
        return self.n_layers - self.n_window

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    def kind(self, windowed: bool) -> AttnKind:
        # a key of 192 is a lane and a half: kept flat (exaone_moe._kept)
        shared = dict(rotary_dim=self.rotary_dim, qk_norm=False,
                      v_scale=self.v_scale, flat=self.head_dim % 128 != 0)
        if windowed:
            return AttnKind(self.swa_n_kv_heads, self.head_dim,
                            self.v_head_dim, window=self.window,
                            rope_theta=self.swa_rope_theta, sink=True,
                            **shared)
        return AttnKind(self.n_kv_heads, self.head_dim, self.v_head_dim,
                        rope_theta=self.rope_theta, **shared)

    @property
    def paged_body(self) -> _em.PagedBody:
        """exaone_moe's, which reads this config's `kind`,
        `sliding_windows` and expert fields."""
        return _em.BODY


def init_params(cfg: MimoV2FlashConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer (normal, std 0.02; projections
    back into the residual stream 0.02 / sqrt(2 n_layers); the router in
    float32, as it is applied; its selection bias and the window layers'
    sinks zero: a zero sink still takes exp(0) of its softmax)."""
    dtype = dtype or cfg.dtype
    D, H, F = cfg.d_model, cfg.n_heads, cfg.moe_d_ff
    nrm, swiglu, s, so = _em.seeded_draws(cfg, key, dtype)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731

    def layer(i):
        kind = cfg.kind(bool(cfg.hybrid_layer_pattern[i]))
        G, Dh, Dv = kind.n_kv_heads, kind.head_dim, kind.v_head_dim
        lp = {"ln1": ones(D), "wq": nrm((D, H, Dh), s),
              "wk": nrm((D, G, Dh), s), "wv": nrm((D, G, Dv), s),
              "wo": nrm((H, Dv, D), so), "ln2": ones(D)}
        if kind.sink:
            lp["sink"] = jnp.zeros((H,), jnp.float32)
        if i < cfg.first_k_dense:
            return dict(lp, **swiglu(cfg.d_ff))
        return dict(lp, router=nrm((D, cfg.n_routed_experts), s,
                                   jnp.float32),
                    router_bias=jnp.zeros((cfg.n_routed_experts,),
                                          jnp.float32),
                    experts=swiglu(F, cfg.experts_held))

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i) for i in range(cfg.n_layers)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}
