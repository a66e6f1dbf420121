"""SDAR-MoE (`sdar_moe`): a Qwen3-MoE decoder (GQA with an RMSNorm over
each head of q and k, RoPE over the whole head, every layer a softmax
router over routed SwiGLU experts whose top-k weights are renormalised,
no shared expert) that GENERATES BY DIFFUSION OVER BLOCKS: positions come
in blocks of `block_length`; a query sees every key of its own block and
of the blocks before it (causal between blocks, full inside one), and
the logits at position i are of token i ITSELF (no shift): a position
still to be generated holds the mask token, and the model predicts what
stands there.

This module is the model as the serving engine runs it: a config object,
seeded weights, the cache it declares and its own paged step, bound into
a declared body (a decode.PagedBody whose `block` is the block length)
that the config names.  Attention's projections, the span loops and the
expert layer are models/exaone_moe.py's and models/deepseek_v2.py's: this
file holds the mask, the router and the block step.  Every expert is
held here, so every routed pair is: `deepseek_v2.routed_experts` runs a
step's (and a chunk's) 4,096 pairs as one slab in line, one grouped
matmul a projection, each chosen expert read once.

The paged step has two shapes (decode.paged_chunk_step's contract):

  chunk        one row's prompt chunk of T tokens from a scalar `pos`
               (whole pages; the block length divides a page and a chunk,
               so no block straddles either).  Query i sees columns
               through the END OF ITS BLOCK, cut at the prompt's last
               real token: min(((pos + i) // B + 1) B, pos + valid) - 1.
               The keys of a prompt's whole blocks are final after it.
  block step   `pos` [S] (multiples of B), `tokens` [S, B]: every row's
               current block, masks standing where nothing is fixed yet.
               The block's keys and values are written at pos..pos+B-1
               (over what the last step wrote there), then all B x H
               queries of a row attend to its pages through column
               pos + B - 1.  The B columns' query heads are laid beside
               each other per key-value head (H' = B x H heads, B x H / G
               a group), so a row is ONE call of the tick's attention:
               `ops/paged_attention.paged_attention` on a TPU, the span
               loop it is held equal to (`exaone_moe._span_tick`) off it.
               Rows at position 0 are idle.

Which position a step fixes is the engine's (`engine._paged_block_step`):
this step returns logits [S, B, V] and knows no schedule.  A block is
WRITTEN SEVERAL TIMES before its keys are final (once a denoising step,
and once more when every position is fixed): a page whose last block is
half-written is a row's private state, which is why the body is not
`framed` (tiers, kv_export / kv_import, migration and session
checkpoints refuse it by name); whole prompt pages are final after
prefill, so the prefix cache shares them as it does any model's.

The cache (one pytree, `engine._cache`): k, v [L, P, page, G x Dh] (a
token's four heads side by side: `kind` says why) and `moe` [7, 2] int32, the expert layers' counters (deepseek_v2.COUNTERS).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import deepseek_v2 as _ds
from ray_tpu.models import exaone_moe as _em
from ray_tpu.models.decode import PagedBody
from ray_tpu.ops import paged_attention as _pa

_HI = lax.Precision.HIGHEST
COUNTERS = _ds.COUNTERS


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """Published SDAR-30B-A3B-Chat sizes by default; `n_layers`,
    `experts_held` and `expert_offset` say the share this chip holds.
    `block_length` and `mask_token_id` are the family's generation
    settings (the published config gives neither).  Hashable: the engine
    passes it as a static argument."""
    max_seq: int
    n_layers: int = 48
    vocab_size: int = 151936
    d_model: int = 2048
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_d_ff: int = 768
    n_routed_experts: int = 128       # what the router scores: never cut
    top_k: int = 8
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    block_length: int = 4
    mask_token_id: int = 151669
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", self.n_routed_experts)
        if self.top_k > self.n_routed_experts:
            raise ValueError("top_k exceeds the routed experts")
        if self.expert_offset < 0 or self.experts_held < 1 \
                or self.expert_offset + self.experts_held \
                > self.n_routed_experts:
            raise ValueError("the held experts must lie among the routed")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.block_length < 2:
            raise ValueError("block_length must be >= 2 (a block of one "
                             "position is the causal decoder)")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("mask_token_id must be a row of the "
                             "vocabulary")

    @property
    def kind(self) -> _em.AttnKind:
        """Every layer's attention as exaone_moe's functions read it.
        A token's four heads are kept side by side (`flat`): the chip's
        compiler gives a pool that ends in [4, 128] a layout of its own
        with the pages innermost, and the chunk then re-lays the whole
        pool on its way in and out (5 GiB of temporaries at
        sdar-30b-a3b-pp8-d6's sizes; tests/test_tpu_compile.py holds
        that it does not)."""
        return _em.AttnKind(self.n_kv_heads, self.head_dim, self.head_dim,
                            rope_theta=self.rope_theta, qk_norm=True,
                            flat=True)

    @property
    def paged_body(self) -> PagedBody:
        return dataclasses.replace(_BODY, block=self.block_length,
                                   mask_token=self.mask_token_id)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attn_keys(cfg: SdarMoeConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one block step's rows at `pos`: every
    layer holds and reads a row's context and its block."""
    keys = int((np.asarray(pos, np.int64) + cfg.block_length).sum()) \
        * cfg.n_layers
    return keys, keys


def attn_keys_gathered(cfg: SdarMoeConfig, last: np.ndarray,
                       page_size: int, nblk: int) -> int:
    """Keys one step pulls from the pool (`last`: every decode row's
    last column of the call, idle ones' too): on a TPU each row's own
    blocks of pages; elsewhere whole spans to the deepest row's."""
    if _on_tpu():
        token = 2 * cfg.n_kv_heads * cfg.head_dim \
            * jnp.dtype(cfg.dtype).itemsize
        return _pa.keys_copied(last, page_size, nblk, token) * cfg.n_layers
    cols = _em._span_pages(_em._TICK_SPAN_KEYS, page_size, nblk) * page_size
    spans = -(-(int(np.asarray(last).max()) + 1) // cols)
    return len(last) * spans * cols * cfg.n_layers


def check_paging(cfg: SdarMoeConfig, *, page_size: int, prefill_chunk: int,
                 speculate_k: int) -> None:
    B = cfg.block_length
    if page_size % B or prefill_chunk % page_size:
        raise ValueError(
            f"a block of {B} positions straddles neither a page nor a "
            f"chunk: page_size must be a multiple of block_length={B} and "
            f"prefill_chunk of page_size, got page_size={page_size}, "
            f"prefill_chunk={prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model that generates by diffusion "
            "over blocks: a step already takes a block's columns, and a "
            "draft is a sequence of next tokens, which it has none of")


# ---------------------------------------------------------------------------
# Weights and cache


def init_params(cfg: SdarMoeConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer (normal, std 0.02; projections
    back into the residual stream 0.02 / sqrt(2 n_layers); the router in
    float32, as it is applied)."""
    dtype = dtype or cfg.dtype
    D, H, G, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    nrm, swiglu, s, so = _em.seeded_draws(cfg, key, dtype)
    ones = lambda *shape: jnp.ones(shape, jnp.float32)  # noqa: E731

    def layer(i):
        return {"ln1": ones(D), "wq": nrm((D, H, Dh), s),
                "wkv": nrm((D, 2, G, Dh), s), "qn": ones(Dh), "kn": ones(Dh),
                "wo": nrm((H, Dh, D), so), "ln2": ones(D),
                "router": nrm((D, cfg.n_routed_experts), s, jnp.float32),
                "experts": swiglu(cfg.moe_d_ff, cfg.experts_held)}

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i) for i in range(cfg.n_layers)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}


def init_paged_cache(cfg: SdarMoeConfig, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    shape = (cfg.n_layers, num_pages, page_size) \
        + _em._kept(cfg.kind, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32)}


# ---------------------------------------------------------------------------
# The expert layer


def route(router, h, cfg: SdarMoeConfig):
    """Softmax over ALL routed experts, in float32.  h [N, D] -> (expert
    ids [N, top_k], weights [N, top_k] float32): the top_k most probable
    are chosen; a chosen expert's weight is its probability over the sum
    of the chosen ones' (wherever those experts live)."""
    logits = jnp.einsum("nd,de->ne", h.astype(jnp.float32),
                        router.astype(jnp.float32), precision=_HI)
    w, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    return ids.astype(jnp.int32), w / w.sum(-1, keepdims=True)


def _ffn(lp, x, live, counts, cfg: SdarMoeConfig):
    """x + the held routed experts of norm(x).  `counts`: this call's
    additions to COUNTERS so far (a block step counts as a tick)."""
    h = _em._rms(x, lp["ln2"], cfg)
    with jax.named_scope("moe_route"):
        ids, weights = route(lp["router"], h, cfg)
    with jax.named_scope("moe_experts"):
        routed, sizes = _ds.routed_experts(lp["experts"], h, ids, weights,
                                           live, cfg)
    counts = _ds.count_routed(counts, live, sizes, True, cfg)
    return x + routed.astype(x.dtype), counts


# ---------------------------------------------------------------------------
# Attention of a block step (a chunk's is exaone_moe._global_chunk under
# this file's mask)


def _block_attn(lp, x, i, cache, bt, pos, cfg: SdarMoeConfig):
    """x [S x B, D], row s's block at positions pos[s]..pos[s]+B-1."""
    kind, B = cfg.kind, cfg.block_length
    S = pos.shape[0]
    G, Dh, H = kind.n_kv_heads, kind.head_dim, cfg.n_heads
    R = H // G
    psz = cache["k"].shape[2]
    cols = pos[:, None] + jnp.arange(B)[None, :]               # [S, B]
    q, k, v = _em._project(lp, x, cols.reshape(S * B), kind, cfg)
    page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)
    kept = (S, B) + _em._kept(kind, Dh)
    ck = cache["k"].at[i, page, cols % psz].set(k.reshape(kept))
    cv = cache["v"].at[i, page, cols % psz].set(v.reshape(kept))
    # the B columns' query heads beside each other per key-value head
    wide = jnp.moveaxis(q.reshape(S, B, G, R, Dh), 1, 2
                        ).reshape(S, G * B * R, Dh)
    last = pos + (B - 1)
    with jax.named_scope("block_attn"):
        if _on_tpu():
            out = _pa.paged_attention(wide, ck, cv, i, bt, last,
                                      n_kv_heads=G)
        else:
            out = _em._span_tick(wide, ck, cv, i, bt, last, kind)
    out = jnp.moveaxis(out.reshape(S, G, B, R, Dh), 2, 1
                       ).reshape(S * B, H, Dh)
    return _em._close(lp, x, out, cfg), dict(cache, k=ck, v=cv)


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, attend, cfg: SdarMoeConfig):
    counts = [jnp.int32(0)] * len(COUNTERS)
    for i, lp in enumerate(params["layers"]):
        x, cache = attend(lp, x, i, cache)
        x, counts = _ffn(lp, x, live, counts, cfg)
    x = _em._rms(x, params["ln_f"], cfg)
    logits = jnp.einsum("nd,dv->nv", x.astype(cfg.dtype),
                        params["wlm"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, dict(cache, moe=_ds._count(cache["moe"], counts))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: SdarMoeConfig, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract
    for a body whose `block` is not 1 (the module's docstring gives the
    two shapes).  `slot` is taken and not used: nothing here is state of
    a decode row.  Only a chunk's first `valid` tokens, and a step's
    rows past position 0, are routed to experts.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    S, t = tokens.shape
    B = cfg.block_length
    psz = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    embed = lambda tok: jnp.take(params["wte"], tok, axis=0  # noqa: E731
                                 ).astype(cfg.dtype)
    if pos.ndim == 0:
        if S != 1 or t % psz or psz % B:
            raise ValueError(f"a chunk is one row of whole pages of {psz} "
                             f"tokens (whole blocks of {B}), got "
                             f"{tokens.shape}")
        valid = jnp.int32(t) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        bt = block_tables[0]
        cols = pos + jnp.arange(t)
        # through the end of the query's block, cut at the last real
        # token; a pad sees up to itself (a query with no key would put
        # NaNs in the keys it leaves behind, and a masked NaN value
        # still poisons a weighted sum)
        last = jnp.maximum(
            jnp.minimum((cols // B + 1) * B, pos + valid) - 1, cols)

        def attend(lp, x, i, c):
            return _em._global_chunk(lp, x, i, c, bt, pos, cfg.kind, cfg,
                                     last=last)
        logits, cache = _through_layers(
            params, embed(tokens[0]), cache, jnp.arange(t) < valid, attend,
            cfg)
        return logits[None], cache
    if t != B:
        raise ValueError(f"a block step takes {B} columns a row, got "
                         f"{tokens.shape}")

    def attend(lp, x, i, c):
        return _block_attn(lp, x, i, c, block_tables, pos, cfg)
    logits, cache = _through_layers(
        params, embed(tokens.reshape(S * B)), cache, jnp.repeat(pos > 0, B),
        attend, cfg)
    return logits.reshape(S, B, -1), cache


_BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    attn_keys_gathered=attn_keys_gathered,
    snapshot_counters=_ds.snapshot_counters,
    read_counters=_ds.read_counters)
