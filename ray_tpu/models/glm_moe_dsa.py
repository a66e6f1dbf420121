"""GLM-5 (`glm_moe_dsa`): a decoder whose every layer is DeepSeek-V2's
multi-head latent attention over a latent page with a DeepSeek-sparse-
attention INDEXER beside it: a second, 128-wide key a token, 32 small
heads that score a query against every key its row holds, and attention
over the 2,048 best alone.  The feed-forward is a dense SwiGLU in the
leading layers, then one shared + 256 sigmoid-routed SwiGLU experts
top-8 (`exaone_moe.route` and `_ffn`, letter for letter).  This module
is the model as the serving engine runs it: a config object, seeded
weights, the cache it declares, and its own paged step for a prefill
chunk and for a decode tick, bound into one declared body (`BODY`, a
decode.PagedBody) that the config names, so the engine's two jitted
programs (`engine._prefill_chunk`, `engine._paged_tick`) run it as they
run every model.

The cache (one pytree, `engine._cache`):

  lat   [L, P, page, 640]    the latent pool: deepseek_v2's row (512
                             normed latent + 64 rotated key part + 64
                             zeros)
  idx   [L, P, page, 128]    the indexer's key of the same token, normed
                             and rotated, under the SAME block table:
                             recomputing it would read the stream of
                             every past token
  moe   [7, 2] int32         the expert layers' counters
                             (deepseek_v2.COUNTERS)
  dsa   [7, 2] int32         DSA_COUNTERS, below

`lat` and `idx` are the pool (`page_keys`: two arrays of unequal width,
one table) and nothing is state of a decode row.  A page is a latent row
and an indexer key, not K then V of [page, Hkv, Dh] (not `framed`): what
frames pages (tiers, kv_export / kv_import, migration, sessions) refuses
the body by that declaration (kv_tier.refuse_unframed), as it refuses
deepseek_v2; the prefix cache shares whole pages of both arrays through
the one table and serves it.

A layer, in a chunk and in a tick alike: the indexer scores the query
against the row's cached `idx` keys span by span, its 32 heads reduced
inside the span (`dsa_index`: I(t, s) = sum_j w_t,j relu(q_t,j . k_s),
float32), and the `index_topk` largest are taken (`dsa_select`: a
threshold found by bisection, no sort; every key while the row holds no
more).  A TICK scores its LIVE rows alone, eight a trip, each over its
own pages, and attends ABSORBED under the choice (`dsa_attend`,
deepseek_v2._attend_under) by the cheaper of two fetches, read from the
rows' depths once a tick (deepseek_v2.walks: the live rows' keys HELD
x 2.0 ns against their keys CHOSEN x 22 ns, which cross where a row
holds 11 x `index_topk`, ~22.5k keys): it WALKS each row's own latent pages under
the 0/1 mask through `ops/paged_attention.py`, a page a copy, or,
deeper, LISTS the chosen positions and GATHERS their latents, and only
those, out of the paged pool (deepseek_v2._attend_chosen); without a
TPU it gathers.  A CHUNK scores its 512
queries against every span up to its last token and attends EXPANDED
under the choice, a 0/1 mask over deepseek_v2's span loop (`dsa_attend`
too): a gather of 512 x 2,048 latent rows, ~19 ms a layer whatever the
context, costs more than the loop (0.072 ms a span of 128 keys) until a
row holds ~25k keys (PERF.md section 6, PR 65 and PR 66).

The expert layer is told which experts it holds (`experts_held`,
`expert_offset`), as exaone_moe's is.

What the published config leaves to the family's convention is argued in
the benchmark's configuration file (`assumed`).  Relabellings under
seeded weights, as deepseek_v2.py lists its own: kv_b is kept as its
halves `wk_b` / `wv_b`, head-major; `wq_b` and the indexer's `wiq` are
heads-major.  RoPE pairs are (2i, 2i + 1), as `rope_interleave` and
`indexer_rope_interleave` say.  The multi-token-prediction layer is not
here.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import deepseek_v2 as _ds
from ray_tpu.models import exaone_moe as _em
from ray_tpu.models.bailing_hybrid import _rope   # pairs (2i, 2i + 1)
from ray_tpu.models.decode import PagedBody

COUNTERS = _ds.COUNTERS
# `keys_scored`: indexer keys a call read, a (query, key) pair each
# (whole spans: a tick's live rows each to its own depth, a chunk's every
# query against every span up to its last token); `keys_chosen`: the
# keys chosen by ticks' live rows and chunks' real tokens, min(position
# + 1, index_topk) each; `tick_keys_chosen`: the ticks' part of that;
# `tick_keys_attended`: the keys a tick's attention weighed, counted
# where it does: by `ops/paged_attention.py` where the mask is applied
# on a walk, by deepseek_v2._attend_chosen as its trips go on a gather
# (the filled slots of the rows a trip visited), so over
# `tick_keys_chosen` it reads 1 where attention touched the chosen and
# nothing else, and moves if a mask bit is lost, the listing fills a
# slot too few or a trip visits a row too many; `rows_selecting` /
# `rows_live`: a tick's live rows past `index_topk`, and its live rows;
# `rows_walked`: its live rows whose pages were walked under the mask
# (the others' chosen latents were gathered); all summed over layers.
DSA_COUNTERS = ("keys_scored", "keys_chosen", "tick_keys_chosen",
                "tick_keys_attended", "rows_selecting", "rows_live",
                "rows_walked")
# Keys one span of the indexer's scoring covers (whole pages that divide
# the table).  A chunk scores all its queries against a span with 32
# heads in float32, [queries, 32, keys], before they are reduced.
_INDEX_TICK_SPAN_KEYS = 4096
_INDEX_CHUNK_SPAN_KEYS = 1024
# Live rows one trip of a tick's scoring takes side by side, each over
# its own pages (8 rows x 4,096 keys of 128 are 8 MB, their float32
# products over 32 heads 4 MB).
_INDEX_TICK_ROWS = 8


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    """Published GLM-5 sizes by default; `n_layers`, `first_k_dense`,
    `experts_held`, `expert_offset` and `vocab_size` say the share this
    chip holds.  It answers what deepseek_v2's latent attention and
    expert walk and exaone_moe's router read of a config.  Hashable: the
    engine passes it as a static argument."""
    max_seq: int
    n_layers: int = 78
    vocab_size: int = 154880
    d_model: int = 6144
    n_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_norm_eps: float = 1e-6
    d_ff: int = 12288             # the leading dense layers
    first_k_dense: int = 3
    moe_d_ff: int = 2048
    n_routed_experts: int = 256   # what the router scores: never cut
    n_shared_experts: int = 1
    top_k: int = 8
    routed_scaling_factor: float = 2.5
    experts_held: Optional[int] = None    # None: all of them
    expert_offset: int = 0
    rms_eps: float = 1e-5
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
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError("first_k_dense must be 0..n_layers")
        if self.index_topk < 1 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the indexer chooses a key or more and "
                             "rotates no more than its head")

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.head_dim ** -0.5

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def attn_keys(cfg: GlmMoeDsaConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and layers.  A layer HOLDS all `pos + 1` and
    READS `min(pos + 1, index_topk)`: the keys read stop growing with
    the position while the keys held do not."""
    pos = np.asarray(pos, np.int64)
    return (int(np.minimum(pos + 1, cfg.index_topk).sum()) * cfg.n_layers,
            int((pos + 1).sum()) * cfg.n_layers)


def attn_keys_gathered(cfg: GlmMoeDsaConfig, pos: np.ndarray,
                       page_size: int, nblk: int) -> int:
    """Latents one tick pulls from the pool (`pos` of all decode rows,
    idle ones at 0), by the fetch it takes (deepseek_v2.walks): where
    it walks, each live row's own blocks of pages, what the kernel
    copies; where it gathers, `index_topk` slots a live row and layer,
    whatever the row holds; an idle row nothing."""
    pos = np.asarray(pos)
    topk = min(cfg.index_topk, nblk * page_size)
    if _ds._on_tpu() and _ds.walks(pos, topk):
        return _ds.attn_keys_gathered(cfg, pos, page_size, nblk)
    return int((pos > 0).sum()) * topk * cfg.n_layers


def chunk_selects(cfg: GlmMoeDsaConfig, start: int) -> bool:
    """Whether the prefill chunk at `start` chooses among more keys than
    it keeps: every query at or past `index_topk` does."""
    return start >= cfg.index_topk


check_paging = _ds.check_paging     # whole latent pages, no speculation


# ---------------------------------------------------------------------------
# Weights and cache


def seeded_gain(cfg: GlmMoeDsaConfig, logit_std: float = 4.0) -> float:
    """What `wq_b` is scaled by so that a seeded attention logit has
    standard deviation `logit_std`: unit-RMS inputs through matrices of
    std 0.02 give a query latent and a key latent of RMS 1 (the norms'
    gains are 1), so q has std 0.02 sqrt(q_lora_rank), k_nope 0.02
    sqrt(kv_lora_rank) and the rotary key part, which no norm follows,
    0.02 sqrt(d_model)."""
    q = 0.02 ** 2 * cfg.q_lora_rank
    k_nope = 0.02 ** 2 * cfg.kv_lora_rank
    k_pe = 0.02 ** 2 * cfg.d_model
    std = (cfg.qk_nope_head_dim * q * k_nope
           + cfg.qk_rope_head_dim * q * k_pe) ** 0.5 * cfg.softmax_scale
    return logit_std / std


def init_params(cfg: GlmMoeDsaConfig, key, dtype=None) -> Dict:
    """Seeded weights, one dict a layer, drawn so that every mechanism
    moves the logits.  Matrices are normal, std 0.02 (projections back
    into the residual stream 0.02 / sqrt(2 n_layers)), norms' gains 1.
    Beside them:

      wq_b     scaled (`seeded_gain`) so that a seeded attention logit
               has standard deviation 4: a handful of keys hold most of
               a head's weight, as in a trained model, so which 2,048 a
               query keeps and how its keys are rotated move the output
               (near-uniform attention over thousands of keys hides
               both)
      indexer  `wiq`, `wik`, `wiw` at 0.02: q_j . k has a standard
               deviation of ~10 over unit-variance keys, half of the 32
               heads' terms pass the ReLU, and the head weights h W_w
               (std ~1.6 before the two constant factors) take both
               signs: I(t, .) spreads over ~0.8 with no two keys equal,
               and the choice is far from "the last 2,048"; the key's
               LayerNorm has gain 1 and a bias normal of std 0.1
               (float32), so that dropping the bias moves the choice
      router   float32, as it is applied: scores sigmoid(N(0, 1.6)); the
               selection bias normal of std 0.02, several times the gap
               between the 8th and 9th best of 256: it moves choices,
               and a zero bias could be seen by no check
    """
    dtype = dtype or cfg.dtype
    D, H, F = cfg.d_model, cfg.n_heads, cfg.moe_d_ff
    rq, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    Hi, di = cfg.index_n_heads, cfg.index_head_dim
    f32 = jnp.float32
    nrm, swiglu, s, so = _em.seeded_draws(cfg, key, dtype, per_layer=24)
    ones = lambda *shape: jnp.ones(shape, f32)  # noqa: E731

    def layer(i):
        lp = {"ln1": ones(D), "wq_a": nrm((D, rq), s), "q_norm": ones(rq),
              "wq_b": nrm((rq, H * (dn + dr)), s * seeded_gain(cfg)),
              "wkv_a": nrm((D, kr + dr), s), "kv_norm": ones(kr),
              "wk_b": nrm((H, dn, kr), s), "wv_b": nrm((H, kr, dv), s),
              "wo": nrm((H, dv, D), so),
              "wiq": nrm((rq, Hi, di), s), "wik": nrm((D, di), s),
              "ik_norm": ones(di), "ik_bias": nrm((di,), 0.1, f32),
              "wiw": nrm((D, Hi), s), "ln2": ones(D)}
        if i < cfg.first_k_dense:
            return dict(lp, **swiglu(cfg.d_ff))
        return dict(lp, router=nrm((D, cfg.n_routed_experts), s, f32),
                    router_bias=nrm((cfg.n_routed_experts,), s, f32),
                    shared=swiglu(cfg.n_shared_experts * F),
                    experts=swiglu(F, cfg.experts_held))

    return {"wte": nrm((cfg.vocab_size, D), s),
            "layers": tuple(layer(i) for i in range(cfg.n_layers)),
            "ln_f": ones(D), "wlm": nrm((D, cfg.vocab_size), s)}


def init_paged_cache(cfg: GlmMoeDsaConfig, num_pages: int, page_size: int,
                     num_slots: Optional[int] = None) -> Dict:
    pool = (cfg.n_layers, num_pages, page_size)
    return {"lat": jnp.zeros(pool + (_ds._lat_width(cfg),), cfg.dtype),
            "idx": jnp.zeros(pool + (cfg.index_head_dim,), cfg.dtype),
            "moe": jnp.zeros((len(COUNTERS), 2), jnp.int32),
            "dsa": jnp.zeros((len(DSA_COUNTERS), 2), jnp.int32)}


def snapshot_counters(cache: Dict) -> Dict:
    """deepseek_v2.snapshot_counters, with the selection's beside the
    expert layers'."""
    snap = _ds.snapshot_counters(cache)
    snap["dsa"] = jnp.copy(cache["dsa"])
    snap["dsa"].copy_to_host_async()
    return snap


def read_counters(cache: Dict, cfg) -> Dict[str, Any]:
    """The expert layers' counters (deepseek_v2.read_counters, as
    `moe_<name>` in the engine's stats) and DSA_COUNTERS beside them
    (`dsa_<name>`): `dsa_tick_keys_attended / dsa_tick_keys_chosen` is 1
    where a tick's attention touched the chosen keys and nothing else;
    `dsa_keys_scored / dsa_keys_chosen` is what the indexer read for
    each key chosen."""
    counts = _ds.read_counters(cache, cfg)
    for name, (hi, lo) in zip(DSA_COUNTERS,
                              np.asarray(cache["dsa"]).astype(np.int64)):
        counts["dsa_" + name] = int((hi << _ds._WORD) + lo)
    return counts


_rms = _em._rms          # reads `rms_eps` of what it is given, no more


# ---------------------------------------------------------------------------
# The projections


def _query_latent(lp, h, cfg: GlmMoeDsaConfig):
    """The normed query latent [n, q_lora_rank] of the normed input h:
    what the query heads and the indexer's both read."""
    return _rms(jnp.einsum("nd,dr->nr", h, lp["wq_a"].astype(cfg.dtype)),
                lp["q_norm"], cfg)


def _project(lp, x, positions, cfg: GlmMoeDsaConfig):
    """deepseek_v2._project's contract (x [n, D] at positions [n] ->
    q_nope [n, H, 192], rotated q_pe [n, H, 64], the normed latent
    [n, 512], the rotated shared key part [n, 64]) with this model's
    eps, plain RoPE at its theta and interleaved pairs."""
    dt = cfg.dtype
    h = _rms(x, lp["ln1"], cfg)
    q = jnp.einsum("nr,rf->nf", _query_latent(lp, h, cfg),
                   lp["wq_b"].astype(dt)
                   ).reshape(x.shape[0], cfg.n_heads, cfg.head_dim)
    kva = jnp.einsum("nd,dr->nr", h, lp["wkv_a"].astype(dt))
    kr, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    return (q[..., :dn], _rope(q[..., dn:], positions, cfg),
            _rms(kva[:, :kr], lp["kv_norm"], cfg),
            _rope(kva[:, kr:], positions, cfg))


def _index_project(lp, x, positions, cfg: GlmMoeDsaConfig):
    """The indexer's side of a layer: x [n, D] at positions [n] -> its
    queries [n, 32, 128] (from the query latent the attention heads
    share), its key [n, 128] (LayerNorm with gain and bias, eps 1e-6),
    both rotated over their first `qk_rope_head_dim` lanes, and the
    heads' weights [n, 32] float32, with the two constant factors
    32^-0.5 and 128^-0.5 in them."""
    dt = cfg.dtype
    rd = cfg.qk_rope_head_dim
    h = _rms(x, lp["ln1"], cfg)
    qi = jnp.einsum("nr,rhd->nhd", _query_latent(lp, h, cfg),
                    lp["wiq"].astype(dt))
    ki = jnp.einsum("nd,de->ne", h, lp["wik"].astype(dt),
                    preferred_element_type=jnp.float32)
    mean = ki.mean(-1, keepdims=True)
    var = ((ki - mean) ** 2).mean(-1, keepdims=True)
    ki = ((ki - mean) * lax.rsqrt(var + cfg.index_norm_eps)
          * lp["ik_norm"] + lp["ik_bias"]).astype(dt)
    turn = lambda a: jnp.concatenate(  # noqa: E731
        [_rope(a[..., :rd], positions, cfg), a[..., rd:]], axis=-1)
    wi = jnp.einsum("nd,dh->nh", h, lp["wiw"].astype(dt),
                    preferred_element_type=jnp.float32) \
        * (cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return turn(qi), turn(ki), wi


# ---------------------------------------------------------------------------
# Selection: the indexer's scores and the choice, for a single-row chunk
# of T tokens and for a tick of B rows.


def _even_span(keys: int, page_size: int, nblk: int) -> int:
    """Pages of a span of about `keys` keys that divide the table, so
    that no span is clamped onto the one before it."""
    most = max(1, min(nblk, keys // page_size))
    return next(d for d in range(most, 0, -1) if nblk % d == 0)


def _head_scores(qi, wi, keys):
    """I of queries qi [n, 32, 128] with head weights wi [n, 32]
    against keys [s, 128] (one sequence's, a chunk's) or [n, s, 128] (a
    sequence a query, a tick's) -> [n, s] float32: the heads are reduced
    here, inside the span."""
    s = jnp.einsum("nhd,sd->nhs" if keys.ndim == 2 else "nhd,nsd->nhs",
                   qi, keys, preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * wi[..., None]).sum(-2)


def _index_chunk(qi, wi, idx_pool, l, bt, start):
    """A chunk's scores [T, S] over the row's whole table width S, -inf
    where a query sees nothing (keys after it, spans past the chunk):
    span by span up to the chunk's last token.  Also the keys a query
    was scored against (whole spans)."""
    T = qi.shape[0]
    psz, nblk = idx_pool.shape[2], bt.shape[0]
    span = _even_span(_INDEX_CHUNK_SPAN_KEYS, psz, nblk)
    width = span * psz
    cols = start + jnp.arange(T)

    def score(j, buf):
        pg = lax.dynamic_slice(bt, (j * span,), (span,))
        s = _head_scores(qi, wi, idx_pool[l, pg].reshape(width, -1))
        kcols = j * width + jnp.arange(width)
        s = jnp.where(kcols[None, :] <= cols[:, None], s, -jnp.inf)
        return lax.dynamic_update_slice(buf, s, (0, j * width))

    spans = (start + T + width - 1) // width
    return lax.fori_loop(
        0, spans, score, jnp.full((T, nblk * psz), -jnp.inf, jnp.float32)
    ), spans * width


def _index_tick(qi, wi, idx_pool, l, bt, pos, order, n_live):
    """A tick's scores [B, S]: the LIVE rows alone (order[:n_live]),
    `_INDEX_TICK_ROWS` of them a trip, each over its own pages and as
    far as the deepest of its block; -inf where a row sees nothing.
    Also the keys it scored for live rows (whole spans)."""
    B = qi.shape[0]
    psz, nblk = idx_pool.shape[2], bt.shape[1]
    span = _even_span(_INDEX_TICK_SPAN_KEYS, psz, nblk)
    width = span * psz
    qb = math.gcd(B, _INDEX_TICK_ROWS)

    def block(j, carry):
        buf, scored = carry
        at, on = _ds._live_block(order, n_live, j, qb)
        p = jnp.where(on, pos[at], -1)
        q, w, tables = qi[at], wi[at], bt[at]

        def score(m, part):
            pg = lax.dynamic_slice_in_dim(tables, m * span, span, axis=1)
            s = _head_scores(q, w, idx_pool[l, pg].reshape(qb, width, -1))
            s = jnp.where(m * width + jnp.arange(width)[None, :]
                          <= p[:, None], s, -jnp.inf)
            return lax.dynamic_update_slice(part, s, (0, m * width))

        spans = jnp.max(p) // width + 1
        part = lax.fori_loop(
            0, spans, score,
            jnp.full((qb, nblk * psz), -jnp.inf, jnp.float32))
        return buf.at[at].set(part), scored + on.sum() * spans * width

    return lax.fori_loop(
        0, -(-n_live // qb), block,
        (jnp.full((B, nblk * psz), -jnp.inf, jnp.float32), jnp.int32(0)))


_LANES = 128          # keys a block of the choice holds: the chip's lanes
_LIST_QUERIES = 64    # rows whose chosen keys are listed side by side


def _blocked(x):
    """x [N, S] -> [N, S / lanes, lanes], in blocks of the chip's 128
    lanes, or one block where S is no whole number of them (toy
    widths)."""
    N, S = x.shape
    return x.reshape(N, -1, _LANES if S % _LANES == 0 else S)


def _running(m3):
    """m3 [N, NB, lanes] 0/1 -> its running count along each block
    (inclusive; at most `lanes`, exact in bfloat16), as a product with
    a triangle of ones: [N, NB, lanes] float32."""
    lanes = m3.shape[-1]
    upto = jnp.triu(jnp.ones((lanes, lanes), jnp.bfloat16))
    return jnp.einsum("nbl,lm->nbm", m3.astype(jnp.bfloat16), upto,
                      preferred_element_type=jnp.float32)


def chosen_keys(scores, topk: int):
    """scores [N, S] float32 (-inf: not visible) -> [N, S] bool: the set
    `lax.top_k(scores, topk)` names among the visible keys, every
    visible key where a query sees no more than `topk`, found without a
    sort (which is what `lax.top_k` of thousands lowers to on a TPU: one
    of [512, S] a chunk and layer).

    As minicpm_sala.chosen_blocks: the `topk`-th largest score is found
    by bisection on the float32 bit pattern made an integer of the same
    order, two bits a pass (three thresholds counted in one read of the
    scores, 16 passes); a key is chosen if it scores more, or scores
    just that and is among the first of its equals that fill the set,
    `lax.top_k`'s own rule on ties (the lower position first)."""
    N, S = scores.shape
    k = min(topk, S)
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)     # ordered as the floats are
    steps = jnp.arange(1, 4, dtype=jnp.int32)

    def two_bits(i, t):
        # t less the lowest integer, unsigned, gains its bits from the
        # top (the sum wraps)
        more = t + (steps[None, :] << (30 - 2 * i))               # [N, 3]
        fits = (key[:, None, :] >= more[:, :, None]).sum(-1) >= k
        return t + (fits.sum(-1, keepdims=True).astype(jnp.int32)
                    << (30 - 2 * i))
    kth = lax.fori_loop(0, 16, two_bits,
                        jnp.full((N, 1), -2 ** 31, jnp.int32))
    visible = scores > -jnp.inf
    above, ties = key > kth, (key == kth) & visible
    room = k - above.sum(-1, keepdims=True)
    run = _running(_blocked(ties))
    before = jnp.cumsum(run[..., -1], axis=-1) - run[..., -1]
    rank = (run + before[..., None]).reshape(N, S)
    return (above & visible) | (ties & (rank <= room))


def _listed(chosen, k: int):
    """chosen [N, S] bool, at most k a row -> (the positions of the
    chosen keys, ascending [N, k] int32; which of the k slots hold one
    [N, k]).  No sort, gather or scatter: a row is cut into blocks of
    128 keys; slot j lies in the block whose running count first passes
    j, that block's running counts are fetched by a product with a
    one-hot row, and the key is where they first pass j's rank in the
    block."""
    run = _running(_blocked(chosen))                         # [N, NB, lanes]
    blocks, lanes = run.shape[1:]
    count = run[..., -1].astype(jnp.int32)
    upto = jnp.cumsum(count, axis=-1)                        # [N, NB]
    before = upto - count
    j = jnp.arange(k, dtype=jnp.int32)[None, :, None]        # [1, k, 1]
    inside = (before[:, None, :] <= j) & (j < upto[:, None, :])
    block = (upto[:, None, :] <= j).sum(-1)                  # [N, k]
    rank = j[..., 0] - jnp.where(inside, before[:, None, :], 0).sum(-1)
    rows = jnp.einsum("nkb,nbl->nkl", inside.astype(jnp.bfloat16),
                      run.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    at = (rows <= rank[..., None].astype(jnp.float32)).sum(-1)
    idx = jnp.minimum(block, blocks - 1) * lanes \
        + jnp.minimum(at, lanes - 1)
    return idx.astype(jnp.int32), j[..., 0] < upto[:, -1:]


def _listed_by_blocks(chosen, k: int):
    """`_listed`, `_LIST_QUERIES` rows at a time: a block's one-hot rows
    are [rows, k, blocks of keys]."""
    N, S = chosen.shape
    qb = math.gcd(N, _LIST_QUERIES)
    idx, ok = lax.map(lambda c: _listed(c, k), chosen.reshape(-1, qb, S))
    return idx.reshape(N, k), ok.reshape(N, k)


def _narrowest(scores, need, least: int, choose):
    """`choose(scores[:, :w])` over the narrowest w of an eighth, a
    quarter, a half and the whole of the table's width that holds every
    visible key (no query sees one at or past `need`, a number the
    device reads: a chunk's end, a tick's deepest row + 1) and is no
    less than `least`: the choice's passes cost by the width."""
    S = scores.shape[1]
    widths = sorted({w for w in (S >> 3, S >> 2, S >> 1, S) if w >= least})
    return lax.switch(
        sum((need > w).astype(jnp.int32) for w in widths[:-1]),
        [lambda s, w=w: choose(s[:, :w]) for w in widths], scores)


def _attn_chunk(lp, x, l, cache, bt, start, valid, dc, cfg):
    T = x.shape[0]
    psz = cache["idx"].shape[2]
    S = bt.shape[0] * psz
    topk = cfg.index_topk
    cols = start + jnp.arange(T)
    qi, ki, wi = _index_project(lp, x, cols, cfg)
    pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
    idx_pool = cache["idx"].at[l, pages].set(ki.reshape(T // psz, psz, -1))
    with jax.named_scope("dsa_index"):
        scores, scored = _index_chunk(qi, wi, idx_pool, l, bt, start)
    with jax.named_scope("dsa_select"):
        mask = _narrowest(
            scores, start + T, min(topk, S),
            lambda s: jnp.pad(chosen_keys(s, topk),
                              ((0, 0), (0, S - s.shape[1]))))
    x, cache = _ds._attn_chunk(lp, x, l, dict(cache, idx=idx_pool), bt,
                               start, cfg, project=_project, chosen=mask)
    # (a call with no real token, the engine's warm-up, counts nothing)
    dc = [dc[0] + jnp.where(valid > 0, T * scored, 0),
          dc[1] + jnp.where(jnp.arange(T) < valid,
                            jnp.minimum(cols + 1, topk), 0).sum()] + dc[2:]
    return x, cache, dc


def _attn_tick(lp, x, l, cache, bt, pos, dc, cfg):
    psz = cache["idx"].shape[2]
    S = bt.shape[1] * psz
    topk = min(cfg.index_topk, S)
    qi, ki, wi = _index_project(lp, x, pos, cfg)
    page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)[:, 0]
    idx_pool = cache["idx"].at[l, page, pos % psz].set(ki)
    live = pos > 0
    n_live = live.sum()
    # the live rows first, then the idle ones, each in their own order
    # (no sort: a row's place is the count of its kind before it)
    order = jnp.zeros_like(pos).at[jnp.where(
        live, jnp.cumsum(live) - 1, n_live + jnp.cumsum(~live) - 1)].set(
            jnp.arange(pos.shape[0], dtype=jnp.int32))
    with jax.named_scope("dsa_index"):
        scores, scored = _index_tick(qi, wi, idx_pool, l, bt, pos, order,
                                     n_live)
    with jax.named_scope("dsa_select"):
        keep = _narrowest(
            scores, jnp.max(pos) + 1, topk,
            lambda s: jnp.pad(chosen_keys(s, topk),
                              ((0, 0), (0, S - s.shape[1]))))

    def listed():
        with jax.named_scope("dsa_select"):
            return _narrowest(keep, jnp.max(pos) + 1, topk,
                              lambda m: _listed_by_blocks(m, topk))
    x, cache, (attended, walked) = _ds._attn_tick(
        lp, x, l, dict(cache, idx=idx_pool), bt, pos, cfg, project=_project,
        chosen=_ds.Chosen(keep, listed, _ds.walks(pos, topk), n_live, order))
    chose = jnp.where(live, jnp.minimum(pos + 1, topk), 0).sum()
    dc = [dc[0] + scored, dc[1] + chose, dc[2] + chose, dc[3] + attended,
          dc[4] + (live & (pos + 1 > topk)).sum(), dc[5] + n_live,
          dc[6] + walked]
    return x, cache, dc


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, x, cache, live, is_tick, attn, cfg):
    counts = [jnp.int32(0)] * len(COUNTERS)
    dc = [jnp.int32(0)] * len(DSA_COUNTERS)
    for l, lp in enumerate(params["layers"]):
        x, cache, dc = attn(lp, x, l, cache, dc)
        x, counts = _em._ffn(lp, x, live, is_tick, counts, cfg)
    with jax.named_scope("lm_head"):
        x = _rms(x, params["ln_f"], cfg)
        logits = jnp.einsum("nd,dv->nv", x.astype(cfg.dtype),
                            params["wlm"].astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
    return logits, dict(cache, moe=_ds._count(cache["moe"], counts),
                        dsa=_ds._count(cache["dsa"], dc))


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: GlmMoeDsaConfig, pad_lo=None, slot=None,
                     valid=None) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill.  It fills the row's pages
    (latents and indexer keys); only its first `valid` tokens (default
    all) are counted and routed to experts (`slot` is taken and unused:
    no state lives outside the pages).  `pos` a [B] vector with one
    token a row: the decode tick.  Rows at position 0 are idle: their
    page writes land wherever their block table points (the trash page),
    they score, choose and gather nothing and are routed nowhere.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    psz = cache["lat"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    embed = lambda tok: jnp.take(params["wte"], tok, axis=0  # noqa: E731
                                 ).astype(cfg.dtype)
    if pos.ndim == 0:
        if B != 1 or t % psz:
            raise ValueError(f"a chunk is one row of whole pages of {psz} "
                             f"tokens, got {tokens.shape}")
        valid = jnp.int32(t) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        bt = block_tables[0]
        logits, cache = _through_layers(
            params, embed(tokens[0]), cache, jnp.arange(t) < valid, False,
            lambda lp, x, l, c, dc: _attn_chunk(lp, x, l, c, bt, pos, valid,
                                                dc, cfg), cfg)
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) are not written for the absorbed latent step")
    logits, cache = _through_layers(
        params, embed(tokens[:, 0]), cache, pos > 0, True,
        lambda lp, x, l, c, dc: _attn_tick(lp, x, l, c, block_tables, pos,
                                           dc, cfg), cfg)
    return logits[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    chunk_selects=chunk_selects, page_keys=("lat", "idx"),
    attn_keys_gathered=attn_keys_gathered,
    snapshot_counters=snapshot_counters, read_counters=read_counters)
