"""Jamba (`model_type` `jamba`): a decoder of Mamba-1 layers with one
attention layer every `attn_layer_period`, every layer followed by a
SwiGLU feed-forward.  This module is the model as the serving engine
runs it: a config object, seeded weights, the cache it declares, and its
own paged step for a prefill chunk and for a decode tick, bound into
one declared body (`BODY`, a decode.PagedBody) that the config names, so
the engine's two jitted programs (`engine._prefill_chunk`,
`engine._paged_tick`) run it as they run every model.

A Mamba layer keeps, per sequence, a selective-scan state and the last
`d_conv - 1` inputs of its causal convolution, whatever the context; an
attention layer keeps a key and a value a token (ONE key-value head for
all query heads, and no positional encoding of any kind).  The cache
(one pytree, `engine._cache`):

  k, v   [A, P, 1, page, Dh]     pages of the A attention layers, a
                                 page's keys contiguous (a [.., 1, 128]
                                 minor pair would be padded to the
                                 chip's tile: minicpm_sala.py's lesson)
  ssm    [M, B, N, E]  float32   the M Mamba layers' scan state, one per
                                 decode row: channels minor (N = 16 on
                                 the lanes would pad it eight times)
  conv   [M, B, (K - 1) E]       the convolution's tail: a row's last
                                 K - 1 REAL inputs side by side, oldest
                                 first, in the model's dtype (as
                                 [.., K - 1, E] its 3 rows would be
                                 padded to a tile, and the chunk, which
                                 reads one row's, re-laid the whole of
                                 it twice a call)

State per decode row is `BODY.row_state_keys`: the pool and the
engine's reservation count the attention layers' pages alone, and what
treats a page as the whole of a sequence's state refuses this model by name
(kv_tier.refuse_row_state).  Here the state is most of the cache
(E = 5120, N = 16: 9.3 MB a row over 26 layers, against 1 KB a token in
pages), so the rows limit the batch, not the pool.

What the engine has to know (the row-state contract of minicpm_sala.py
and exaone_moe.py): the chunk that starts at position 0 zeroes `slot`'s
state and tail inside the program; a chunk moves them by its first
`valid` tokens only (a pad's delta is 0: it neither decays nor adds, and
the tail is read before the pads); a tick steps every row whose position
is past 0 and leaves the others (idle rows, the row a prefill is
filling) exactly as they are.

The mixer's three pieces of device code live in `ray_tpu/ops/ssm.py`
and run under the named scopes `ssm_conv`, `ssm_scan` (a chunk) and
`ssm_step` (a tick); attention's score-and-attend under `attn_nope`.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models.decode import PagedBody, _swiglu
from ray_tpu.ops import ssm

ATTN, MAMBA = "attention", "mamba"
# Keys one span of an attention layer's softmax covers (whole pages): a
# tick gathers a span's pages for every row of the call; a chunk scores
# all its queries against a span in float32, [heads, queries, keys].
_TICK_SPAN_KEYS = 256
_CHUNK_SPAN_KEYS = 512


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """Published AI21-Jamba2-3B sizes by default.  Hashable: the engine
    passes it as a static argument."""
    max_seq: int
    n_layers: int = 28
    vocab_size: int = 65536
    d_model: int = 2560
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    d_ff: int = 8192
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_expand: int = 2
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_kv_heads != 1:
            raise ValueError("the attention layers are written for one "
                             "key-value head")
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset must lie inside a period")
        if self.d_conv < 2:
            raise ValueError("the convolution keeps d_conv - 1 >= 1 inputs")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(
            ATTN if i % self.attn_layer_period == self.attn_layer_offset
            else MAMBA for i in range(self.n_layers))

    @property
    def n_attn(self) -> int:
        return sum(k == ATTN for k in self.layer_kinds)

    @property
    def n_mamba(self) -> int:
        return self.n_layers - self.n_attn

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """Runs of equal layers in order: (kind, count, index of the
        run's first layer among the layers of its kind)."""
        out, seen = [], {ATTN: 0, MAMBA: 0}
        for kind, group in itertools.groupby(self.layer_kinds):
            n = len(list(group))
            out.append((kind, n, seen[kind]))
            seen[kind] += n
        return tuple(out)

    @property
    def paged_body(self) -> PagedBody:
        return BODY


def _span_pages(keys: int, page_size: int, nblk: int) -> int:
    return max(1, min(keys // page_size, nblk))


def attn_keys(cfg: JambaConfig, pos: np.ndarray) -> Tuple[int, int]:
    """(keys read, keys held) by one tick's decode rows at positions
    `pos`, summed over rows and the attention layers: each reads all it
    holds.  (A Mamba layer holds no key.)"""
    held = (int(np.asarray(pos, np.int64).sum()) + len(pos)) * cfg.n_attn
    return held, held


def attn_keys_gathered(cfg: JambaConfig, pos: np.ndarray, page_size: int,
                       nblk: int) -> int:
    """Keys one tick pulls from the pool: for EVERY row of the call
    (`pos` of all decode rows, idle ones at 0) whole spans up to the
    deepest row's token, the trip count the program reads from `pos`."""
    cols = _span_pages(_TICK_SPAN_KEYS, page_size, nblk) * page_size
    spans = -(-(int(np.asarray(pos).max()) + 1) // cols)
    return len(pos) * spans * cols * cfg.n_attn


def check_paging(cfg: JambaConfig, *, page_size: int, prefill_chunk: int,
                 speculate_k: int) -> None:
    if prefill_chunk % page_size:
        raise ValueError(f"a prefill chunk writes whole pages of the "
                         f"attention layers: prefill_chunk must be a "
                         f"multiple of page_size={page_size}, got "
                         f"{prefill_chunk}")
    if speculate_k:
        raise NotImplementedError(
            "speculative verify on a model with per-row recurrent state "
            "needs the state rolled back to the accepted token")


# ---------------------------------------------------------------------------
# Weights and cache


def init_params(cfg: JambaConfig, key, dtype=None) -> Dict:
    """Seeded weights, one stack per run of equal layers, in order:
    normal, std 0.02 (projections back into the residual stream 0.02 /
    sqrt(2 n_layers)); the Mamba paper's initialisation of what shapes
    the recurrence: A = -(1..d_state) a channel, a `b_dt` whose softplus
    is log-uniform in [1e-3, 1e-1], `w_dt` of std dt_rank^-0.5, D = 1;
    the convolution's taps of std d_conv^-0.5.  What feeds the scan
    (A_log, D, b_dt, the convolution, the norms) is float32."""
    dtype = dtype or cfg.dtype
    D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    E, N, R, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    s = 0.02
    so = s / np.sqrt(2 * cfg.n_layers)
    keys = iter(jax.random.split(key, 1 + 12 * len(cfg.runs)))
    f32 = jnp.float32

    def nrm(shape, scale, dt=dtype):
        return (scale * jax.random.normal(next(keys), shape, f32)).astype(dt)

    ones = lambda *shape: jnp.ones(shape, f32)  # noqa: E731

    def run(kind, n):
        ffn = {"ln1": ones(n, D), "ln2": ones(n, D),
               "w_gate": nrm((n, D, F), s), "w_up": nrm((n, D, F), s),
               "w_down": nrm((n, F, D), so)}
        if kind == ATTN:
            return dict(ffn, wq=nrm((n, D, H, Dh), s),
                        wkv=nrm((n, D, 2, Dh), s), wo=nrm((n, H, Dh, D), so))
        dt0 = jnp.exp(jax.random.uniform(
            next(keys), (n, E), f32, np.log(1e-3), np.log(1e-1)))
        return dict(
            ffn, w_in=nrm((n, D, 2, E), s), w_conv=nrm((n, K, E), K ** -0.5,
                                                       f32),
            b_conv=nrm((n, E), s, f32), w_x=nrm((n, E, R + 2 * N), s),
            dt_ln=ones(n, R), b_ln=ones(n, N), c_ln=ones(n, N),
            w_dt=nrm((n, R, E), R ** -0.5),
            b_dt=dt0 + jnp.log(-jnp.expm1(-dt0)),      # softplus^-1(dt0)
            a_log=jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=f32))
                                   [None, :, None], (n, N, E)),
            d_skip=ones(n, E), w_out=nrm((n, E, D), so))

    return {"wte": nrm((cfg.vocab_size, D), s),       # = the head (tied)
            "runs": tuple(run(kind, n) for kind, n, _ in cfg.runs),
            "ln_f": ones(D)}


def init_paged_cache(cfg: JambaConfig, num_pages: int, page_size: int,
                     num_slots: int) -> Dict:
    kv = (cfg.n_attn, num_pages, 1, page_size, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            "ssm": jnp.zeros((cfg.n_mamba, num_slots, cfg.d_state,
                              cfg.d_inner), jnp.float32),
            "conv": jnp.zeros((cfg.n_mamba, num_slots,
                               (cfg.d_conv - 1) * cfg.d_inner), cfg.dtype)}


def _rms(x, scale, cfg: JambaConfig):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + cfg.rms_eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# The Mamba mixer, for a single-row chunk of T tokens (x [T, D]) and for
# a tick of B rows (x [B, D]).  `i` indexes the layer among the layers
# of its kind (its state, or its pages).


def _mamba_in(lp, x, cfg: JambaConfig):
    az = jnp.einsum("nd,dce->nce", _rms(x, lp["ln1"], cfg),
                    lp["w_in"].astype(cfg.dtype))
    return az[:, 0], az[:, 1]


def _mamba_select(lp, c, cfg: JambaConfig):
    """The convolution's output c [n, E] float32 -> (delta [n, E],
    B [n, N], C [n, N]) float32: the input-dependent step and the
    state's input and output maps, each normed over its own width."""
    dt, R, N = cfg.dtype, cfg.dt_rank, cfg.d_state
    dbc = jnp.einsum("ne,er->nr", c.astype(dt), lp["w_x"].astype(dt),
                     preferred_element_type=jnp.float32)
    low = _rms(dbc[:, :R], lp["dt_ln"], cfg)
    delta = jax.nn.softplus(
        jnp.einsum("nr,re->ne", low.astype(dt), lp["w_dt"].astype(dt),
                   preferred_element_type=jnp.float32) + lp["b_dt"])
    return (delta, _rms(dbc[:, R:R + N], lp["b_ln"], cfg),
            _rms(dbc[:, R + N:], lp["c_ln"], cfg))


def _mamba_out(lp, x, y, c, z, cfg: JambaConfig):
    y = (y + lp["d_skip"] * c) * jax.nn.silu(z.astype(jnp.float32))
    return x + jnp.einsum("ne,ed->nd", y.astype(cfg.dtype),
                          lp["w_out"].astype(cfg.dtype))


def _mamba_chunk(lp, x, i, cache, start, slot, valid, cfg: JambaConfig):
    T = x.shape[0]
    a, z = _mamba_in(lp, x, cfg)
    fresh = start == 0                                    # a row begins
    with jax.named_scope("ssm_conv"):
        tail = jnp.where(fresh, 0, cache["conv"][i, slot]).reshape(
            cfg.d_conv - 1, cfg.d_inner)
        c, tail = ssm.ssm_conv(a, tail, lp["w_conv"], lp["b_conv"], valid)
        conv = cache["conv"].at[i, slot].set(tail.reshape(-1))
    delta, Bm, Cm = _mamba_select(lp, c, cfg)
    with jax.named_scope("ssm_scan"):
        h0 = jnp.where(fresh, 0.0, cache["ssm"][i, slot])
        delta = jnp.where((jnp.arange(T) < valid)[:, None], delta, 0.0)
        y, h = ssm.ssm_scan(delta, c, Bm, Cm, -jnp.exp(lp["a_log"]), h0)
        state = cache["ssm"].at[i, slot].set(h)
    return _mamba_out(lp, x, y, c, z, cfg), dict(cache, ssm=state, conv=conv)


def _mamba_tick(lp, x, i, cache, pos, cfg: JambaConfig):
    a, z = _mamba_in(lp, x, cfg)
    active = pos > 0
    with jax.named_scope("ssm_conv"):
        c, tail = ssm.ssm_conv_step(a, cache["conv"][i], lp["w_conv"],
                                    lp["b_conv"], active)
        conv = cache["conv"].at[i].set(tail)
    delta, Bm, Cm = _mamba_select(lp, c, cfg)
    with jax.named_scope("ssm_step"):
        y, state = ssm.ssm_step(delta, c, Bm, Cm, -jnp.exp(lp["a_log"]),
                                cache["ssm"], i, active)
    return _mamba_out(lp, x, y, c, z, cfg), dict(cache, ssm=state, conv=conv)


# ---------------------------------------------------------------------------
# Attention: one key-value head for all query heads, no positions


def _attn_project(lp, x, cfg: JambaConfig):
    dt = cfg.dtype
    h = _rms(x, lp["ln1"], cfg)
    q = jnp.einsum("nd,dhk->nhk", h, lp["wq"].astype(dt))
    kv = jnp.einsum("nd,dck->nck", h, lp["wkv"].astype(dt))
    return q, kv[:, 0], kv[:, 1]


def _attn_close(lp, x, out, cfg: JambaConfig):
    return x + jnp.einsum("nhk,hkd->nd", out, lp["wo"].astype(cfg.dtype))


def _merge(part, s, attend):
    """One span's masked scores s [..., keys] float32 merged into the
    running softmax `part` = (maxima, sums, accumulator [..., Dh])."""
    top, total, acc = part
    new = jnp.maximum(top, s.max(-1))
    safe = jnp.where(jnp.isfinite(new), new, 0.0)
    scale = jnp.exp(top - safe)
    e = jnp.exp(s - safe[..., None])
    return (new, total * scale + e.sum(-1),
            acc * scale[..., None] + attend(e))


def _attn_chunk(lp, x, i, cache, bt, start, cfg: JambaConfig):
    T = x.shape[0]
    Dh, psz = cfg.head_dim, cache["k"].shape[3]
    dt = cfg.dtype
    cols = start + jnp.arange(T)
    q, k, v = _attn_project(lp, x, cfg)
    pages = lax.dynamic_slice(bt, (start // psz,), (T // psz,))
    ck = cache["k"].at[i, pages, 0].set(k.reshape(T // psz, psz, Dh))
    cv = cache["v"].at[i, pages, 0].set(v.reshape(T // psz, psz, Dh))

    with jax.named_scope("attn_nope"):
        nblk = bt.shape[0]
        span = _span_pages(_CHUNK_SPAN_KEYS, psz, nblk)
        width = span * psz

        def attend(j, part):
            first = jnp.minimum(j * span, nblk - span)   # as the slice clamps
            pg = lax.dynamic_slice(bt, (first,), (span,))
            ks = ck[i, pg, 0].reshape(width, Dh)
            vs = cv[i, pg, 0].reshape(width, Dh)
            s = jnp.einsum("thd,sd->hts", q, ks,
                           preferred_element_type=jnp.float32) * Dh ** -0.5
            kcols = first * psz + jnp.arange(width)
            seen = (kcols[None, :] <= cols[:, None]) \
                & (kcols[None, :] >= j * width)
            s = jnp.where(seen[None], s, -jnp.inf)
            return _merge(part, s, lambda e: jnp.einsum(
                "hts,sd->htd", e.astype(dt), vs,
                preferred_element_type=jnp.float32))

        stat = jnp.full((cfg.n_heads, T), -jnp.inf, jnp.float32)
        _, total, acc = lax.fori_loop(
            0, (start + T + width - 1) // width, attend,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((cfg.n_heads, T, Dh), jnp.float32)))
        out = (acc / total[..., None]).astype(dt).swapaxes(0, 1)
    return _attn_close(lp, x, out, cfg), dict(cache, k=ck, v=cv)


def _attn_tick(lp, x, i, cache, bt, pos, cfg: JambaConfig):
    B = x.shape[0]
    Dh, psz = cfg.head_dim, cache["k"].shape[3]
    dt = cfg.dtype
    q, k, v = _attn_project(lp, x, cfg)
    page = jnp.take_along_axis(bt, (pos // psz)[:, None], axis=1)[:, 0]
    ck = cache["k"].at[i, page, 0, pos % psz].set(k)
    cv = cache["v"].at[i, page, 0, pos % psz].set(v)

    with jax.named_scope("attn_nope"):
        nblk = bt.shape[1]
        span = _span_pages(_TICK_SPAN_KEYS, psz, nblk)
        width = span * psz

        def attend(j, part):
            first = jnp.minimum(j * span, nblk - span)
            pg = lax.dynamic_slice(bt, (0, first), (B, span))
            ks = ck[i, pg, 0].reshape(B, width, Dh)
            vs = cv[i, pg, 0].reshape(B, width, Dh)
            s = jnp.einsum("bhd,bsd->bhs", q, ks,
                           preferred_element_type=jnp.float32) * Dh ** -0.5
            kcols = first * psz + jnp.arange(width)
            seen = (kcols[None, :] <= pos[:, None]) \
                & (kcols[None, :] >= j * width)
            s = jnp.where(seen[:, None], s, -jnp.inf)
            return _merge(part, s, lambda e: jnp.einsum(
                "bhs,bsd->bhd", e.astype(dt), vs,
                preferred_element_type=jnp.float32))

        stat = jnp.full((B, cfg.n_heads), -jnp.inf, jnp.float32)
        _, total, acc = lax.fori_loop(
            0, (jnp.max(pos) + width) // width, attend,
            (stat, jnp.zeros_like(stat),
             jnp.zeros((B, cfg.n_heads, Dh), jnp.float32)))
        out = (acc / total[..., None]).astype(dt)
    return _attn_close(lp, x, out, cfg), dict(cache, k=ck, v=cv)


# ---------------------------------------------------------------------------
# The paged step


def _through_layers(params, tokens, cache, cfg: JambaConfig, attn, mamba):
    """Tokens [n] through every layer in order and the tied head: a run
    of equal layers is one scan over its stack, the cache rides as the
    carry (updated in place at [layer], as decode.paged_chunk_step does
    for its pool)."""
    def one(kind):
        mixer = attn if kind == ATTN else mamba

        def layer(carry, inputs):
            x, cache = carry
            lp, i = inputs
            x, cache = mixer(lp, x, i, cache)
            x = x + _swiglu(lp, _rms(x, lp["ln2"], cfg), cfg.dtype)
            return (x, cache), None
        return layer

    x = jnp.take(params["wte"], tokens, axis=0).astype(cfg.dtype)
    for (kind, n, i0), stack in zip(cfg.runs, params["runs"]):
        (x, cache), _ = lax.scan(one(kind), (x, cache),
                                 (stack, i0 + jnp.arange(n)))
    x = _rms(x, params["ln_f"], cfg)
    logits = jnp.einsum("nd,vd->nv", x.astype(cfg.dtype),
                        params["wte"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, cache


def paged_chunk_step(params: Dict, tokens, pos, cache: Dict, block_tables,
                     cfg: JambaConfig, pad_lo=None, slot=None, valid=None
                     ) -> Tuple[Any, Dict]:
    """The model's paged step, under decode.paged_chunk_step's contract.

    `pos` a scalar: ONE row's chunk of T tokens starting there (T and
    `pos` whole pages) — single-row prefill.  It fills the row's pages
    of the attention layers and carries the scan state and convolution
    tail of decode row `slot` (default 0), zeroing both first when `pos`
    is 0; only the first `valid` tokens (default all) move them.  `pos`
    a [B] vector with one token a row: the decode tick.  Rows at
    position 0 are idle: their page writes land wherever their block
    table points (the trash page) and their state and tail stay as they
    are.
    Returns (logits [B, t, V] float32, cache)."""
    if pad_lo is not None:
        raise NotImplementedError("left-padded rows")
    B, t = tokens.shape
    psz = cache["k"].shape[3]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        if B != 1 or t % psz:
            raise ValueError(f"a chunk is one row of whole pages of {psz} "
                             f"tokens, got {tokens.shape}")
        slot = jnp.int32(0) if slot is None else jnp.asarray(slot, jnp.int32)
        valid = jnp.int32(t) if valid is None \
            else jnp.asarray(valid, jnp.int32)
        bt = block_tables[0]
        logits, cache = _through_layers(
            params, tokens[0], cache, cfg,
            lambda lp, x, i, c: _attn_chunk(lp, x, i, c, bt, pos, cfg),
            lambda lp, x, i, c: _mamba_chunk(lp, x, i, c, pos, slot, valid,
                                             cfg))
        return logits[None], cache
    if t != 1:
        raise NotImplementedError(
            "several tokens a row at per-row positions (speculative "
            "verify) need the recurrent state rolled back on rejection")
    logits, cache = _through_layers(
        params, tokens[:, 0], cache, cfg,
        lambda lp, x, i, c: _attn_tick(lp, x, i, c, block_tables, pos, cfg),
        lambda lp, x, i, c: _mamba_tick(lp, x, i, c, pos, cfg))
    return logits[:, None], cache


BODY = PagedBody(
    init_paged_cache=init_paged_cache, paged_chunk_step=paged_chunk_step,
    check_paging=check_paging, attn_keys=attn_keys,
    row_state_keys=("ssm", "conv"), n_attn=lambda cfg: cfg.n_attn,
    attn_keys_gathered=attn_keys_gathered)
