"""Kimi Delta Attention (arXiv:2510.26692): the delta rule over a state
of [key 128, value 128] a head under a decay PER CHANNEL of the key
(`kda_chunk` over a chunk of one row, `kda_step` for one token of every
row; each the Pallas kernel on a TPU at widths it tiles, plain XLA
elsewhere), and the short depthwise convolution in front of it (`kda_conv`,
`kda_conv_step`: ops/ssm.py's, three streams side by side, no bias).

The recurrence, per head, in float32 (`a_t` <= 0 the log of the decay,
`alpha_t = exp(a_t)`; q, k, v are what the mixer made of them: k of unit
length, q of unit length over sqrt(d)):

    S'  = diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

A token with `a_t = 0` and `beta_t = 0` leaves the state exactly as it
is: that is how a chunk's pads (tokens at or past `valid`) are made.

The chunk is that recurrence unrolled over C = 64 tokens.  With
`b_r = sum_{i<=r} a_i` (per channel, from the chunk's start):

    A[r,i]   = beta_r sum_c k_r[c] k_i[c] exp(b_r[c] - b_i[c])   (i < r)
    [U | W]  = (I + A)^-1 diag(beta) [V | K * exp(b)]
    V~       = U - W S_0              (what each token wrote)
    o_r      = S_0^T (q_r * exp(b_r)) + sum_{i<=r} Q[r,i] v~_i
    S_C      = diag(exp(b_C)) S_0 + sum_i (k_i * exp(b_C - b_i)) v~_i^T

where Q is A's sum with q_r for k_r, the diagonal kept and no beta.
EVERY exponent is a difference that is <= 0, taken as a difference
(factoring `exp(-b_i)` out over 64 tokens overflows at the bound of -5 a
token), and the solve is a forward substitution: the Neumann product
`(I - A)(I + A^2)...` cancels catastrophically where neighbouring keys
are alike.  It has two forms.

`chunk_xla` is what every backend but a TPU runs, and the tests' second
opinion.  `_decayed` scores a sub-block of 16 tokens against itself on
`b_r - b_i` directly and against earlier tokens through the sub-block's
first cumulative decay (`exp(b_r - ref)` times `exp(ref - b_i)`, both
<= 1).  What depends on no state (A, Q, the solve: `triangular_solve`)
is computed for all chunks at once; the walk over chunks carries the
state through three matmuls.

`chunk_pallas` is one kernel over a grid of (block of 8 heads, chunk),
the chunks innermost: q, k, v, a are read as they lie (a block of
[64, 8, 128] of `[T, H, d]`, a head taken out by a strided read), the
block's states stay in fast memory across its chunks, TRANSPOSED (value,
key: the decay scales lanes), read from `S_0` at the first chunk and
written once after the last.  The decays are formed ONCE for A and Q, by
halves: tokens i < r meet at the one level s in 1, 2, .. 32 where i's
block of s tokens is the left and r's the right half of a block of 2s,
and there `exp(b_r - b_i)` is `exp(loc_r)` (the decay from the right
half's start to r) times `exp(tot_i - loc_i)` (from i to the left half's
end): sums of few `a`, each <= 0, doubled from level to level by a roll
and an add, so that a level is one matmul `K~ [Q~; beta K~]^T` under a
mask and six of them are A^T and Q^T.  The solve is applied to
`beta (V - (K * exp(b)) S_0)`, which is V~ itself and half the columns:
a forward substitution by rows inside a 16 x 16 diagonal block (its
inverse T_I, the four at once) and by blocks across the four,
`V~_I = T_I (rhs_I - sum_{J<I} A_IJ V~_J)`.  Every product is float32
at `Precision.HIGHEST`.  A chunk of 64 tokens wholly at or past `valid`
is NOT walked: its `o` is zeros, the state passes through, and its block
index is the last walked chunk's, so nothing is fetched for it.

The step has two forms too.  `step_xla` is the equations as XLA fuses them:
the state is read for `S'^T k` and `S'^T q` (the output follows from
those two and never needs the new state), then read and written for the
update, every row of the call.  `step_pallas` reads a block of rows x
heads once, steps it and writes it back IN PLACE in the array of every
layer's states (aliased to its result, the layer picked by a prefetched
scalar in the index map: no layer is sliced out, nothing is copied),
and a block none of whose rows is active is neither computed nor, its
block index being pinned to the last active block's, moved.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import ssm

_HI = lax.Precision.HIGHEST
CHUNK, SUB = 64, 16           # tokens a chunk, and a sub-block of it


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# The chunk


def _decayed(x, k, b, sub: int):
    """x, k, b [..., C, d] -> [..., C, C]: entry (r, i) is
    sum_c x_r[c] k_i[c] exp(b_r[c] - b_i[c]) for i <= r, 0 above the
    diagonal.  `b` is cumulative and falls; no exponent is positive."""
    *lead, C, d = x.shape
    nb = C // sub
    blocks = lambda a: a.reshape(*lead, nb, sub, d)          # noqa: E731
    xs, ks, bs = blocks(x), blocks(k), blocks(b)
    # a sub-block's reference: the cumulative decay before its first token
    ref = jnp.concatenate([jnp.zeros_like(bs[..., :1, -1, :]),
                           bs[..., :-1, -1, :]], axis=-2)    # [..., nb, d]
    xr = xs * jnp.exp(bs - ref[..., None, :])
    early = jnp.arange(C)[None, :] < (jnp.arange(nb) * sub)[:, None]
    fall = ref[..., :, None, :] - b[..., None, :, :]         # [..., nb, C, d]
    kk = k[..., None, :, :] * jnp.exp(
        jnp.where(early[..., None], fall, -jnp.inf))
    off = jnp.einsum("...nsd,...ncd->...nsc", xr, kk, precision=_HI)
    within = bs[..., :, None, :] - bs[..., None, :, :]       # [.., s, s, d]
    tri = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
    diag = (xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(
        jnp.where(tri[..., None], within, -jnp.inf))).sum(-1)
    # the sub-blocks' own squares go on the diagonal of [nb, sub, nb, sub]
    eye = jnp.eye(nb, dtype=x.dtype)
    full = off.reshape(*lead, nb, sub, nb, sub) \
        + diag[..., :, :, None, :] * eye[:, None, :, None]
    return full.reshape(*lead, C, C)


def chunk_xla(q, k, v, a, beta, S0, chunk: int = CHUNK, sub: int = SUB):
    """q, k, v, a [T, H, d], beta [T, H], S0 [H, d, d] (key, value), all
    float32; `a` <= 0 is the log of the decay.  Returns (o [T, H, d],
    the state after token T - 1).  T is whole chunks (or one shorter
    chunk of whole sub-blocks)."""
    T, H, d = q.shape
    C = min(chunk, T)
    sub = min(sub, C)
    if T % C or C % sub:
        raise ValueError(f"{T} tokens are not whole chunks of {C} in "
                         f"sub-blocks of {sub}")
    N = T // C
    cut = lambda x: x.reshape(N, C, H, -1).swapaxes(1, 2)    # noqa: E731
    q, k, v, a = cut(q), cut(k), cut(v), cut(a)              # [N, H, C, d]
    beta = beta.reshape(N, C, H).swapaxes(1, 2)[..., None]   # [N, H, C, 1]
    b = jnp.cumsum(a, axis=2)
    strict = jnp.arange(C)[:, None] > jnp.arange(C)[None, :]
    A = jnp.where(strict, _decayed(k, k, b, sub), 0.0) * beta
    Q = _decayed(q, k, b, sub)
    grown = jnp.exp(b)                                       # G_r, <= 1
    UW = lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=A.dtype),
        jnp.concatenate([v, k * grown], axis=-1) * beta,
        left_side=True, lower=True, unit_diagonal=True)
    U, W = UW[..., :d], UW[..., d:]
    end = b[:, :, -1:, :]
    k_end = k * jnp.exp(end - b)
    g_end = jnp.exp(end[:, :, 0, :])                         # [N, H, d]
    qg = q * grown

    def walk(S, inp):
        U, W, Q, qg, k_end, g_end = inp
        wrote = U - jnp.einsum("hck,hkv->hcv", W, S, precision=_HI)
        o = jnp.einsum("hck,hkv->hcv", qg, S, precision=_HI) \
            + jnp.einsum("hci,hiv->hcv", Q, wrote, precision=_HI)
        S = g_end[..., None] * S \
            + jnp.einsum("hck,hcv->hkv", k_end, wrote, precision=_HI)
        return S, o

    S, o = lax.scan(walk, S0, (U, W, Q, qg, k_end, g_end))
    return o.swapaxes(1, 2).reshape(T, H, d), S


_CHUNK_HEADS = 8              # a block of the chunk kernel: 8 heads wide


def _chunk_kernel(walk_ref, q_ref, k_ref, v_ref, a_ref, beta_ref, s0_ref,
                  o_ref, s_ref, st_ref, *, heads: int):
    """One chunk of CHUNK tokens of `heads` heads: q, k, v, a, beta
    [CHUNK, heads, 128] as they lie, s0 / s [heads, 128, 128]
    (key, value); `st_ref` carries the block's states TRANSPOSED (value,
    key: the decay then scales lanes) across the chunks of the grid's
    inner axis."""
    n = pl.program_id(1)
    C, d = CHUNK, 128
    f32 = jnp.float32
    dot = functools.partial(lax.dot_general, precision=_HI,
                            preferred_element_type=f32)
    NN = (((1,), (0,)), ((), ()))       # x y
    NT = (((1,), (1,)), ((), ()))       # x y^T
    TN = (((0,), (0,)), ((), ()))       # x^T y

    @pl.when(n == 0)
    def _():
        def take(h, carry):
            st_ref[h] = s0_ref[h].T
            return carry
        lax.fori_loop(0, heads, take, 0)

    @pl.when(n < walk_ref[0])
    def _():
        # Every pair i < r of the chunk is scored at ONE level s (1, 2,
        # .. 32): the one at which i's block of s tokens is the left and
        # r's the right half of a block of 2s, the highest bit of i ^ r.
        # `lvl` [i, lane]: lanes 0..63 are r of Q^T (its diagonal is
        # level 0), lanes 64..127 r of A^T (strictly i < r).
        i = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
        lane = lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
        r = lane & (C - 1)
        x = i ^ r
        lvl = jnp.int32(1)
        for s in (2, 4, 8, 16, 32):
            lvl = jnp.where(x >= s, s, lvl)
        lvl = jnp.where(i < r, lvl, jnp.where((i == r) & (lane < C), 0, -1))
        tok = lax.broadcasted_iota(jnp.int32, (C, d), 0)
        row16 = lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
        eye16 = (row16 == lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
                 ).astype(f32)

        def head(h, carry):
            q, k, a = q_ref[:, h, :], k_ref[:, h, :], a_ref[:, h, :]
            beta = beta_ref[:, h, :]
            kb = k * beta
            # `loc` the decay summed from the start of a token's block of
            # s tokens to the token, `tot` over its whole block: exact
            # sums of few terms, and every exponent below is one of them
            # or a difference of the two, <= 0.
            loc = tot = a
            acc = jnp.where(lvl == 0, jnp.sum(q * k, axis=1, keepdims=True),
                            0.0)
            for s in (1, 2, 4, 8, 16, 32):
                late = jnp.exp(loc)                 # exp(b_r - b_left's end)
                early = k * jnp.exp(tot - loc)      # exp(b_left's end - b_i)
                acc = jnp.where(lvl == s, dot(
                    early, jnp.concatenate([q * late, kb * late]), NT), acc)
                left = pltpu.roll(tot, s, 0)
                odd = (tok & s) != 0
                loc = loc + jnp.where(odd, left, 0.0)
                tot = tot + jnp.where(odd, left, pltpu.roll(tot, C - s, 0))
            grown = jnp.exp(loc)                    # loc is b now, tot b_C
            St = st_ref[h]
            read = dot(jnp.concatenate([q * grown, kb * grown]), St, NT)
            rhs = beta * v_ref[:, h, :] - read[C:]
            # (I + A) wrote = rhs by forward substitution: by rows inside
            # a diagonal block (its inverse T), by blocks across the four
            wrote = []
            for I in range(C // SUB):
                lo = I * SUB
                own = acc[lo:lo + SUB, C + lo:C + lo + SUB]     # [i, r]
                T = eye16
                for t in range(1, SUB):
                    T = T - jnp.where(row16 == t, jnp.sum(
                        own[:, t:t + 1] * T, axis=0, keepdims=True), 0.0)
                mine = rhs[lo:lo + SUB]
                if I:
                    mine = mine - dot(acc[:lo, C + lo:C + lo + SUB],
                                      jnp.concatenate(wrote), TN)
                wrote.append(dot(T, mine, NN))
            wrote = jnp.concatenate(wrote)
            o_ref[:, h, :] = read[:C] + dot(acc[:, :C], wrote, TN)
            st_ref[h] = St * jnp.exp(tot[:1]) \
                + dot(wrote, k * jnp.exp(tot - loc), TN)
            return carry

        lax.fori_loop(0, heads, head, 0)

    @pl.when(n >= walk_ref[0])
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, f32)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        def give(h, carry):
            s_ref[h] = st_ref[h].T
            return carry
        lax.fori_loop(0, heads, give, 0)


def chunk_pallas(q, k, v, a, beta, S0, valid=None, *,
                 interpret: bool = False):
    """As chunk_xla, in one kernel; T must be whole chunks of 64, H whole
    blocks of `_CHUNK_HEADS` heads and d 128.  The grid walks a head
    block's chunks innermost with the block's states in fast memory,
    read from `S0` at the first chunk and written once after the last.
    A chunk wholly at or past `valid` (default T; pads are made as for
    chunk_xla) is not walked: its `o` is zeros, the state passes through,
    and its block index is the last walked chunk's, so nothing is
    fetched for it."""
    T, H, d = q.shape
    heads = _CHUNK_HEADS
    if not _chunk_tiles(T, H, d):
        raise ValueError(f"the kernel walks chunks of {CHUNK} tokens x "
                         f"{heads} heads of 128, got {T} x {H} x {d}")
    walk = chunks_walked(T, valid).reshape(1)
    # (a chunk that is not walked takes the last walked one's index)
    seq = pl.BlockSpec(
        (CHUNK, heads, d), lambda i, n, walk:
        (jnp.minimum(n, jnp.maximum(walk[0], 1) - 1), i, 0))
    state = pl.BlockSpec((heads, d, d), lambda i, n, walk: (i, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H // heads, T // CHUNK),
            in_specs=[seq] * 5 + [state],
            out_specs=[pl.BlockSpec((CHUNK, heads, d),
                                    lambda i, n, walk: (n, i, 0)), state],
            scratch_shapes=[pltpu.VMEM((heads, d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct(S0.shape, jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        name="kda_chunk",
    )(walk, q, k, v, a, jnp.broadcast_to(beta[..., None], q.shape), S0)
    return o, S


def chunks_walked(T: int, valid=None):
    """How many of a call's chunks of CHUNK tokens hold a real one."""
    if valid is None:
        return jnp.int32(T // CHUNK)
    return jnp.clip((jnp.asarray(valid, jnp.int32) + CHUNK - 1) // CHUNK,
                    0, T // CHUNK)


def _chunk_tiles(T: int, H: int, d: int) -> bool:
    return T % CHUNK == 0 and H % _CHUNK_HEADS == 0 and d == 128


def kda_chunk(q, k, v, a, beta, S0, valid=None):
    """The chunk in the form this backend runs: the kernel on a TPU at
    widths it tiles, plain XLA elsewhere.  `valid`: the tokens from it on
    are pads (made as the module says), which the kernel skips by whole
    chunks.  Returns (o, the state after the last real token)."""
    if _on_tpu() and _chunk_tiles(*q.shape):
        return chunk_pallas(q, k, v, a, beta, S0, valid)
    return chunk_xla(q, k, v, a, beta, S0)


def chunk_tokens_walked(T: int, H: int, d: int, valid=None):
    """The tokens `kda_chunk` walks in such a call: the chunks that hold
    a real token where the kernel runs, every token elsewhere."""
    if _on_tpu() and _chunk_tiles(T, H, d):
        return chunks_walked(T, valid) * CHUNK
    return jnp.int32(T)


# ---------------------------------------------------------------------------
# One token of every row


def step_xla(q, k, v, a, beta, S, active):
    """q, k, v, a [B, H, d], beta [B, H], S [B, H, d, d] float32, active
    [B] -> (o [B, H, d], S'): row b moves one token where active[b],
    and is left exactly as it is where not (its `o` is then of no
    use)."""
    alpha = jnp.exp(a)
    Sd = alpha[..., None] * S
    u = (Sd * k[..., None]).sum(-2)                          # S'^T k
    p = (Sd * q[..., None]).sum(-2)                          # S'^T q
    wrote = beta[..., None] * (v - u)
    o = p + (q * k).sum(-1, keepdims=True) * wrote
    stepped = Sd + k[..., None] * wrote[..., None, :]
    return o, jnp.where(active[:, None, None, None], stepped, S)


_STEP_ROWS, _STEP_HEADS = 4, 8     # a block of the step kernel: 2 MiB


def _step_kernel(layer_ref, blk_ref, act_ref, q_ref, k_ref, v_ref, a_ref,
                 beta_ref, s_ref, o_ref, out_ref, *, rows: int, heads: int):
    del layer_ref                       # read by the index maps
    j = pl.program_id(1)
    eye = (lax.broadcasted_iota(jnp.int32, (128, 128), 0)
           == lax.broadcasted_iota(jnp.int32, (128, 128), 1)
           ).astype(jnp.float32)

    def column(row):
        """[1, d] along the lanes -> [d, 1] down the sublanes."""
        return jnp.sum(eye * row, axis=1, keepdims=True)

    def row(r, carry):
        live = act_ref[j * rows + r] > 0
        q8, k8, v8 = q_ref[r], k_ref[r], v_ref[r]            # [heads, d]
        alpha8, beta8 = jnp.exp(a_ref[r]), beta_ref[r]
        os = []
        for h in range(heads):
            cut = lambda x: x[h:h + 1]                       # noqa: E731
            S = s_ref[0, r, h]                               # [dk, dv]
            q, k = cut(q8), cut(k8)
            Sd = column(cut(alpha8)) * S
            kc = column(k)
            u = jnp.sum(Sd * kc, axis=0, keepdims=True)
            p = jnp.sum(Sd * column(q), axis=0, keepdims=True)
            wrote = cut(beta8) * (cut(v8) - u)
            os.append(p + jnp.sum(q * k, axis=1, keepdims=True) * wrote)
            out_ref[0, r, h] = jnp.where(live, Sd + kc * wrote, S)
        o_ref[r] = jnp.concatenate(os, axis=0)
        return carry

    # A block none of whose rows is active is another block's turn over
    # again (`blk`): nothing was fetched for it, and what the turn before
    # left in the result's buffer is what goes back, once.
    @pl.when(blk_ref[j] == j)
    def _():
        lax.fori_loop(0, rows, row, 0)


def step_pallas(q, k, v, a, beta, states, layer, active, *,
                interpret: bool = False):
    """One token of every row in ONE pass over the state: `states`
    [L, B, H, d, d] is every layer's, of which layer `layer`'s rows are
    read, stepped and written back in place (the array is aliased to the
    result).  The grid walks the row blocks innermost, and a block of
    `_STEP_ROWS` rows none of which is active takes the index of the
    last block before it that has one (the first block where there is
    none yet): the pipeline fetches and writes a block only when the
    index changes, so an idle block crosses the memory in neither
    direction.  An inactive row's `o` is undefined.
    Returns (o [B, H, d], states)."""
    B, H, d = q.shape
    rows, heads = _STEP_ROWS, _STEP_HEADS
    if B % rows or H % heads or d != 128:
        raise ValueError(f"the kernel walks blocks of {rows} rows x "
                         f"{heads} heads of 128, got {B} x {H} x {d}")
    act = active.astype(jnp.int32)
    own = jnp.arange(B // rows, dtype=jnp.int32)
    blk = lax.cummax(jnp.where(act.reshape(B // rows, rows).max(1) > 0,
                               own, 0))
    vec = pl.BlockSpec((rows, heads, d),
                       lambda i, j, layer, blk, act: (blk[j], i, 0))
    state = pl.BlockSpec((1, rows, heads, d, d),
                         lambda i, j, layer, blk, act:
                         (layer[0], blk[j], i, 0, 0))
    o, states = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(H // heads, B // rows),
            in_specs=[vec] * 5 + [state], out_specs=[vec, state]),
        out_shape=[jax.ShapeDtypeStruct((B, H, d), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={8: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        name="kda_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), blk, act, q, k, v, a,
      jnp.broadcast_to(beta[..., None], q.shape), states)
    return o, states


def kda_step(q, k, v, a, beta, states, layer, active):
    """The tick's step of layer `layer` of `states` [L, B, H, d, d] in
    the form this backend runs: the kernel on a TPU at widths it tiles,
    plain XLA elsewhere.  Returns (o [B, H, d], zeros for an inactive
    row; states; the row states it read and wrote: the rows of the
    kernel's blocks that hold an active row, or every row of the
    call)."""
    B, H, d = q.shape
    if _on_tpu() and B % _STEP_ROWS == 0 and H % _STEP_HEADS == 0 \
            and d == 128:
        o, states = step_pallas(q, k, v, a, beta, states, layer, active)
        blocks = active.reshape(B // _STEP_ROWS, _STEP_ROWS).any(1)
        return jnp.where(active[:, None, None], o, 0.0), states, \
            blocks.sum() * _STEP_ROWS
    o, S = step_xla(q, k, v, a, beta, states[layer], active)
    return jnp.where(active[:, None, None], o, 0.0), \
        states.at[layer].set(S), jnp.int32(B)


# ---------------------------------------------------------------------------
# The convolution: ops/ssm.py's, with no bias


def kda_conv(u, tail, w, valid):
    """u [T, E] (q, k and v side by side), tail [K - 1, E], w [K, E] ->
    (silu(conv) [T, E] float32, the K - 1 inputs before token `valid`)."""
    return ssm.ssm_conv(u, tail, w, jnp.zeros((w.shape[1],), jnp.float32),
                        valid)


def kda_conv_step(u, tail, w, active):
    """u [B, E], tail [B, (K - 1) E] -> (silu(conv) [B, E] float32,
    tail'): an inactive row's tail stays as it is."""
    return ssm.ssm_conv_step(u, tail, w,
                             jnp.zeros((w.shape[1],), jnp.float32), active)
