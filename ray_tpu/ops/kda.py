"""Kimi Delta Attention (arXiv:2510.26692): the delta rule over a state
of [key 128, value 128] a head under a decay PER CHANNEL of the key
(`kda_chunk` over a chunk of one row, `kda_step` for one token of every
row), and the short depthwise convolution in front of it (`kda_conv`,
`kda_conv_step`: ops/ssm.py's, three streams side by side, no bias).

The recurrence, per head, in float32 (`a_t` <= 0 the log of the decay,
`alpha_t = exp(a_t)`; q, k, v are what the mixer made of them: k of unit
length, q of unit length over sqrt(d)):

    S'  = diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

A token with `a_t = 0` and `beta_t = 0` leaves the state exactly as it
is: that is how a chunk's pads (tokens at or past `valid`) are made.

The chunk (`chunk_xla`) is that recurrence unrolled over C = 64 tokens.
With `b_r = sum_{i<=r} a_i` (per channel, from the chunk's start):

    A[r,i]   = beta_r sum_c k_r[c] k_i[c] exp(b_r[c] - b_i[c])   (i < r)
    [U | W]  = (I + A)^-1 diag(beta) [V | K * exp(b)]
    V~       = U - W S_0              (what each token wrote)
    o_r      = S_0^T (q_r * exp(b_r)) + sum_{i<=r} Q[r,i] v~_i
    S_C      = diag(exp(b_C)) S_0 + sum_i (k_i * exp(b_C - b_i)) v~_i^T

where Q is A's sum with q_r for k_r, the diagonal kept and no beta.
EVERY exponent is a difference that is <= 0, taken as a difference:
`_decayed` scores a sub-block of 16 tokens against itself on
`b_r - b_i` directly and against earlier tokens through the sub-block's
first cumulative decay (`exp(b_r - ref)` times `exp(ref - b_i)`, both
<= 1: the bound of -5 a token is what keeps 16 x 5 = 80 under the 88
float32 holds; factoring `exp(-b_i)` out over 64 tokens overflows).
What depends on no state (A, Q, the solve) is computed for all chunks
at once; the walk over chunks carries the state through three matmuls.
The solve is a forward substitution (`triangular_solve`): the Neumann
product `(I - A)(I + A^2)...` cancels catastrophically where
neighbouring keys are alike.

The step has two forms.  `step_xla` is the equations as XLA fuses them:
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


kda_chunk = chunk_xla        # the one form there is: no kernel yet


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
