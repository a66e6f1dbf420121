"""The selective scan of a Mamba-1 mixer (`ssm_scan` over a chunk of one
row, `ssm_step` for one token of every row) and its depthwise causal
convolution (`ssm_conv`, `ssm_conv_step`).

The recurrence, per channel e of E and state n of N, in float32:

    h_t[n, e] = exp(delta_t[e] * A[n, e]) * h_{t-1}[n, e]
                + delta_t[e] * x_t[e] * B_t[n]
    y_t[e]    = sum_n h_t[n, e] * C_t[n]

The decay differs per token, channel and state, so a chunk cannot be
folded into block matmuls (as `minicpm_sala.lightning_chunked` folds a
scalar decay a head).  Everything here keeps the CHANNELS MINOR (state
[N, E], never [E, N]): N = 16 on the chip's 128 lanes would pad every
state eight times over.

Two forms of the chunk's scan, the same arithmetic (each decay is
`exp(delta A)`, never a polynomial of it):

  scan_pallas   a kernel that holds a block of 1,024 channels' state in
                registers ([N, 8, 128]: N full vregs) and walks the chunk
                token by token; B_t and C_t are scalars read from SMEM,
                so nothing is broadcast across lanes and the [T, N, E]
                products never exist
  scan_blocked  plain XLA: `lax.associative_scan` inside sub-chunks of
                `sub` tokens, the state carried between them; it forms
                [sub, N, E] float32 temporaries in HBM a few times a
                sub-chunk.  What runs where there is no TPU, and what the
                kernel was measured against (PERF.md section 6)

A pad (a token at or past `valid`) has delta = 0: its decay is
exp(0) = 1 and it adds 0, so the state after a chunk is the state after
its last real token.

The tick's step has two forms too.  `step_xla` is the equations as XLA
fuses them: one fusion for y and one for the state, so the state is
read twice and the decay computed twice.  `step_pallas` reads a block of
32 rows x 1,024 channels once, steps it and writes it back IN PLACE in
the array of every layer's states (aliased to its result, the layer
picked by a prefetched scalar in the index map: no layer is sliced out,
nothing is copied); B_t and C_t reach it as [rows, N, 1] columns that
are broadcast along the lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB, _LANES = 8, 128
_BLOCK = _SUB * _LANES        # channels one kernel instance holds
BLOCKED_SUB = 32              # tokens a sub-chunk of scan_blocked


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# The chunk's scan


def scan_blocked(delta, x, Bm, Cm, A, h0, sub: int = BLOCKED_SUB):
    """delta, x [T, E], Bm, Cm [T, N], A [N, E] (negative), h0 [N, E],
    all float32 -> (y [T, E], h after token T - 1 [N, E])."""
    T = delta.shape[0]
    S = sub if T % sub == 0 else T
    cut = lambda a: a.reshape((T // S, S) + a.shape[1:])  # noqa: E731

    def combine(first, then):
        return then[0] * first[0], then[0] * first[1] + then[1]

    def body(h, inp):
        d, u, b, c = inp
        decay = jnp.exp(d[:, None, :] * A[None])            # [S, N, E]
        add = b[:, :, None] * (d * u)[:, None, :]
        pa, pb = lax.associative_scan(combine, (decay, add), axis=0)
        hs = pa * h[None] + pb
        return hs[-1], (hs * c[:, :, None]).sum(1)

    h, y = lax.scan(body, h0, (cut(delta), cut(x), cut(Bm), cut(Cm)))
    return y.reshape(delta.shape), h


def _scan_kernel(b_ref, c_ref, d_ref, u_ref, a_ref, h0_ref, y_ref, h_ref,
                 *, T: int, N: int, unroll: int):
    a = [a_ref[n] for n in range(N)]                         # [8, 128] each

    def token(t, h):
        d = d_ref[t]
        du = d * u_ref[t]
        y = jnp.zeros_like(d)
        out = []
        for n in range(N):
            hn = jnp.exp(d * a[n]) * h[n] + b_ref[t * N + n] * du
            y = y + c_ref[t * N + n] * hn
            out.append(hn)
        y_ref[t] = y
        return tuple(out)

    def tokens(i, h):          # `unroll` tokens a trip, written out
        for k in range(unroll):
            h = token(i * unroll + k, h)
        return h

    h = lax.fori_loop(0, T // unroll, tokens,
                      tuple(h0_ref[n] for n in range(N)))
    for n in range(N):
        h_ref[n] = h[n]


def scan_pallas(delta, x, Bm, Cm, A, h0, *, unroll: int = 4,
                interpret: bool = False):
    """As scan_blocked; E must be whole blocks of 1,024 channels."""
    T, E = delta.shape
    N = A.shape[0]
    if E % _BLOCK:
        raise ValueError(f"the kernel walks blocks of {_BLOCK} channels, "
                         f"got {E}")
    if T % unroll:
        unroll = 1
    rows = E // _LANES
    tiled = lambda a: a.reshape(a.shape[0], rows, _LANES)    # noqa: E731
    seq = pl.BlockSpec((T, _SUB, _LANES), lambda j, *_: (0, j, 0))
    state = pl.BlockSpec((N, _SUB, _LANES), lambda j, *_: (0, j, 0))
    y, h = pl.pallas_call(
        functools.partial(_scan_kernel, T=T, N=N, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // _SUB,),
            in_specs=[seq, seq, state, state], out_specs=[seq, state]),
        out_shape=[jax.ShapeDtypeStruct((T, rows, _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((N, rows, _LANES), jnp.float32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32, 8 * T * _BLOCK * 4 >> 20) << 20),
        name="ssm_scan",
    )(Bm.reshape(-1), Cm.reshape(-1), tiled(delta), tiled(x), tiled(A),
      tiled(h0))
    return y.reshape(T, E), h.reshape(N, E)


def ssm_scan(delta, x, Bm, Cm, A, h0):
    """The chunk's scan in the form this backend runs: the kernel on a
    TPU at widths it tiles, the blocked XLA scan elsewhere."""
    if _on_tpu() and delta.shape[1] % _BLOCK == 0:
        return scan_pallas(delta, x, Bm, Cm, A, h0)
    return scan_blocked(delta, x, Bm, Cm, A, h0)


# ---------------------------------------------------------------------------
# One token of every row


def step_xla(delta, x, Bm, Cm, A, h, active):
    """delta, x [B, E], Bm, Cm [B, N], A [N, E], h [B, N, E] float32,
    active [B] -> (y [B, E], h'): row b moves one token where
    active[b], and is left exactly as it is where not."""
    stepped = jnp.exp(delta[:, None, :] * A[None]) * h \
        + Bm[:, :, None] * (delta * x)[:, None, :]
    h = jnp.where(active[:, None, None], stepped, h)
    return (h * Cm[:, :, None]).sum(1), h


_STEP_ROWS, _STEP_CHANNELS = 32, 1024     # a block of the step kernel


def _step_kernel(layer_ref, act_ref, d_ref, u_ref, b_ref, c_ref, a_ref,
                 h_ref, y_ref, out_ref, *, rows: int):
    del layer_ref                       # read by the index maps
    first = pl.program_id(0) * rows
    a = a_ref[...]                                           # [N, cb]

    def eight(g, carry):
        r0 = pl.multiple_of(g * _SUB, _SUB)
        d8 = d_ref[pl.ds(r0, _SUB), :]
        du8 = d8 * u_ref[pl.ds(r0, _SUB), :]
        ys = []
        for k in range(_SUB):
            r = r0 + k
            h = h_ref[0, r]                                  # [N, cb]
            stepped = jnp.exp(d8[k:k + 1] * a) * h \
                + b_ref[r] * du8[k:k + 1]
            h = jnp.where(act_ref[first + r] > 0, stepped, h)
            out_ref[0, r] = h
            ys.append(jnp.sum(h * c_ref[r], axis=0, keepdims=True))
        y_ref[pl.ds(r0, _SUB), :] = jnp.concatenate(ys, axis=0)
        return carry

    lax.fori_loop(0, rows // _SUB, eight, 0)


def step_pallas(delta, x, Bm, Cm, A, states, layer, active, *,
                interpret: bool = False):
    """One token of every row in ONE pass over the state: `states`
    [M, B, N, E] is every layer's, of which layer `layer`'s rows are read,
    stepped and written back in place (the array is aliased to the
    result, so no layer of it is sliced out or copied); the others are
    not touched.  Returns (y [B, E], states)."""
    B, E = delta.shape
    N = A.shape[0]
    rows = _STEP_ROWS if B % _STEP_ROWS == 0 else _SUB
    cb = _STEP_CHANNELS
    if B % rows or E % cb:
        raise ValueError(f"the kernel walks blocks of {_SUB} rows and "
                         f"{cb} channels, got {B} x {E}")
    flat = pl.BlockSpec((rows, cb), lambda j, k, *_: (j, k))
    column = pl.BlockSpec((rows, N, 1), lambda j, k, *_: (j, 0, 0))
    state = pl.BlockSpec((1, rows, N, cb),
                         lambda j, k, layer, act: (layer[0], j, 0, k))
    y, states = pl.pallas_call(
        functools.partial(_step_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B // rows, E // cb),
            in_specs=[flat, flat, column, column,
                      pl.BlockSpec((N, cb), lambda j, k, *_: (0, k)), state],
            out_specs=[flat, state]),
        out_shape=[jax.ShapeDtypeStruct((B, E), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, states.dtype)],
        input_output_aliases={7: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=48 << 20),
        name="ssm_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      delta, x, Bm[:, :, None], Cm[:, :, None], A, states)
    return y, states


def ssm_step(delta, x, Bm, Cm, A, states, layer, active):
    """The tick's step of layer `layer` of `states` [M, B, N, E] in the
    form this backend runs: the kernel on a TPU at widths it tiles,
    plain XLA elsewhere (which reads the state twice: PERF.md section
    6).  Returns (y [B, E], states)."""
    B, E = delta.shape
    if _on_tpu() and B % _SUB == 0 and E % _STEP_CHANNELS == 0:
        return step_pallas(delta, x, Bm, Cm, A, states, layer, active)
    y, h = step_xla(delta, x, Bm, Cm, A, states[layer], active)
    return y, states.at[layer].set(h)


# ---------------------------------------------------------------------------
# The convolution


def ssm_conv(a, tail, w, bias, valid):
    """Depthwise causal convolution of K taps over one row's chunk.
    a [T, E]; tail [K - 1, E]: the K - 1 inputs before the chunk (zeros
    where the sequence starts); w [K, E] (tap K - 1 multiplies the
    token itself), bias [E].  Returns (silu(conv) [T, E] float32, the
    K - 1 inputs before token `valid`: the tail the next chunk needs,
    whatever pads follow)."""
    K, T = w.shape[0], a.shape[0]
    ext = jnp.concatenate([tail.astype(a.dtype), a])         # [T + K - 1, E]
    acc = bias.astype(jnp.float32)[None]
    for j in range(K):
        acc = acc + w[j].astype(jnp.float32)[None] \
            * ext[j:j + T].astype(jnp.float32)
    return jax.nn.silu(acc), lax.dynamic_slice_in_dim(ext, valid, K - 1)


def ssm_conv_step(a, tail, w, bias, active):
    """One token of every row.  a [B, E]; tail [B, (K - 1) E]: a row's
    last K - 1 inputs side by side, oldest first -> (silu(conv) [B, E]
    float32, tail'): an inactive row's tail stays as it is."""
    K, E = w.shape
    a = a.astype(tail.dtype)
    acc = bias.astype(jnp.float32)[None] \
        + w[K - 1].astype(jnp.float32)[None] * a.astype(jnp.float32)
    for j in range(K - 1):
        acc = acc + w[j].astype(jnp.float32)[None] \
            * tail[:, j * E:(j + 1) * E].astype(jnp.float32)
    moved = jnp.concatenate([tail[:, E:], a], axis=1)
    return jax.nn.silu(acc), jnp.where(active[:, None], moved, tail)
