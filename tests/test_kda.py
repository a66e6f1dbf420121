"""ops/kda.py on the CPU at a small size, in float32: the chunked delta
rule (plain XLA, and the kernel interpreted) and the tick's step against
the recurrence token by token, and the convolution's tail."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

H, D = 2, 128


def recurrence(q, k, v, a, beta, S0):
    """The three lines of the module's docstring, one token at a time."""
    def token(S, inp):
        q, k, v, a, beta = inp
        Sd = jnp.exp(a)[..., None] * S
        u = jnp.einsum("hkv,hk->hv", Sd, k, precision="highest")
        S = Sd + k[..., None] * (beta[:, None] * (v - u))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision="highest")
    S, o = jax.lax.scan(token, S0, (q, k, v, a, beta))
    return o, S


def draws(seed, T, heads=H, bound=False, alpha_one=False, beta_zero=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (T, heads, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (T, heads, D)))
    v = jax.random.normal(ks[2], (T, heads, D))
    # log decays from -5 (the bound) to ~0, spread over channels
    a = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, heads, D)) * 3 - 3)
    if bound:
        a = jnp.full_like(a, -5.0)
    if alpha_one:
        a = jnp.zeros_like(a)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, heads)))
    if beta_zero:
        beta = jnp.zeros_like(beta)
    S0 = jax.random.normal(ks[5], (heads, D, D)) * 0.1
    return q, k, v, a, beta, S0


def close(got, want, tol=2e-5):
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=0)


FORMS = ["xla", "pallas"]


def chunk_form(form):
    """(the chunk in that form, the heads it is tried at): the kernel is
    interpreted here, and walks blocks of 8 heads."""
    if form == "pallas":
        return functools.partial(kda.chunk_pallas, interpret=True), \
            kda._CHUNK_HEADS
    return kda.chunk_xla, H


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", ["plain", "bound", "alpha_one", "beta_zero",
                                  "short"])
def test_chunk_is_the_recurrence(case, form):
    """The chunk over 128 tokens (two chunks of 64, the state carried
    between them inside the call) equals the recurrence, in both forms:
    with live gates; with EVERY decay at the bound -5 for whole chunks
    (finite: no exponent is taken but as a difference that is <= 0);
    with no decay; with nothing written; and over one chunk shorter than
    64, which is `chunk_xla`'s alone: the kernel walks whole chunks of
    64 and says so."""
    chunk, heads = chunk_form(form)
    T = 32 if case == "short" else 128
    args = draws(1, T, heads, bound=case == "bound",
                 alpha_one=case == "alpha_one",
                 beta_zero=case == "beta_zero")
    if (form, case) == ("pallas", "short"):
        with pytest.raises(ValueError, match="chunks of 64"):
            chunk(*args)
        return
    o, S = jax.jit(chunk)(*args)
    want_o, want_S = recurrence(*args)
    close(o, want_o)
    close(S, want_S)
    if case == "beta_zero":        # the state only decays, o reads it
        close(S, jnp.exp(args[3].sum(0))[..., None] * args[5], 1e-6)


@pytest.mark.parametrize("form", FORMS)
def test_chunk_carries_its_state_across_calls(form):
    """Two calls of 64 tokens, the second from the state the first left,
    equal one call of 128 and the recurrence over all of them."""
    chunk, heads = chunk_form(form)
    q, k, v, a, beta, S0 = draws(2, 128, heads)
    first = chunk(q[:64], k[:64], v[:64], a[:64], beta[:64], S0)
    second = chunk(q[64:], k[64:], v[64:], a[64:], beta[64:], first[1])
    want_o, want_S = recurrence(q, k, v, a, beta, S0)
    close(jnp.concatenate([first[0], second[0]]), want_o)
    close(second[1], want_S)


def padded(args, valid):
    """The call with its tokens at or past `valid` made pads as the
    mixer makes them (a = 0, beta = 0)."""
    q, k, v, a, beta, S0 = args
    real = jnp.arange(q.shape[0]) < valid
    return (q, k, v, jnp.where(real[:, None, None], a, 0.0),
            jnp.where(real[:, None], beta, 0.0), S0)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("valid", [1, 37, 64])
def test_pads_move_nothing(valid, form):
    """Tokens at or past `valid` made as the mixer makes them: the state
    after the chunk is the state after token `valid - 1`, and the real
    tokens' outputs are theirs."""
    chunk, heads = chunk_form(form)
    q, k, v, a, beta, S0 = args = padded(draws(3, 64, heads), valid)
    o, S = chunk(*args)
    want_o, want_S = recurrence(q[:valid], k[:valid], v[:valid], a[:valid],
                                beta[:valid], S0)
    close(o[:valid], want_o)
    close(S, want_S)


@pytest.mark.parametrize("valid", [0, 1, 64, 65, 449, 512])
def test_a_chunk_past_valid_is_not_walked(valid):
    """A call of 512 tokens told `valid`: the kernel leaves the state of
    token `valid - 1` (the state it was given where no token is real:
    the engine's warm-up), the real tokens' outputs are the
    recurrence's, a chunk of 64 wholly past `valid` yields zeros, and
    64 x ceil(valid / 64) tokens count as walked."""
    q, k, v, a, beta, S0 = args = padded(draws(7, 512, kda._CHUNK_HEADS),
                                         valid)
    o, S = jax.jit(functools.partial(kda.chunk_pallas, interpret=True))(
        *args, jnp.int32(valid))
    want_o, want_S = recurrence(q[:valid], k[:valid], v[:valid], a[:valid],
                                beta[:valid], S0)
    close(o[:valid], want_o)
    close(S, want_S)
    walked = -(-valid // kda.CHUNK) * kda.CHUNK
    assert not np.asarray(o[walked:]).any()
    assert int(kda.chunks_walked(512, valid)) * kda.CHUNK == walked
    if walked > valid:             # the walked chunk's own pads are read
        assert np.asarray(o[valid:walked]).any()


def test_kda_chunk_picks_its_form(monkeypatch):
    """On a TPU (patched in, the kernel interpreted in its place) the
    chunk is the kernel at the widths it tiles and `chunk_xla` at any
    other; off it, `chunk_xla` always.  What it says it walked follows
    the form: whole chunks up to `valid`, or every token."""
    calls = []
    real = functools.partial(kda.chunk_pallas, interpret=True)

    def kernel(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, **kw)

    monkeypatch.setattr(kda, "chunk_pallas", kernel)
    wide = padded(draws(8, 512, 32), 100)
    narrow = padded(draws(8, 128, 4), 100)
    off, _ = kda.kda_chunk(*wide, jnp.int32(100))
    assert not calls
    assert int(kda.chunk_tokens_walked(512, 32, D, 100)) == 512
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    on, _ = kda.kda_chunk(*wide, jnp.int32(100))
    assert calls == [(512, 32, D)]
    close(on[:100], off[:100])
    assert not np.asarray(on[128:]).any() and np.asarray(off[128:]).any()
    assert int(kda.chunk_tokens_walked(512, 32, D, 100)) == 128
    for shape, args in (((128, 4, D), narrow),
                        ((32, 32, D), padded(draws(8, 32, 32), 32))):
        kda.kda_chunk(*args, jnp.int32(100))
        assert calls == [(512, 32, D)], shape
        assert int(kda.chunk_tokens_walked(*shape, 100)) == shape[0]


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_step_is_the_recurrence_and_leaves_idle_rows(form):
    """One token of 8 rows, three of them idle, in layer 1 of 3: an
    active row's state and output are the recurrence's, an idle row's
    state and every other layer's are bit for bit what they were.  The
    kernel (interpreted here) and the plain form alike."""
    B, heads, L = 8, 8, 3
    q, k, v, a, beta, _ = draws(4, B, heads)
    states = jax.random.normal(jax.random.PRNGKey(9), (L, B, heads, D, D))
    active = jnp.array([1, 0, 1, 1, 0, 0, 0, 0], bool)   # block 1 all idle
    if form == "pallas":
        o, new = kda.step_pallas(q, k, v, a, beta, states, jnp.int32(1),
                                 active, interpret=True)
    else:
        o, S = kda.step_xla(q, k, v, a, beta, states[1], active)
        new = states.at[1].set(S)
    for b in range(B):
        if not active[b]:
            assert (np.asarray(new[1, b]) == np.asarray(states[1, b])).all()
            continue
        want_o, want_S = recurrence(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                    a[b:b + 1], beta[b:b + 1], states[1, b])
        close(o[b], want_o[0])
        close(new[1, b], want_S)
    for l in (0, 2):
        assert (np.asarray(new[l]) == np.asarray(states[l])).all()


def test_kda_step_says_what_it_touched(monkeypatch):
    """Off the chip the step reads and writes every row of the call; the
    kernel (interpreted here in the TPU's place) the blocks of 4 rows
    that hold an active one."""
    B, heads = 12, 8
    q, k, v, a, beta, _ = draws(6, B, heads)
    states = jnp.zeros((2, B, heads, D, D))
    active = jnp.array([1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 1, 1], bool)
    o, new, touched = kda.kda_step(q, k, v, a, beta, states, 0, active)
    assert int(touched) == B and not np.asarray(o[1]).any()
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "step_pallas", functools.partial(
        kda.step_pallas, interpret=True))
    o2, new2, touched = kda.kda_step(q, k, v, a, beta, states, 0, active)
    assert int(touched) == 2 * kda._STEP_ROWS
    close(o2, o)
    close(new2, new)


@pytest.mark.parametrize("valid", [2, 5, 8])
def test_conv_tail_over_a_boundary(valid):
    """Two chunks of 8 tokens, the second of which starts from the tail
    the first left after `valid` real tokens, equal one convolution over
    the real tokens in a row; a tick's step from that tail equals the
    next position."""
    E, K = 24, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    u = jax.random.normal(ks[0], (8 + 8 + 1, E))
    w = jax.random.normal(ks[1], (K, E))
    zeros = jnp.zeros((K - 1, E))
    seq = jnp.concatenate([u[:valid], u[8:]])            # the real tokens
    want, _ = kda.kda_conv(seq, zeros, w, len(seq))
    first, tail = kda.kda_conv(u[:8], zeros, w, valid)
    second, tail = kda.kda_conv(u[8:16], tail, w, 8)
    close(first[:valid], want[:valid], 1e-6)
    close(second, want[valid:valid + 8], 1e-6)
    step, moved = kda.kda_conv_step(
        jnp.stack([u[16], u[16]]), jnp.stack([tail.reshape(-1)] * 2), w,
        jnp.array([True, False]))
    close(step[0], want[-1], 1e-6)
    assert (np.asarray(moved[1]) == np.asarray(tail.reshape(-1))).all()
