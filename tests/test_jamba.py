"""Jamba (`model_type` `jamba`) at toy widths on the CPU, seeded weights:
the engine's own two programs (chunks whose last is padded, then ticks)
against one forward of the plain reference, a long chunk against the
same tokens in several, chunks with fewer real tokens than the
convolution's tail is long, two rows in one tick and a row admitted into
a slot another sequence left, the scan and the step against a direct
transcription of the recurrence (and the Pallas kernel, interpreted,
against the same), attention without positions over one key-value head,
the engine's page count, admission by rows and refusals by name, the
controls a comparison must catch, the benchmark's architecture files
against the program, and the toy configuration served to `correct` from
a temporary benchmark root."""

import ast
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode
from ray_tpu.models import jamba
from ray_tpu.ops import ssm
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ARCH_DIR = os.path.join(BENCH, "archs", "jamba")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The toy configuration, as a benchmark file would state it: six layers,
# attention at 1 and 5 (period 4, offset 1), so the runs are 1 Mamba,
# 1 attention, 3 Mamba, 1 attention; 64 channels of 4 states, 4 taps.
L = 6
C = {
    "name": "toy-jamba", "arch": "jamba", "attn_layer_offset": 1,
    "attn_layer_period": 4, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 64, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 4, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "num_attention_heads": 4, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": L,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
    "torch_dtype": "float32",
    "serving": {"engine": {"num_slots": 3, "max_seq": 128, "page_size": 4,
                           "kv_pages": 96, "prefill_chunk": 12,
                           "enable_prefix_cache": False},
                "check": {"prompt_len": 36, "decode_tokens": 10,
                          "tolerance": {"max_abs_diff": 1e-4,
                                        "mean_abs_diff": 1e-5}}}}
ROWS = 3
N_MAMBA, N_ATTN = 4, 2


def _load(name):
    spec = importlib.util.spec_from_file_location(
        "jamba_" + name, os.path.join(ARCH_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arch():
    from benchmarks.lib.registry import arch_of
    return arch_of(C, BENCH)


@pytest.fixture(scope="module")
def reference():
    return _load("reference")


def _bumped(params, seed=8):
    """Norms, biases and skips that are not all ones or zeros, so a
    missing one shows (the vectors; the matrices stay as drawn)."""
    bump = iter(jax.random.split(jax.random.PRNGKey(seed), 128))
    return jax.tree_util.tree_map(
        lambda w: w if w.ndim != 2 or w.shape[-1] > 64 else
        w + 0.1 * jax.random.normal(next(bump), w.shape), params)


@pytest.fixture(scope="module")
def model(arch):
    cfg = arch.build(C, C["serving"]["engine"]["max_seq"], remat=False)
    return cfg, _bumped(arch.init(cfg, jax.random.PRNGKey(7), jnp.float32))


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, C["vocab_size"], size=n).astype(np.int32)


class Driver:
    """The engine's two jitted programs over one cache, driven by hand
    as the engine's admission and tick do."""

    def __init__(self, cfg, params, psz, chunk, pages=96, nblk=32):
        self.cfg, self.params, self.psz, self.chunk = cfg, params, psz, chunk
        self.cache = decode.init_paged_cache(cfg, pages + 1, psz, ROWS)
        self.bt = np.zeros((ROWS, nblk), np.int32)
        self.pos = np.zeros((ROWS,), np.int32)
        self.tok = np.zeros((ROWS,), np.int32)
        self.next_page = 1

    def admit(self, slot, toks, total):
        """Prefill `toks` into fresh pages for `total` tokens, chunk by
        chunk (the last one padded), then activate row `slot`."""
        n = -(-total // self.psz)
        row = np.zeros((self.bt.shape[1],), np.int32)
        row[:n] = np.arange(self.next_page, self.next_page + n)
        self.next_page += n
        rows = []
        for s in range(0, len(toks), self.chunk):
            real = toks[s:s + self.chunk]
            chunk = np.zeros((1, self.chunk), np.int32)
            chunk[0, :len(real)] = real
            logits, self.cache = engine_mod._prefill_chunk(
                self.params, jnp.asarray(chunk), jnp.int32(s), self.cache,
                jnp.asarray(row[None]), self.cfg, slot=jnp.int32(slot),
                valid=jnp.int32(len(real)))
            rows.append(np.asarray(logits[0, :len(real)]))
        self.bt[slot], self.pos[slot] = row, len(toks)
        return np.concatenate(rows)

    def leave(self, slot):
        self.bt[slot], self.pos[slot], self.tok[slot] = 0, 0, 0

    def tick(self, feed):
        """One tick; `feed` {slot: token}.  Returns the logits [B, V]."""
        for slot, t in feed.items():
            self.tok[slot] = t
        _, logits, self.cache = engine_mod._paged_tick(
            self.params, jnp.asarray(self.tok), jnp.asarray(self.pos),
            self.cache, jnp.asarray(self.bt), self.cfg, with_logits=True)
        logits = np.asarray(logits)
        assert np.isfinite(logits).all()       # idle rows too
        for slot in feed:
            self.pos[slot] += 1
        return logits


def _one_sequence(drv, slot, toks, n_prompt):
    """Logits of every position of `toks`: the prompt through chunks,
    the rest tick by tick."""
    rows = [drv.admit(slot, toks[:n_prompt], len(toks))]
    for t in toks[n_prompt:]:
        rows.append(drv.tick({slot: t})[slot][None])
    return np.concatenate(rows)


# ------------------------------------ the engine's programs = one forward

CASES = {
    # page, chunk, prompt, ticks: chunk boundaries at 12, 24, 36 (each
    # inside the convolution's reach of the chunk before it); a last
    # chunk of 5 real tokens and 7 pads
    "chunk-12-page-4": (4, 12, 41, 20),
    "chunk-16-page-8": (8, 16, 53, 12),
    "whole-chunks": (4, 12, 36, 9),
    # last chunks with 1, 2 and 3 real tokens: fewer than the tail is long
    "valid-1": (4, 12, 25, 6),
    "valid-2": (4, 12, 26, 6),
    "valid-3": (4, 12, 27, 6),
    # a prompt of one token, and of one chunk
    "one-token-prompt": (4, 12, 1, 14),
    "short-prompt": (4, 12, 5, 14),
}


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_chunks_then_ticks_are_one_reference_forward(
        model, reference, case):
    """A prompt in several chunks whose last is padded, then ticks: every
    position's logits against the reference's token-by-token forward, so
    a chunk continues from the state and the tail the chunk before it
    left, a pad moves neither, and the ticks go on from both."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES[case]
    drv = Driver(cfg, params, psz, chunk)
    toks = _tokens(n_prompt + n_decode, seed=len(case))
    got = _one_sequence(drv, 1, toks, n_prompt)
    want = np.asarray(reference.forward(params, jnp.asarray(toks), C))
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("chunk", [4, 12, 24])
def test_one_long_chunk_is_the_same_tokens_in_several(model, chunk):
    """48 tokens as one chunk and as chunks of 4, 12 and 24: the same
    logits, the same state, the same tail, the same pages."""
    cfg, params = model
    toks = _tokens(48, seed=3)
    whole = Driver(cfg, params, 4, 48)
    parts = Driver(cfg, params, 4, chunk)
    np.testing.assert_allclose(parts.admit(2, toks, 48),
                               whole.admit(2, toks, 48), atol=2e-6)
    for key in ("ssm", "conv", "k", "v"):
        np.testing.assert_allclose(np.asarray(parts.cache[key]),
                                   np.asarray(whole.cache[key]), atol=2e-6)
    # the tail is the last three REAL inputs, and other rows hold nothing
    assert float(jnp.abs(parts.cache["ssm"][:, 2]).max()) > 0
    assert float(jnp.abs(parts.cache["ssm"][:, :2]).max()) == 0
    assert float(jnp.abs(parts.cache["conv"][:, :2]).max()) == 0


@pytest.mark.parametrize("valid", [0, 1, 2, 3, 7, 12])
def test_a_padded_chunk_leaves_state_and_tail_as_after_its_last_real_token(
        model, valid):
    """A chunk of 12 with `valid` real tokens after 12 earlier ones:
    state and tail are what `valid` tokens alone leave (with 0: what the
    chunk found), whatever the pads hold."""
    cfg, params = model
    toks = _tokens(24, seed=5)
    row = np.zeros((1, 32), np.int32)
    row[0, :6] = np.arange(1, 7)

    def after(second, valid, width):
        cache = decode.init_paged_cache(cfg, 97, 4, ROWS)
        _, cache = engine_mod._prefill_chunk(
            params, jnp.asarray(toks[None, :12]), jnp.int32(0), cache,
            jnp.asarray(row), cfg, slot=jnp.int32(1), valid=jnp.int32(12))
        if width:
            chunk = np.full((1, width), 77, np.int32)      # pads: garbage
            chunk[0, :valid] = second[:valid]
            _, cache = engine_mod._prefill_chunk(
                params, jnp.asarray(chunk), jnp.int32(12), cache,
                jnp.asarray(row), cfg, slot=jnp.int32(1),
                valid=jnp.int32(valid))
        return cache
    padded = after(toks[12:], valid, 12)
    # the same real tokens with no pad: `valid` of them as a chunk of
    # whole pages where that is one (else token by token below)
    if valid % 4 == 0:
        exact = after(toks[12:], valid, valid)
        for key in ("ssm", "conv"):
            np.testing.assert_allclose(np.asarray(padded[key]),
                                       np.asarray(exact[key]), atol=2e-6)
    # ...and against ticks from the first chunk's state
    drv = Driver(cfg, params, 4, 12)
    drv.admit(1, toks[:12], 24)
    for t in toks[12:12 + valid]:
        drv.tick({1: t})
    for key in ("ssm", "conv"):
        np.testing.assert_allclose(np.asarray(padded[key]),
                                   np.asarray(drv.cache[key]), atol=2e-6)


def test_two_rows_in_one_tick_and_a_slot_that_changes_hands(model,
                                                            reference):
    """Rows 0 and 2 decode at different depths in the same ticks; row 2's
    sequence ends and a SHORTER one is admitted into its slot while row 0
    goes on: the chunk at position 0 zeroes what the earlier sequence
    left, and the ticks of row 0 during the prefill leave the new row's
    state and tail alone."""
    cfg, params = model
    drv = Driver(cfg, params, 4, 12)
    a, b, c2 = _tokens(70, seed=1), _tokens(33, seed=2), _tokens(21, seed=3)
    got_a = [drv.admit(0, a[:30], len(a))]
    got_b = [drv.admit(2, b[:20], len(b))]
    for i in range(13):                            # both rows tick
        out = drv.tick({0: a[30 + i], 2: b[20 + i]})
        got_a.append(out[0][None])
        got_b.append(out[2][None])
    drv.leave(2)
    left = np.asarray(drv.cache["ssm"][:, 2])
    assert np.abs(left).max() > 0                  # the slot is not clean
    # row 0 ticks on between the new row's admission and its first tick
    got_c = [drv.admit(2, c2[:5], len(c2))]
    row, drv.bt[2], drv.pos[2] = drv.bt[2].copy(), 0, 0   # not yet active
    mid = {k: np.asarray(drv.cache[k]) for k in ("ssm", "conv")}
    out = drv.tick({0: a[43]})
    got_a.append(out[0][None])
    np.testing.assert_array_equal(np.asarray(drv.cache["ssm"][:, 2]),
                                  mid["ssm"][:, 2])
    np.testing.assert_array_equal(np.asarray(drv.cache["conv"][:, 2]),
                                  mid["conv"][:, 2])
    np.testing.assert_array_equal(np.asarray(drv.cache["ssm"][:, 1]),
                                  mid["ssm"][:, 1])      # an idle row
    drv.bt[2], drv.pos[2] = row, 5
    for i in range(16):
        out = drv.tick({0: a[44 + i], 2: c2[5 + i]})
        got_a.append(out[0][None])
        got_c.append(out[2][None])
    for got, toks in ((got_a, a[:60]), (got_b, b), (got_c, c2)):
        want = reference.forward(params, jnp.asarray(toks), C)
        np.testing.assert_allclose(np.concatenate(got), np.asarray(want),
                                   atol=2e-6)


# ------------------------------------------- the mixer's pieces, alone

def _recurrence(delta, x, Bm, Cm, A, h0):
    """The equations, in float64: h_t = exp(delta_t A) h_{t-1} +
    delta_t x_t B_t; y_t = h_t C_t."""
    h, ys = np.asarray(h0, np.float64), []
    for t in range(delta.shape[0]):
        h = np.exp(delta[t][None, :] * A) * h \
            + (delta[t] * x[t])[None, :] * Bm[t][:, None]
        ys.append((h * Cm[t][:, None]).sum(0))
    return np.stack(ys), h


def _scan_inputs(T, E, N, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    delta = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), size=(T, E))
                   ).astype(np.float32)
    A = -np.tile(np.arange(1, N + 1, dtype=np.float32)[:, None], (1, E))
    return delta, f(T, E), f(T, N), f(T, N), A, f(N, E)


@pytest.mark.parametrize("form", ["blocked-32", "blocked-whole",
                                  "pallas-1", "pallas-2", "pallas-4"])
def test_the_scan_is_the_recurrence(form):
    """Both forms of the chunk's scan (the kernel interpreted) against
    the recurrence written out; a pad (delta 0) moves nothing."""
    T, E, N = (96, 1024, 16) if form.startswith("pallas") else (96, 64, 4)
    delta, x, Bm, Cm, A, h0 = _scan_inputs(T, E, N)
    delta[80:] = 0.0                                  # 16 pads
    kind, knob = form.split("-")
    if kind == "pallas":
        fn = lambda *a: ssm.scan_pallas(  # noqa: E731
            *a, unroll=int(knob), interpret=True)
    else:
        fn = lambda *a: ssm.scan_blocked(  # noqa: E731
            *a, sub=32 if knob == "32" else 7)
    y, h = jax.jit(fn)(delta, x, Bm, Cm, A, h0)
    want_y, want_h = _recurrence(delta, x, Bm, Cm, A, h0)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h), want_h, atol=2e-6)
    np.testing.assert_allclose(np.asarray(h),
                               _recurrence(*(a[:80] for a in (
                                   delta, x, Bm, Cm)), A, h0)[1], atol=2e-6)


def test_the_step_applied_t_times_is_the_scan():
    """The step over three rows of which one is inactive: T steps of the
    active rows are `ssm_scan` over T, and the inactive row's state is
    bit for bit what it was."""
    T, E, N, B = 40, 64, 4, 3
    rows = [_scan_inputs(T, E, N, seed=s) for s in range(B)]
    A = rows[0][4]
    h = jnp.stack([r[5] for r in rows])
    active = jnp.asarray([True, False, True])
    ys = []
    for t in range(T):
        y, h = jax.jit(ssm.step_xla)(
            *(jnp.stack([r[i][t] for r in rows]) for i in range(4)), A, h,
            active)
        ys.append(np.asarray(y))
    np.testing.assert_array_equal(np.asarray(h[1]), rows[1][5])
    for b in (0, 2):
        want_y, want_h = ssm.scan_blocked(*rows[b])
        np.testing.assert_allclose(np.stack(ys)[:, b], np.asarray(want_y),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(h[b]), np.asarray(want_h),
                                   atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(h[b]), _recurrence(*rows[b])[1], atol=2e-6)


@pytest.mark.parametrize("rows", [8, 32, 40])
def test_the_step_kernel_is_the_step(rows):
    """The tick's step as the chip runs it (interpreted): layer 1 of
    three layers' states stepped in place, inactive rows and the other
    layers bit for bit what they were."""
    rng = np.random.default_rng(rows)
    E, N = 2048, 16
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    delta, x = np.abs(f(rows, E)) * 0.1, f(rows, E)
    Bm, Cm, states = f(rows, N), f(rows, N), f(3, rows, N, E)
    A = -np.tile(np.arange(1, N + 1, dtype=np.float32)[:, None], (1, E))
    active = rng.integers(0, 2, size=rows).astype(bool)
    want_y, want_h = ssm.step_xla(delta, x, Bm, Cm, A, states[1], active)
    y, got = jax.jit(lambda *a: ssm.step_pallas(*a, interpret=True))(
        delta, x, Bm, Cm, A, states, jnp.int32(1), active)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want_h),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got[1])[~active],
                                  states[1][~active])
    for other in (0, 2):
        np.testing.assert_array_equal(np.asarray(got[other]), states[other])


def test_the_convolution_is_four_taps_with_a_tail():
    """`ssm_conv` against the sum written out, its tail against the last
    three real inputs, and `ssm_conv_step` token by token against it."""
    rng = np.random.default_rng(1)
    T, E, K = 10, 16, 4
    a = rng.normal(size=(T, E)).astype(np.float32)
    tail = rng.normal(size=(K - 1, E)).astype(np.float32)
    w = rng.normal(size=(K, E)).astype(np.float32)
    bias = rng.normal(size=(E,)).astype(np.float32)
    ext = np.concatenate([tail, a])
    want = bias + sum(w[j] * ext[j:j + T] for j in range(K))
    want = want / (1 + np.exp(-want))
    for valid in (0, 1, 2, 3, 10):
        c, new = ssm.ssm_conv(jnp.asarray(a), jnp.asarray(tail),
                              jnp.asarray(w), jnp.asarray(bias),
                              jnp.int32(valid))
        np.testing.assert_allclose(np.asarray(c), want, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(new),
                                      ext[valid:valid + K - 1])
    t2 = jnp.asarray(np.stack([tail.reshape(-1)] * 2))     # two rows
    for t in range(T):
        c, t2 = ssm.ssm_conv_step(
            jnp.asarray(np.stack([a[t], a[t]])), t2, jnp.asarray(w),
            jnp.asarray(bias), jnp.asarray([True, False]))
        np.testing.assert_allclose(np.asarray(c[0]), want[t], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(t2[0]), ext[T:].reshape(-1))
    np.testing.assert_array_equal(np.asarray(t2[1]), tail.reshape(-1))


def test_attention_has_no_positions_and_one_key_value_head(model):
    """Permuting a row's cached keys together with their values changes
    no logit of a tick (nothing in attention knows a position), and the
    pages hold ONE head: all four query heads read it."""
    cfg, params = model
    toks = _tokens(25, seed=9)
    plain, mixed = Driver(cfg, params, 4, 12), Driver(cfg, params, 4, 12)
    for drv in (plain, mixed):
        drv.admit(0, toks[:24], 32)
    assert plain.cache["k"].shape == (N_ATTN, 97, 1, 4, 8)
    pages = plain.bt[0, :6]
    perm = np.random.default_rng(0).permutation(24)
    for key in ("k", "v"):
        rows = np.asarray(mixed.cache[key][:, pages]).reshape(N_ATTN, 24, 8)
        mixed.cache[key] = mixed.cache[key].at[:, pages].set(
            jnp.asarray(rows[:, perm].reshape(N_ATTN, 6, 1, 4, 8)))
    np.testing.assert_allclose(mixed.tick({0: toks[24]})[0],
                               plain.tick({0: toks[24]})[0], atol=2e-6)


# ------------------------------------------------ the controls

CONTROLS = {"state not carried": {"_state_reset_every": 12},
            "tail not carried": {"_tail_reset_every": 12},
            "state in bfloat16": {"_state_dtype": "bfloat16"},
            "dt / B / C norms dropped": {"_no_dtbc_norms": True},
            "D dropped": {"_no_D": True},
            "no softplus": {"_no_softplus": True},
            "linear decay": {"_linear_decay": True},
            "RoPE applied": {"_rope": 10000.0},
            "no convolution bias": {"_no_conv_bias": True}}


@pytest.mark.parametrize("control", list(CONTROLS) + ["float8 matmuls"])
def test_each_control_is_another_model(model, reference, control):
    """What tools/jamba_limits.py sets the cell's limits from: the
    reference computed wrong in one way is not what the program
    computes, by far more than the program differs from the reference
    (2e-6 above; a bf16 state moves float32 logits the least, 1e-5)."""
    cfg, params = model
    psz, chunk, n_prompt, n_decode = CASES["chunk-12-page-4"]
    toks = _tokens(n_prompt + n_decode, seed=1)
    got = _one_sequence(Driver(cfg, params, psz, chunk), 0, toks, n_prompt)
    kw = {"round_to": "float8_e4m3fn"} if control == "float8 matmuls" else {}
    wrong = np.asarray(reference.forward(
        params, jnp.asarray(toks), dict(C, **CONTROLS.get(control, {})),
        **kw))
    assert not np.isfinite(wrong).all() \
        or np.abs(got - wrong).max() > 5e-6, control


# ---------------------------------------------- the benchmark's files

def test_the_benchmarks_init_is_the_programs(arch, model):
    cfg, _ = model
    key = jax.random.PRNGKey(11)
    gain = np.float32(arch.SEEDED_ATTN_LOGIT_STD ** 0.5)
    for dtype in (jnp.float32, jnp.bfloat16):
        ours = jax.jit(lambda k: arch.init(cfg, k, dtype))(key)
        theirs = jax.jit(lambda k: jamba.init_params(cfg, k, dtype))(key)
        assert jax.tree_util.tree_structure(ours) \
            == jax.tree_util.tree_structure(theirs)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(ours),
                                jax.tree_util.tree_leaves(theirs)):
            assert a.dtype == b.dtype and a.shape == b.shape
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            if path[-1].key == "wq":
                b = b * gain
            elif path[-1].key == "wkv":
                b = np.stack([b[:, :, 0] * gain, b[:, :, 1]], 2)
            tol = 1e-2 if dtype == jnp.bfloat16 else 0
            np.testing.assert_allclose(a, b, rtol=tol)
    # the Mamba paper's initialisation of what shapes the recurrence
    run = ours["runs"][0]
    assert run["a_log"].dtype == run["b_dt"].dtype == jnp.float32
    np.testing.assert_allclose(np.exp(np.asarray(run["a_log"][0, :, 0])),
                               np.arange(1, 5), rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(run["b_dt"]))
    assert 1e-3 * 0.99 <= dt0.min() and dt0.max() <= 1e-1 * 1.01
    assert float(jnp.abs(run["d_skip"] - 1).max()) == 0
    assert "wlm" not in ours                          # the head is tied


def test_the_layer_kinds_come_from_period_and_offset():
    cfg = jamba.JambaConfig(max_seq=64)
    kinds = cfg.layer_kinds
    assert [i for i, k in enumerate(kinds) if k == jamba.ATTN] == [7, 21]
    assert (cfg.n_attn, cfg.n_mamba, cfg.d_inner) == (2, 26, 5120)
    assert cfg.runs == (("mamba", 7, 0), ("attention", 1, 0),
                        ("mamba", 13, 7), ("attention", 1, 1),
                        ("mamba", 6, 20))
    assert hash(cfg) == hash(jamba.JambaConfig(max_seq=64))
    cache = jax.eval_shape(
        lambda: decode.init_paged_cache(cfg, 6145, 64, 128))
    assert cache["k"].shape == (2, 6145, 1, 64, 128)
    assert (cache["ssm"].shape, cache["ssm"].dtype) \
        == ((26, 128, 16, 5120), jnp.float32)
    assert (cache["conv"].shape, cache["conv"].dtype) \
        == ((26, 128, 3 * 5120), jnp.bfloat16)


def test_the_reference_imports_jax_alone():
    with open(os.path.join(ARCH_DIR, "reference.py")) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add("." if node.level else node.module.split(".")[0])
    assert imported <= {"__future__", "jax"}, imported


def test_the_architecture_fails_by_name_on_a_program_without_the_model(
        monkeypatch):
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "ray_tpu.models.jamba"
        else real(name, *a))
    spec = importlib.util.spec_from_file_location(
        "jamba_arch_probe", os.path.join(ARCH_DIR, "__init__.py"),
        submodule_search_locations=[ARCH_DIR])
    mod = importlib.util.module_from_spec(spec)
    with pytest.raises(ImportError, match="ray_tpu.models.jamba"):
        spec.loader.exec_module(mod)


def test_no_other_configuration_imports_the_model():
    """Nothing this model brings runs at import or at replica start for
    another configuration: `ray_tpu.models` does not import it or its
    kernels, nor do the engine, decode or the other architectures."""
    code = ("import sys; import ray_tpu.models, ray_tpu.serve.llm.engine; "
            "sys.path.insert(0, %r); "
            "from benchmarks.lib.registry import arch_of; arch_of({}); "
            "[arch_of({'arch': a}) for a in "
            "('deepseek_v2', 'exaone_moe', 'minicpm_sala')]; "
            "bad = [m for m in sys.modules if 'jamba' in m "
            "or m == 'ray_tpu.ops.ssm']; assert not bad, bad" % REPO)
    import subprocess
    subprocess.run([sys.executable, "-c", code], check=True, timeout=300,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _real_config():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_costs_against_hand_counts(arch):
    c = _real_config()
    mixer = 2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 160 * 5120 \
        + 5120 + 5120 * 16 + 5120 + 5120 * 2560
    assert mixer == 41_241_600                        # ISSUE 48's 41.24 M
    # ...and the dt / B / C norms' 160 + 16 + 16 weights
    assert arch.mamba_matmul_params(c) + arch.mamba_other_params(c) \
        == mixer + 192
    assert arch.ffn_params(c) == 3 * 2560 * 8192 == 62_914_560
    assert arch.attention_params(c) == 2 * 2560 * 2560 + 2 * 2560 * 128 \
        == 13_762_560
    assert arch.layer_matmul_params(c, "attention") == 76_677_120
    assert abs(arch.total_params(c) - 3.03e9) < 0.005e9
    # ISSUE 48's table: 5.42 + 0.31 + 0.34 = 6.06 GB resident
    assert abs(arch.weight_bytes(c) - 6.06e9) < 0.005 * 6.06e9
    assert arch.kv_bytes_per_token(c) == 1024
    assert arch.state_bytes_per_row(c) == 26 * (5120 * 16 * 4 + 5120 * 3 * 2) \
        == 9_318_400
    # the program holds what the yardstick counts
    cfg = arch.build(c, 3072, remat=False)
    shapes = jax.eval_shape(lambda k: jamba.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(shapes))
    assert held == arch.weight_bytes(c)
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == arch.total_params(c)
    cache = jax.eval_shape(lambda: decode.init_paged_cache(cfg, 2, 64, 128))
    assert sum(cache[k].size * cache[k].dtype.itemsize
               for k in jamba.BODY.row_state_keys) \
        == 128 * arch.state_bytes_per_row(c)
    # a tick reads and writes the state: 2 x 9.32 MB a row, beside the
    # weights and the keys
    tick = arch.decode_tick(c, 128, 128 * 600)
    step = arch.ssm_step(c, 128)
    assert step["bytes"] == 2 * 128 * 26 * 5120 * 16 * 4
    assert step["flops"] == 6 * 128 * 26 * 5120 * 16
    assert tick["bytes"] > arch.matmul_params(c) * 2 + step["bytes"]
    scan = arch.ssm_scan(c, 256)
    assert scan["flops"] == 6 * 256 * 26 * 5120 * 16
    assert arch.attn_nope(c, 10, 7) == {"flops": 2 * 2 * 20 * 2 * 128 * 10,
                                        "bytes": 1024 * 7}
    assert arch.ssm_conv(c, 256)["bytes"] > 0
    with pytest.raises(NotImplementedError, match="serves only"):
        arch.train_flops_per_token(c, 4096)


def test_the_configuration_file_holds_the_catalogs_numbers(arch):
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        rows = [json.loads(ln) for ln in f if ln.strip()]
    row = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    c = _real_config()
    assert c["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if c.get(k, "absent") != v] \
        == [] == c["reduced"]
    assert (c["num_hidden_layers"], c["vocab_size"]) == (28, 65536)
    assert "holds the model whole" in c["stands_for"]
    for key in ("assumed", "departures", "resident_bytes", "reduced_why"):
        assert c[key], key
    for dagger in ("order of the layer types", "block (norm placement)",
                   "dt / B / C norms", "state and tail dtypes",
                   "seeded weights", "serving.engine.num_slots"):
        assert c["assumed"][dagger], dagger
    cfg = arch.build(c, c["serving"]["engine"]["max_seq"], remat=False)
    assert cfg == jamba.JambaConfig(max_seq=3072)     # the defaults


def test_the_new_cells_files_load_through_the_registry():
    from benchmarks.lib.registry import Registry
    reg = Registry(REPO)
    cell = reg.cell("jamba2-chat")
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("jamba2-3b", "ssm_chat", 1)
    c, mix = reg.config(cell["config"]), reg.traffic(cell["traffic"])
    assert c["arch"] == "jamba"
    assert (mix["loop"], mix["clients"], mix["block"], mix["blocks"],
            mix["warmup_first_tokens"], mix["trace_seconds"]) \
        == ("closed", 256, 128, 64, 256, 6)
    assert "order_seed" not in mix
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 1.0, "min": 32, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.7, "min": 32, "max": 1024}
    e = c["serving"]["engine"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == e["max_seq"]
    assert mix["block"] == e["num_slots"] and not e["enable_prefix_cache"]
    # every row at the mix's largest request: the pool never refuses
    assert e["kv_pages"] == e["num_slots"] * e["max_seq"] // e["page_size"]
    names = {m["name"] for m in reg.metrics_for("jamba2-chat", "per_layer")}
    assert {"row_state_gb.tput", "state_resets_per_s.tput",
            "paged_tick_roofline.tput", "prefill_chunk_roofline.tput",
            "hbm_filled_gb.tput", "replica_start_s", "start_warm_s"} <= names
    # (28 when the cell came, PR 48; PR 61 appended it to
    # `idle_host_work_share.tput`)
    assert len(names) == 29
    assert {m["name"] for m in reg.metrics_for(
        "jamba2-chat", "end_to_end")} == {"out_tok_per_s", "setup_s"}
    for name in names:
        spec = reg.metric(name)
        reg.reader(spec["reader"])
    assert len(reg.spec["per_layer"]) <= 128
    def read(name, obs):
        spec = reg.metric(name)
        return reg.reader(spec["reader"])(obs, **spec["args"])
    obs = {"stats0": {"state_resets": 10, "uptime_s": 100.0},
           "stats1": {"state_resets": 550, "uptime_s": 145.0,
                      "row_state_bytes": 128 * 9_318_400}}
    assert read("state_resets_per_s.tput", obs) == 12.0
    assert abs(read("row_state_gb.tput", obs) - 1.1928) < 1e-3
    # a parent without the key reads nothing, quietly
    assert read("row_state_gb.tput", {"stats1": {"uptime_s": 1.0}}) is None
    assert read("row_state_gb.tput", {}) is None


# ------------------------------------------------------------- guards

@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, num_slots=ROWS, page_size=4,
                           prefill_chunk=12, kv_pages=96,
                           enable_prefix_cache=False)
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_tiering", "kv_export", "kv_import", "session",
    "session_resurrect", "migrate_local", "speculation", "prefill_chunk"])
def test_what_cannot_carry_a_state_refuses_by_name(model, served, what):
    cfg, params = model
    kw = dict(num_slots=ROWS, page_size=4, prefill_chunk=12,
              enable_prefix_cache=False)
    missing = "per-row recurrent state .JambaConfig."
    if what == "prefix_cache":
        with pytest.raises(NotImplementedError, match=missing):
            GenerationEngine(params, cfg, **dict(kw, enable_prefix_cache=True))
    elif what == "kv_tiering":
        with pytest.raises(NotImplementedError, match=missing):
            GenerationEngine(params, cfg, kv_tiering=True, **kw)
    elif what == "kv_export":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_export([1, 2, 3])
    elif what == "kv_import":
        with pytest.raises(NotImplementedError, match=missing):
            served.kv_import([1, 2, 3], np.zeros(1), np.zeros(1))
    elif what == "session":
        with pytest.raises(NotImplementedError, match=missing):
            served.submit([1, 2, 3], max_new_tokens=2, session_id="s")
    elif what == "session_resurrect":
        with pytest.raises(NotImplementedError, match=missing):
            served.session_resurrect("s")
    elif what == "migrate_local":
        with pytest.raises(NotImplementedError, match=missing):
            kv_transfer.migrate_local(served, served, [1, 2, 3])
    elif what == "speculation":
        with pytest.raises(NotImplementedError, match="rolled back"):
            GenerationEngine(params, cfg, speculate_k=2, **kw)
    else:
        with pytest.raises(ValueError, match="whole pages"):
            GenerationEngine(params, cfg, **dict(kw, prefill_chunk=10))


def test_the_pool_and_the_reservation_count_attention_layers_only(model,
                                                                  served):
    """`_blocks_for` counts tokens over the page size, whatever the
    layers: a page is the two attention layers' keys and values alone,
    the pool has no Mamba layer in it, and the state is reported apart."""
    cfg, _ = model
    assert served._blocks_for(41, 14) == -(-(41 + 14) // 4)
    assert served._cache["k"].shape[0] == N_ATTN
    assert served._commit_cap == 4 * 96
    st = served.stats()
    assert st.kv_blocks_total == 96
    state = N_MAMBA * ROWS * (4 * 64 * 4 + 3 * 64 * 4)   # float32 toy tail
    assert st.row_state_bytes == state == sum(
        int(served._cache[k].nbytes) for k in jamba.BODY.row_state_keys)


def test_the_engine_serves_it_and_admission_is_by_rows(model, served,
                                                       reference):
    """Seven requests on three rows of a pool that could hold them all
    at once: never more than three are active (the rows limit, the pool
    does not), slots change hands, greedy tokens equal the reference's
    argmax chain, and the counters say what ran."""
    cfg, params = model
    before = served.stats().to_dict()
    prompts = [_tokens(n, seed=n).tolist() for n in (41, 5, 30, 17, 22, 1, 12)]
    need = sum(served._blocks_for(len(p), 14) for p in prompts)
    assert need < served.kv_pages                      # the pool is not it
    streams = [served.submit(p, max_new_tokens=14) for p in prompts]
    most, free_least = 0, served.kv_pages
    while served.stats().requests_completed \
            < before["requests_completed"] + len(prompts):
        st = served.stats()
        most = max(most, st.active_slots + st.queue_depth)
        free_least = min(free_least, st.kv_blocks_free)
        assert st.active_slots <= ROWS
    outs = [s.result(timeout=300) for s in streams]
    assert most > ROWS and free_least > 0       # requests waited for rows
    for p, out in zip(prompts, outs):
        seq = jnp.asarray(list(p) + out[:13], jnp.int32)
        logits = reference.forward(params, seq, C)
        assert out == np.asarray(logits)[len(p) - 1:].argmax(-1).tolist()
    gain = {k: v - before[k] for k, v in served.stats().to_dict().items()
            if isinstance(v, (int, float))}
    held = sum((pos + 1) * N_ATTN for p in prompts
               for pos in range(len(p), len(p) + 13))
    assert gain["prefill_tokens"] == sum(map(len, prompts))
    assert gain["attn_keys_context"] == held
    assert gain["attn_keys_attended"] == gain["attn_keys_resident"] == held
    assert gain["attn_keys_gathered"] >= held
    assert gain["state_resets"] == 7 and gain["prefill_tokens_sparse"] == 0
    assert gain["row_state_bytes"] == 0                # a level


@pytest.mark.parametrize("name", ["minicpm_sala", "exaone_moe"])
def test_the_other_bodies_with_row_state_report_theirs(name):
    """`row_state_bytes` for SALA's lightning state and K-EXAONE's rings:
    the entries each module names, and no program of theirs changed."""
    mod = importlib.import_module("ray_tpu.models." + name)
    if name == "minicpm_sala":
        cfg = mod.SalaConfig(
            mixer_types=(mod.LIN, mod.ATTN), max_seq=256, vocab_size=64,
            d_model=32, n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
            lin_heads=4, lin_head_dim=8, dtype=jnp.float32)
        kw = dict(page_size=64, prefill_chunk=64, kv_pages=8)
        want = 1 * 2 * 4 * 8 * 8 * 4
    else:
        cfg = mod.ExaoneMoeConfig(
            max_seq=64, n_layers=2, vocab_size=64, d_model=32, n_heads=4,
            n_kv_heads=2, head_dim=8, d_ff=64, first_k_dense=1, moe_d_ff=16,
            n_routed_experts=4, top_k=2, sliding_windows=(8, 0),
            dtype=jnp.float32)
        kw = dict(page_size=4, prefill_chunk=8, kv_pages=16)
        want = 2 * (1 * 2 * 8 * 2 * 8 * 4)
    params = mod.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    eng = GenerationEngine(params, cfg, num_slots=2,
                           enable_prefix_cache=False, **kw)
    try:
        assert eng.stats().row_state_bytes == want
    finally:
        eng.stop()


def test_a_model_without_row_state_reports_none():
    from ray_tpu.models import llama
    cfg = llama.LlamaConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, n_layers=2, d_ff=64, max_seq=64,
                            dtype=jnp.float32)
    eng = GenerationEngine(llama.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, num_slots=2, page_size=4, prefill_chunk=8,
                           kv_pages=32)
    try:
        assert eng.stats().row_state_bytes == 0
    finally:
        eng.stop()


# ------------------------------------- the toy configuration as a cell

def test_the_toy_configuration_is_served_to_correct(tmp_path):
    """A temporary benchmark root gets a configuration that names
    `jamba`, a chat mix at toy size and a cell; the benchmark's own run
    serves it under a mix half of whose prompts are one padded chunk,
    and its check (36 + 10 positions: three whole chunks, as the probe
    gives a chunk no count of real tokens, then ten ticks) comes out
    correct."""
    sys.path.insert(0, os.path.join(BENCH, "tests"))
    try:
        import toy
    finally:
        sys.path.pop(0)
    from benchmarks import run as bench_run
    from benchmarks.lib.registry import Registry

    root = toy.build(str(tmp_path))
    b = os.path.join(root, "bm")
    with open(os.path.join(b, "configs", "toy-jamba.json"), "w") as f:
        json.dump(C, f)
    with open(os.path.join(b, "traffic", "chat-toy.json"), "w") as f:
        json.dump({"kind": "serve", "loop": "closed", "clients": 6,
                   "block": 3, "blocks": 64, "warmup_first_tokens": 6,
                   "prompt_len": {"dist": "lognormal", "median": 12,
                                  "sigma": 1.0, "min": 2, "max": 40},
                   "output_len": {"dist": "lognormal", "median": 12,
                                  "sigma": 0.7, "min": 4, "max": 24},
                   "trace_seconds": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-jamba", "source": "none",
                            "file": "bm/configs/toy-jamba.json",
                            "reduced": [], "why": "toy"})
    spec["workloads"].append({"name": "jamba-toy", "config": "toy-jamba",
                              "traffic": "chat-toy", "chips": 1,
                              "why": "toy"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_per_s":
            m["workloads"].append("jamba-toy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    reg = Registry(root)
    lines = []
    out = bench_run.run_cell(reg, "jamba-toy", seed=2**31 + 48,
                             seconds=4.0, trace=False, platform="cpu",
                             init_kwargs={"num_cpus": 6}, emit=lines.append)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"out_tok_per_s", "setup_s"}
    check = json.loads(lines[0])["check"]
    assert check["finite"] and check["positions"] == 46
    assert check["max_abs_diff"] <= 1e-4 and check["argmax_equal"] == 46
