"""KV-cache decoding vs the full-forward oracle (models/decode.py).

The contract under test: prefill+decode_step with a static-shape cache
produce exactly the same next-token logits as running the whole growing
sequence through forward() — for GPT (learned positions) and LLaMA
(RoPE + GQA, cache kept at Hkv size)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, gpt, llama

GPT_CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
LLAMA_CFG = llama.LlamaConfig(vocab_size=97, d_model=32, n_heads=4,
                              n_kv_heads=2, n_layers=2, d_ff=48,
                              max_seq=64, dtype=jnp.float32,
                              remat=False, use_flash=False)


def _params(cfg):
    mod = llama if isinstance(cfg, llama.LlamaConfig) else gpt
    return mod.init_params(cfg, jax.random.PRNGKey(0))


def _fwd(cfg):
    mod = llama if isinstance(cfg, llama.LlamaConfig) else gpt
    return mod.forward


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_prefill_matches_forward(cfg):
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                cfg.vocab_size)
    cache = decode.init_cache(cfg, 2, max_seq=16)
    logits, cache = decode.prefill(params, tokens, cfg, cache)
    oracle = _fwd(cfg)(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)
    # cache holds T entries, the rest untouched zeros
    assert cache["k"].shape[2] == 16
    assert np.abs(np.asarray(cache["k"][:, :, 9:])).max() == 0.0


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_decode_step_matches_growing_forward(cfg):
    params = _params(cfg)
    B, T, new = 2, 5, 4
    seq = jax.random.randint(jax.random.PRNGKey(2), (B, T + new), 0,
                             cfg.vocab_size)
    cache = decode.init_cache(cfg, B, max_seq=T + new)
    _, cache = decode.prefill(params, seq[:, :T], cfg, cache)
    for i in range(new):
        pos = T + i
        logits, cache = decode.decode_step(
            params, seq[:, pos], jnp.int32(pos), cache, cfg)
        oracle = _fwd(cfg)(params, seq[:, :pos + 1], cfg)[:, -1]
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(oracle),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_greedy_generate_matches_no_cache_argmax(cfg):
    params = _params(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 6), 0,
                                cfg.vocab_size)
    out = decode.generate(params, prompt, cfg, max_new_tokens=5)
    assert out.shape == (2, 5)
    # oracle: grow the sequence one argmax at a time, full forward each
    seq = prompt
    fwd = _fwd(cfg)
    for _ in range(5):
        nxt = jnp.argmax(fwd(params, seq, cfg)[:, -1], -1)
        seq = jnp.concatenate([seq, nxt[:, None].astype(seq.dtype)], 1)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(seq[:, 6:]))


def test_sampling_and_eos():
    params = _params(GPT_CFG)
    prompt = jnp.zeros((1, 3), jnp.int32)
    a = decode.generate(params, prompt, GPT_CFG, max_new_tokens=6,
                        temperature=1.0, top_k=8,
                        key=jax.random.PRNGKey(7))
    b = decode.generate(params, prompt, GPT_CFG, max_new_tokens=6,
                        temperature=1.0, top_k=8,
                        key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))  # seeded
    c = decode.generate(params, prompt, GPT_CFG, max_new_tokens=6,
                        temperature=1.0, top_k=8,
                        key=jax.random.PRNGKey(9))
    assert not np.array_equal(np.asarray(a), np.asarray(c))

    # eos truncation (host-side): force a row to contain the token
    greedy = decode.generate(params, prompt, GPT_CFG, max_new_tokens=6)
    eos = int(np.asarray(greedy)[0, 2])
    rows = decode.generate(params, prompt, GPT_CFG, max_new_tokens=6,
                           eos_token=eos)
    assert len(rows[0]) == 2  # cut before the first eos


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_left_padded_batch_matches_unbatched(cfg):
    """The serving-critical property: mixed-length prompts left-padded
    into one batch generate EXACTLY what each row generates alone."""
    params = _params(cfg)
    k = jax.random.PRNGKey(5)
    p_short = jax.random.randint(k, (1, 4), 1, cfg.vocab_size)
    p_long = jax.random.randint(jax.random.PRNGKey(6), (1, 9), 1,
                                cfg.vocab_size)
    solo_short = decode.generate(params, p_short, cfg, max_new_tokens=4)
    solo_long = decode.generate(params, p_long, cfg, max_new_tokens=4)
    padded = jnp.concatenate(
        [jnp.concatenate([jnp.zeros((1, 5), p_short.dtype), p_short], 1),
         p_long], axis=0)
    out = decode.generate(params, padded, cfg, max_new_tokens=4,
                          prompt_lens=jnp.asarray([4, 9]))
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(solo_short[0]))
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.asarray(solo_long[0]))


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_chunk_step_matches_sequential_steps(cfg):
    params = _params(cfg)
    B, T, k = 2, 5, 3
    seq = jax.random.randint(jax.random.PRNGKey(8), (B, T + k), 1,
                             cfg.vocab_size)
    c1 = decode.init_cache(cfg, B, max_seq=T + k)
    _, c1 = decode.prefill(params, seq[:, :T], cfg, c1)
    c2 = jax.tree_util.tree_map(lambda x: x, c1)
    # sequential singles
    singles = []
    for i in range(k):
        l, c1 = decode.decode_step(params, seq[:, T + i],
                                   jnp.int32(T + i), c1, cfg)
        singles.append(l)
    # one chunk
    chunk_logits, c2 = decode.chunk_step(params, seq[:, T:],
                                         jnp.int32(T), c2, cfg)
    for i in range(k):
        np.testing.assert_allclose(np.asarray(chunk_logits[:, i]),
                                   np.asarray(singles[i]),
                                   rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cfg", [GPT_CFG, LLAMA_CFG],
                         ids=["gpt", "llama"])
def test_speculative_identical_to_greedy(cfg):
    """The acceptance rule guarantees bit-identical output to plain
    greedy decode on ANY input — speculation is a pure perf transform."""
    params = _params(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(11), (2, 8), 1,
                                cfg.vocab_size)
    plain = decode.generate(params, prompt, cfg, max_new_tokens=10)
    spec, stats = decode.generate(params, prompt, cfg,
                                  max_new_tokens=10,
                                  speculate_ngram=2, speculate_k=3,
                                  return_stats=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))
    assert 1 <= stats["verify_steps"] <= 10


def test_speculative_accelerates_repetitive_text():
    """When the continuation really is predictable from context, the
    verify-step count collapses to ~n/(k+1).  A zero-weight model
    emits token 0 forever (zero hidden states -> zero logits -> argmax
    0), so every prompt-lookup draft comes true."""
    params = _params(GPT_CFG)
    params = jax.tree_util.tree_map(jnp.zeros_like, params)
    # restore the norm scales (zeroing them is fine too, but keep the
    # model shaped like a real one)
    params["ln_f"] = jnp.ones_like(params["ln_f"])
    prompt = jnp.zeros((1, 8), jnp.int32)
    n, k = 16, 4
    plain = decode.generate(params, prompt, GPT_CFG, max_new_tokens=n)
    assert np.asarray(plain).max() == 0  # the cycle is real
    spec, stats = decode.generate(params, prompt, GPT_CFG,
                                  max_new_tokens=n, speculate_ngram=3,
                                  speculate_k=k, return_stats=True)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(spec))
    # every draft accepted: ceil(n / (k+1)) verify steps
    assert stats["verify_steps"] <= -(-n // (k + 1)) + 1, stats


def test_speculative_guards():
    params = _params(GPT_CFG)
    prompt = jnp.ones((1, 5), jnp.int32)
    with pytest.raises(ValueError, match="greedy-only"):
        decode.generate(params, prompt, GPT_CFG, max_new_tokens=4,
                        temperature=0.5, speculate_ngram=2,
                        speculate_k=2)
    with pytest.raises(ValueError, match="speculate_ngram"):
        decode.generate(params, prompt, GPT_CFG, max_new_tokens=4,
                        speculate_k=2)
    with pytest.raises(ValueError, match="shorter"):
        decode.generate(params, prompt, GPT_CFG, max_new_tokens=4,
                        speculate_ngram=9, speculate_k=2)


def test_generate_bounds_checked():
    params = _params(GPT_CFG)
    prompt = jnp.zeros((1, 60), jnp.int32)
    with pytest.raises(ValueError):
        decode.generate(params, prompt, GPT_CFG, max_new_tokens=10)
    moe_cfg = gpt.GPTConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq=32,
                            n_experts=2, dtype=jnp.float32, remat=False)
    with pytest.raises(NotImplementedError):
        decode.generate(gpt.init_params(moe_cfg, jax.random.PRNGKey(0)),
                        jnp.zeros((1, 4), jnp.int32), moe_cfg,
                        max_new_tokens=2)
    with pytest.raises(ValueError):
        decode.generate(params, jnp.zeros((1, 4), jnp.int32), GPT_CFG,
                        max_new_tokens=0)


# ---------------------------------------------------------------------------
# The dense paged tick on a TPU: each row's own pages through
# ops/paged_attention.py (interpreted here), held to the span loop

# heads that fill the chip's lanes, as the kernel path asks
WIDE_CFG = llama.LlamaConfig(vocab_size=97, d_model=256, n_heads=2,
                             n_kv_heads=1, n_layers=2, d_ff=64,
                             max_seq=256, dtype=jnp.float32, remat=False,
                             use_flash=False)
PAGE, TICKS = 16, 32
DEPTHS = [70, 0, 5, 33, 0, 120]       # a prompt a row; 0: the row is idle


def _as_on_a_tpu(monkeypatch, calls):
    """`decode` believes it is on a TPU; the kernel runs interpreted, in
    blocks of two pages, and counts how often it is traced."""
    from ray_tpu.ops import paged_attention as pa

    real = pa.paged_attention

    def counted(*a, **kw):
        calls.append(a[0].shape)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(decode, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "paged_attention", counted)
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 2 * PAGE)
    monkeypatch.setattr(pa, "_BLOCK_BYTES", 0)


def _prefilled(cfg, params):
    """(pool, block tables, positions, first tokens): every live row's
    prompt prefilled by single-row chunks, on pages of its own."""
    rng = np.random.default_rng(7)
    nblk = cfg.max_seq // PAGE
    B = len(DEPTHS)
    pool = decode.init_paged_cache(cfg, B * nblk + 1, PAGE)
    bt = 1 + rng.permutation(B * nblk).reshape(B, nblk).astype(np.int32)
    first = np.zeros(B, np.int32)
    for b, n in enumerate(DEPTHS):
        if not n:
            bt[b] = 0
            continue
        prompt = jnp.asarray(rng.integers(1, cfg.vocab_size, size=(1, n)),
                             jnp.int32)
        logits, pool = decode.paged_chunk_step(
            params, prompt, jnp.int32(0), pool, jnp.asarray(bt[b:b + 1]),
            cfg)
        first[b] = int(jnp.argmax(logits[0, -1]))
    return pool, bt, np.asarray(DEPTHS, np.int32), first


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_paged_tick_through_the_kernel_is_the_span_loops(dtype, monkeypatch):
    """32 ticks of a toy Llama over rows of unequal depth, two of them
    idle: the tick through `ops/paged_attention.py` gives the span
    loop's logits at every tick (teacher-forced on the kernel path's
    own greedy tokens), within float32's rounding and within
    bfloat16's, and in float32 the same greedy tokens."""
    import dataclasses
    cfg = dataclasses.replace(WIDE_CFG, dtype=dtype)
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                    _params(cfg))
    pool, bt, pos, tok = _prefilled(cfg, params)
    live, bt = pos > 0, jnp.asarray(bt)

    def ticks(pool, feed):
        """TICKS ticks from `pool` (a new program each time this is
        called), fed `feed[i]` at tick i, or else their own greedy
        tokens: (logits a tick, the tokens fed a tick)."""
        tick = jax.jit(lambda *a: decode.paged_chunk_step(*a, cfg))
        rows, fed, t = [], [], tok
        for i in range(TICKS):
            t = t if feed is None else feed[i]
            at = jnp.asarray(np.where(live, pos + i, 0))
            logits, pool = tick(params, jnp.asarray(t)[:, None], at, pool,
                                bt)
            rows.append(np.asarray(logits[:, 0]))
            fed.append(t)
            t = np.where(live, rows[-1].argmax(-1), 0).astype(np.int32)
        return np.stack(rows), fed

    calls = []
    with monkeypatch.context() as m:
        _as_on_a_tpu(m, calls)
        got, fed = ticks(pool, None)
    assert calls == [(len(pos), 2, 128)]         # traced once, 32 ticks
    want, _ = ticks(pool, fed)
    assert len(calls) == 1                       # ...and not again
    assert np.isfinite(got).all()
    assert (got[:, live] != want[:, live]).any()    # two programs
    np.testing.assert_allclose(
        got[:, live], want[:, live],
        atol=2e-4 if dtype == jnp.float32 else 6e-2)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(got[:, live].argmax(-1),
                                      want[:, live].argmax(-1))


def test_the_verify_the_chunk_and_padded_rows_walk_spans(monkeypatch):
    """On a TPU too, only the tick (one token a row at per-row
    positions, no left padding) calls the kernel: the speculative
    verify (t > 1 a row), the single-row chunk (one shared start) and a
    call with left-padded rows trace the span loop, and so does a tick
    of heads narrower than the chip's lanes."""
    cfg, calls = WIDE_CFG, []
    params = _params(cfg)
    pool, bt, pos, tok = _prefilled(cfg, params)
    _as_on_a_tpu(monkeypatch, calls)
    step = lambda *a, **kw: decode.paged_chunk_step(  # noqa: E731
        params, *a, cfg, **kw)
    bt, at = jnp.asarray(bt), jnp.asarray(pos)
    B = len(pos)
    step(jnp.ones((B, 4), jnp.int32), at, pool, bt)             # verify
    step(jnp.ones((1, 8), jnp.int32), jnp.int32(3), pool, bt[:1])  # chunk
    step(jnp.asarray(tok)[:, None], at, pool, bt,
         pad_lo=jnp.zeros((B,), jnp.int32))
    assert calls == []
    step(jnp.asarray(tok)[:, None], at, pool, bt)               # the tick
    assert calls == [(B, 2, 128)]
    narrow = _params(LLAMA_CFG)
    thin = decode.init_paged_cache(LLAMA_CFG, 9, PAGE)
    decode.paged_chunk_step(
        narrow, jnp.ones((2, 1), jnp.int32), jnp.asarray([3, 5]), thin,
        jnp.asarray([[1, 2], [3, 4]], jnp.int32), LLAMA_CFG)
    assert len(calls) == 1
