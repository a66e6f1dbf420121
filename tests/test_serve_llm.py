"""Continuous-batching LLM serving (ray_tpu.serve.llm).

The load-bearing contract is PARITY: iteration-level scheduling —
chunked prefill, slot insertion, per-row-position decode, eviction,
slot reuse — is a pure scheduling transform.  Every request served
through the engine under staggered arrivals must produce EXACTLY the
tokens decode.generate() produces for that prompt alone.  On top of
that: slot recycling, backpressure, token streaming through the serve
transport, and SSE at the HTTP wire.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, gpt, llama
from ray_tpu.serve.llm import (EngineOverloadedError, GenerationEngine,
                               llm_deployment)

GPT_CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
LLAMA_CFG = llama.LlamaConfig(vocab_size=97, d_model=32, n_heads=4,
                              n_kv_heads=2, n_layers=2, d_ff=48,
                              max_seq=64, dtype=jnp.float32,
                              remat=False, use_flash=False)

# One shared shape vocabulary across tests so jit compilations are
# reused: 2 slots, S=40 cache, chunk-4 prefill.
ENGINE_KW = dict(num_slots=2, max_seq=40, prefill_chunk=4)


def _params(cfg):
    mod = llama if isinstance(cfg, llama.LlamaConfig) else gpt
    return mod.init_params(cfg, jax.random.PRNGKey(0))


GPT_PARAMS = _params(GPT_CFG)


def _prompt(seed, n, cfg=GPT_CFG):
    return [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, cfg.vocab_size))]


def _oracle(params, cfg, prompt, max_new, eos_token=None):
    out = decode.generate(params, jnp.asarray([prompt]), cfg,
                          max_new_tokens=max_new, eos_token=eos_token)
    return np.asarray(out[0])


# ---------------------------------------------------------------------------
# Decode primitives the engine is built on (per-row positions, slot
# reset/insert, vectorized EOS truncation).  They live here rather than
# in test_decode.py because they exist FOR this subsystem — and so the
# budget-limited fast tier spends its window on the pre-existing decode
# oracles first.


@pytest.mark.parametrize(
    "cfg", [GPT_CFG,
            pytest.param(LLAMA_CFG, marks=pytest.mark.slow)],
    ids=["gpt", "llama"])
def test_decode_step_per_row_positions_match_scalar(cfg):
    """The continuous-batching primitive: decode_step with a [B]
    position vector must equal per-row scalar-pos decode_steps — rows
    at DIFFERENT depths in one fused call."""
    params = _params(cfg)
    S = 24
    lens = [5, 9]
    seqs = [jax.random.randint(jax.random.PRNGKey(20 + i), (1, n), 1,
                               cfg.vocab_size)
            for i, n in enumerate(lens)]
    # solo path: per-request caches, scalar positions
    solo_logits = []
    solo_caches = []
    for i, (seq, n) in enumerate(zip(seqs, lens)):
        c = decode.init_cache(cfg, 1, max_seq=S)
        _, c = decode.prefill(params, seq, cfg, c)
        tok = jnp.asarray([7 + i], jnp.int32)
        lg, c = decode.decode_step(params, tok, jnp.int32(n), c, cfg)
        solo_logits.append(lg)
        solo_caches.append(c)
    # pooled path: insert each prefilled row into a 2-slot cache, one
    # decode_step with per-row positions
    pool = decode.init_cache(cfg, 2, max_seq=S)
    for i, (seq, n) in enumerate(zip(seqs, lens)):
        c = decode.init_cache(cfg, 1, max_seq=S)
        _, c = decode.prefill(params, seq, cfg, c)
        pool = decode.insert_cache_slot(pool, c, jnp.int32(i))
    toks = jnp.asarray([7, 8], jnp.int32)
    pos = jnp.asarray(lens, jnp.int32)
    logits, pool = decode.decode_step(params, toks, pos, pool, cfg)
    # Tolerance is last-ulp only: XLA may vectorize a batch-2 einsum
    # differently from batch-1, but the math must be the same.
    for i in range(2):
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   np.asarray(solo_logits[i][0]),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(pool["k"][:, i]),
            np.asarray(solo_caches[i]["k"][:, 0]),
            rtol=1e-6, atol=1e-7)


def test_cache_slot_reset_and_insert_touch_only_their_row():
    cfg = GPT_CFG
    params = GPT_PARAMS
    S = 16
    pool = decode.init_cache(cfg, 3, max_seq=S)
    seq = jax.random.randint(jax.random.PRNGKey(31), (3, 6), 1,
                             cfg.vocab_size)
    _, pool = decode.prefill(params, seq, cfg, pool)
    before = np.asarray(pool["k"])
    assert np.abs(before[:, 1, :6]).max() > 0
    pool = decode.reset_cache_slot(pool, jnp.int32(1))
    after = np.asarray(pool["k"])
    assert np.abs(after[:, 1]).max() == 0.0          # target zeroed
    np.testing.assert_array_equal(after[:, 0], before[:, 0])
    np.testing.assert_array_equal(after[:, 2], before[:, 2])

    row = decode.init_cache(cfg, 1, max_seq=S)
    _, row = decode.prefill(params, seq[:1], cfg, row)
    pool = decode.insert_cache_slot(pool, row, jnp.int32(1))
    filled = np.asarray(pool["k"])
    np.testing.assert_array_equal(filled[:, 1],
                                  np.asarray(row["k"])[:, 0])
    np.testing.assert_array_equal(filled[:, 0], before[:, 0])
    np.testing.assert_array_equal(filled[:, 2], before[:, 2])


def test_eos_truncation_ragged_rows():
    """generate(eos_token=...) returns a ragged LIST: rows cut before
    their first EOS, rows without one at full width (the vectorized
    host-side truncation must preserve per-row behavior)."""
    prompt = jnp.concatenate(
        [jnp.zeros((1, 4), jnp.int32),
         jnp.full((1, 4), 3, jnp.int32)], axis=0)
    full = np.asarray(decode.generate(GPT_PARAMS, prompt, GPT_CFG,
                                      max_new_tokens=6))
    # pick an eos appearing in row 0; row 1 checked for whichever case
    # (present or absent) it lands in
    eos = int(full[0, 2])
    rows = decode.generate(GPT_PARAMS, prompt, GPT_CFG,
                           max_new_tokens=6, eos_token=eos)
    assert isinstance(rows, list) and len(rows) == 2
    first_hit = np.where(full[0] == eos)[0][0]
    np.testing.assert_array_equal(rows[0], full[0][:first_hit])
    hits1 = np.where(full[1] == eos)[0]
    want1 = full[1][:hits1[0]] if hits1.size else full[1]
    np.testing.assert_array_equal(rows[1], want1)


# ---------------------------------------------------------------------------
# Engine core (no cluster)


def test_engine_parity_under_staggered_arrivals():
    """THE acceptance property: tokens streamed for each request under
    staggered arrivals are bit-identical to the whole-batch generate()
    output for that prompt alone — more requests than slots, admissions
    landing mid-generation of earlier requests."""
    prompts = [_prompt(i + 10, n) for i, n in enumerate((5, 9, 13, 3))]
    oracles = [_oracle(GPT_PARAMS, GPT_CFG, p, 10) for p in prompts]

    async def run():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            s0 = eng.submit(prompts[0], max_new_tokens=10)
            # Stagger: only submit the rest after request 0 is visibly
            # mid-generation (2 tokens out, 8 to go).
            first_two = [await s0.__anext__(), await s0.__anext__()]
            rest = [eng.submit(p, max_new_tokens=10)
                    for p in prompts[1:]]
            outs = [first_two + [t async for t in s0]]
            for s in rest:
                outs.append(await s.collect())
            stats = eng.stats()
        return outs, stats

    outs, stats = asyncio.run(run())
    for got, want in zip(outs, oracles):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert stats.requests_completed == 4
    assert stats.tokens_generated == 40


def test_engine_slot_eviction_and_reuse():
    """5 requests with different lengths through 2 slots: eviction must
    recycle slots (completions > num_slots) and the pool must drain
    clean; a zeroed slot must not leak state into its next occupant
    (parity per request is re-asserted)."""
    prompts = [_prompt(i + 30, 4 + i) for i in range(5)]
    lens = [4, 8, 6, 10, 3]
    oracles = [_oracle(GPT_PARAMS, GPT_CFG, p, n)
               for p, n in zip(prompts, lens)]

    async def run():
        peak = 0
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            streams = [eng.submit(p, max_new_tokens=n)
                       for p, n in zip(prompts, lens)]
            outs = []
            for s in streams:
                outs.append(await s.collect())
                peak = max(peak, eng.stats().active_slots)
            end = eng.stats()
        return outs, peak, end

    outs, peak, end = asyncio.run(run())
    for got, want in zip(outs, oracles):
        np.testing.assert_array_equal(np.asarray(got), want)
    assert peak <= 2
    assert end.active_slots == 0 and end.queue_depth == 0
    assert end.requests_completed == 5  # 5 through 2 slots => reuse


def test_engine_backpressure_rejects_when_queue_full():
    async def run():
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, max_queue_len=2,
                               **ENGINE_KW)
        with eng:
            admitted = []
            # A flood outruns the 2-deep queue long before the worker
            # can drain it into slots.
            with pytest.raises(EngineOverloadedError):
                for i in range(12):
                    admitted.append(eng.submit(_prompt(50 + i, 6),
                                               max_new_tokens=20))
            # everything actually admitted still completes
            for s in admitted:
                assert len(await s.collect()) == 20
            st = eng.stats()
        assert st.requests_rejected >= 1
        assert st.requests_completed == len(admitted)

    asyncio.run(run())


def test_engine_streams_before_completion():
    """Streaming means streaming: the first token must be delivered
    while the engine is still generating the rest (TTFT decoupled from
    total latency)."""
    async def run():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            s = eng.submit(_prompt(70, 6), max_new_tokens=30)
            first = await s.__anext__()
            st = eng.stats()
            # the request is demonstrably still in flight
            assert st.active_slots == 1
            rest = [t async for t in s]
        assert len([first] + rest) == 30

    asyncio.run(run())


def test_engine_eos_truncation_matches_generate():
    """eos_token semantics mirror generate(): truncate BEFORE the first
    EOS, ragged per request."""
    prompt = _prompt(80, 6)
    greedy = _oracle(GPT_PARAMS, GPT_CFG, prompt, 10)
    eos = int(greedy[4])  # force a cut 4 tokens in
    want = _oracle(GPT_PARAMS, GPT_CFG, prompt, 10, eos_token=eos)

    async def run():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            return await eng.generate(prompt, max_new_tokens=10,
                                      eos_token=eos)

    got = asyncio.run(run())
    np.testing.assert_array_equal(np.asarray(got), want)
    assert len(got) == 4


def test_engine_sampling_seeded_and_varied():
    async def run():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            a = await eng.generate(_prompt(90, 5), max_new_tokens=8,
                                   temperature=0.8, top_k=10, seed=7)
            b = await eng.generate(_prompt(90, 5), max_new_tokens=8,
                                   temperature=0.8, top_k=10, seed=7)
            c = await eng.generate(_prompt(90, 5), max_new_tokens=8,
                                   temperature=0.8, top_k=10, seed=8)
            # top_k beyond the vocab means "unrestricted", and must not
            # take down the engine (it samples on the worker thread,
            # where an error would fail every co-resident request)
            d = await eng.generate(_prompt(90, 5), max_new_tokens=4,
                                   temperature=0.8, top_k=10**6, seed=7)
            with pytest.raises(ValueError, match="top_k"):
                eng.submit(_prompt(90, 5), max_new_tokens=4,
                           temperature=0.5, top_k=-1)
            with pytest.raises(ValueError, match="temperature"):
                eng.submit(_prompt(90, 5), max_new_tokens=4,
                           temperature=float("inf"))
        return a, b, c, d

    a, b, c, d = asyncio.run(run())
    assert a == b and len(a) == 8  # same seed => same tokens
    assert a != c                  # different seed => (overwhelmingly)
    assert len(d) == 4


def test_engine_cancel_frees_slot():
    async def run():
        with GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW) as eng:
            s = eng.submit(_prompt(95, 6), max_new_tokens=30)
            got = [await s.__anext__() for _ in range(3)]
            s.cancel()
            got += [t async for t in s]  # drains whatever was buffered
            deadline = time.monotonic() + 10
            while eng.stats().active_slots and time.monotonic() < deadline:
                await asyncio.sleep(0.01)
            st = eng.stats()
        assert st.active_slots == 0
        assert st.requests_cancelled == 1
        assert len(got) < 30

    asyncio.run(run())


def test_engine_validation_errors():
    eng = GenerationEngine(GPT_PARAMS, GPT_CFG, **ENGINE_KW)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit([])
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(_prompt(1, 35), max_new_tokens=10)  # 35+10 > 40
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompt(1, 4), max_new_tokens=0)
    with pytest.raises(ValueError):
        GenerationEngine(GPT_PARAMS, GPT_CFG, num_slots=0)


def test_engine_metrics_exported_via_prometheus():
    async def run():
        eng = GenerationEngine(GPT_PARAMS, GPT_CFG, name="promtest",
                               **ENGINE_KW)
        with eng:
            await eng.generate(_prompt(99, 5), max_new_tokens=6)

    asyncio.run(run())
    from ray_tpu.util.metrics import prometheus_text, registry_snapshot
    text = prometheus_text(registry_snapshot())
    for needle in ("serve_llm_ttft_seconds", "serve_llm_inter_token_seconds",
                   "serve_llm_tokens_generated_total",
                   "serve_llm_requests_total", "serve_llm_queue_depth",
                   "serve_llm_slot_occupancy"):
        assert needle in text, needle
    assert 'engine="promtest"' in text


@pytest.mark.slow
def test_engine_parity_llama_gqa():
    """Same parity property on the LLaMA path (RoPE positions + GQA
    cache folding are the parts most sensitive to per-row positions)."""
    params = _params(LLAMA_CFG)
    prompts = [_prompt(i + 40, n, LLAMA_CFG)
               for i, n in enumerate((4, 7, 11))]
    oracles = [_oracle(params, LLAMA_CFG, p, 8) for p in prompts]

    async def run():
        with GenerationEngine(params, LLAMA_CFG, **ENGINE_KW) as eng:
            s0 = eng.submit(prompts[0], max_new_tokens=8)
            first = await s0.__anext__()
            rest = [eng.submit(p, max_new_tokens=8) for p in prompts[1:]]
            outs = [[first] + [t async for t in s0]]
            for s in rest:
                outs.append(await s.collect())
        return outs

    outs = asyncio.run(run())
    for got, want in zip(outs, oracles):
        np.testing.assert_array_equal(np.asarray(got), want)


def test_llm_server_http_503_when_overloaded():
    """__call__ maps EngineOverloadedError to a structured 503 the proxy
    turns into a real HTTP response (backpressure at the wire)."""
    from ray_tpu.serve._private.replica import Request
    from ray_tpu.serve.llm.api import LLMServer

    srv = LLMServer(lambda: (GPT_PARAMS, GPT_CFG),
                    engine_config=dict(max_queue_len=1, **ENGINE_KW))
    try:
        # Deterministic saturation: park the worker so queued requests
        # cannot drain, then fill the 1-deep queue.  (Timing the real
        # worker races generation speed against the HTTP call.)
        srv.engine.stop()
        srv.engine.start = lambda: srv.engine
        srv.engine.submit(_prompt(0, 6), max_new_tokens=10)

        async def call():
            import json
            req = Request(method="POST", path="/", body=json.dumps(
                {"tokens": _prompt(7, 5),
                 "max_new_tokens": 10}).encode())
            return await srv(req)

        out = asyncio.run(call())
        assert out["__http__"] is True and out["status"] == 503
        assert ("Retry-After", "1.000") in out["headers"]
    finally:
        srv.engine.stop()


# ---------------------------------------------------------------------------
# Serve integration (real cluster)


@pytest.fixture
def serve_instance():
    import ray_tpu
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _loader():
    cfg = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _loader_held_for_its_reader():
    """_loader for a replica whose generation waits for its reader: once
    a first token has been pushed, every decode tick is held until a
    stream is cancelled (the replica-side generator's finally), so a
    close after the first token always lands mid-generation, however
    slowly it travels.  The engine does not wait for a reader by itself:
    a toy generation is over in milliseconds."""
    import threading

    from ray_tpu.serve.llm import engine as engine_mod
    pushed, cancelled = threading.Event(), threading.Event()
    tick = engine_mod._paged_tick
    push, cancel = engine_mod.TokenStream._push, engine_mod.TokenStream.cancel

    def held_tick(*a, **k):
        if pushed.is_set():
            assert cancelled.wait(60), "the close never reached the stream"
        return tick(*a, **k)

    def noted_push(self, *a, **k):
        push(self, *a, **k)
        pushed.set()

    def noted_cancel(self):
        cancel(self)
        cancelled.set()
    engine_mod._paged_tick = held_tick
    engine_mod.TokenStream._push = noted_push
    engine_mod.TokenStream.cancel = noted_cancel
    return _loader()


def test_generic_stream_transport(serve_instance):
    """handle.stream() on a plain deployment: items arrive one by one
    (first item long before the generator finishes) and a mid-stream
    exception reaches the consumer."""
    from ray_tpu import serve

    @serve.deployment(name="streamer")
    class Streamer:
        async def counted(self, n):
            for i in range(n):
                await asyncio.sleep(0.15)
                yield i

        def sync_counted(self, n):
            for i in range(n):  # plain generator: driven off-loop
                yield i * 10

        async def broken(self):
            yield 1
            raise ValueError("boom mid-stream")

    handle = Streamer.deploy()
    stream = handle.counted.stream(5)
    t0 = time.monotonic()
    items, stamps = [], []
    for item in stream:
        items.append(item)
        stamps.append(time.monotonic() - t0)
    assert items == list(range(5))
    # first item must arrive while later items are still being produced
    assert stamps[0] < stamps[-1] - 0.25, stamps

    assert list(handle.sync_counted.stream(4)) == [0, 10, 20, 30]

    with pytest.raises(ValueError, match="boom mid-stream"):
        list(handle.broken.stream())

    # A stream closed before its first iteration must not leak the
    # router's in-flight slot (acquisition is lazy, inside the
    # generator body).  NB: each attribute access mints a new
    # sub-handle with its own router, so keep ONE and inspect it.
    sub = handle.counted
    never_started = sub.stream(3)
    never_started.close()
    rs = sub._router.replica_set
    deadline = time.monotonic() + 10
    while rs.stats()["in_flight"] and time.monotonic() < deadline:
        time.sleep(0.05)
    assert rs.stats()["in_flight"] == 0, rs.stats()


def test_a_stream_closed_while_the_replica_starts_it_is_cancelled(
        serve_instance):
    """Callers beyond `max_concurrent_queries` wait at the router; when
    every caller leaves at once (a load generator closing its window),
    a waiter can take the slot a closing stream just freed and be
    cancelled while its start RPC is in flight, before the router knows
    the stream's id.  The replica must still hear of the close: no
    generator keeps running for nobody."""
    from ray_tpu import serve

    @serve.deployment(name="endless", max_concurrent_queries=2)
    class Endless:
        def __init__(self):
            self.live = 0

        async def ticks(self):
            self.live += 1
            try:
                while True:
                    await asyncio.sleep(0.02)
                    yield 0
            finally:
                self.live -= 1

        def live_now(self):
            return self.live

    handle = Endless.deploy()
    sub = handle.options("ticks")

    async def one():
        stream = sub.stream()
        try:
            async for _ in stream:
                pass
        except asyncio.CancelledError:
            await stream.aclose()
            raise

    async def drive():
        tasks = [asyncio.ensure_future(one()) for _ in range(64)]
        await asyncio.sleep(1.0)    # two stream, the rest wait for a slot
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    asyncio.run(drive())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        live = handle.live_now.remote().result(timeout=30)
        if live == 0:
            break
        time.sleep(0.1)
    assert live == 0, live
    assert sub._router.replica_set.stats()["in_flight"] == 0


def test_replica_stream_ttl_sweep():
    """A stream whose consumer vanished (no polls, no cancel) is torn
    down at the next streaming admission instead of buffering forever."""
    import cloudpickle

    from ray_tpu.serve._private.replica import RTServeReplica

    class Gen:
        async def tokens(self):
            for i in range(3):
                yield i
                await asyncio.sleep(1000)  # a stream that never ends

    async def run():
        rep = RTServeReplica("d", "tag:1", cloudpickle.dumps(Gen), (),
                             {}, None, "1")
        sid = (await rep.handle_request_streaming("tokens", (), {})
               )["stream_id"]
        # polled streams are NOT swept
        rep._streams[sid]["last_poll"] -= rep.STREAM_IDLE_TTL_S / 2
        sid2 = (await rep.handle_request_streaming("tokens", (), {})
                )["stream_id"]
        assert sid in rep._streams
        # ...but an idle-past-TTL one is
        rep._streams[sid]["last_poll"] -= rep.STREAM_IDLE_TTL_S
        sid3 = (await rep.handle_request_streaming("tokens", (), {})
                )["stream_id"]
        assert sid not in rep._streams
        assert sid2 in rep._streams and sid3 in rep._streams
        await rep.stream_cancel(sid2)
        await rep.stream_cancel(sid3)

    asyncio.run(run())


def test_sync_generator_cancel_runs_cleanup(tmp_path):
    """Cancelling a stream backed by a PLAIN sync generator must still
    run the generator's finally blocks — and must not race the pool
    thread mid-next() into 'generator already executing'."""
    import cloudpickle

    from ray_tpu.serve._private.replica import RTServeReplica

    flag = str(tmp_path / "cleaned")

    class G:
        def __init__(self, path):
            self.path = path

        def tokens(self):
            try:
                while True:
                    time.sleep(0.02)
                    yield 1
            finally:
                with open(self.path, "w") as f:
                    f.write("cleaned")

    async def run():
        import os
        rep = RTServeReplica("d", "tag:2", cloudpickle.dumps(G),
                             (flag,), {}, None, "1")
        sid = (await rep.handle_request_streaming("tokens", (), {})
               )["stream_id"]
        out = await rep.stream_next(sid, 0, timeout_s=10)
        assert out["items"], out  # stream is live mid-next() cycles
        await rep.stream_cancel(sid)
        deadline = time.monotonic() + 15
        while not os.path.exists(flag) and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        assert os.path.exists(flag), \
            "sync generator finally never ran after cancel"

    asyncio.run(run())


def test_llm_deployment_generate_and_stream(serve_instance):
    """End-to-end through serve: unary parity AND streamed parity with
    incremental delivery (first token before the request finishes)."""
    params, cfg = _loader()
    prompt = _prompt(3, 6)
    want = _oracle(params, cfg, prompt, 12)

    handle = llm_deployment(
        _loader, engine_config=dict(ENGINE_KW),
        default_generation={"max_new_tokens": 12}).deploy()
    got = handle.generate.remote(prompt).result(timeout=120)
    np.testing.assert_array_equal(np.asarray(got), want)

    stream = handle.options("stream").stream(prompt)
    toks = list(stream)
    np.testing.assert_array_equal(np.asarray(toks), want)

    st = handle.stats.remote().result(timeout=60)
    assert st["requests_completed"] >= 2

    # Early close frees the engine slot (the replica-side generator's
    # finally cancels its engine request), on a replica whose ticks wait
    # for that cancel once the reader has its first token: the request
    # cannot finish before the close has landed.
    held = llm_deployment(
        _loader_held_for_its_reader, name="llm-held",
        engine_config=dict(ENGINE_KW)).deploy()
    s2 = held.options("stream").stream(prompt, max_new_tokens=34)
    assert next(s2) == int(want[0])
    s2.close()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = held.stats.remote().result(timeout=60)
        if st["active_slots"] == 0 and st["requests_cancelled"] >= 1:
            break
        time.sleep(0.1)
    assert st["requests_cancelled"] >= 1, st
    assert st["active_slots"] == 0, st

    # close() after a TIMED-OUT result() must also tear down: the
    # pending step keeps the transport generator suspended inside
    # __anext__, and teardown has to unwind it (not silently fail on
    # "aclose(): async generator is already running" and leave the
    # router's in-flight slot held forever).  The deterministic
    # observable is the in-flight release — whether the engine request
    # was cancelled mid-flight or had already finished is a race.
    sub3 = handle.options("stream")
    s3 = sub3.stream(prompt, max_new_tokens=34)
    try:
        s3.result(timeout=0.0001)
    except TimeoutError:
        pass
    s3.close()
    rs3 = sub3._router.replica_set
    deadline = time.monotonic() + 30
    while rs3.stats()["in_flight"] and time.monotonic() < deadline:
        time.sleep(0.05)
    assert rs3.stats()["in_flight"] == 0, rs3.stats()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        st = handle.stats.remote().result(timeout=60)
        if st["active_slots"] == 0:
            break
        time.sleep(0.1)
    assert st["active_slots"] == 0, st


def _glm5_loader():
    """GLM-5 at toy widths: every layer chooses 12 keys a query with a
    2-head indexer, 4 of 16 experts held."""
    from ray_tpu.models import glm_moe_dsa
    cfg = glm_moe_dsa.GlmMoeDsaConfig(
        max_seq=128, n_layers=3, vocab_size=97, d_model=32, n_heads=2,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
        index_head_dim=16, index_topk=12, d_ff=48, first_k_dense=1,
        moe_d_ff=16, n_routed_experts=16, top_k=4, experts_held=4,
        dtype=jnp.float32)
    return glm_moe_dsa.init_params(cfg, jax.random.PRNGKey(0)), cfg


def test_llm_deployment_serves_a_body_that_chooses_its_keys(serve_instance):
    """models/glm_moe_dsa.py through serve, as every body is: a pool of
    two arrays of unequal width under one block table.  Two prompts,
    one longer than `index_topk`, generate and stream the tokens an
    engine of its own yields for each alone, and the replica's stats
    carry the selection's counters."""
    params, cfg = _glm5_loader()
    kw = dict(num_slots=2, page_size=8, prefill_chunk=16, kv_pages=40,
              enable_prefix_cache=True)
    prompts = [_prompt(5, 37, cfg), _prompt(6, 7, cfg)]
    alone = GenerationEngine(params, cfg, **kw)
    try:
        want = [alone.submit(p, max_new_tokens=10).result(timeout=300)
                for p in prompts]
    finally:
        alone.stop()
    handle = llm_deployment(
        _glm5_loader, name="llm-glm5", engine_config=kw,
        default_generation={"max_new_tokens": 10}).deploy()
    futures = [handle.generate.remote(p) for p in prompts]
    for fut, w in zip(futures, want):
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=300)), w)
    assert list(handle.options("stream").stream(prompts[0])) == want[0]
    st = handle.stats.remote().result(timeout=60)
    assert st["requests_completed"] == 3
    ticks = [p for pr in (prompts[0], prompts[1], prompts[0])
             for p in range(len(pr), len(pr) + 9)]
    assert st["dsa_rows_live"] == len(ticks) * cfg.n_layers
    assert st["dsa_rows_selecting"] == cfg.n_layers * sum(
        p + 1 > cfg.index_topk for p in ticks)
    assert st["dsa_tick_keys_attended"] == st["dsa_tick_keys_chosen"] > 0
    assert st["dsa_keys_scored"] > st["dsa_keys_chosen"] \
        > st["dsa_tick_keys_chosen"]
    assert st["attn_keys_attended"] < st["attn_keys_resident"]
    assert st["row_state_bytes"] == 0
    assert st["prefix_hit_tokens"] >= 32     # the repeated prompt's pages
    with pytest.raises(NotImplementedError, match="GlmMoeDsaConfig"):
        GenerationEngine(params, cfg, kv_tiering=True, **kw)


@pytest.mark.slow
def test_llm_http_sse_wire_level(serve_instance):
    """The acceptance wire test: SSE through the real HTTP proxy —
    incremental `data:` events, token parity, [DONE] terminator, and a
    plain JSON POST on the same route; first event must be received
    BEFORE the stream completes (generation paced slower than network)."""
    import json

    import requests

    from ray_tpu import serve

    params, cfg = _loader()
    prompt = _prompt(3, 6)
    want = _oracle(params, cfg, prompt, 10)

    @serve.deployment(name="slowstream")
    class SlowStream:
        async def __call__(self, request):
            async def gen():
                for i in range(5):
                    await asyncio.sleep(0.15)
                    yield {"i": i}
            return gen()

    llm_deployment(_loader, engine_config=dict(ENGINE_KW),
                   default_generation={"max_new_tokens": 10}).deploy()
    serve.run(serve.get_deployment("llm"), _start_proxy=True)
    SlowStream.deploy()
    addr = serve.get_proxy_address()
    base = f"http://{addr['host']}:{addr['port']}"

    # Plain JSON (no Accept header): one-shot response, exact tokens.
    r = requests.post(f"{base}/llm", json={"tokens": prompt}, timeout=60)
    assert r.status_code == 200
    assert r.json()["tokens"] == [int(t) for t in want]

    # SSE: headers + framing + parity.
    r = requests.post(f"{base}/llm", json={"tokens": prompt},
                      headers={"Accept": "text/event-stream"},
                      stream=True, timeout=60)
    assert r.status_code == 200
    assert r.headers["Content-Type"].startswith("text/event-stream")
    lines = [ln for ln in r.iter_lines() if ln.startswith(b"data: ")]
    assert lines[-1] == b"data: [DONE]"
    toks = [json.loads(ln[6:])["token"] for ln in lines[:-1]]
    assert toks == [int(t) for t in want]

    # Incremental delivery, measured: a paced generator's first event
    # arrives well before its last (buffered-together would collapse
    # the gap to ~0).
    r = requests.get(f"{base}/slowstream", params={"stream": "1"},
                     stream=True, timeout=60)
    assert r.status_code == 200
    stamps = []
    for ln in r.iter_lines():
        if ln.startswith(b"data: "):
            stamps.append(time.monotonic())
    assert len(stamps) == 6  # 5 events + [DONE]
    assert stamps[0] < stamps[-1] - 0.3, "SSE events were not incremental"

    # Bad request surfaces as 400, overload as 503 (wire-level check of
    # the structured-error path).
    r = requests.post(f"{base}/llm", json={"nope": 1}, timeout=60)
    assert r.status_code == 400

    # ... and streaming INTENT must not eat the status code: the same
    # bad request with Accept: text/event-stream degrades to a plain
    # 400, not a 200 SSE stream with an error event buried inside.
    r = requests.post(f"{base}/llm", json={"nope": 1},
                      headers={"Accept": "text/event-stream"},
                      timeout=60)
    assert r.status_code == 400
    assert not r.headers["Content-Type"].startswith("text/event-stream")

    # A NON-streaming deployment keeps working for event-stream clients
    # (unary fallback — pre-existing deployments must not break).
    @serve.deployment(name="plain")
    def plain(req):
        return {"plain": True}

    plain.deploy()
    r = requests.get(f"{base}/plain",
                     headers={"Accept": "text/event-stream"}, timeout=60)
    assert r.status_code == 200
    assert r.json() == {"plain": True}

    # The root routes listing ignores streaming intent.
    r = requests.get(f"{base}/",
                     headers={"Accept": "text/event-stream"}, timeout=60)
    assert r.status_code == 200 and "routes" in r.json()
