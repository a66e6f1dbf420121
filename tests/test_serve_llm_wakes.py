"""A turn's tokens leave the engine's worker thread once.

`TokenStream._push` / `_finish` hand the wake-ups they take to the
engine's batch; when the rows of an emit phase have all been advanced
the engine makes ONE `call_soon_threadsafe` into each event loop with a
waiting reader and sets the sync readers' events
(`engine._fire_wakeups`, from `GenerationEngine._flush_emits`), counted
in `stats().stream_wakes`.  Same tokens, same order, a finish after its
row's last token, errors as before; and the flush never waits for a
dispatch or a device result.

Most cases here ARE the worker thread: the engine's thread is never
started and the test calls the loop's body a turn at a time (`_Driven`),
so "one tick" is one call and a counter read after it cannot race.
"""

import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode, gpt
from ray_tpu.serve.llm import GenerationEngine
from ray_tpu.serve.llm import engine as engine_mod
from ray_tpu.util import metrics

CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4, n_layers=2,
                    d_ff=64, max_seq=64, dtype=jnp.float32, remat=False,
                    use_flash=False)
PARAMS = gpt.init_params(CFG, jax.random.PRNGKey(0))
# One shape vocabulary for the file, so the jitted steps compile once.
ENGINE_KW = dict(num_slots=6, max_seq=40, prefill_chunk=8)
WAIT_S = 20.0


def _prompt(seed, n):
    return [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, CFG.vocab_size))]


def _oracle(prompt, max_new):
    out = decode.generate(PARAMS, jnp.asarray([prompt]), CFG,
                          max_new_tokens=max_new)
    return [int(t) for t in np.asarray(out[0])]


def _until(cond, what):
    deadline = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


class _Driven:
    """An engine whose worker thread is the test: `turn()` is one pass
    of `GenerationEngine._loop`'s body."""

    def __init__(self, **kw):
        self.eng = eng = GenerationEngine(PARAMS, CFG,
                                          **{**ENGINE_KW, **kw})
        eng.start = lambda: eng          # submit() must not spawn it
        eng._phase_name, eng._phase_t = "idle", time.monotonic()

    def turn(self):
        eng = self.eng
        try:
            eng._admit_one_chunk()
            eng._decode_tick()
        except Exception as e:           # as _loop does
            eng._fail_all(e)
        eng._turns += 1

    def admit_all(self, streams):
        """Turns until every stream's request decodes in a row."""
        eng = self.eng
        _until(lambda: (self.turn() or True) and eng._prefill is None
               and eng._scheduler.depth == 0, "admission")
        assert sum(r is not None for r in eng._slots) == len(streams)


class _LoopThread:
    """An event loop on a thread of its own, with readers on it."""

    def __init__(self):
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(target=self.loop.run_forever,
                                       daemon=True)
        self.thread.start()
        self.calls = 0
        real = self.loop.call_soon_threadsafe

        def counted(*a, **k):
            self.calls += 1
            return real(*a, **k)
        self.loop.call_soon_threadsafe = counted

    def read(self, stream):
        """-> (tokens list it appends to, future of its end)."""
        got = []

        async def consume():
            async for t in stream:
                got.append(t)
        return got, asyncio.run_coroutine_threadsafe(consume(), self.loop)

    def close(self):
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive()
        self.loop.close()


def _sync_read(stream):
    got, end = [], {}

    def consume():
        try:
            for t in stream:
                got.append(t)
        except BaseException as e:       # handed to the test
            end["error"] = e
    th = threading.Thread(target=consume, daemon=True)
    th.start()
    return got, end, th


def _all_waiting(streams):
    """Every reader has drained its stream and registered a wake-up."""
    return all(len(s._wakeups) == 1 and not s._buf for s in streams)


@pytest.fixture
def loops():
    made = []

    def make():
        made.append(_LoopThread())
        return made[-1]
    yield make
    for lt in made:
        if not lt.loop.is_closed():
            lt.close()


def test_one_call_a_loop_a_tick(loops):
    """(a) 3 readers on one loop + 2 on a second + a sync reader: a
    tick of six rows makes 2 calls into loops + 1 event set, whatever
    the rows; every reader gets its tokens, in order, over many
    ticks."""
    a, b = loops(), loops()
    d = _Driven()
    prompts = [_prompt(100 + i, 6) for i in range(6)]
    streams = [d.eng.submit(p, max_new_tokens=24) for p in prompts]
    reads = [a.read(s) for s in streams[:3]] \
        + [b.read(s) for s in streams[3:5]]
    sync_got, sync_end, sync_th = _sync_read(streams[5])
    d.admit_all(streams)
    for _ in range(4):
        _until(lambda: _all_waiting(streams), "six waiting readers")
        wakes0 = d.eng.stats().stream_wakes
        calls0 = (a.calls, b.calls)
        tokens0 = d.eng.stats().tokens_generated
        d.turn()
        st = d.eng.stats()
        assert st.tokens_generated - tokens0 == 6
        assert st.stream_wakes - wakes0 == 2 + 1
        assert (a.calls - calls0[0], b.calls - calls0[1]) == (1, 1)
    while any(r is not None for r in d.eng._slots):
        d.turn()
    for _got, fut in reads:
        fut.result(WAIT_S)
    sync_th.join(WAIT_S)
    assert not sync_th.is_alive() and not sync_end
    got = [g for g, _ in reads] + [sync_got]
    for g, p in zip(got, prompts):
        assert g == _oracle(p, 24)
    st = d.eng.stats()
    assert st.tokens_generated == 6 * 24
    assert st.tokens_generated / st.stream_wakes > 1.5


def test_last_token_and_finish_share_one_wake(loops):
    """(b) a row that completes in a tick: its reader is woken once and
    finds the token, then the end."""
    a = loops()
    d = _Driven()
    prompt = _prompt(7, 6)
    s = d.eng.submit(prompt, max_new_tokens=3)
    got, fut = a.read(s)
    d.admit_all([s])                     # its turn ticks too: 2 tokens
    _until(lambda: _all_waiting([s]) and len(got) == 2, "two tokens")
    wakes0, calls0 = d.eng.stats().stream_wakes, a.calls
    d.turn()                             # token 3 + the finish
    assert d.eng.stats().stream_wakes - wakes0 == 1
    assert a.calls - calls0 == 1
    fut.result(WAIT_S)                   # StopAsyncIteration ended it
    assert got == _oracle(prompt, 3)
    assert d.eng.stats().requests_completed == 1


def test_a_closed_loop_loses_only_its_own_wakes(loops):
    """(c) a reader that abandoned its wait and closed its loop keeps
    no other reader's token from arriving, and nothing is raised on the
    worker thread."""
    a, gone = loops(), loops()
    d = _Driven()
    prompts = [_prompt(40 + i, 6) for i in range(3)]
    streams = [d.eng.submit(p, max_new_tokens=6) for p in prompts]
    # the abandoned reader is registered FIRST in every batch
    _lost_got, lost_fut = gone.read(streams[0])
    reads = [a.read(s) for s in streams[1:]]
    d.admit_all(streams)
    _until(lambda: _all_waiting(streams), "three waiting readers")
    gone.loop.call_soon_threadsafe(lost_fut.cancel)
    gone.close()
    assert len(streams[0]._wakeups) == 1   # its wake-up stays behind
    wakes0 = d.eng.stats().stream_wakes
    d.turn()
    assert d.eng.stats().stream_wakes - wakes0 == 1   # loop `a` alone
    assert d.eng.stats().active_slots == 3            # no _fail_all
    while any(r is not None for r in d.eng._slots):
        d.turn()
    for (g, fut), p in zip(reads, prompts[1:]):
        fut.result(WAIT_S)
        assert g == _oracle(p, 6)
    assert d.eng.stats().requests_completed == 3


def test_tokens_before_a_fault_arrive_then_the_error(loops):
    """(d) the row walk faults after two of four rows: those two
    readers get the tick's token and then the error, the others the
    error alone."""
    a = loops()
    d = _Driven()
    prompts = [_prompt(60 + i, 6) for i in range(4)]
    streams = [d.eng.submit(p, max_new_tokens=10) for p in prompts]
    reads = [a.read(s) for s in streams]
    d.admit_all(streams)
    d.turn()
    _until(lambda: _all_waiting(streams), "four waiting readers")
    before = [len(g) for g, _ in reads]
    real, calls = d.eng._advance, []

    def faulty(slot, req, produced, now):
        if len(calls) == 2:
            raise RuntimeError("injected fault")
        calls.append(slot)
        return real(slot, req, produced, now)
    d.eng._advance = faulty
    d.turn()
    d.eng._advance = real
    for g, fut in reads:
        with pytest.raises(RuntimeError, match="injected fault"):
            fut.result(WAIT_S)
    grew = [len(g) - n for (g, _), n in zip(reads, before)]
    assert sorted(grew) == [0, 0, 1, 1]
    for (g, _), p in zip(reads, prompts):
        assert g == _oracle(p, 10)[:len(g)]
    assert d.eng.stats().active_slots == 0
    # the engine serves again
    s = d.eng.submit(prompts[0], max_new_tokens=3)
    while not s._done:
        d.turn()
    assert s.result(WAIT_S) == _oracle(prompts[0], 3)


def test_first_token_waits_for_no_tick(monkeypatch):
    """(e) a prompt's first token is emitted in the turn that then
    dispatches a tick: its reader is woken before that dispatch, not at
    the turn's end.  The engine's own thread runs; the tick's step
    blocks until the reader has its token."""
    gate, armed = threading.Event(), threading.Event()
    real = engine_mod._paged_tick
    dispatched = []

    def blocking_tick(*a, **k):
        if armed.is_set():
            dispatched.append(time.monotonic())
            assert gate.wait(WAIT_S), "the reader never got its token"
        return real(*a, **k)
    monkeypatch.setattr(engine_mod, "_paged_tick", blocking_tick)
    prompt = _prompt(5, 6)
    with GenerationEngine(PARAMS, CFG, **ENGINE_KW) as eng:
        _until(lambda: eng._phase_t is not None, "the warm-up")
        armed.set()
        s = eng.submit(prompt, max_new_tokens=4)
        try:
            first = next(s)              # while the tick is held
            got_t = time.monotonic()
            _until(lambda: dispatched, "the tick's dispatch")
            st = eng.stats()
            assert st.tokens_generated == 1 and st.stream_wakes == 1
            assert st.loop_turns == 0    # the first turn has not ended
        finally:
            gate.set()
        rest = list(s)
    assert [first] + rest == _oracle(prompt, 4)
    assert got_t < dispatched[0] + WAIT_S / 2


def test_four_rows_greedy_is_token_for_token(loops):
    """(f) four rows streaming at once to one loop through the engine's
    own thread: each request's tokens are generate()'s for that prompt
    alone, and a wake carries more than one row."""
    a = loops()
    prompts = [_prompt(10 + i, n) for i, n in enumerate((5, 9, 13, 3))]
    with GenerationEngine(PARAMS, CFG, **ENGINE_KW) as eng:
        reads = [a.read(eng.submit(p, max_new_tokens=16))
                 for p in prompts]
        for _g, fut in reads:
            fut.result(WAIT_S)
        st = eng.stats()
    for (g, _), p in zip(reads, prompts):
        assert g == _oracle(p, 16)
    assert st.tokens_generated == 4 * 16
    assert 0 < st.stream_wakes < st.tokens_generated


def test_a_verify_tick_wakes_once_for_a_rows_tokens(loops):
    """Speculation on: a zero-weight model emits token 0 forever, so
    every prompt-lookup draft is accepted and a verify tick produces
    several tokens a row; all are pushed, then one wake a loop."""
    a = loops()
    zero = jax.tree_util.tree_map(jnp.zeros_like, PARAMS)
    zero["ln_f"] = jnp.ones_like(zero["ln_f"])
    d = _Driven(speculate_k=3, speculate_ngram=2)
    d.eng.params = zero
    streams = [d.eng.submit([0] * 8, max_new_tokens=20) for _ in range(2)]
    reads = [a.read(s) for s in streams]
    d.admit_all(streams)
    _until(lambda: _all_waiting(streams), "two waiting readers")
    st0, calls0 = d.eng.stats(), a.calls
    d.turn()
    st = d.eng.stats()
    assert st.spec_accepted_tokens > st0.spec_accepted_tokens
    assert st.tokens_generated - st0.tokens_generated > 2
    assert st.stream_wakes - st0.stream_wakes == 1 == a.calls - calls0
    while any(r is not None for r in d.eng._slots):
        d.turn()
    for g, fut in reads:
        fut.result(WAIT_S)
        assert g == [0] * 20


def test_stop_wakes_every_waiting_reader(loops):
    """stop() finishes what is queued and what decodes with one call a
    loop; the readers get the error, and it is not a flush of the
    worker's (stream_wakes stays)."""
    a = loops()
    d = _Driven()
    streams = [d.eng.submit(_prompt(80 + i, 6), max_new_tokens=20)
               for i in range(3)]
    reads = [a.read(s) for s in streams]
    d.turn()                             # one admitted, two queued
    _until(lambda: _all_waiting(streams), "three waiting readers")
    wakes0, calls0 = d.eng.stats().stream_wakes, a.calls
    d.eng.stop()
    assert a.calls - calls0 == 1
    assert d.eng.stats().stream_wakes == wakes0
    for _g, fut in reads:
        with pytest.raises(RuntimeError, match="engine stopped"):
            fut.result(WAIT_S)


def test_push_outside_the_engine_fires_at_once(loops):
    """A caller with no batch (any thread but the engine's worker)
    keeps the old behaviour: the reader is woken by the push itself."""
    a = loops()
    s = engine_mod.TokenStream("solo")
    got, fut = a.read(s)
    _until(lambda: _all_waiting([s]), "a waiting reader")
    s._push(11)
    _until(lambda: got == [11] and _all_waiting([s]), "the token")
    s._push(12)
    s._finish()
    fut.result(WAIT_S)
    assert got == [11, 12]


@pytest.mark.parametrize("values", [
    [], [0.004], [0.001, 0.0010001, 0.5, 31.0, 0.0, 30.0],
    [i * 0.0037 for i in range(300)]])
def test_observe_many_is_observe_for_each(values):
    """(g) the same buckets, sum (to the last bit) and count."""
    bounds = [0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
              1, 2.5, 5, 10, 30]
    one = metrics.Histogram("test_wakes_hist_one", "", boundaries=bounds,
                            tag_keys=("engine",))
    many = metrics.Histogram("test_wakes_hist_many", "", boundaries=bounds,
                             tag_keys=("engine",))
    tags = {"engine": "e"}
    for v in values:
        one.observe(v, tags=tags)
    many.observe_many(values, tags=tags)
    # ...and through a handle resolved once, on top of both
    for v in values:
        one.series(tags).observe(v)
    many.series(tags).observe_many(values)
    assert one.snapshot()["values"] == many.snapshot()["values"]
    entry = many.snapshot()["values"].get(("e",))
    if values:
        assert entry["count"] == 2 * len(values) == sum(entry["buckets"])
    else:
        assert entry is None    # no observation, no series: as observe()
