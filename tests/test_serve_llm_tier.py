"""KV memory hierarchy: cold-page tiering + durable sessions.

The contract under test (PR 19): the decode pool (T0) is only the HOT
tier — pages that miss their decode ticks demote to host shared-memory
arenas (T1) and on to the object store (T2) with the transfer plane's
per-page CRC framing, and promote back on the next prefix match with
greedy output bit-identical to never-demoted decoding.  A `session`
id makes a conversation durable: its pages and sampler state
checkpoint to the store at finish, and ANY replica resurrects it —
minutes later, even after the origin replica died — again
bit-identically.  Admission prefers demoting cold pages over evicting
(demoted bytes survive; evicted bytes are gone), and every failure
path degrades to re-prefill, never to a corrupt cache.
"""

import asyncio
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu.models import decode, gpt
from ray_tpu.serve.llm.engine import (EngineOverloadedError,
                                      GenerationEngine)
from ray_tpu.serve.llm.kv_tier import HostKVArena, KVPageStore, \
    frame_crc, page_frame, split_frame
from ray_tpu.serve.llm.paging import (TIER_FLIGHT, TIER_HOST, TIER_POOL,
                                      TIER_STORE, BlockAllocator,
                                      RadixPrefixCache,
                                      prefix_fingerprints)

GPT_CFG = gpt.GPTConfig(vocab_size=97, d_model=32, n_heads=4,
                        n_layers=2, d_ff=64, max_seq=64,
                        dtype=jnp.float32, remat=False, use_flash=False)
PAGED_KW = dict(num_slots=3, max_seq=48, prefill_chunk=5, page_size=4,
                kv_pages=40)
ENGINE_KW = dict(num_slots=2, max_seq=40, prefill_chunk=4, page_size=4,
                 kv_pages=40)


def _loader():
    cfg = GPT_CFG
    return gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg


def _prompt(seed, n, vocab=97):
    return [int(t) for t in np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 1, vocab))]


def _oracle(prompt, max_new, cfg=GPT_CFG, model=gpt):
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    out = decode.generate(params, jnp.asarray([prompt]), cfg,
                          max_new_tokens=max_new)
    return [int(t) for t in np.asarray(out[0])]


def _engine(name="tier", **kw):
    params = gpt.init_params(GPT_CFG, jax.random.PRNGKey(0))
    return GenerationEngine(params, GPT_CFG, name=name,
                            **{**PAGED_KW, **kw})


def _sweep(eng):
    """Force one tier sweep on the worker thread (the pages' owner)."""
    return eng.run_on_worker(
        lambda: eng._maybe_sweep_tiers(force=True))


@pytest.fixture
def serve_instance():
    from ray_tpu import serve
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    serve.start()
    yield
    serve.shutdown()
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Radix tier state (pure units: no engine, no device)


def _tree(pages=16, page=4):
    alloc = BlockAllocator(pages)
    return RadixPrefixCache(page, alloc), alloc


def test_radix_tier_demote_promote_roundtrip():
    """apply_demote frees the pool page and moves the node's tier;
    promote reattaches a pool page.  tier_nodes tracks every move and
    the allocator's free list agrees."""
    tree, alloc = _tree()
    toks = _prompt(1, 12)
    got = alloc.alloc(3)
    tree.insert(toks, got)
    for p in got:
        alloc.decref(p)  # tree-owned now
    free0 = alloc.free_pages
    assert tree.tier_nodes[TIER_POOL] == 3

    nodes = tree.demote_candidates(0.0)
    assert len(nodes) == 3
    victim = nodes[0]
    tree.apply_demote(victim, TIER_HOST, ("t1", 0, 123, 64))
    assert victim.tier == TIER_HOST and victim.page is None
    assert victim.payload == ("t1", 0, 123, 64)
    assert tree.tier_nodes[TIER_POOL] == 2
    assert tree.tier_nodes[TIER_HOST] == 1
    assert alloc.free_pages == free0 + 1  # the pool page came back

    new = alloc.alloc(1)[0]
    tree.promote(victim, new)
    assert victim.tier == TIER_POOL and victim.page == new
    assert tree.tier_nodes == [3, 0, 0]


def test_demote_skips_shared_and_busy_pages():
    """A page a live request still holds (refcount > 1) must never
    demote out from under it — demotion is for TREE-ONLY pages, the
    same invariant releasable() counts."""
    tree, alloc = _tree()
    toks = _prompt(2, 12)
    got = alloc.alloc(3)
    tree.insert(toks, got)
    for p in got:
        alloc.decref(p)
    # a running request shares the first page (prefix hit)
    alloc.incref(got[0])
    victims = {n.page for n in tree.demote_candidates(0.0)}
    assert got[0] not in victims
    assert victims == {got[1], got[2]}
    # min_idle_s gates on last decode tick
    tree.match(toks)  # touches the path: everything is hot again
    assert tree.demote_candidates(1e9) == []
    alloc.decref(got[0])


def test_match_stops_at_tiered_node_but_match_nodes_sees_through():
    """match() hands out POOL pages only (callers index the device
    cache with them); match_nodes() surfaces the tiered tail so the
    engine can promote it before reserving."""
    tree, alloc = _tree()
    toks = _prompt(3, 12)
    got = alloc.alloc(3)
    tree.insert(toks, got)
    for p in got:
        alloc.decref(p)
    mid = tree.match_nodes(toks)[0][1]
    tree.apply_demote(mid, TIER_STORE, ("t2", "fp", 1, 64))
    pages, n = tree.match(toks)
    assert n == 4 and pages == [got[0]]  # stops AT the demoted node
    nodes, matched = tree.match_nodes(toks)
    assert matched == 12 and len(nodes) == 3
    assert [x.tier for x in nodes] == [TIER_POOL, TIER_STORE, TIER_POOL]


def test_releasable_and_evict_are_tier_aware():
    """releasable() counts only T0 tree-only pages (a demoted node
    frees no pool page when evicted); evict() of a tiered node calls
    the release_payload hook instead of touching the allocator."""
    tree, alloc = _tree()
    freed = []
    tree.release_payload = lambda payload: freed.append(payload)
    toks = _prompt(4, 12)
    got = alloc.alloc(3)
    tree.insert(toks, got)
    for p in got:
        alloc.decref(p)
    assert tree.releasable() == 3
    leaf = tree.match_nodes(toks)[0][-1]
    tree.apply_demote(leaf, TIER_HOST, ("t1", 7, 99, 64))
    assert tree.releasable() == 2  # the T1 node frees no pool page
    free0 = alloc.free_pages
    tree.evict(free0 + 3)  # unreachable target: unwind the whole trie
    assert freed == [("t1", 7, 99, 64)]  # payload hook fired
    assert alloc.free_pages == free0 + 2
    assert tree.tier_nodes == [0, 0, 0]


def test_demote_candidates_keep_the_walks_order_without_the_walk():
    """The pool's nodes are kept in demotion order as paths are touched:
    what demote_candidates hands out — coldest touch first, within one
    touched path the deepest first, tree-only pages only — is what a
    walk of the whole tree sorted by (last_used, -depth) gave, through
    inserts, matches, demotions, promotions and evictions."""
    import random
    rnd = random.Random(7)
    tree, alloc = _tree(pages=64)
    prompts = [_prompt(100 + i, 4 * rnd.randint(2, 5)) for i in range(8)]
    prompts += [prompts[0][:8] + _prompt(200, 8),      # shared prefixes
                prompts[1][:4] + _prompt(201, 12)]

    def walk():
        out, stack = [], list(tree._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.tier == TIER_POOL and alloc.refcount(n.page) == 1:
                out.append(n)
        return sorted(out, key=lambda n: (n.last_used, -n.depth))

    def check():
        want = walk()
        assert tree.demote_candidates(0.0) == want
        assert tree.demote_candidates(0.0, limit=3) == want[:3]
        assert tree.releasable() == len(want)
        assert len(tree._pool_lru) == tree.tier_nodes[TIER_POOL]

    held = []
    for toks in prompts:
        have, _ = tree.match(toks)
        got = alloc.alloc(len(toks) // 4 - len(have))
        tree.insert(toks, list(have) + got)
        for p in got:
            alloc.decref(p)
        check()
    for step in range(40):
        toks = rnd.choice(prompts)
        what = rnd.randrange(5)
        if what == 0:
            tree.match(toks[:4 * rnd.randint(1, len(toks) // 4)])
        elif what == 1 and tree.demote_candidates(0.0):
            node = tree.demote_candidates(0.0)[0]
            tree.apply_demote(node, TIER_HOST, ("t1", step, 0, 64))
        elif what == 2:
            tiered = [n for n in tree.match_nodes(toks)[0]
                      if n.tier == TIER_HOST]
            for n in tiered:
                tree.promote(n, alloc.alloc(1)[0])
        elif what == 3:
            pages, _ = tree.match(toks)
            if pages and not held:          # a request holds a prefix
                held = pages[:1]
                alloc.incref(held[0])
        else:
            for p in held:
                alloc.decref(p)
            held = []
            tree.evict(alloc.free_pages + 1)
        check()
    assert tree.demote_candidates(1e9) == []        # all touched just now
    assert tree.demote_candidates(0.0, tier=TIER_HOST) == sorted(
        tree._host_nodes, key=lambda n: (n.last_used, -n.depth))


# ---------------------------------------------------------------------------
# Framing + stores (kv_tier units)


def test_page_frame_split_roundtrip_and_crc():
    kshape = vshape = (2, 4, 2, 8)
    k = np.arange(np.prod(kshape), dtype=np.float32).reshape(kshape)
    v = -k
    frame = page_frame(k, v)
    assert len(frame) == k.nbytes + v.nbytes
    k2, v2 = split_frame(frame, k.nbytes, kshape, vshape, np.float32)
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)
    assert frame_crc(frame) == frame_crc(bytes(frame))
    assert frame_crc(frame) != frame_crc(frame[:-1] + b"\x00")


def test_kv_store_roundtrip_sessions_and_corruption_is_a_miss(tmp_path):
    store = KVPageStore(str(tmp_path))
    frame = bytes(range(256)) * 4
    assert store.put_page("fp-a", frame)
    assert store.get_page("fp-a") == frame
    assert store.get_page("fp-missing") is None
    # torn/corrupt file: read must be a MISS (re-prefill), never bytes
    # that don't match the checksum
    path = store._page_path("fp-a")
    with open(path, "r+b") as f:
        f.seek(20)
        f.write(b"\xff\xff")
    assert store.get_page("fp-a") is None
    assert not store.has_page("fp-a")  # poisoned file was unlinked
    man = {"tokens": [1, 2, 3], "rng_state": {"state": 7}, "t": 1.0}
    assert store.put_session("sess", man)
    assert store.get_session("sess")["tokens"] == [1, 2, 3]
    assert store.get_session("nope") is None


def test_host_arena_put_get_free_and_budget(tmp_path):
    arena = HostKVArena(page_nbytes=64, budget_bytes=192, name="t")
    try:
        frames = [bytes([i]) * 64 for i in range(3)]
        slots = [arena.put(f) for f in frames]
        assert None not in slots and arena.free_slots == 0
        assert arena.put(b"x" * 64) is None  # budget-bounded, no grow
        for s, f in zip(slots, frames):
            assert arena.get(s) == f
        arena.free(slots[1])
        s2 = arena.put(b"y" * 64)
        assert s2 == slots[1]  # LIFO slot reuse
        assert arena.get(s2) == b"y" * 64
    finally:
        arena.close()


# ---------------------------------------------------------------------------
# Engine: demote -> promote parity, pressure demotion, resurrect


def test_demote_promote_greedy_parity(tmp_path, monkeypatch):
    """Pages demoted to T1/T2 and promoted back on the next match
    produce bit-identical greedy output — the bar that makes tiering
    an invisible optimization."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_t2_idle_s", 1e9)
    prompt = _prompt(11, 16)
    want = _oracle(prompt, 8)

    async def run():
        eng = _engine(name="tierpar", kv_store_dir=str(tmp_path))
        with eng:
            first = await eng.generate(prompt, max_new_tokens=8)
            demoted = _sweep(eng)
            mid = eng.stats()
            again = await eng.generate(prompt, max_new_tokens=8)
            end = eng.stats()
        return first, demoted, mid, again, end

    first, demoted, mid, again, end = asyncio.run(run())
    assert first == want and again == want
    assert demoted > 0 and mid.kv_t1_pages > 0
    assert end.kv_promotions > 0
    assert end.prefix_hit_tokens >= 4  # promoted pages hit as cache


def test_t1_pages_cool_to_store_and_still_promote(tmp_path,
                                                  monkeypatch):
    """Second sweep stage: idle T1 arena slots spill to the T2 store
    (arena slots come back) and a later match promotes straight from
    the store with parity intact."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_t2_idle_s", 0.0)
    prompt = _prompt(12, 12)
    want = _oracle(prompt, 6)

    async def run():
        eng = _engine(name="tiert2", kv_store_dir=str(tmp_path))
        with eng:
            first = await eng.generate(prompt, max_new_tokens=6)
            _sweep(eng)   # T0 -> T1
            _sweep(eng)   # T1 -> T2 (t2_idle_s = 0)
            mid = eng.stats()
            store_stats = eng._tier_store().stats()
            again = await eng.generate(prompt, max_new_tokens=6)
            end = eng.stats()
        return first, mid, store_stats, again, end

    first, mid, store_stats, again, end = asyncio.run(run())
    assert first == want and again == want
    assert mid.kv_t2_pages > 0 and mid.kv_t1_pages == 0
    assert store_stats["pages"] >= mid.kv_t2_pages
    assert end.kv_promotions > 0


def test_pressure_demotes_cold_pages_instead_of_evicting(monkeypatch,
                                                         tmp_path):
    """A pool full of COLD cached pages admits new work by demoting
    them (bytes survive in the hierarchy) rather than evicting (bytes
    gone): afterwards the old prefix is still present in T1/T2 and
    the new request completed with parity."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 1e9)
    cold = _prompt(13, 24)
    hot = _prompt(14, 24)
    want_cold = _oracle(cold, 8)
    want_hot = _oracle(hot, 8)

    async def run():
        # 24+8 tokens -> 8 pages each; 12 usable pages cannot hold two
        # cached prompts, so the second admission must reclaim
        eng = _engine(name="tierpress", kv_pages=12, num_slots=2,
                      kv_store_dir=str(tmp_path))
        with eng:
            got_cold = await eng.generate(cold, max_new_tokens=8)
            got_hot = await eng.generate(hot, max_new_tokens=8)
            end = eng.stats()
        return got_cold, got_hot, end

    got_cold, got_hot, end = asyncio.run(run())
    assert got_cold == want_cold and got_hot == want_hot
    assert end.kv_demotions > 0, "pressure path must demote, not evict"
    assert end.kv_t1_pages + end.kv_t2_pages > 0


def test_session_checkpoint_resurrects_on_fresh_engine(tmp_path):
    """Durable sessions: engine A checkpoints a session's pages +
    manifest to the store at finish; a FRESH engine (new process-worth
    of state, same store) resurrects it and continues bit-identically
    — including the page import making the continuation's prefill
    collapse to cache hits."""
    prompt = _prompt(15, 12)
    want = _oracle(prompt, 14)

    async def first_life():
        eng = _engine(name="life1", kv_store_dir=str(tmp_path))
        with eng:
            out = await eng.generate(prompt, max_new_tokens=6,
                                     session_id="sess-res")
            flushed = eng.run_on_worker(eng.kv_flush_to_store)
        return out, flushed

    out, flushed = asyncio.run(first_life())
    assert out == want[:6] and flushed > 0
    man = KVPageStore(str(tmp_path)).get_session("sess-res")
    assert man["tokens"] == prompt + want[:6]

    async def second_life():
        eng = _engine(name="life2", kv_store_dir=str(tmp_path))
        with eng:
            res = eng.run_on_worker(
                lambda: eng.session_resurrect("sess-res"))
            toks = [int(t) for t in res["tokens"]]
            rest = await eng.generate(toks, max_new_tokens=8,
                                      session_id="sess-res",
                                      rng_state=res.get("rng_state"))
            end = eng.stats()
        return res, rest, end

    res, rest, end = asyncio.run(second_life())
    assert res["imported"] > 0 and res["cached_pages"] == 0
    assert out + rest == want
    assert end.session_resurrections == 1
    assert end.prefix_hit_tokens >= res["imported"] * 4


def test_resurrect_missing_session_is_none_and_corrupt_page_reprefills(
        tmp_path):
    """No manifest -> None (caller re-prefills from scratch).  A
    corrupt store page stops the import at that depth and the tail
    re-prefills — parity survives every failure path."""
    prompt = _prompt(16, 12)
    want = _oracle(prompt, 6)

    async def run():
        eng = _engine(name="tiercor", kv_store_dir=str(tmp_path))
        with eng:
            assert eng.run_on_worker(
                lambda: eng.session_resurrect("ghost")) is None
            await eng.generate(prompt, max_new_tokens=6,
                               session_id="sess-cor")
            eng.run_on_worker(eng.kv_flush_to_store)
        # poison the SECOND page of the chain on disk
        store = KVPageStore(str(tmp_path))
        fps = prefix_fingerprints(prompt + want, 4, 8)
        with open(store._page_path(fps[1]), "r+b") as f:
            f.seek(16)
            f.write(b"\xde\xad")
        eng2 = _engine(name="tiercor2", kv_store_dir=str(tmp_path))
        with eng2:
            res = eng2.run_on_worker(
                lambda: eng2.session_resurrect("sess-cor"))
            toks = [int(t) for t in res["tokens"]]
            rest = await eng2.generate(toks, max_new_tokens=4)
        return res, rest

    res, rest = asyncio.run(run())
    assert res["imported"] == 1  # stopped at the poisoned page
    assert rest == _oracle(prompt + want, 4)


# ---------------------------------------------------------------------------
# The lander: a demotion's host half off the tick thread (PR 37)


class _Gate:
    """Holds an engine's lander before it copies a batch to the host,
    until opened: what the worker thread does meanwhile is what it does
    while pages are in flight, with no clock in the test."""

    def __init__(self, eng):
        self.entered = threading.Event()
        self.open = threading.Event()
        lander = eng.run_on_worker(eng._tier_lander)
        land = lander._land

        def gated(job):
            self.entered.set()
            assert self.open.wait(120)
            land(job)
        lander._land = gated


def _pool_frames(eng):
    """(node, frame, crc) of every demotable pool page, framed the way
    the synchronous path did it: a blocking read of the pool's own
    arrays, K bytes then V bytes.  Worker thread."""
    nodes = eng._prefix.demote_candidates(0.0)
    pages = [n.page for n in nodes]
    k = np.asarray(eng._cache["k"][:, pages])
    v = np.asarray(eng._cache["v"][:, pages])
    frames = [page_frame(k[:, i], v[:, i]) for i in range(len(nodes))]
    return [(n, f, frame_crc(f)) for n, f in zip(nodes, frames)]


def _lander_threads(eng):
    return [t for t in threading.enumerate()
            if t.name == f"llm-lander-{eng.name}"]


@pytest.mark.parametrize("dest", ["arena", "store"])
def test_landed_frame_and_crc_equal_the_synchronous_paths(
        tmp_path, monkeypatch, dest):
    """What the lander leaves in the arena (the sweep) or the store
    (the flush) is, byte for byte and CRC for CRC, the frame the
    blocking read of the same pool contents gives."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_t2_idle_s", 1e9)
    eng = _engine(name=f"land-{dest}", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(_prompt(31, 22), max_new_tokens=4).result(timeout=120)
        want = eng.run_on_worker(lambda: _pool_frames(eng))
        assert len(want) == 5
        if dest == "arena":
            assert _sweep(eng) == len(want)
        else:
            assert eng.run_on_worker(eng.kv_flush_to_store) == len(want)
        st = eng.stats()
        for node, frame, crc in want:
            kind, key, got_crc, nbytes = node.payload
            assert (kind, got_crc, nbytes) == (
                "t1" if dest == "arena" else "t2", crc, len(frame))
            held = eng._arena.get(key) if dest == "arena" \
                else eng._tier_store().get_page(key)
            assert held == frame
        assert len(_lander_threads(eng)) == 1
    assert st.kv_pages_landed == st.kv_demotions == len(want)
    assert st.kv_land_lost == 0 and st.kv_land_s > 0
    assert _lander_threads(eng) == []      # it ends with the engine


def test_pages_overwritten_after_dispatch_land_with_their_old_bytes(
        tmp_path, monkeypatch):
    """The pool pages are released when the gather is DISPATCHED, and
    the next request's prefill chunks rewrite them through the donated
    cache while the lander has not copied a byte: the frames that land
    are the old pages', because the device ran the gather first."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 1e9)
    cold, hot = _prompt(32, 24), _prompt(33, 24)
    want_hot = _oracle(hot, 8)
    # 8 pages a request; 12 cannot hold both, so the second admission
    # demotes the first's pages and takes them over (LIFO free list)
    eng = _engine(name="land-order", kv_pages=12, num_slots=2,
                  kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(cold, max_new_tokens=8).result(timeout=120)
        old = eng.run_on_worker(lambda: _pool_frames(eng))
        old_pages = [n.page for n, _, _ in old]
        gate = _Gate(eng)
        got_hot = eng.submit(hot, max_new_tokens=8).result(timeout=120)
        assert gate.entered.wait(120)
        st = eng.stats()
        assert st.kv_demotions == 0 and st.kv_sweeps == 1
        # the pages are another request's now, and hold other bytes
        demoted = [(n, f) for n, f, _ in old if n.page is None]
        assert demoted and all(n.tier == TIER_FLIGHT for n, _ in demoted)
        now = eng.run_on_worker(lambda: {
            p: page_frame(np.asarray(eng._cache["k"][:, p]),
                          np.asarray(eng._cache["v"][:, p]))
            for p in old_pages})
        rewritten = [f for (n, f, _), p in zip(old, old_pages)
                     if n.page is None and now[p] != f]
        assert rewritten, "the hot request reused no demoted page"
        gate.open.set()
        eng.run_on_worker(eng._land_drain)
        for node, frame in demoted:
            assert node.tier == TIER_HOST
            assert eng._arena.get(node.payload[1]) == frame
            assert node.payload[2] == frame_crc(frame)
        end = eng.stats()
    assert got_hot == want_hot
    assert end.kv_pages_landed == len(demoted) and end.kv_land_lost == 0


def test_match_at_an_inflight_node_stops_there_and_reprefills(
        tmp_path, monkeypatch):
    """A match that reaches a node whose bytes are still landing does
    not wait: it stops there, the tail is prefilled cold (the output is
    the oracle's), the re-published page takes the node back and the
    landing, when it arrives, is discarded with its slot."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_tier_sweep_s", 3600.0)
    head, tail_a, tail_b = _prompt(34, 8), _prompt(35, 8), _prompt(36, 9)
    want = _oracle(head + tail_b, 6)
    eng = _engine(name="land-match", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(head + tail_a, max_new_tokens=4).result(timeout=120)
        gate = _Gate(eng)

        def due_sweep():        # unforced: dispatch and go on
            eng._last_sweep = float("-inf")
            return eng._maybe_sweep_tiers()
        moved = eng.run_on_worker(due_sweep)
        assert moved == 4 and eng._prefix.inflight_nodes == 4
        s0 = eng.stats()
        got = eng.submit(head + tail_b, max_new_tokens=6).result(timeout=120)
        s1 = eng.stats()
        assert got == want
        assert s1.kv_inflight_matches - s0.kv_inflight_matches == 1
        assert s1.prefix_hit_tokens == s0.prefix_hit_tokens
        assert s1.prefix_cache_misses - s0.prefix_cache_misses == 1
        # head's two pages were re-published by the request's own
        # prefill; tail_a's two are still in flight
        assert eng._prefix.inflight_nodes == 2
        free0 = eng._arena.free_slots
        gate.open.set()
        eng.run_on_worker(eng._land_drain)
        assert eng.stats().kv_pages_landed == 2
        assert eng._arena.free_slots == free0 + 2   # head's slots came back
        assert eng._prefix.inflight_nodes == 0
        again = eng.submit(head + tail_b,
                           max_new_tokens=6).result(timeout=120)
        s2 = eng.stats()
    assert again == want
    assert s2.prefix_hit_tokens - s1.prefix_hit_tokens == 16
    assert s2.kv_land_lost == 0


def test_evicting_an_inflight_node_frees_its_slot_when_it_lands(
        tmp_path, monkeypatch):
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    eng = _engine(name="land-evict", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(_prompt(37, 16), max_new_tokens=4).result(timeout=120)
        gate = _Gate(eng)
        n = eng.run_on_worker(
            lambda: eng._demote_for_pressure(eng.kv_pages + 1))
        arena = eng._arena
        assert n == 4 and arena.free_slots == arena.capacity - 4
        # the tree lets go of them while their bytes are on the way
        dropped = eng.run_on_worker(
            lambda: eng._prefix.evict(eng.kv_pages + 1))
        assert dropped == 4 and eng._prefix.nodes == 0
        assert arena.free_slots == arena.capacity - 4   # still being written
        gate.open.set()
        assert eng.run_on_worker(eng._land_drain) == 0
        assert arena.free_slots == arena.capacity
        st = eng.stats()
    assert st.kv_demotions == st.kv_pages_landed == st.kv_land_lost == 0
    assert st.kv_t1_pages == st.kv_t2_pages == 0


def test_flush_returns_only_when_every_inflight_page_is_in_the_store(
        tmp_path, monkeypatch):
    """kv_flush_to_store is the drain path: pages dispatched before it
    (toward the arena) and by it (toward the store) are all store
    entries when it returns, and not before the lander ran."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 1e9)
    first, second = _prompt(38, 16), _prompt(39, 16)
    eng = _engine(name="land-flush", kv_store_dir=str(tmp_path))
    store = KVPageStore(str(tmp_path))
    with eng:
        eng.submit(first, max_new_tokens=4).result(timeout=120)
        gate = _Gate(eng)
        # four pages on their way to the arena...
        assert eng.run_on_worker(
            lambda: eng._demote_for_pressure(eng.kv_pages + 1)) == 4
        eng.submit(second, max_new_tokens=4).result(timeout=120)
        # ...and the flush sends four more to the store
        result = []
        caller = threading.Thread(target=lambda: result.append(
            eng.run_on_worker(eng.kv_flush_to_store, timeout=120)))
        caller.start()
        assert gate.entered.wait(120)
        assert caller.is_alive() and not result
        assert store.stats()["pages"] == 0
        gate.open.set()
        caller.join(120)
        st = eng.stats()
        for prompt in (first, second):
            for fp in prefix_fingerprints(prompt, 4, 4):
                assert store.has_page(fp)
        assert eng._prefix.tier_nodes == [0, 0, 8]
        assert eng._prefix.inflight_nodes == 0
    assert result == [12]       # 8 landed + the 4 arena slots emptied
    assert st.kv_land_wait_s > 0 and st.kv_land_lost == 0


def test_demotions_of_different_page_counts_compile_the_read_once(
        tmp_path, monkeypatch):
    from ray_tpu._private import jax_utils
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_t2_idle_s", 1e9)
    eng = _engine(name="land-once", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(_prompt(40, 16), max_new_tokens=4).result(timeout=120)
        assert _sweep(eng) == 4         # the one compile lands here
        eng.submit(_prompt(41, 32), max_new_tokens=4).result(timeout=120)
        compiles = jax_utils.compile_counters()[0]
        programs = decode.paged_read_pages._cache_size()
        assert _sweep(eng) == 8
        eng.submit(_prompt(42, 8), max_new_tokens=4).result(timeout=120)
        # ...and the blocking read of an export is the same program
        exported = eng.run_on_worker(lambda: eng.kv_export(_prompt(42, 8)))
        assert exported["matched_tokens"] == 8 and len(exported["pages"]) == 2
        eng.run_on_worker(lambda: eng.kv_export_release(exported["pages"]))
        assert _sweep(eng) == 2
        assert jax_utils.compile_counters()[0] == compiles
        assert decode.paged_read_pages._cache_size() == programs


@pytest.mark.parametrize("why", ["tiering_off", "pages_not_kv"])
def test_an_engine_without_tiers_never_has_a_lander(monkeypatch, why):
    """kv_tiering=False, or a model whose pages no tier can frame (the
    engine turns tiering off by itself): pressure evicts, and no lander
    thread is ever created."""
    kw = {}
    if why == "tiering_off":
        kw["kv_tiering"] = False
    else:
        unframed = dataclasses.replace(decode.DENSE_BODY, framed=False)
        monkeypatch.setattr(decode, "paged_body", lambda cfg: unframed)
    before = set(threading.enumerate())
    eng = _engine(name=f"land-none-{why}", kv_pages=12, num_slots=2, **kw)
    with eng:
        assert not eng._tiering
        for seed in (43, 44, 45):
            eng.submit(_prompt(seed, 24),
                       max_new_tokens=8).result(timeout=120)
        assert eng.run_on_worker(
            lambda: eng._maybe_sweep_tiers(force=True)) == 0
        st = eng.stats()
        born = [t.name for t in set(threading.enumerate()) - before]
    assert eng._lander is None and _lander_threads(eng) == []
    assert born == [f"llm-engine-{eng.name}"]
    assert st.kv_demotions == st.kv_pages_landed == st.kv_sweeps == 0


def test_a_landing_with_nowhere_to_land_drops_the_node(tmp_path,
                                                       monkeypatch):
    """Arena full and no store: the pages left the pool at dispatch, so
    their nodes are dropped (counted in kv_land_lost), and the next
    request for that prefix prefills it again."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(HostKVArena, "reserve", lambda self: None)
    monkeypatch.setattr(KVPageStore, "put_page", lambda *a, **k: False)
    prompt = _prompt(46, 16)
    want = _oracle(prompt, 6)
    eng = _engine(name="land-lost", kv_store_dir=str(tmp_path))
    with eng:
        first = eng.submit(prompt, max_new_tokens=6).result(timeout=120)
        free0 = eng.stats().kv_blocks_free
        assert _sweep(eng) == 0
        s0 = eng.stats()
        assert s0.kv_land_lost == 4 and s0.kv_demotions == 0
        assert eng._prefix.nodes == 0 and s0.kv_blocks_free == free0 + 4
        again = eng.submit(prompt, max_new_tokens=6).result(timeout=120)
        s1 = eng.stats()
    assert first == again == want
    assert s1.prefix_hit_tokens == s0.prefix_hit_tokens
    assert s1.prefix_cache_misses - s0.prefix_cache_misses == 1


def test_the_stores_ttl_sweep_runs_on_the_lander(tmp_path, monkeypatch):
    """Aging the store out is a listing of every file it holds: the
    sweep hands it to the lander and goes on."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    ran = []
    sweep = KVPageStore.sweep
    monkeypatch.setattr(
        KVPageStore, "sweep", lambda self, ttl: ran.append(
            (threading.current_thread().name, sweep(self, ttl))))
    eng = _engine(name="land-gc", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(_prompt(48, 16), max_new_tokens=4).result(timeout=120)
        assert eng.run_on_worker(eng.kv_flush_to_store) == 4
        assert not ran
        eng._last_store_gc = float("-inf")
        _sweep(eng)
        eng.run_on_worker(eng._land_drain)
    assert ran == [("llm-lander-land-gc", 0)]


def test_inflight_bytes_are_bounded_by_waiting_on_the_lander(
        tmp_path, monkeypatch):
    """Past the bound on device stacks awaiting their copy the worker
    waits on the lander inside the sweep (kv_land_wait_s); a demotion
    of more pages than one dispatch carries is several stacks of the
    one shape, the last padded, and every page lands whole."""
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    monkeypatch.setattr(_cfg, "serve_kv_t2_idle_s", 1e9)
    monkeypatch.setattr(decode, "_READ_BYTES", 0)      # 8 pages a stack
    eng = _engine(name="land-bound", kv_store_dir=str(tmp_path))
    with eng:
        eng.submit(_prompt(47, 44), max_new_tokens=4).result(timeout=120)
        want = eng.run_on_worker(lambda: _pool_frames(eng))
        assert len(want) == 11
        gate = _Gate(eng)
        eng._inflight_cap = 1
        result = []
        caller = threading.Thread(
            target=lambda: result.append(eng.run_on_worker(
                lambda: eng._demote_for_pressure(eng.kv_pages + 1),
                timeout=120)))
        caller.start()
        assert gate.entered.wait(120)
        # the second stack waits for the first one's copy
        assert caller.is_alive() and eng._prefix.inflight_nodes == 8
        gate.open.set()
        caller.join(120)
        assert result == [11]
        eng.run_on_worker(eng._land_drain)
        for node, frame, crc in want:
            assert eng._arena.get(node.payload[1]) == frame
            assert node.payload[2] == crc
        st = eng.stats()
    assert st.kv_land_wait_s > 0 and st.kv_pages_landed == 11


# ---------------------------------------------------------------------------
# Structured backpressure (satellite: config-derived Retry-After)


def _parked_engine(**kw):
    eng = _engine(**kw)
    eng.stop()
    eng.start = lambda: eng
    return eng


def test_retry_after_from_config_and_demotion_headroom(monkeypatch):
    """kv_exhausted Retry-After comes from RT_SERVE_KV_RETRY_AFTER_S,
    not a hardcoded 5.0 — and when the demotion sweeper could free
    enough cold pages by its next pass, the hint shrinks to the sweep
    horizon (sub-second, which is why the wire format is float)."""
    monkeypatch.setattr(_cfg, "serve_kv_retry_after_s", 2.5)
    monkeypatch.setattr(_cfg, "serve_kv_tier_sweep_s", 0.25)
    eng = _parked_engine(name="tierretry", num_slots=2, kv_pages=6,
                         max_queue_len=50, kv_commit_factor=1.0)
    eng.submit(_prompt(1, 6), max_new_tokens=6)
    eng.submit(_prompt(2, 6), max_new_tokens=6)
    with pytest.raises(EngineOverloadedError) as ei:
        eng.submit(_prompt(3, 6), max_new_tokens=6)
    assert ei.value.reason == "kv_exhausted"
    assert ei.value.retry_after_s == 2.5
    # demotable cold pages cover the request -> retry on sweep horizon
    eng._demotable_hint = 10
    with pytest.raises(EngineOverloadedError) as ei:
        eng.submit(_prompt(4, 6), max_new_tokens=6)
    assert ei.value.retry_after_s == 0.25


# ---------------------------------------------------------------------------
# Autoscale gauges + router weighting (satellites 2/3)


def test_load_info_splits_tiers_and_reports_reclaimable(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    prompt = _prompt(17, 16)

    async def run():
        eng = _engine(name="tiergauge", kv_store_dir=str(tmp_path))
        with eng:
            await eng.generate(prompt, max_new_tokens=4)
            info0 = eng.load_info()
            _sweep(eng)
            info1 = eng.load_info()
        return info0, info1

    info0, info1 = asyncio.run(run())
    # before the sweep: cached pages sit in T0, all reclaimable
    assert info0["kv_tier_pages"]["t0"] > 0
    assert info0["kv_blocks_reclaimable"] \
        == info0["kv_blocks_free"] + info0["kv_demotable"]
    # after: same bytes in T1, pool pages back on the free list
    assert info1["kv_tier_pages"]["t1"] == info0["kv_tier_pages"]["t0"]
    assert info1["kv_tier_pages"]["t0"] == 0
    assert info1["kv_blocks_free"] > info0["kv_blocks_free"]


def test_controller_load_uses_reclaimable_not_free():
    """Idle sessions parked in the pool are a CACHE, not demand: with
    every page demotable the KV term contributes zero load (no phantom
    scale-up), while a genuinely pinned pool still saturates."""
    from ray_tpu.serve._private.controller import _replica_load
    base = {"ongoing": 0, "num_slots": 0, "kv_blocks_total": 40}
    idle_cache = dict(base, kv_blocks_free=0, kv_blocks_reclaimable=40)
    assert _replica_load(idle_cache, 4.0) == 0.0
    pinned = dict(base, kv_blocks_free=0, kv_blocks_reclaimable=0)
    assert _replica_load(pinned, 4.0) == 1.0
    # pre-tiering replicas (no reclaimable gauge) keep the old signal
    legacy = dict(base, kv_blocks_free=10)
    assert _replica_load(legacy, 4.0) == pytest.approx(0.75)


def _rset(infos, in_flight=None):
    from ray_tpu.serve._private.router import ReplicaSet
    rs = ReplicaSet("tier", loop=None, qos=None)
    rs.update_replicas(infos)
    for tag, n in (in_flight or {}).items():
        rs._in_flight[tag] = n
    return rs


def _rinfo(tag, fps=None, page=4, maxq=8, tier=0):
    info = {"replica_tag": tag, "actor": None,
            "max_concurrent_queries": maxq}
    if fps is not None:
        info["kv_digest"] = {
            "page": page,
            "roots": [{"fp": f, "d": d, "t": tier}
                      for d, f in enumerate(fps, 1)]}
    return info


def test_router_weighs_hot_hits_above_tiered_hits():
    """Two replicas hold the same prefix, one in the decode pool and
    one demoted: the T0 holder wins at equal load (its pages need no
    promotion), but a tiered hit still beats a cold replica."""
    toks = _prompt(18, 12)
    fps = prefix_fingerprints(toks, 4, _cfg.serve_affinity_digest_depth)
    rs = _rset([_rinfo("hot", fps=fps, tier=0),
                _rinfo("demoted", fps=fps, tier=2)])
    for _ in range(8):
        choice = rs._pick((), {"tokens": toks})
        assert choice["replica_tag"] == "hot"
    assert choice["_affinity"]["tier"] == 0
    rs = _rset([_rinfo("demoted", fps=fps, tier=1), _rinfo("cold")])
    for _ in range(8):
        choice = rs._pick((), {"tokens": toks})
        assert choice["replica_tag"] == "demoted"
    assert choice["_affinity"]["tier"] == 1


# ---------------------------------------------------------------------------
# Observability (satellite 3)


def test_tier_metrics_exported_via_prometheus(tmp_path, monkeypatch):
    monkeypatch.setattr(_cfg, "serve_kv_demote_idle_s", 0.0)
    prompt = _prompt(19, 12)

    async def run():
        eng = _engine(name="tierprom", kv_store_dir=str(tmp_path))
        with eng:
            await eng.generate(prompt, max_new_tokens=4,
                               session_id="sess-prom")
            _sweep(eng)
            await eng.generate(prompt, max_new_tokens=4)
            eng.run_on_worker(
                lambda: eng.session_resurrect("sess-prom"))
            st = eng.stats()
        return st

    st = asyncio.run(run())
    assert st.kv_demotions > 0 and st.kv_promotions > 0
    assert st.session_resurrections == 1

    from ray_tpu.util.metrics import prometheus_text, registry_snapshot
    text = prometheus_text(registry_snapshot())
    for needle in ("serve_llm_kv_tier_pages",
                   "serve_llm_kv_demotions_total",
                   "serve_llm_kv_promotions_total",
                   "serve_llm_session_resurrections_total"):
        assert needle in text, needle
    assert 'engine="tierprom"' in text


# ---------------------------------------------------------------------------
# Chaos: replica death -> resurrect anywhere (in `make chaos`)


def _wait(pred, timeout=30.0, interval=0.2, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = pred()
        if out:
            return out
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.mark.slow  # in `make chaos` explicitly; keeps tier-1 lean
def test_kill_replica_with_demoted_sessions_resurrects_elsewhere(
        serve_instance, tmp_path):
    """Chaos: a replica holding a durable session is SIGKILLed after
    flushing its pages to the store (the drain path a dying replica
    runs).  A resume cursor carrying only the session id then lands on
    the survivor, which resurrects the conversation from the store —
    greedy-bit-identical, with the prefill collapsed to imported
    pages."""
    from ray_tpu.serve.llm.api import llm_deployment

    prompt = _prompt(20, 12)
    want = _oracle(prompt, 12)
    handle = llm_deployment(
        _loader, name="tierchaos", num_replicas=2,
        engine_config=dict(ENGINE_KW,
                           kv_store_dir=str(tmp_path))).deploy()
    sub = handle.options("stream")
    got = list(sub.stream(prompt, max_new_tokens=12,
                          session="sess-chaos"))
    assert got == want
    rs = sub._router.replica_set
    router_loop = rs._loop
    _wait(lambda: len(rs._replicas) == 2, msg="both replicas up")

    def stats_of(info):
        return ray_tpu.get(info["actor"].handle_request.remote(
            "stats", (), {}), timeout=30)

    origin = _wait(
        lambda: next((r for r in rs._replicas
                      if stats_of(r)["requests_completed"] > 0), None),
        msg="origin replica identified")
    # the dying replica's drain path: demote everything to the store
    man = ray_tpu.get(origin["actor"].handle_request.remote(
        "kv_drain_manifest", (), {}), timeout=60)
    assert man is not None
    survivor = next(r for r in rs._replicas
                    if r["replica_tag"] != origin["replica_tag"])
    assert stats_of(survivor)["session_resurrections"] == 0
    ray_tpu.kill(origin["actor"])

    k = 4
    resume = {"delivered": k, "items": want[:k],
              "session": "sess-chaos"}

    async def _resumed():
        rs._suppressed[origin["replica_tag"]] = \
            asyncio.get_event_loop().time() + 60.0
        ait = await rs.assign_replica_stream(
            "stream", (prompt,), {"max_new_tokens": 12},
            resume=resume)
        return [int(t) async for t in ait]

    rest = asyncio.run_coroutine_threadsafe(
        _resumed(), router_loop).result(120)
    assert want[:k] + rest == want, (rest, want)
    st = stats_of(survivor)
    assert st["session_resurrections"] >= 1
    assert st["prefix_hit_tokens"] > 0  # store pages fed the prefill
