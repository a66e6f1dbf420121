"""GenerationEngine: iteration-level scheduling over a PAGED KV cache.

The decode loop of models/decode.py serves one batch from arrival to
completion; here the batch dimension becomes a pool of rows that
requests flow through independently (Orca's continuous batching), and
the KV memory behind those rows is a pool of fixed-size PAGES addressed
through per-row block tables (vLLM's PagedAttention) with a radix
prefix cache sharing pages between requests (SGLang's RadixAttention at
page granularity):

  * one [L, num_pages, page_size, Hkv, Dh] page pool is allocated once;
    page 0 is a TRASH page — inactive batch rows' block tables point at
    it, so the fused tick's static-shape scatter always has somewhere
    harmless to write;
  * a request reserves ceil((prompt + max_new + spec slack)/page) pages
    at admission (all-or-nothing, so a resident request can never be
    starved mid-generation) — admission is FREE-PAGE-bounded, not
    row-bounded: mixed-length workloads pack by what they actually
    need instead of every request pinning max_seq;
  * the radix prefix cache maps full-page token prefixes to pages with
    live K/V: a prompt that hits skips prefill for the shared pages
    (refcounted — evicting one sharer never frees a page another still
    gathers) and goes straight to chunked prefill of the tail;
  * arriving requests wait in an FCFS queue (scheduler.py) and are
    prefilled ONE CHUNK PER TICK directly into their own pages through
    their block table (no scratch cache, no slot splice), so admission
    never stalls decoding for more than one chunk of prefill compute;
  * every tick runs ONE fused paged_decode_step across all rows with a
    per-row position vector; when speculation is on and any greedy row
    has a prompt-lookup draft, the tick is instead ONE fused
    paged_chunk_step verifying (pending token + k drafts) per row —
    per-row acceptance (not the lockstep batch-minimum of standalone
    generate()), so one row's miss never throttles another's streak;
  * each sampled token is pushed to that request's TokenStream
    immediately; rows hitting EOS/max-tokens are evicted by FREEING
    their pages (host-side accounting only — stale K/V in a recycled
    page is overwritten before any unmasked read, so there is no
    zeroing pass on the device).

The device loop runs on a dedicated worker thread: jax dispatch blocks,
and the replica's asyncio loop must stay free to serve stream polls.
Greedy sampling stays on device (argmax); temperature>0 rows sample
host-side from the row's logits with a per-request seeded RNG.

Parity contract (tested): with temperature=0 the tokens a request
streams are bit-identical to decode.generate() run on that prompt
alone — chunked prefill, paging, prefix-cache hits, and speculative
verification are pure scheduling transforms, never result transforms.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import logging
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import jax_utils as _jax_utils
from ray_tpu._private import locksan
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu.models import decode
from ray_tpu.serve.llm.kv_tier import (HostKVArena, KVPageStore, PageLander,
                                       refuse_row_state, refuse_unframed,
                                       frame_crc, split_frame)
from ray_tpu.serve.llm.paging import (TIER_HOST, TIER_POOL, TIER_STORE,
                                      BlockAllocator, RadixPrefixCache,
                                      prefix_fingerprints)
from ray_tpu.serve.llm.scheduler import EngineOverloadedError, FCFSScheduler
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tpu_profiler as _tpu_profiler

logger = logging.getLogger(__name__)

# Latency boundaries tuned for token-scale events (the default metric
# buckets start at 5ms and top out at 10s — fine for TTFT, too coarse
# for inter-token gaps on a fast chip).
_LATENCY_BOUNDARIES = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5,
    5, 10, 30]

TTFT_HISTOGRAM = _metrics.Histogram(
    "serve_llm_ttft_seconds",
    "Time from submit() to the first streamed token",
    boundaries=_LATENCY_BOUNDARIES, tag_keys=("engine",))
ITL_HISTOGRAM = _metrics.Histogram(
    "serve_llm_inter_token_seconds",
    "Gap between consecutive streamed tokens of one request",
    boundaries=_LATENCY_BOUNDARIES, tag_keys=("engine",))
TOKENS_COUNTER = _metrics.Counter(
    "serve_llm_tokens_generated_total",
    "Tokens streamed to clients", tag_keys=("engine",))
REQUESTS_COUNTER = _metrics.Counter(
    "serve_llm_requests_total",
    "Requests by terminal status",
    tag_keys=("engine", "status"))
QUEUE_GAUGE = _metrics.Gauge(
    "serve_llm_queue_depth",
    "Requests waiting for admission (excludes the one mid-prefill; "
    "EngineStats.queue_depth adds it)",
    tag_keys=("engine",))
OCCUPANCY_GAUGE = _metrics.Gauge(
    "serve_llm_slot_occupancy",
    "Fraction of decode batch rows mid-generation", tag_keys=("engine",))
THROUGHPUT_GAUGE = _metrics.Gauge(
    "serve_llm_tokens_per_sec",
    "Streamed tokens/sec over the last measurement window",
    tag_keys=("engine",))
KV_BLOCKS_TOTAL_GAUGE = _metrics.Gauge(
    "serve_llm_kv_blocks_total",
    "Allocatable KV pages in the pool (excludes the trash page)",
    tag_keys=("engine",))
KV_BLOCKS_FREE_GAUGE = _metrics.Gauge(
    "serve_llm_kv_blocks_free",
    "KV pages currently on the free list", tag_keys=("engine",))
PREFIX_HITS_COUNTER = _metrics.Counter(
    "serve_llm_prefix_cache_hits_total",
    "Admissions whose prompt hit >=1 cached prefix page",
    tag_keys=("engine",))
PREFIX_MISSES_COUNTER = _metrics.Counter(
    "serve_llm_prefix_cache_misses_total",
    "Admissions with no cached prefix page", tag_keys=("engine",))
SPEC_ACCEPTED_COUNTER = _metrics.Counter(
    "serve_llm_spec_accepted_tokens_total",
    "Draft tokens accepted by speculative verification",
    tag_keys=("engine",))
KV_TIER_PAGES_GAUGE = _metrics.Gauge(
    "serve_llm_kv_tier_pages",
    "Prefix-cache pages by tier (t0=decode pool, t1=host arena, "
    "t2=store)", tag_keys=("engine", "tier"))
KV_DEMOTIONS_COUNTER = _metrics.Counter(
    "serve_llm_kv_demotions_total",
    "Pages demoted out of the decode pool / host arena, by "
    "destination tier", tag_keys=("engine", "to"))
KV_PROMOTIONS_COUNTER = _metrics.Counter(
    "serve_llm_kv_promotions_total",
    "Demoted pages promoted back into the decode pool on a prefix "
    "hit", tag_keys=("engine",))
RESURRECTIONS_COUNTER = _metrics.Counter(
    "serve_llm_session_resurrections_total",
    "Durable sessions restored from the store tier",
    tag_keys=("engine",))


def _set_all(setters):
    for set_event in setters:
        set_event()


def _fire_wakeups(wakeups) -> int:
    """Wake the readers whose `(loop, set_event)` wake-ups were taken
    from their streams: ONE `call_soon_threadsafe` into each distinct
    event loop, whose callback sets every event of that loop, and the
    sync readers' events (loop None) set directly.  Returns the calls
    made.  A consumer that abandoned its wait and closed its loop
    loses its own wake-ups, which are moot, and must neither keep
    another loop's from being made nor poison the calling thread."""
    calls = 0
    by_loop: Dict[Any, List] = {}
    for loop, set_event in wakeups:
        if loop is None:
            set_event()
            calls += 1
        else:
            by_loop.setdefault(loop, []).append(set_event)
    for loop, setters in by_loop.items():
        try:
            loop.call_soon_threadsafe(_set_all, setters)
        except RuntimeError:
            continue
        calls += 1
    return calls


class TokenStream:
    """Per-request stream of generated token ids.

    Producer is the engine's worker thread; consumers may be sync
    (`for tok in stream`, `stream.result()`) or async
    (`async for tok in stream`, `await stream.collect()`) on any event
    loop.  A reader that finds nothing buffered registers a wake-up,
    `(its loop, event.set)`, and waits.  The engine's worker thread
    does not make that call row by row: `_push` / `_finish` hand the
    taken wake-ups to the engine's batch, and when every row of the
    emit phase has been advanced the engine makes ONE
    `loop.call_soon_threadsafe` per distinct loop for all of them
    (`_fire_wakeups`, from `GenerationEngine._flush_emits`): before it
    leaves the phase, so a token waits for no dispatch and no device
    result, and no consumer loop ever blocks on the device.  Any other
    caller (no batch) fires at once."""

    def __init__(self, request_id: str):
        self.request_id = request_id
        self._buf: collections.deque = collections.deque()
        self._lock = locksan.make_lock("TokenStream._lock")
        # (loop or None, zero-arg event setter), fired once each
        self._wakeups: List = []
        self._done = False
        self._error: Optional[BaseException] = None
        self._cancel = threading.Event()
        self._partial: List[int] = []  # result()'s drained-so-far stash

    # -- producer side (engine worker thread) --

    def _push(self, token: int, batch: Optional[List] = None):
        with self._lock:
            self._buf.append(token)
            wakeups, self._wakeups = self._wakeups, []
        self._hand(wakeups, batch)

    def _finish(self, error: Optional[BaseException] = None,
                batch: Optional[List] = None):
        with self._lock:
            self._done = True
            self._error = error
            wakeups, self._wakeups = self._wakeups, []
        self._hand(wakeups, batch)

    @staticmethod
    def _hand(wakeups, batch):
        """The taken wake-ups join the caller's batch, which it fires
        when its rows are all advanced; with no batch they fire now."""
        if not wakeups:
            return
        if batch is None:
            _fire_wakeups(wakeups)
        else:
            batch.extend(wakeups)

    # -- consumer side --

    def cancel(self):
        """Ask the engine to stop this request; the stream finishes
        with whatever tokens were already generated."""
        self._cancel.set()

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def _pop_or_register(self, wakeup):
        """Pop a buffered item, or register a wakeup and return _DONE /
        None.  Returns (kind, value): ('tok', t) | ('end', err) |
        ('wait', None)."""
        with self._lock:
            if self._buf:
                return "tok", self._buf.popleft()
            if self._done:
                return "end", self._error
            self._wakeups.append(wakeup)
            return "wait", None

    def __aiter__(self):
        return self

    async def __anext__(self):
        import asyncio
        while True:
            loop = asyncio.get_running_loop()
            ev = asyncio.Event()
            kind, val = self._pop_or_register((loop, ev.set))
            if kind == "tok":
                return val
            if kind == "end":
                if val is not None:
                    raise val
                raise StopAsyncIteration
            await ev.wait()

    def __iter__(self):
        return self

    def __next__(self):
        while True:
            ev = threading.Event()
            kind, val = self._pop_or_register((None, ev.set))
            if kind == "tok":
                return val
            if kind == "end":
                if val is not None:
                    raise val
                raise StopIteration
            ev.wait()

    async def collect(self) -> List[int]:
        """Await the full generation as a token list."""
        return [t async for t in self]

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block (sync) for the full generation.  On TimeoutError no
        tokens are lost: whatever was drained is kept and a later
        result() call returns the COMPLETE list from the start."""
        deadline = None if timeout is None else time.monotonic() + timeout
        out = self._partial  # resume whatever an earlier timeout drained
        while True:
            ev = threading.Event()
            kind, val = self._pop_or_register((None, ev.set))
            if kind == "tok":
                out.append(val)
            elif kind == "end":
                if val is not None:
                    raise val
                self._partial = []
                return list(out)
            else:
                remain = None if deadline is None \
                    else deadline - time.monotonic()
                if remain is not None and remain <= 0:
                    raise TimeoutError(
                        f"request {self.request_id} still generating "
                        f"after {timeout}s")
                ev.wait(remain)


# The width of a prefill chunk where the caller names none: the chip's
# ridge.  A chunk's matmuls read every weight once whatever its width,
# and in bf16 a parameter is 2 bytes read and 2 FLOPs a token, so a call
# is bound by the weights' bytes below `peak FLOP/s / peak B/s` tokens
# (a v5e: 197e12 / 819e9 = 240) and by compute past it: up to there a
# wider chunk costs little more than a narrow one, and a prompt costs
# its count of chunks.  256 is the next multiple of every page size in
# use (16, 64).  Measured on a v5e (PERF.md section 5, PR 33).
_RIDGE_CHUNK = 256


# The worker loop's phases, named by what the chip is doing meanwhile.
# The loop is always in exactly one (GenerationEngine._phase), so over
# any interval their times sum to the thread's wall time.
LOOP_PHASES = ("idle", "commands", "sweep", "admit", "prefill_dispatch",
               "tick_dispatch", "device_wait", "emit")
# ...and each but `idle` is a region of that name in a profiler capture.
_PHASE_ANNOTATION = {p: "engine." + p for p in LOOP_PHASES if p != "idle"}


@dataclasses.dataclass
class EngineStats:
    queue_depth: int
    active_slots: int
    num_slots: int
    tokens_generated: int
    requests_completed: int
    requests_rejected: int
    requests_cancelled: int
    tokens_per_sec: float
    uptime_s: float
    page_size: int = 0
    kv_blocks_total: int = 0
    kv_blocks_free: int = 0
    prefix_cache_hits: int = 0
    prefix_cache_misses: int = 0
    prefix_hit_tokens: int = 0
    spec_drafted_tokens: int = 0
    spec_accepted_tokens: int = 0
    kv_t1_pages: int = 0
    kv_t2_pages: int = 0
    kv_demotions: int = 0
    kv_promotions: int = 0
    session_resurrections: int = 0
    # Where the worker loop's time went, cumulative seconds by phase
    # (LOOP_PHASES): over any interval the eight differences sum to the
    # thread's wall time.
    loop_s_idle: float = 0.0
    loop_s_commands: float = 0.0
    loop_s_sweep: float = 0.0
    loop_s_admit: float = 0.0
    loop_s_prefill_dispatch: float = 0.0
    loop_s_tick_dispatch: float = 0.0
    loop_s_device_wait: float = 0.0
    loop_s_emit: float = 0.0
    loop_turns: int = 0               # loop turns that left the idle wait
    loop_turns_with_chunk: int = 0    # ...that dispatched a prefill chunk
    token_gaps: int = 0               # tokens out that are no request's first
    token_gaps_stalled: int = 0       # ...out of a turn that swept pages,
    #                                   ran a command or compiled
    # Calls a flush made to wake readers: one into each event loop with
    # a waiting reader + one a waiting sync reader.  `tokens_generated`
    # over it is the rows one wake carries (~ the rows of a full batch
    # streaming to one loop; 1 with a single reader).
    stream_wakes: int = 0
    kv_sweeps: int = 0                # sweeps/demotions that moved >= 1 page
    kv_sweep_s: float = 0.0           # ...and the loop time they took
    # The host half of a demotion runs on the lander thread
    # (kv_tier.PageLander); the loop's part above is the dispatch.
    kv_pages_landed: int = 0          # pages it landed, committed to a tier
    kv_land_s: float = 0.0            # its busy seconds
    kv_land_wait_s: float = 0.0       # seconds the LOOP waited on it:
    #                                   back-pressure, flush, forced sweep
    kv_land_lost: int = 0             # pages that found nowhere to land
    #                                   (their nodes were dropped)
    kv_inflight_matches: int = 0      # matches cut short at a node whose
    #                                   bytes were still landing
    jit_compiles: int = 0             # process-wide (jax_utils listener)
    jit_compile_s: float = 0.0
    # What attention reads against what a row holds, summed over decode
    # rows, ticks and attention layers: their ratio is what page
    # selection saves (1 for a model that attends to all it holds).
    attn_keys_attended: int = 0
    attn_keys_resident: int = 0
    # ...and the columns the same calls pulled from the pool to read
    # them: every row of the call, whole spans up to the deepest row
    # (decode.paged_chunk_step); over `resident`, what a tick gathers
    # for each key its rows hold.
    attn_keys_gathered: int = 0
    # ...and the tokens those rows have SEEN, times the layers that
    # attend: `resident` over it is the share of its context a row still
    # holds (1 unless some layer forgets: a window layer's ring).
    attn_keys_context: int = 0
    prefill_tokens: int = 0           # prompt tokens run through prefill
    prefill_pad_tokens: int = 0       # ...and the columns those chunks
    #                                   computed that held no prompt token
    prefill_tokens_sparse: int = 0    # ...in chunks that selected pages
    state_resets: int = 0             # per-row recurrent states zeroed
    # Bytes of the cache that are state per decode row (the entries the
    # model names in ROW_STATE_KEYS): resident whatever the traffic, and
    # in no page count.  0 for a model whose pages are all its state.
    row_state_bytes: int = 0
    # A model that routes tokens to experts counts on the device, inside
    # its cache (its `read_counters`): (token, expert) pairs routed and
    # those whose expert this replica holds, summed over live rows,
    # real prompt tokens and expert layers; held experts that got a
    # token / held experts, per tick and expert layer; the busiest held
    # expert's tokens and the mean over the held, per call and layer.
    # As of the last decode tick.  Zeros for a model without experts.
    moe_pairs_routed: int = 0
    moe_pairs_local: int = 0
    moe_experts_touched: int = 0
    moe_experts_held: int = 0
    moe_load_max: int = 0
    moe_load_mean: float = 0.0

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _span_for(req: "_Request", name: str, t0_mono: float,
              dur_s: float, args: Optional[Dict] = None) -> None:
    """One engine-stage span linked into the REQUEST's trace (captured
    at submit() — the engine worker thread has no contextvar context of
    its own).  t0 is monotonic (the engine's clock); re-anchored to the
    epoch so the span aligns with every other process's events."""
    if not _tracing.enabled():
        return
    tr = req.trace
    link = None
    if tr is not None:
        link = {"trace_id": tr["trace_id"],
                "span_id": _tracing.fresh_id(),
                "parent_id": tr.get("parent_id")}
    _tracing.record("engine", name,
                    time.time() - (time.monotonic() - t0_mono),
                    dur_s, trace=link, args=args)


class _Request:
    __slots__ = ("id", "prompt", "max_new_tokens", "temperature",
                 "top_k", "eos_token", "rng", "stream", "submit_t",
                 "first_token_t", "last_token_t", "emitted", "n_blocks",
                 "pages", "tokens", "prefix_hit_tokens", "ngram_map",
                 "ngram_upto", "trace", "session")

    def __init__(self, rid, prompt, max_new_tokens, temperature, top_k,
                 eos_token, seed, n_blocks, session=None,
                 rng_state=None):
        self.id = rid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.eos_token = eos_token
        self.session = session   # durable-session id (None = ephemeral)
        self.rng = np.random.default_rng(seed) if temperature > 0 else None
        if self.rng is not None and rng_state is not None:
            # Resurrected sampled session: continue the EXACT random
            # stream the checkpoint froze, so the continuation draws
            # what the original replica would have drawn.
            try:
                self.rng.bit_generator.state = rng_state
            except (TypeError, ValueError, KeyError):
                logger.warning("request %s: stale sampler state "
                               "ignored; reseeding", rid)
        self.stream = TokenStream(rid)
        self.submit_t = time.monotonic()
        self.first_token_t: Optional[float] = None
        self.last_token_t: Optional[float] = None
        self.emitted = 0
        self.n_blocks = n_blocks     # worst-case page reservation
        self.pages: List[int] = []   # held pages (shared prefix + own)
        self.tokens: List[int] = []  # prompt + produced (draft source)
        self.prefix_hit_tokens = 0
        self.ngram_map: Dict = {}    # trailing-ngram -> latest end pos
        self.ngram_upto = 0          # positions indexed so far
        # The submitter's span context: TTFT-stage spans (queue /
        # prefill / first_tick) recorded on the engine worker thread
        # link under the serve request's trace.
        self.trace = _tracing.current_dict()


class _PrefillState:
    __slots__ = ("req", "slot", "next_start", "bt_row", "t0", "chunks",
                 "sparse_chunks")

    def __init__(self, req: _Request, slot: int, start: int, bt_row):
        self.req = req
        self.slot = slot
        self.next_start = start
        self.t0 = time.monotonic()   # prefill-stage span start
        self.chunks = 0
        self.sparse_chunks = 0       # chunks that attended to chosen pages
        # The row's block table stays PRIVATE until activation: the
        # fused tick scatters a garbage write for every inactive batch
        # row, and the engine-wide table must keep pointing those rows
        # at the trash page — never at this request's (possibly shared)
        # pages.
        self.bt_row = bt_row


def _lookup_draft(req: "_Request", ngram: int, k: int) -> List[int]:
    """Prompt-lookup draft (host twin of decode's speculative lookup):
    the tokens that followed the most recent EARLIER occurrence of the
    trailing n-gram, which ends at the pending token.  Returns up to k
    tokens ([] when no earlier occurrence exists — a wrong or short
    draft costs a little verify compute, never correctness).

    The request carries an incrementally maintained ngram -> latest-end
    -position map, so a tick's lookup only indexes the tokens appended
    since the last tick (amortized O(1) per generated token) instead of
    rescanning the whole history — the no-match case on non-repetitive
    text is the common one, and it sits on the tick hot path."""
    tokens = req.tokens
    n = len(tokens)
    if n < ngram + 1:
        return []
    # Index windows ENDING at positions [ngram-1, n-2]: the window at
    # n-1 ends at the pending token and must stay out of the map (a
    # draft may only come from a strictly earlier occurrence).
    for p in range(max(req.ngram_upto, ngram - 1), n - 1):
        req.ngram_map[tuple(tokens[p - ngram + 1:p + 1])] = p
    req.ngram_upto = n - 1
    j = req.ngram_map.get(tuple(tokens[n - ngram:]))
    if j is None:
        return []
    return tokens[j + 1:j + 1 + k]


@functools.partial(jax.jit, static_argnames=("cfg", "with_logits"),
                   donate_argnames=("cache",))
def _paged_tick(params, token, pos, cache, block_tables, cfg,
                with_logits):
    """One paged decode_step across every row (per-row positions) +
    on-device greedy argmax; logits ride back to host only when a
    sampled-mode request is active."""
    logits, cache = decode.paged_decode_step(params, token, pos, cache,
                                             block_tables, cfg)
    sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return sampled, (logits if with_logits else None), cache


@functools.partial(jax.jit, static_argnames=("cfg", "with_logits"),
                   donate_argnames=("cache",))
def _paged_verify(params, chunk, pos, cache, block_tables, cfg,
                  with_logits):
    """Fused speculative tick: each row's (pending token + k draft
    tokens) scored in one paged_chunk_step.  preds[b, i] is the greedy
    next token after row b's chunk prefix 0..i; sampling rows read only
    their position-0 logits (their draft columns are dead weight,
    overwritten before any unmasked read)."""
    logits, cache = decode.paged_chunk_step(params, chunk, pos, cache,
                                            block_tables, cfg)
    preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return preds, (logits[:, 0] if with_logits else None), cache


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("cache",))
def _prefill_chunk(params, tokens, pos, cache, block_table, cfg,
                   slot=None, valid=None):
    """One single-row prefill chunk.  `slot` and `valid` are passed only
    for a model with per-row state (decode.has_row_state): the decode
    row whose state the chunk carries, and how many of the chunk's
    tokens are real; left out they mean row 0 and the full width."""
    row = {} if slot is None and valid is None \
        else {"slot": slot, "valid": valid}
    return decode.paged_chunk_step(params, tokens, pos, cache,
                                   block_table, cfg, **row)


def _host_sample(row_logits: np.ndarray, temperature: float, top_k: int,
                 rng: np.random.Generator) -> int:
    """Temperature/top-k sampling on host from one row's fp32 logits."""
    logits = row_logits.astype(np.float64) / max(temperature, 1e-6)
    top_k = min(top_k, len(logits))  # a huge k means "no restriction"
    if top_k > 0:
        kth = np.sort(logits)[-top_k]
        logits = np.where(logits < kth, -np.inf, logits)
    logits -= logits.max()
    probs = np.exp(logits)
    probs /= probs.sum()
    return int(rng.choice(len(probs), p=probs))


class GenerationEngine:
    """Continuous-batching generation over a paged KV pool.

    Knobs:
      num_slots        decode batch width B (rows recycled on finish)
      max_seq          per-request bound: prompt + max_new_tokens <= it
      page_size        KV page width in tokens (page_size >= max_seq
                       degenerates to the old one-slot-per-request
                       layout — the bench's "slot mode" baseline)
      kv_pages         allocatable pages in the pool (default:
                       num_slots * ceil(max_seq / page_size) — equal
                       memory to the old contiguous slot pool)
      enable_prefix_cache  share full prompt pages between requests via
                       the radix cache (prefill skipped for shared pages)
      speculate_k / speculate_ngram
                       >0 enables in-engine prompt-lookup speculative
                       decoding for greedy rows (fused verify tick)
      prefill_chunk    tokens of prompt prefilled per engine tick, the
                       width of the ONE prefill program an engine
                       compiles (a prompt's last chunk is padded to
                       it).  None (the default) is the chip's ridge
                       width, _RIDGE_CHUNK = 256 tokens: a call reads
                       every weight once, so up to there a wider chunk
                       costs little more and a prompt costs its count
                       of chunks.  Either is clipped to the table's
                       width, and `engine.prefill_chunk` is the int in
                       force.  Only a chunk that would run past the
                       table's end (start > table width - prefill_chunk:
                       column 3,840 of 4,096 at the default) is
                       narrowed to what is left, since paged_chunk_step
                       clips writes past the table into the row's last
                       block; that narrower program is compiled when a
                       prompt first reaches it, not at start-up.
      max_queue_len    admission-queue cap; past it submit() raises
                       EngineOverloadedError(reason="queue_full")
      kv_commit_factor submit() bounds OUTSTANDING worst-case page
                       demand (waiting + resident) at factor*kv_pages;
                       past it submit() raises
                       EngineOverloadedError(reason="kv_exhausted")
      name             metrics tag value

    `submit()` may be called from any thread / event loop; the returned
    TokenStream is consumable sync or async.  `start()` is implicit on
    first submit; `stop()` fails outstanding work and joins the worker.
    """

    def __init__(self, params, cfg, *, num_slots: int = 4,
                 max_seq: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 max_queue_len: int = 64,
                 default_max_new_tokens: int = 64,
                 name: str = "default",
                 page_size: int = 16, kv_pages: Optional[int] = None,
                 enable_prefix_cache: bool = True,
                 speculate_k: int = 0, speculate_ngram: int = 3,
                 kv_commit_factor: float = 4.0,
                 kv_tiering: Optional[bool] = None,
                 kv_store_dir: Optional[str] = None):
        # A start's spans (engine.build here, engine.warm on the worker
        # thread, which has no contextvar of its own) link under
        # whoever constructs the engine, as a request's do.
        t_build = time.time()
        self._start_trace = _tracing.current_dict()
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if prefill_chunk is None:
            prefill_chunk = _RIDGE_CHUNK
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if speculate_k < 0:
            raise ValueError("speculate_k must be >= 0")
        if speculate_k and speculate_ngram < 1:
            raise ValueError("speculate_ngram must be >= 1 when "
                             "speculate_k is set")
        self._model = decode.paged_model(cfg)
        if self._model is None and getattr(cfg, "n_experts", 0):
            raise NotImplementedError(
                "continuous batching runs the dense body for dense models "
                "only (it has no expert layer; a model that routes brings "
                "its own paged step)")
        self._row_state = decode.has_row_state(cfg)
        if enable_prefix_cache:
            refuse_row_state(cfg, "the prefix cache "
                                  "(enable_prefix_cache=True)")
        if kv_tiering:
            refuse_unframed(cfg, "KV tiering (kv_tiering=True)")
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_seq = int(max_seq or cfg.max_seq)
        self.page_size = int(page_size)
        self.speculate_k = int(speculate_k)
        self.speculate_ngram = int(speculate_ngram)
        # Speculation writes up to k tokens past a row's position before
        # acceptance is known; the reservation slack keeps those writes
        # inside the row's own pages (a write clipped to the trash page
        # would LOSE accepted K/V).  +1 mirrors generate()'s slack.
        self._slack = self.speculate_k + 1 if self.speculate_k else 0
        self._max_blocks = -(-(self.max_seq + self._slack)
                             // self.page_size)
        self._s_virt = self._max_blocks * self.page_size
        # Default sizing includes the speculation slack: every request
        # the max_seq check admits must also fit the pool (a max-length
        # request reserves _max_blocks pages).
        self.kv_pages = int(kv_pages if kv_pages is not None
                            else num_slots * self._max_blocks)
        if self.kv_pages < 1:
            raise ValueError("kv_pages must be >= 1")
        self.prefill_chunk = min(prefill_chunk, self._s_virt)
        if self._model is not None:
            self._model.check_paging(cfg, page_size=self.page_size,
                                     prefill_chunk=self.prefill_chunk,
                                     speculate_k=self.speculate_k)
        self.default_max_new_tokens = default_max_new_tokens
        self.name = name
        # With kv_commit_factor >= 1 a lone request always fits the cap
        # (its n_blocks is bounded by kv_pages via the submit check).
        self._commit_cap = max(1, int(kv_commit_factor * self.kv_pages))

        self._scheduler = FCFSScheduler(max_queue_len)
        self._cond = locksan.make_condition("GenerationEngine._cond")
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._started_t = time.monotonic()
        # Worker-thread command queue (run_on_worker): KV export/import
        # and other paging surgery run BETWEEN ticks on the one thread
        # that owns the device + paging state — the single-owner
        # discipline stays intact and a migration can never stall a
        # tick mid-dispatch.
        self._commands: collections.deque = collections.deque()

        # Device + paging state (worker-thread-owned after start).
        # Page 0 is the trash page: every inactive row's block table
        # points at it, so the fused tick's scatter writes land there.
        self._cache = decode.init_paged_cache(
            cfg, self.kv_pages + 1, self.page_size, num_slots)
        # Columns one span of a tick's attention gathers for a row (the
        # dense body's; attn_keys_gathered counts with it).
        self._tick_span = None if self._model is not None else (
            self.page_size * decode.paged_span_blocks(
                self._cache, num_slots, self._max_blocks))
        self._alloc = BlockAllocator(self.kv_pages, first_page=1)
        self._prefix = (RadixPrefixCache(
            self.page_size, self._alloc,
            digest_depth=_cfg.serve_affinity_digest_depth)
            if enable_prefix_cache else None)
        # --- KV tier hierarchy (T0 pool / T1 host arena / T2 store) ---
        # One page's at-rest frame: K then V bytes of [L, psz, Hkv, Dh].
        self._page_dtype = np.dtype(cfg.dtype)
        # (Of the dense pool only: a model that declares its own cache
        # has no frame yet, and every path that would build one refuses
        # it by name.)
        self._page_kshape = (cfg.n_layers, self.page_size,
                             decode._kv_heads(cfg), cfg.head_dim)
        self._page_k_nbytes = (int(np.prod(self._page_kshape))
                               * self._page_dtype.itemsize)
        self._page_nbytes = 2 * self._page_k_nbytes
        self._tiering = bool(_cfg.serve_kv_tiering
                             if kv_tiering is None else kv_tiering) \
            and enable_prefix_cache and decode.pages_are_kv(cfg)
        self._kv_store_dir = kv_store_dir
        self._arena: Optional[HostKVArena] = None   # lazy (worker)
        self._store: Optional[KVPageStore] = None   # lazy (worker)
        # The host half of a demotion runs on a lander thread, born at
        # the first demotion (never for an engine without tiers); what
        # it landed waits in _landed for the worker to commit.
        self._lander: Optional[PageLander] = None
        self._landed: collections.deque = collections.deque()
        self._inflight_cap = 0      # bytes; derived with the lander
        self._pages_landed = 0
        self._land_lost = 0
        self._land_wait_s = 0.0
        self._land_s_closed = 0.0   # busy seconds of landers now closed
        self._inflight_matches = 0
        self._last_sweep = time.monotonic()
        self._last_store_gc = time.monotonic()
        self._demotions = 0
        self._promotions = 0
        self._resurrections = 0
        # Racy-read hint for submit()'s Retry-After and load_info's
        # reclaimable gauge: pool pages a pressure demotion could free
        # (tree-only T0 pages).  Worker thread refreshes it with the
        # gauges; readers tolerate staleness.
        self._demotable_hint = 0
        if self._prefix is not None:
            self._prefix.release_payload = self._release_tier_payload

        self._block_tables = np.zeros((num_slots, self._max_blocks),
                                      np.int32)
        self._pos = np.zeros((num_slots,), np.int32)
        self._tok = np.zeros((num_slots,), np.int32)
        self._slots: List[Optional[_Request]] = [None] * num_slots
        self._prefill: Optional[_PrefillState] = None

        # Counters (worker thread writes; stats() reads).
        self._tokens_generated = 0
        self._completed = 0
        self._rejected = 0
        self._cancelled = 0
        self._committed_blocks = 0   # outstanding worst-case demand
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_hit_tokens = 0
        self._spec_drafted = 0
        self._spec_accepted = 0
        self._win_t = time.monotonic()
        self._win_tokens = 0
        # What an emit phase leaves for its one flush (_flush_emits;
        # worker thread only): the wake-ups its pushes and finishes took
        # from their streams, and the books of its tokens.
        self._wakes: List = []
        self._stream_wakes = 0
        self._emit_tokens = 0
        self._emit_gaps: List[float] = []
        # Recent per-request TTFT samples (bounded ring, worker thread
        # appends) backing the ttft_p99_s gauge in load_info — the SLO
        # attainment signal the autopilot broker arbitrates on.
        self._recent_ttft = collections.deque(maxlen=256)
        # Loop-time accounting (worker thread writes; stats() reads).
        self._loop_s = dict.fromkeys(LOOP_PHASES, 0.0)
        self._phase_name = "idle"
        self._phase_t: Optional[float] = None   # None: loop not running
        self._phase_ann = None        # the open profiler annotation
        self._turns = 0
        self._turns_with_chunk = 0
        self._token_gaps = 0
        self._token_gaps_stalled = 0
        self._turn_gaps = 0           # gaps emitted in the running turn
        self._turn_stalled = False    # ...which swept or ran a command
        self._kv_sweeps = 0
        self._kv_sweep_s = 0.0
        self._sweep: Optional[Dict] = None   # the running sweep's account
        self._keys_attended = 0
        self._keys_resident = 0
        self._keys_gathered = 0
        self._keys_context = 0
        self._prefill_tokens = 0
        self._prefill_pad_tokens = 0
        self._prefill_tokens_sparse = 0
        self._state_resets = 0
        self._row_state_bytes = sum(
            int(self._cache[k].nbytes)
            for k in getattr(self._model, "ROW_STATE_KEYS", ()))
        # A routing model's device-side counters, as last fetched by
        # the worker thread (stats() must not touch a cache that every
        # step donates).
        self._read_model_counters = getattr(self._model, "read_counters",
                                            None)
        self._model_counters: Dict[str, Any] = {}
        _jax_utils.install_compile_listener()

        self._tags = {"engine": name}
        self._ttft_hist = TTFT_HISTOGRAM.series(self._tags)
        self._itl_hist = ITL_HISTOGRAM.series(self._tags)
        self._tokens_counter = TOKENS_COUNTER.series(self._tags)
        self._throughput_gauge = THROUGHPUT_GAUGE.series(self._tags)
        QUEUE_GAUGE.set(0, tags=self._tags)
        OCCUPANCY_GAUGE.set(0.0, tags=self._tags)
        KV_BLOCKS_TOTAL_GAUGE.set(self.kv_pages, tags=self._tags)
        KV_BLOCKS_FREE_GAUGE.set(self.kv_pages, tags=self._tags)
        _tracing.start_record(
            "engine", "engine.build", t_build, time.time(),
            trace=self._start_link(),
            args={"cache_bytes": _jax_utils.tree_nbytes(self._cache)})

    def _start_link(self, parent_id: Optional[str] = None):
        """Linkage of one span of this engine's start: a child of the
        constructor's caller, or of `parent_id`."""
        tr = self._start_trace
        if tr is None:
            return None
        return {"trace_id": tr["trace_id"], "span_id": _tracing.fresh_id(),
                "parent_id": parent_id or tr.get("parent_id")}

    # ------------------------------------------------------------------
    # Public API

    def start(self):
        with self._cond:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name=f"llm-engine-{self.name}",
                daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 30.0):
        """Stop the worker; outstanding requests fail with
        RuntimeError("engine stopped")."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        err = RuntimeError("engine stopped")
        with self._cond:
            leftovers = self._scheduler.drain()
            if self._prefill is not None:
                leftovers.append(self._prefill.req)
                self._prefill = None
            self._committed_blocks = 0
            commands, self._commands = \
                list(self._commands), collections.deque()
            QUEUE_GAUGE.set(0, tags=self._tags)
        for _fn, fut in commands:
            if not fut.done():
                fut.set_exception(RuntimeError("engine stopped"))
        # This thread's own batch, fired once and left out of
        # `stream_wakes`: a worker that outlived the join still owns the
        # engine's batch and its counters.
        wakes: List = []
        for req in leftovers:
            req.stream._finish(err, wakes)
        wedged = t is not None and t.is_alive()
        if not wedged:
            for s, req in enumerate(self._slots):
                if req is not None:
                    req.stream._finish(err, wakes)
                    self._slots[s] = None
        _fire_wakeups(wakes)
        if wedged:
            # join() timed out: the worker is wedged mid-tick and still
            # OWNS the slot table, cache, and paging state.  Mutating
            # them from here would race a live thread (found by
            # RTC101); it will see _stop and exit on its own — leave
            # its state alone.
            logger.warning(
                "engine %s worker did not exit within %.1fs; leaving "
                "slot/paging state for it to tear down", self.name,
                timeout)
            return
        # Pages still landing reach their tier (a store page outlives
        # the engine) before the arena under the lander is closed.
        lander, self._lander = self._lander, None
        if lander is not None:
            lander.close()
            self._land_s_closed += lander.busy_s
        self._reset_paging()
        OCCUPANCY_GAUGE.set(0.0, tags=self._tags)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _blocks_for(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new + self._slack) // self.page_size)

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0,
               eos_token: Optional[int] = None, seed: int = 0,
               request_id: Optional[str] = None,
               session_id: Optional[str] = None,
               rng_state: Optional[Dict] = None) -> TokenStream:
        """Queue one prompt; returns its TokenStream immediately.

        Raises EngineOverloadedError when admission is saturated —
        reason "queue_full" (waiting line at max_queue_len) or
        "kv_exhausted" (outstanding worst-case KV page demand past the
        commit cap) — and ValueError for prompts the pool can never
        hold."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if session_id is not None:
            refuse_unframed(self.cfg, "a durable session checkpoint")
        max_new = int(self.default_max_new_tokens
                      if max_new_tokens is None else max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if len(prompt) + max_new > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) "
                f"exceeds the engine's max_seq={self.max_seq}")
        n_blocks = self._blocks_for(len(prompt), max_new)
        if n_blocks > self.kv_pages:
            raise ValueError(
                f"request needs {n_blocks} KV pages of {self.page_size} "
                f"tokens; the pool only has {self.kv_pages}")
        # Sampling knobs are validated HERE, the single entry point: a
        # bad value surfacing later, inside the worker tick, would fail
        # every co-resident request (_fail_all), not just this one.
        temperature = float(temperature)
        top_k = int(top_k)
        if not np.isfinite(temperature) or temperature < 0:
            raise ValueError(f"temperature must be finite and >= 0, "
                             f"got {temperature}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        req = _Request(request_id or uuid.uuid4().hex[:12], prompt,
                       max_new, temperature, top_k, eos_token, seed,
                       n_blocks, session=session_id, rng_state=rng_state)
        with self._cond:
            if self._committed_blocks + n_blocks > self._commit_cap:
                self._rejected += 1
                REQUESTS_COUNTER.inc(tags={**self._tags,
                                           "status": "rejected"})
                # Retry hint from config, not a constant — and when the
                # demotion sweeper could free enough cold pages for
                # this request by its next pass, say THAT horizon (the
                # client should come back after one sweep, not after
                # the generic backoff).
                retry = max(0.05, float(_cfg.serve_kv_retry_after_s))
                if self._tiering and self._demotable_hint >= n_blocks:
                    retry = min(retry, max(
                        0.05, float(_cfg.serve_kv_tier_sweep_s)))
                raise EngineOverloadedError(
                    f"KV pool exhausted: {self._committed_blocks} pages "
                    f"of worst-case demand outstanding + {n_blocks} "
                    f"needed exceeds the commit cap "
                    f"({self._commit_cap} = factor * {self.kv_pages} "
                    f"pages); retry later",
                    reason="kv_exhausted", retry_after_s=retry)
            try:
                self._scheduler.enqueue(req)
            except EngineOverloadedError:
                self._rejected += 1
                REQUESTS_COUNTER.inc(tags={**self._tags,
                                           "status": "rejected"})
                raise
            self._committed_blocks += n_blocks
            QUEUE_GAUGE.set(self._scheduler.depth, tags=self._tags)
            self._cond.notify_all()
        self.start()
        return req.stream

    async def generate(self, prompt: Sequence[int], **kw) -> List[int]:
        """submit() + collect(): the whole generation as a list."""
        return await self.submit(prompt, **kw).collect()

    # ------------------------------------------------------------------
    # Worker commands (KV migration surface)

    def run_on_worker(self, fn, timeout: float = 30.0):
        """Run fn() on the engine worker thread between ticks and
        return its result.  The worker owns the device handle and every
        paging structure; a command is how any other thread touches
        them — same single-owner rule the tick itself relies on.
        Blocks the CALLING thread only (never the tick)."""
        import concurrent.futures as _cf
        fut: _cf.Future = _cf.Future()
        with self._cond:
            if self._stop:
                raise RuntimeError("engine stopped")
            self._commands.append((fn, fut))
            self._cond.notify_all()
        self.start()
        return fut.result(timeout)

    def _drain_commands(self):
        while True:
            with self._cond:
                if not self._commands:
                    return
                fn, fut = self._commands.popleft()
            self._phase("commands")
            self._turn_stalled = True
            try:
                res = fn()
            except BaseException as e:  # fail THIS command only
                if not fut.done():
                    fut.set_exception(e)
            else:
                if not fut.done():
                    fut.set_result(res)

    def kv_export(self, tokens: Sequence[int]) -> Optional[Dict]:
        """Worker command: snapshot the K/V pages of `tokens`' longest
        cached full-page prefix, page-major on host — ANY tier.  Pool
        pages are INCREF'd before the device read — an eviction racing
        the migration can drop the radix nodes but never recycle the
        pages under the wire — and stay pinned until
        kv_export_release().  Demoted pages are CRC-verified host
        bytes already and are copied synchronously (nothing to pin; an
        unreadable tier frame truncates the export there).  Returns
        {"pages" (the pinned pool pages only), "matched_tokens", "k",
        "v"} or None when nothing is cached."""
        refuse_unframed(self.cfg, "kv_export")
        if self._prefix is None:
            return None
        tokens = [int(t) for t in tokens]
        self._apply_landings()
        nodes, _ = self._prefix.match_nodes(tokens)
        usable, frames = [], {}
        for n in nodes:
            if n.tier == TIER_POOL:
                usable.append(n)
                continue
            frame = self._tier_frame(n)
            if frame is None:
                break
            frames[id(n)] = frame
            usable.append(n)
        if not usable:
            return None
        pool_pages = [n.page for n in usable if n.tier == TIER_POOL]
        for p in pool_pages:
            self._alloc.incref(p)
        try:
            if pool_pages:
                k0, v0 = decode.paged_read_pages_host(self._cache,
                                                      pool_pages)
            k = np.empty((len(usable),) + self._page_kshape,
                         self._page_dtype)
            v = np.empty_like(k)
            j = 0
            for i, n in enumerate(usable):
                if n.tier == TIER_POOL:
                    k[i], v[i] = k0[j], v0[j]
                    j += 1
                else:
                    k[i], v[i] = split_frame(
                        frames[id(n)], self._page_k_nbytes,
                        self._page_kshape, self._page_kshape,
                        self._page_dtype)
        except BaseException:
            for p in pool_pages:
                self._alloc.decref(p)
            raise
        return {"pages": pool_pages,
                "matched_tokens": len(usable) * self.page_size,
                "k": k, "v": v}

    def kv_export_release(self, pages: Sequence[int]) -> None:
        """Worker command: drop the export pins taken by kv_export —
        called only after the destination sealed (or the migration
        aborted), so the origin's pages outlive the transfer."""
        for p in pages:
            self._alloc.decref(p)
        self._update_kv_gauges()

    def kv_import(self, tokens: Sequence[int], k: np.ndarray,
                  v: np.ndarray) -> int:
        """Worker command: land migrated K/V pages (page-major
        [n, L, page_size, Hkv, Dh] host arrays for tokens' full pages)
        into freshly reserved pool pages and publish them in the radix
        cache.  Pages this replica already holds are skipped; on any
        failure the reservation is released whole — the cache is never
        left referencing a partially written page.  Returns the number
        of pages imported (0 = re-prefill instead)."""
        refuse_unframed(self.cfg, "kv_import")
        if self._prefix is None:
            return 0
        tokens = [int(t) for t in tokens]
        psz = self.page_size
        usable = min(len(k), len(tokens) // psz)
        have, _ = self._prefix.match(tokens, max_tokens=usable * psz)
        start = len(have)
        if start >= usable:
            return 0
        need = usable - start
        got = self._alloc.alloc(need)
        if got is None:
            # Same pressure order as admission: demote cold pages
            # before evicting shared prefixes.
            self._demote_for_pressure(need)
            got = self._alloc.alloc(need)
        if got is None \
                and self._alloc.free_pages + self._prefix.releasable() \
                >= need:
            self._prefix.evict(need)
            got = self._alloc.alloc(need)
        if got is None:
            return 0  # pool too hot to host the import: re-prefill
        try:
            self._cache = decode.paged_write_pages(
                self._cache, jnp.asarray(np.asarray(got, np.int32)),
                jnp.asarray(k[start:usable]),
                jnp.asarray(v[start:usable]))
            self._prefix.insert(tokens[:usable * psz],
                                list(have) + list(got))
        except BaseException:
            for p in got:
                self._alloc.decref(p)
            self._update_kv_gauges()
            raise
        # insert() increfs each NEW node's page; our allocation ref is
        # now redundant — the radix tree is the sole owner, exactly as
        # if these pages had been prefilled and released here.
        for p in got:
            self._alloc.decref(p)
        self._update_kv_gauges()
        return need

    def kv_hot_prefixes(self, top_k: int) -> List[List[int]]:
        """Worker command: token sequences of the hottest cached
        prefixes (drain migration walks these)."""
        if self._prefix is None:
            return []
        return self._prefix.hot_prefixes(top_k)

    # ------------------------------------------------------------------
    # KV memory hierarchy (worker thread owns every method here)

    def _tier_arena(self) -> HostKVArena:
        if self._arena is None:
            self._arena = HostKVArena(
                self._page_nbytes,
                int(_cfg.serve_kv_t1_budget_bytes), name=self.name)
        return self._arena

    def _tier_store(self) -> KVPageStore:
        if self._store is None:
            self._store = KVPageStore(self._kv_store_dir or None)
        return self._store

    def _release_tier_payload(self, payload) -> None:
        """RadixPrefixCache.release_payload hook: hand a T1 slot back
        to the arena when the tree stops owning it.  T2 entries are
        left in the store on purpose (the TTL sweep owns them — their
        persistence is what durable sessions resurrect from)."""
        if payload and payload[0] == "t1" and self._arena is not None:
            self._arena.free(payload[1])

    def _tier_frame(self, node) -> Optional[bytes]:
        """CRC-checked at-rest bytes of a demoted node, or None — a
        MISS: the caller truncates its match there and the chunk is
        re-prefilled (bit-identical by determinism).  A page is never
        imported unverified.  A node whose bytes are still landing is a
        miss too (the match does not wait for the lander; counted in
        kv_inflight_matches): the tail's own prefill then re-publishes
        the page and the landing, when it arrives, is discarded."""
        payload = node.payload
        if payload is None:
            return None
        kind, key, crc, nbytes = payload
        if kind == "fl":
            self._inflight_matches += 1
            return None
        if kind == "t1":
            frame = (self._arena.get(key)
                     if self._arena is not None else None)
        else:
            frame = self._tier_store().get_page(key)
        if frame is None or len(frame) != nbytes \
                or frame_crc(frame) != crc:
            return None
        return frame

    def _frames_to_arrays(self, frames):
        n = len(frames)
        k = np.empty((n,) + self._page_kshape, self._page_dtype)
        v = np.empty_like(k)
        for i, fr in enumerate(frames):
            k[i], v[i] = split_frame(fr, self._page_k_nbytes,
                                     self._page_kshape,
                                     self._page_kshape,
                                     self._page_dtype)
        return k, v

    def _sweep_due(self) -> bool:
        return (self._tiering and self._prefix is not None
                and time.monotonic() - self._last_sweep
                >= max(0.05, float(_cfg.serve_kv_tier_sweep_s)))

    def _maybe_sweep_tiers(self, force: bool = False) -> int:
        """The demotion sweeper: pool pages with no decode tick in
        serve_kv_demote_idle_s move to the host arena (overflow goes
        straight to the store), arena pages idle serve_kv_t2_idle_s
        move to the store, and the store's TTL sweep ages dead entries
        out.  Runs between ticks at serve_kv_tier_sweep_s cadence;
        `force` is the test hook."""
        if not self._tiering or self._prefix is None:
            return 0
        now = time.monotonic()
        if not force and now - self._last_sweep \
                < max(0.05, float(_cfg.serve_kv_tier_sweep_s)):
            return 0
        self._last_sweep = now
        with self._sweeping("idle"):
            moved = self._demote_t0(self._prefix.demote_candidates(
                max(0.0, float(_cfg.serve_kv_demote_idle_s))))
            if force:
                # The caller wants the pages moved, not on their way.
                moved = self._land_drain()
            moved += self._demote_t1(max(0.0,
                                         float(_cfg.serve_kv_t2_idle_s)))
            if self._store is not None \
                    and now - self._last_store_gc >= 60.0:
                # A listing of every file the store holds (1.1 s for
                # 3,000 of them on the chip's host): the lander's.
                self._last_store_gc = now
                self._tier_lander().call(
                    self._store.sweep, float(_cfg.serve_kv_store_ttl_s))
            self._update_kv_gauges()
        return moved

    @contextlib.contextmanager
    def _sweeping(self, cause: str):
        """One demotion pass (`cause`: idle | pressure | flush) under
        the loop's `sweep` phase, whatever phase it interrupts.  The
        demote methods account into `self._sweep`; a pass that moved
        pages (for pool pages: dispatched them on their way) counts in
        kv_sweeps / kv_sweep_s, marks the turn's token gaps as stalled
        and leaves ONE engine.tier_sweep span — at most one per
        serve_kv_tier_sweep_s, so the ring never churns.  A pass that
        found nothing records nothing.  The span is this thread's part;
        the lander's is engine.tier_land."""
        prev = self._phase("sweep")
        acc = self._sweep = {"pages": 0, "to_t1": 0, "to_t2": 0,
                             "read_s": 0.0, "compile_s": 0.0,
                             "frame_s": 0.0, "put_s": 0.0}
        t0 = time.monotonic()
        try:
            yield
        finally:
            dur = time.monotonic() - t0
            self._sweep = None
            self._phase(prev)
            if acc["pages"]:
                self._turn_stalled = True
                self._kv_sweeps += 1
                self._kv_sweep_s += dur
                _tracing.record(
                    "engine", "engine.tier_sweep", time.time() - dur, dur,
                    args={"cause": cause, "pages": acc["pages"],
                          "to_t1": acc["to_t1"], "to_t2": acc["to_t2"],
                          **{k[:-2] + "_ms": round(acc[k] * 1e3, 3)
                             for k in ("read_s", "compile_s", "frame_s",
                                       "put_s")}})

    def _demoted(self, dest: str, frame_s: float, put_s: float) -> None:
        """Account one arena page's move to the store (this thread's
        own: _demote_t1) to the running sweep; `dest` "" when it found
        nowhere to land."""
        acc = self._sweep
        acc["frame_s"] += frame_s
        acc["put_s"] += put_s
        if dest:
            acc["pages"] += 1
            acc["to_" + dest] += 1
            self._demotions += 1
            KV_DEMOTIONS_COUNTER.inc(tags={**self._tags, "to": dest})

    def _demote_t0(self, nodes, arena: bool = True) -> int:
        """Start tree-only pool pages (refcount 1, selected by the
        caller) on their way into the arena — or the store when the
        arena budget is spent, or `arena` is False.  This thread's part
        is a DISPATCH: the gather of their bytes in stacks of one
        compiled shape (decode.paged_read_stack), a reserved
        arena slot or a store fingerprint for each page, and the pool
        page's release — the device runs programs in order, so a later
        step that reuses the page writes after the gather has read it.
        The nodes are then IN FLIGHT (paging.TIER_FLIGHT); the lander
        thread copies, checks and stores the bytes and
        _apply_landings commits each node to its tier, or drops a node
        whose bytes found nowhere to land.  Device stacks awaiting
        their copy are bounded (_inflight_cap): past it this thread
        waits on the lander here, inside the `sweep` phase.  Returns
        the pages dispatched."""
        if not nodes:
            return 0
        lander = self._tier_lander()
        acc = self._sweep
        compile_s0 = _jax_utils.compile_counters()[1]
        t0 = time.monotonic()
        size = decode.paged_read_batch(self._cache)
        host = self._tier_arena() if arena else None
        store = None
        for lo in range(0, len(nodes), size):
            part = nodes[lo:lo + size]
            self._land_wait(lander.wait_room, size * self._page_nbytes,
                            self._inflight_cap)
            stack = decode.paged_read_stack(
                self._cache, [n.page for n in part])
            entries = []
            for node in part:
                slot = host.reserve() if host is not None else None
                fp = None
                if slot is None:
                    fp = self._prefix.path_fp(node)
                    store = self._tier_store()
                entries.append(
                    (node, self._prefix.begin_demote(node), slot, fp))
                acc["to_t2" if slot is None else "to_t1"] += 1
            acc["pages"] += len(part)
            lander.submit(stack, entries, host, store)
        # read_s holds the program's one compile (the first pass that
        # demotes) and any wait on the lander; compile_s says how much
        # of it the compile was.
        acc["read_s"] += time.monotonic() - t0
        acc["compile_s"] += _jax_utils.compile_counters()[1] - compile_s0
        return len(nodes)

    def _tier_lander(self) -> PageLander:
        if self._lander is None:
            self._lander = PageLander(self.name, self._landed)
            # What may wait on the device for its copy: a quarter of
            # the pool's bytes, and no more than a quarter of what the
            # device says it has free (the CPU says nothing); one stack
            # always fits.
            cap = self.kv_pages * self._page_nbytes // 4
            try:
                ms = next(iter(self._cache["k"].devices())).memory_stats()
                cap = min(cap, (ms["bytes_limit"] - ms["bytes_in_use"]) // 4)
            except (TypeError, KeyError, AttributeError):
                pass
            self._inflight_cap = max(cap, 0)
        return self._lander

    def _land_wait(self, wait, *args) -> None:
        """Wait on the lander (back-pressure, a drain), counted in
        kv_land_wait_s."""
        t0 = time.monotonic()
        wait(*args)
        self._land_wait_s += time.monotonic() - t0

    def _land_drain(self) -> int:
        """Wait until every page in flight has landed, then commit:
        for a caller that needs the result (flush, the forced sweep).
        Returns the pages committed."""
        if self._lander is not None:
            self._land_wait(self._lander.drain)
        return self._apply_landings()

    def _apply_landings(self) -> int:
        """Commit what the lander has landed: each node still holding
        the ticket it was dispatched with moves to the tier its bytes
        reached; one that found nowhere to land is dropped with what
        hangs below it (kv_land_lost); a node evicted or re-published
        while its bytes were landing is left alone and the slot
        reserved for it handed back.  The worker runs this at the top
        of a turn and before every match."""
        if not self._landed:
            return 0
        done = {"t1": 0, "t2": 0}
        while self._landed:
            node, ticket, slot, payload = self._landed.popleft()
            if node.payload is ticket and payload is not None:
                self._prefix.apply_demote(
                    node, TIER_HOST if payload[0] == "t1" else TIER_STORE,
                    payload)
                done[payload[0]] += 1
                continue
            if slot is not None and self._arena is not None:
                self._arena.free(slot)
            if node.payload is ticket:
                self._prefix.drop(node)
                self._land_lost += 1
        moved = 0
        for dest, n in done.items():
            if n:
                moved += n
                KV_DEMOTIONS_COUNTER.inc(n, tags={**self._tags, "to": dest})
        self._demotions += moved
        self._pages_landed += moved
        self._update_tier_gauges()
        return moved

    def _demote_t1(self, min_idle_s: float) -> int:
        """Arena pages idle past min_idle_s move to the store (CRC
        re-verified on the way out; an unreadable slot is skipped and
        the promote path treats it as a miss)."""
        if self._arena is None:
            return 0
        moved = 0
        for node in self._prefix.demote_candidates(min_idle_s,
                                                   tier=TIER_HOST):
            _, slot, crc, nbytes = node.payload
            t0 = time.monotonic()
            frame = self._arena.get(slot)
            ok = frame is not None and frame_crc(frame) == crc
            t1 = time.monotonic()
            if ok:
                fp = self._prefix.path_fp(node)
                ok = self._tier_store().put_page(fp, frame)
            if ok:
                self._prefix.apply_demote(node, TIER_STORE,
                                          ("t2", fp, crc, nbytes))
            self._demoted("t2" if ok else "", t1 - t0,
                          time.monotonic() - t1)
            moved += bool(ok)
        return moved

    def _demote_for_pressure(self, need: int) -> int:
        """Admission under memory pressure prefers DEMOTING cold
        tree-only pages (their bytes survive in a lower tier and can
        be promoted back) over EVICTING shared prefixes (their bytes
        are gone).  min_idle 0: under pressure anything tree-only is
        fair game, coldest first.  The pages are free when this
        returns — their bytes are on their way (_demote_t0) — so the
        admission that called goes on after a dispatch."""
        if not self._tiering or self._prefix is None:
            return 0
        short = need - self._alloc.free_pages
        if short <= 0:
            return 0
        with self._sweeping("pressure"):
            return self._demote_t0(
                self._prefix.demote_candidates(0.0, limit=short))

    def kv_flush_to_store(self) -> int:
        """Worker command: demote EVERY demotable page — tree-only
        pool pages and all arena slots — straight to the store.  The
        drain/teardown path: a dying replica demotes instead of
        dropping, so its sessions resurrect anywhere from T2.  Returns
        only when every page in flight has landed (or been counted
        lost): this caller needs durability, so it waits for the
        lander."""
        if not self._tiering or self._prefix is None:
            return 0
        with self._sweeping("flush"):
            self._demote_t0(self._prefix.demote_candidates(0.0),
                            arena=False)
            flushed = self._land_drain()
            flushed += self._demote_t1(0.0)
            self._update_kv_gauges()
        return flushed

    # ------------------------------------------------------------------
    # Durable sessions (store-backed checkpoint / resurrect)

    def _maybe_checkpoint_session(self, req: _Request) -> None:
        """Worker thread, called BEFORE the request's pages are
        released: publish the session's full K/V pages into the radix
        tree (the tiering sweeper then owns their cooling toward the
        store) and write the session manifest — token history plus
        sampler RNG state — to the store.  The manifest is what lets
        ANY replica resurrect the conversation: pages rejoin from the
        store by fingerprint or by re-prefill, both bit-identical."""
        if not self._tiering or req.session is None:
            return
        psz = self.page_size
        if req.tokens:
            toks = list(req.tokens)
            # The LAST sampled token was never fed back through a tick,
            # so its K/V was never written — only positions
            # [0, len(toks)-2] hold state.
            full = max(0, (len(toks) - 1) // psz)
        else:
            toks = [int(t) for t in req.prompt]
            full = len(toks) // psz   # prefill covered every position
        full = min(full, len(req.pages))
        try:
            if full and self._prefix is not None:
                self._prefix.insert(toks[:full * psz],
                                    req.pages[:full])
            man = {"tokens": [int(t) for t in toks],
                   "t": time.time(), "engine": self.name}
            if req.rng is not None:
                man["rng_state"] = req.rng.bit_generator.state
            self._tier_store().put_session(req.session, man)
        except Exception:
            # A failed checkpoint degrades durability, never the
            # request (its stream already has every token).
            logger.exception("engine %s: session %s checkpoint failed",
                             self.name, req.session)

    def session_resurrect(self, session_id: str,
                          tokens: Optional[Sequence[int]] = None
                          ) -> Optional[Dict]:
        """Worker command: restore a durable session from the store.

        Loads the manifest, then imports whatever store pages the
        local radix tree does not already cover (per-page CRC gate: an
        unreadable page stops the import there and the tail
        re-prefills — deterministic prefill makes the fallback exact,
        so resurrection never trades parity for durability).  Returns
        {"tokens", "rng_state", "imported", "cached_pages"} or None
        when no manifest exists."""
        refuse_unframed(self.cfg, "session_resurrect")
        if not self._tiering or self._prefix is None:
            return None
        man = self._tier_store().get_session(session_id)
        if man is None:
            return None
        toks = [int(t) for t in (tokens if tokens is not None
                                 else man.get("tokens") or [])]
        psz = self.page_size
        usable = len(toks) // psz
        self._apply_landings()
        nodes, _ = self._prefix.match_nodes(toks)
        depth_lo = len(nodes)
        imported = 0
        if depth_lo < usable:
            fps = prefix_fingerprints(toks, psz, usable)
            frames = []
            store = self._tier_store()
            for d in range(depth_lo, usable):
                frame = store.get_page(fps[d])
                if frame is None or len(frame) != self._page_nbytes:
                    break
                frames.append(frame)
            if frames:
                imported = self._import_store_frames(toks, nodes,
                                                     frames)
        self._resurrections += 1
        RESURRECTIONS_COUNTER.inc(tags=self._tags)
        self._update_kv_gauges()
        return {"tokens": man.get("tokens"),
                "rng_state": man.get("rng_state"),
                "imported": imported,
                "cached_pages": depth_lo}

    def _import_store_frames(self, toks, path_nodes, frames) -> int:
        """Land store frames below an existing (any-tier) matched
        path: reserve pool pages, splice, publish.  Existing path
        nodes pass page=None through insert(), so a demoted ancestor
        keeps its payload instead of adopting garbage."""
        psz = self.page_size
        need = len(frames)
        got = self._alloc.alloc(need)
        if got is None:
            self._demote_for_pressure(need)
            got = self._alloc.alloc(need)
        if got is None \
                and self._alloc.free_pages + self._prefix.releasable() \
                >= need:
            self._prefix.evict(need)
            got = self._alloc.alloc(need)
        if got is None:
            return 0   # pool too hot: resurrect by re-prefill instead
        try:
            k, v = self._frames_to_arrays(frames)
            self._cache = decode.paged_write_pages(
                self._cache, jnp.asarray(np.asarray(got, np.int32)),
                jnp.asarray(k), jnp.asarray(v))
            depth_hi = len(path_nodes) + need
            self._prefix.insert(toks[:depth_hi * psz],
                                [None] * len(path_nodes) + list(got))
        except BaseException:
            for p in got:
                self._alloc.decref(p)
            self._update_kv_gauges()
            raise
        for p in got:
            self._alloc.decref(p)   # the tree's refs own them now
        return need

    def load_info(self) -> Dict[str, int]:
        """The autoscaler's saturation gauges, as plain field reads —
        polled every control-loop tick, so no EngineStats construction
        and no rate-window math on this path."""
        info = {"queue_depth": self._scheduler.depth
                + (1 if self._prefill is not None else 0),
                "active_slots": sum(r is not None for r in self._slots),
                "num_slots": self.num_slots,
                "kv_blocks_total": self.kv_pages,
                "kv_blocks_free": self._alloc.free_pages}
        if self._prefix is not None:
            tn = self._prefix.tier_nodes
            info["kv_tier_pages"] = {"t0": tn[0], "t1": tn[1],
                                     "t2": tn[2]}
            info["kv_demotable"] = self._demotable_hint
            # What admission can ACTUALLY claim: the free list plus
            # everything pressure demotion would surrender.  The
            # autoscaler reads this instead of kv_blocks_free so idle
            # sessions parked in the pool never look like saturation
            # (no phantom scale-ups).
            info["kv_blocks_reclaimable"] = (self._alloc.free_pages
                                             + self._demotable_hint)
        if self._recent_ttft:
            # p99 over the recent ring (snapshot first: the worker
            # thread appends concurrently).
            samples = sorted(self._recent_ttft)
            info["ttft_p99_s"] = samples[
                min(len(samples) - 1, int(len(samples) * 0.99))]
        if self._prefix is not None and _cfg.serve_affinity:
            try:
                # Racy-but-safe read of the worker-owned digest index
                # (best-effort gauge: a poll that loses the race just
                # publishes the previous digest next tick).
                info["kv_digest"] = {
                    "page": self.page_size,
                    "roots": self._prefix.digest(
                        _cfg.serve_affinity_digest_top_k)}
            except RuntimeError:
                pass  # index mutated mid-iteration; skip this sample
        return info

    def stats(self) -> EngineStats:
        now = time.monotonic()
        win = now - self._win_t
        tps = self._win_tokens / win if win > 0.2 else 0.0
        # The phase the loop is in right now has not been added yet (a
        # racy read of two fields: off by one phase switch at most).
        loop_s = dict(self._loop_s)
        t, name = self._phase_t, self._phase_name
        if t is not None:
            loop_s[name] += max(0.0, now - t)
        jit_compiles, jit_compile_s = _jax_utils.compile_counters()
        lander = self._lander
        return EngineStats(
            queue_depth=self._scheduler.depth
            + (1 if self._prefill is not None else 0),
            active_slots=sum(r is not None for r in self._slots),
            num_slots=self.num_slots,
            tokens_generated=self._tokens_generated,
            requests_completed=self._completed,
            requests_rejected=self._rejected,
            requests_cancelled=self._cancelled,
            tokens_per_sec=round(tps, 2),
            uptime_s=round(now - self._started_t, 3),
            page_size=self.page_size,
            kv_blocks_total=self.kv_pages,
            kv_blocks_free=self._alloc.free_pages,
            prefix_cache_hits=self._prefix_hits,
            prefix_cache_misses=self._prefix_misses,
            prefix_hit_tokens=self._prefix_hit_tokens,
            spec_drafted_tokens=self._spec_drafted,
            spec_accepted_tokens=self._spec_accepted,
            kv_t1_pages=(self._prefix.tier_nodes[TIER_HOST]
                         if self._prefix is not None else 0),
            kv_t2_pages=(self._prefix.tier_nodes[TIER_STORE]
                         if self._prefix is not None else 0),
            kv_demotions=self._demotions,
            kv_promotions=self._promotions,
            session_resurrections=self._resurrections,
            **{f"loop_s_{p}": round(v, 6) for p, v in loop_s.items()},
            loop_turns=self._turns,
            loop_turns_with_chunk=self._turns_with_chunk,
            token_gaps=self._token_gaps,
            token_gaps_stalled=self._token_gaps_stalled,
            stream_wakes=self._stream_wakes,
            kv_sweeps=self._kv_sweeps,
            kv_sweep_s=round(self._kv_sweep_s, 6),
            kv_pages_landed=self._pages_landed,
            kv_land_s=round(self._land_s_closed
                            + (lander.busy_s if lander else 0.0), 6),
            kv_land_wait_s=round(self._land_wait_s, 6),
            kv_land_lost=self._land_lost,
            kv_inflight_matches=self._inflight_matches,
            jit_compiles=jit_compiles,
            jit_compile_s=round(jit_compile_s, 6),
            attn_keys_attended=self._keys_attended,
            attn_keys_resident=self._keys_resident,
            attn_keys_gathered=self._keys_gathered,
            attn_keys_context=self._keys_context,
            prefill_tokens=self._prefill_tokens,
            prefill_pad_tokens=self._prefill_pad_tokens,
            prefill_tokens_sparse=self._prefill_tokens_sparse,
            state_resets=self._state_resets,
            row_state_bytes=self._row_state_bytes,
            **{"moe_" + k: v for k, v in self._model_counters.items()})

    # ------------------------------------------------------------------
    # Worker thread

    def _run(self):
        try:
            self._warm_kernels()
        except Exception as e:
            logger.exception("engine %s kernel warmup failed", self.name)
            self._fail_all(e)
        # Loop-time accounting starts with the loop (the warm-up's
        # compiles above are set-up, not a phase of any turn).
        self._phase_name, self._phase_t = "idle", time.monotonic()
        try:
            self._loop()
        finally:
            self._phase("idle")
            self._phase_t = None

    def _loop(self):
        while True:
            with self._cond:
                # The idle wait must ALSO break for a due tier sweep:
                # an engine with no work is exactly the one whose pages
                # are going cold, and sweeps are what move them out of
                # the decode pool.
                while not self._stop and not self._has_work_locked() \
                        and not self._sweep_due():
                    self._phase("idle")
                    self._cond.wait(timeout=0.1)
                if self._stop:
                    return
            compile_s0 = _jax_utils.compile_counters()[1]
            # Commands (KV export/import) run BETWEEN ticks: they own
            # the device + paging state for their duration, and their
            # failures are their caller's, never the batch's.
            self._drain_commands()
            if self._landed:
                self._phase("commands")
                self._apply_landings()
            try:
                self._maybe_sweep_tiers()
                self._admit_one_chunk()
                self._decode_tick()
            except Exception as e:  # engine-level fault: fail fast,
                logger.exception("engine %s tick failed", self.name)
                self._fail_all(e)
            # Close the turn's books.  Its tokens left at its end, so
            # whatever stalled the turn — pages swept or demoted, a
            # command, a compile on any thread — lengthened their gaps.
            self._turns += 1
            if self._turn_gaps and (
                    self._turn_stalled or
                    _jax_utils.compile_counters()[1] != compile_s0):
                self._token_gaps_stalled += self._turn_gaps
            self._turn_gaps = 0
            self._turn_stalled = False

    def _phase(self, name: str) -> str:
        """Close the loop's running phase and open `name` (one of
        LOOP_PHASES); returns the one closed, for a caller that
        interrupts a phase and resumes it.  The elapsed time goes to
        the closed phase's counter, so the phases partition the
        thread's time by construction; no ring event — stats() carries
        the counters.  Each phase but `idle` is also a region
        `engine.<name>` in a profiler capture (tpu_profiler.annotate):
        with no capture running that is one flag check."""
        now = time.monotonic()
        prev = self._phase_name
        self._loop_s[prev] += now - self._phase_t
        self._phase_t = now
        if name != prev:
            if self._phase_ann is not None:
                self._phase_ann.__exit__(None, None, None)
            region = _PHASE_ANNOTATION.get(name)
            self._phase_ann = region and \
                _tpu_profiler.annotate(region).__enter__()
            self._phase_name = name
        return prev

    def _warm_kernels(self):
        """Compile the fused tick kernels at worker startup, against the
        engine's own (still empty) state: every write lands in the trash
        page, so this is free of side effects — and the first real
        request never pays XLA compilation of the decode tick, nor does
        the first DRAFT pay the verify kernel's (it would otherwise land
        mid-generation, a latency spike the bench used to misreport as
        speculation overhead).

        Leaves the start's last spans: engine.warm, and one child a
        program with the seconds its call took (trace, compile or load
        from the persistent cache, dispatch)."""
        programs = []   # (span name, t0, t1)
        t_warm = time.time()
        tok = jnp.zeros((self.num_slots,), jnp.int32)
        pos = jnp.zeros((self.num_slots,), jnp.int32)
        bt = jnp.asarray(self._block_tables)
        t0 = time.time()
        _, _, self._cache = _paged_tick(
            self.params, tok, pos, self._cache, bt, self.cfg,
            with_logits=False)
        programs.append(("engine.warm.tick", t0, time.time()))
        if self.speculate_k:
            chunk = jnp.zeros((self.num_slots, 1 + self.speculate_k),
                              jnp.int32)
            t0 = time.time()
            _, _, self._cache = _paged_verify(
                self.params, chunk, pos, self._cache, bt, self.cfg,
                with_logits=False)
            programs.append(("engine.warm.verify", t0, time.time()))
        # ...and the standard-width prefill chunk (row 0's table is all
        # trash while nothing is admitted).
        t0 = time.time()
        _, self._cache = _prefill_chunk(
            self.params, jnp.zeros((1, self.prefill_chunk), jnp.int32),
            jnp.int32(0), self._cache, bt[:1], self.cfg,
            **self._row_args(0, 0))
        programs.append(("engine.warm.chunk", t0, time.time()))
        t1 = time.time()
        link = self._start_link()
        args = {"programs": len(programs)}
        ready = _tracing.start_noted("init")
        if ready is not None:
            # Readiness does not wait for the warm-up: how long after
            # the replica's constructor returned did it end?
            args["after_ready_s"] = round(max(0.0, t1 - ready[1]), 6)
        _tracing.start_record("engine", "engine.warm", t_warm, t1,
                              trace=link, args=args)
        for name, p0, p1 in programs:
            _tracing.record(
                "engine", name, p0, p1 - p0,
                trace=link and self._start_link(link["span_id"]))

    def _row_args(self, slot: int, valid: int) -> Dict:
        """What a prefill chunk takes beside the dense arguments when the
        model brings its own step: the decode row the request will
        occupy and the count of real tokens in the chunk (a recurrent
        state cannot un-see a pad, and a pad is routed to no expert).
        Nothing otherwise, so the dense models' program is the one it
        always was."""
        if self._model is None:
            return {}
        return {"slot": jnp.int32(slot), "valid": jnp.int32(valid)}

    def _has_work_locked(self) -> bool:
        return (self._scheduler.depth > 0 or self._prefill is not None
                or bool(self._commands) or bool(self._landed)
                or any(r is not None for r in self._slots))

    def _free_slot(self) -> Optional[int]:
        reserved = self._prefill.slot if self._prefill else -1
        for s, r in enumerate(self._slots):
            if r is None and s != reserved:
                return s
        return None

    def _release_pages(self, req: _Request):
        for p in req.pages:
            self._alloc.decref(p)
        req.pages = []
        self._update_kv_gauges()

    def _try_reserve(self, req: _Request):
        """Prefix-match + page reservation for one request.  Returns
        (pages, matched_tokens) or None when the pool can't cover the
        request right now (caller requeues and retries after evictions
        free pages).

        Tier-aware: the match walks ALL tiers; demoted nodes on the
        matched path are PROMOTED — their frames are CRC-verified on
        host FIRST (an unreadable frame truncates the match there and
        the tail re-prefills, bit-identical by determinism), then
        spliced into freshly reserved pool pages inside the same
        all-or-nothing reservation that admits the request."""
        L = len(req.prompt)
        matched_nodes: List = []
        promote: List = []   # (node, verified frame) in path order
        if self._prefix is not None:
            # Cap at L-1: at least one prompt token must run through
            # tail prefill — logits come from computation, not cache.
            self._apply_landings()
            nodes, _ = self._prefix.match_nodes(req.prompt,
                                                max_tokens=L - 1)
            for n in nodes:
                if n.tier == TIER_POOL:
                    matched_nodes.append(n)
                    continue
                if not self._tiering:
                    break
                frame = self._tier_frame(n)
                if frame is None:
                    break   # dead payload: re-prefill from here on
                matched_nodes.append(n)
                promote.append((n, frame))
        matched_tok = len(matched_nodes) * self.page_size
        pool_pages = [n.page for n in matched_nodes
                      if n.tier == TIER_POOL]
        # Hold the matched pool pages BEFORE any demotion or eviction
        # can run: evict() may drop their tree nodes, and only our refs
        # keep the pages from being recycled under us.  (The extra ref
        # also makes them ineligible for pressure demotion below.)
        for p in pool_pages:
            self._alloc.incref(p)
        need = req.n_blocks - len(pool_pages)
        got = self._alloc.alloc(need)
        if got is None:
            # Pressure order: demote cold tree-only pages first (their
            # bytes survive in a lower tier), evict shared prefixes
            # only when that still doesn't cover the reservation.
            self._demote_for_pressure(need)
            got = self._alloc.alloc(need)
        if got is None and promote:
            # About to fall back to eviction, which may drop the very
            # tiered leaves queued for promotion (a demoted node holds
            # no pinnable pool page).  Truncate the match at the first
            # demoted node — the tail re-prefills — rather than let
            # promote() run against an orphaned node.
            cut = matched_nodes.index(promote[0][0])
            for n in matched_nodes[cut:]:
                if n.tier == TIER_POOL:
                    self._alloc.decref(n.page)
            matched_nodes = matched_nodes[:cut]
            promote = []
            matched_tok = len(matched_nodes) * self.page_size
            pool_pages = [n.page for n in matched_nodes]
            need = req.n_blocks - len(pool_pages)
        if got is None and self._prefix is not None \
                and self._alloc.free_pages + self._prefix.releasable() \
                >= need:
            # Evict only when reclaim can actually cover the request —
            # an unsatisfiable reservation must not wipe the prefix
            # cache for nothing (the request waits for resident rows to
            # finish instead).
            self._prefix.evict(need)
            got = self._alloc.alloc(need)
        if got is None:
            for p in pool_pages:
                self._alloc.decref(p)
            return None
        if promote:
            try:
                k, v = self._frames_to_arrays([f for _, f in promote])
                landing = got[:len(promote)]
                self._cache = decode.paged_write_pages(
                    self._cache,
                    jnp.asarray(np.asarray(landing, np.int32)),
                    jnp.asarray(k), jnp.asarray(v))
            except BaseException:
                for p in got:
                    self._alloc.decref(p)
                for p in pool_pages:
                    self._alloc.decref(p)
                self._update_kv_gauges()
                raise
            for (node, _), page in reversed(list(zip(promote, landing))):
                # The page's allocation ref becomes the TREE's ref;
                # the request then takes its own, same as a pool hit.
                # (Deepest first: of one path, the deepest node is the
                # first to demote again.)
                self._prefix.promote(node, page)
                self._alloc.incref(page)
            self._promotions += len(promote)
            KV_PROMOTIONS_COUNTER.inc(len(promote), tags=self._tags)
            got = got[len(promote):]
        if matched_tok > 0:
            self._prefix_hits += 1
            self._prefix_hit_tokens += matched_tok
            PREFIX_HITS_COUNTER.inc(tags=self._tags)
        else:
            self._prefix_misses += 1
            PREFIX_MISSES_COUNTER.inc(tags=self._tags)
        req.pages = [n.page for n in matched_nodes] + got
        req.prefix_hit_tokens = matched_tok
        self._update_kv_gauges()
        return req.pages, matched_tok

    def _admit_one_chunk(self):
        """Advance admission by AT MOST one prefill chunk (the bound on
        how long a tick's decode can be delayed by an arrival)."""
        if self._prefill is None:
            self._phase("admit")
            slot = self._free_slot()
            if slot is None:
                return
            with self._cond:
                req = self._scheduler.next_request()
                QUEUE_GAUGE.set(self._scheduler.depth, tags=self._tags)
            while req is not None and req.stream.cancelled:
                self._finish_request(req, "cancelled")
                with self._cond:
                    req = self._scheduler.next_request()
                    QUEUE_GAUGE.set(self._scheduler.depth,
                                    tags=self._tags)
            self._flush_emits()   # the cancelled wait for no dispatch
            if req is None:
                return
            reserved = self._try_reserve(req)
            if reserved is None:
                # KV-starved: requests resident in the pool will finish
                # and free pages; FCFS order is preserved by putting
                # the head back.
                with self._cond:
                    self._scheduler.requeue_head(req)
                    QUEUE_GAUGE.set(self._scheduler.depth,
                                    tags=self._tags)
                return
            pages, matched_tok = reserved
            bt_row = np.zeros((self._max_blocks,), np.int32)
            bt_row[:len(pages)] = pages
            # _prefill writes stay under _cond: stop() tears the field
            # down under _cond after a join that may have TIMED OUT
            # with this thread still mid-tick, so the handoff must be
            # a real critical section, not owner-confinement.
            with self._cond:
                self._prefill = _PrefillState(req, slot, matched_tok,
                                              bt_row)
            # TTFT stage 1 of 3 — queue: submit() to admission (pages
            # reserved, prefill about to start).
            _span_for(req, "engine.queue", req.submit_t,
                      time.monotonic() - req.submit_t,
                      args={"request_id": req.id,
                            "prefix_hit_tokens": matched_tok})

        self._phase("prefill_dispatch")
        st = self._prefill
        req = st.req
        if req.stream.cancelled:
            with self._cond:
                self._prefill = None
            self._release_pages(req)
            self._finish_request(req, "cancelled")
            self._flush_emits()
            return
        L = len(req.prompt)
        start = st.next_start
        width = min(self.prefill_chunk, self._s_virt - start)
        real = req.prompt[start:start + width]
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :len(real)] = real
        logits, self._cache = _prefill_chunk(
            self.params, jnp.asarray(chunk), jnp.int32(start),
            self._cache, jnp.asarray(st.bt_row[None, :]), self.cfg,
            **self._row_args(st.slot, len(real)))
        st.next_start = start + width
        st.chunks += 1
        self._prefill_tokens += len(real)
        self._prefill_pad_tokens += width - len(real)
        if self._model is not None \
                and self._model.chunk_selects(self.cfg, start):
            st.sparse_chunks += 1
            self._prefill_tokens_sparse += len(real)
        if self._row_state and start == 0:
            self._state_resets += 1   # the chunk at 0 zeroes the row's
        self._turns_with_chunk += 1   # at most one chunk a turn
        if st.next_start < L:
            return  # more chunks to go; decode proceeds meanwhile

        # Prefill complete: sample the first token from the last REAL
        # column of the final chunk (pad columns carry garbage).
        with self._cond:
            self._prefill = None
        t_fc = time.monotonic()
        # TTFT stage 2 of 3 — prefill: admission to the last chunk's
        # dispatch (chunk count makes chunked-prefill interleaving
        # visible against concurrent decode ticks).
        _span_for(req, "engine.prefill", st.t0, t_fc - st.t0,
                  args={"request_id": req.id, "chunks": st.chunks,
                        "sparse_chunks": st.sparse_chunks,
                        "prompt_tokens": L,
                        "prefix_hit_tokens": req.prefix_hit_tokens})
        if self._prefix is not None:
            # The request's FULL prompt pages now hold final K/V (decode
            # writes start at column L, outside any full prompt page) —
            # publish them for future prompts to share.  Already-cached
            # chunks are no-ops; this request's duplicates stay private.
            self._prefix.insert(req.prompt,
                                req.pages[:L // self.page_size])
        self._phase("device_wait")
        row = np.asarray(logits[0, len(real) - 1])
        self._phase("emit")
        try:
            self._emit_first(st, req, self._sample_host(row, req), t_fc)
        finally:
            # The first token's reader is woken HERE, before this turn's
            # tick is dispatched: it waits for no device work.
            self._flush_emits()

    def _emit_first(self, st: _PrefillState, req: _Request, first: int,
                    t_fc: float):
        """A finished prefill's first token: out to its stream, and the
        request into the decode batch unless it ends here."""
        L = len(req.prompt)
        now = time.monotonic()
        # TTFT stage 3 of 3 — first tick: forcing the prefill logits
        # off-device + sampling the first token.  queue + prefill +
        # first_tick sums to submit→first-token, so `rt trace` derives
        # the TTFT breakdown instead of guessing.
        _span_for(req, "engine.first_tick", t_fc, now - t_fc,
                  args={"request_id": req.id})
        if req.eos_token is not None and first == req.eos_token:
            req.tokens = list(req.prompt) + [first]
            self._maybe_checkpoint_session(req)
            self._release_pages(req)
            self._finish_request(req, "completed")
            return
        if req.max_new_tokens == 1:
            # Nothing left to decode: never joins the batch.
            self._emit(req, first, now)
            req.tokens = list(req.prompt) + [first]
            self._maybe_checkpoint_session(req)
            self._release_pages(req)
            self._finish_request(req, "completed")
            return
        # Join the decode batch BEFORE the token is emitted: a consumer
        # woken by its first token must observe the request as an
        # active slot, not a phantom.  Publishing the block-table row is
        # the activation — from the next tick on, the fused scatter
        # writes into this request's pages instead of the trash page.
        self._block_tables[st.slot] = st.bt_row
        self._pos[st.slot] = L
        self._tok[st.slot] = first
        req.tokens = list(req.prompt) + [first]
        self._slots[st.slot] = req
        self._update_occupancy()
        self._emit(req, first, now)

    def _decode_tick(self):
        actives = [s for s in range(self.num_slots)
                   if self._slots[s] is not None]
        if not actives:
            return
        self._phase("tick_dispatch")
        spec_drafts: Dict[int, List[int]] = {}
        if self.speculate_k:
            for s in actives:
                req = self._slots[s]
                if req.temperature == 0 and not req.stream.cancelled:
                    d = _lookup_draft(req, self.speculate_ngram,
                                      self.speculate_k)
                    if d:
                        spec_drafts[s] = d
        if spec_drafts:
            self._verify_tick(actives, spec_drafts)
        else:
            self._plain_tick(actives)

    def _plain_tick(self, actives):
        sample_rows = [s for s in actives
                       if self._slots[s].temperature > 0]
        sampled, logits, self._cache = _paged_tick(
            self.params, jnp.asarray(self._tok), jnp.asarray(self._pos),
            self._cache, jnp.asarray(self._block_tables), self.cfg,
            with_logits=bool(sample_rows))
        self._count_keys(actives)
        self._phase("device_wait")
        sampled = np.asarray(sampled)
        if self._read_model_counters is not None:
            # the tick has ended, so this copies 40 bytes and waits for
            # nothing; chunks dispatched before it are in the numbers
            self._model_counters = self._read_model_counters(
                self._cache, self.cfg)
        logits_np, row_of = self._ship_sample_logits(logits, sample_rows)
        self._phase("emit")
        now = time.monotonic()
        try:
            for s in actives:
                req = self._slots[s]
                if req.stream.cancelled:
                    self._evict(s, "cancelled")
                    continue
                if req.temperature > 0:
                    t = _host_sample(logits_np[row_of[s]],
                                     req.temperature, req.top_k, req.rng)
                else:
                    t = int(sampled[s])
                self._advance(s, req, [t], now)
        finally:
            self._flush_emits()

    def _count_keys(self, actives, t: int = 1) -> None:
        """attn_keys_*: what this tick's rows hold, what their attention
        layers read of it and what the call gathered from the pool to
        read it (while the device runs the tick of `t` tokens a row).
        The dense body reads all a row holds, in every layer, and
        gathers for EVERY row of the call whole spans up to the deepest
        row's last column: the trip count its program reads from the
        same `_pos`.  A model with its own step reports what it reads."""
        pos = self._pos[actives]
        if self._model is None:
            read = held = (int(pos.sum()) + len(actives)) * self.cfg.n_layers
            spans = -(-(int(self._pos.max()) + t) // self._tick_span)
            gathered = self.num_slots * self.cfg.n_layers \
                * spans * self._tick_span
        else:
            read, held = self._model.attn_keys(self.cfg, pos)
            counted = getattr(self._model, "attn_keys_gathered", None)
            gathered = read if counted is None else counted(
                self.cfg, self._pos, self.page_size, self._max_blocks)
        self._keys_attended += read
        self._keys_resident += held
        self._keys_gathered += gathered
        # (a model that mixes in layers of another kind says how many
        # attend: `n_attn`)
        self._keys_context += (int(pos.sum()) + len(actives)) \
            * getattr(self.cfg, "n_attn", self.cfg.n_layers)

    def _verify_tick(self, actives, spec_drafts):
        """One fused paged_chunk_step verifying every row's pending
        token + drafts; per-row longest-matching-prefix acceptance turns
        idle verify bandwidth into extra tokens without ever changing
        the greedy output (accepted drafts EQUAL the argmax chain by
        construction)."""
        k = self.speculate_k
        chunk = np.zeros((self.num_slots, 1 + k), np.int32)
        chunk[:, 0] = self._tok
        for s, d in spec_drafts.items():
            chunk[s, 1:1 + len(d)] = d
        sample_rows = [s for s in actives
                       if self._slots[s].temperature > 0]
        preds, logits0, self._cache = _paged_verify(
            self.params, jnp.asarray(chunk), jnp.asarray(self._pos),
            self._cache, jnp.asarray(self._block_tables), self.cfg,
            with_logits=bool(sample_rows))
        self._count_keys(actives, 1 + k)
        self._phase("device_wait")
        preds = np.asarray(preds)
        logits_np, row_of = self._ship_sample_logits(logits0, sample_rows)
        self._phase("emit")
        now = time.monotonic()
        try:
            for s in actives:
                req = self._slots[s]
                if req.stream.cancelled:
                    self._evict(s, "cancelled")
                    continue
                if req.temperature > 0:
                    t = _host_sample(logits_np[row_of[s]],
                                     req.temperature, req.top_k, req.rng)
                    self._advance(s, req, [t], now)
                    continue
                d = spec_drafts.get(s, [])
                m = 0
                while m < len(d) and preds[s, m] == d[m]:
                    m += 1
                # The bonus prediction always rides along, so produced
                # length is m+1; cap so the row never exceeds max_new.
                m = min(m, req.max_new_tokens - req.emitted - 1)
                self._spec_drafted += len(d)
                self._spec_accepted += m
                if m:
                    SPEC_ACCEPTED_COUNTER.inc(m, tags=self._tags)
                self._advance(s, req,
                              list(d[:m]) + [int(preds[s, m])], now)
        finally:
            self._flush_emits()

    def _ship_sample_logits(self, logits, sample_rows):
        """Host transfer scales with the SAMPLING rows, not the whole
        pool: one temperature>0 request must not ship
        [num_slots, vocab] off-device every tick."""
        if not sample_rows:
            return None, None
        logits_np = np.asarray(
            logits[jnp.asarray(np.asarray(sample_rows, np.int32))])
        return logits_np, {s: i for i, s in enumerate(sample_rows)}

    def _advance(self, slot: int, req: _Request, produced: List[int],
                 now: float):
        """Commit one row's tick outcome: len(produced) tokens (1
        normally; accepted drafts + bonus under speculation), emitted in
        order with EOS / max_new eviction exactly as if they had been
        produced one tick at a time."""
        self._pos[slot] += len(produced)
        self._tok[slot] = produced[-1]
        req.tokens.extend(produced)
        for t in produced:
            if req.eos_token is not None and t == req.eos_token:
                self._evict(slot, "completed")
                return
            self._emit(req, t, now)
            if req.emitted >= req.max_new_tokens:
                self._evict(slot, "completed")
                return

    def _sample_host(self, row_logits: np.ndarray, req: _Request) -> int:
        if req.temperature > 0:
            return _host_sample(row_logits, req.temperature, req.top_k,
                                req.rng)
        return int(row_logits.argmax())

    def _emit(self, req: _Request, token: int, now: float):
        """One token out to its stream.  The reader's wake-up and the
        token's exported metrics wait for the phase's flush
        (_flush_emits); what stats() reports is counted here, so a
        reader the flush wakes never finds its token uncounted."""
        req.emitted += 1
        if req.first_token_t is None:
            req.first_token_t = now
            self._ttft_hist.observe(now - req.submit_t)
            self._recent_ttft.append(now - req.submit_t)
        else:
            self._emit_gaps.append(now - req.last_token_t)
            self._token_gaps += 1
            self._turn_gaps += 1
        req.last_token_t = now
        self._tokens_generated += 1
        self._emit_tokens += 1
        req.stream._push(token, self._wakes)

    def _flush_emits(self):
        """Close an emit phase, before the thread leaves it for any
        dispatch or device result.  Wake the readers of every stream
        the phase pushed to or finished: one call into each event loop
        (_fire_wakeups), a finish after its row's last token.  Then the
        exported metrics of the phase's tokens, once for all of them:
        one take of each metric's lock.  A phase that emitted nothing
        pays two truth tests."""
        if self._wakes:
            wakes, self._wakes = self._wakes, []
            self._stream_wakes += _fire_wakeups(wakes)
        n = self._emit_tokens
        if not n:
            return
        self._emit_tokens = 0
        self._tokens_counter.inc(n)
        if self._emit_gaps:
            gaps, self._emit_gaps = self._emit_gaps, []
            self._itl_hist.observe_many(gaps)
        self._win_tokens += n
        now = time.monotonic()
        if now - self._win_t >= 0.5:
            self._throughput_gauge.set(
                self._win_tokens / (now - self._win_t))
            self._win_t = now
            self._win_tokens = 0

    def _evict(self, slot: int, status: str):
        """Eviction is pure accounting: point the row back at the trash
        page and decref its pages.  No device work — stale K/V in a
        recycled page is always overwritten before an unmasked read
        (prefill covers the tail from its start column; decode writes a
        column before attending to it), which is what makes page
        recycling free compared to the old whole-row zeroing pass."""
        req = self._slots[slot]
        self._slots[slot] = None
        self._pos[slot] = 0
        self._tok[slot] = 0
        self._block_tables[slot, :] = 0
        # Durable sessions checkpoint BEFORE the pages are released —
        # publishing them into the radix tree needs the refs alive.
        self._maybe_checkpoint_session(req)
        self._release_pages(req)
        self._update_occupancy()
        self._finish_request(req, status)

    def _finish_request(self, req: _Request, status: str):
        if status == "cancelled":
            self._cancelled += 1
        else:
            self._completed += 1
        with self._cond:
            self._committed_blocks = max(
                0, self._committed_blocks - req.n_blocks)
        REQUESTS_COUNTER.inc(tags={**self._tags, "status": status})
        req.stream._finish(batch=self._wakes)

    def _update_occupancy(self):
        OCCUPANCY_GAUGE.set(
            sum(r is not None for r in self._slots) / self.num_slots,
            tags=self._tags)

    def _update_kv_gauges(self):
        KV_BLOCKS_FREE_GAUGE.set(self._alloc.free_pages, tags=self._tags)
        if self._prefix is not None:
            self._update_tier_gauges()
            if self._tiering:
                self._demotable_hint = self._prefix.releasable()

    def _update_tier_gauges(self):
        for tier, count in zip(("t0", "t1", "t2"),
                               self._prefix.tier_nodes):
            KV_TIER_PAGES_GAUGE.set(
                count, tags={**self._tags, "tier": tier})

    def _reset_paging(self):
        # Nothing may land in the arena closed below, nor be committed
        # to the tree built here.
        if self._lander is not None:
            self._lander.drain()
        self._landed.clear()
        self._alloc = BlockAllocator(self.kv_pages, first_page=1)
        if self._prefix is not None:
            self._prefix = RadixPrefixCache(
                self.page_size, self._alloc,
                digest_depth=_cfg.serve_affinity_digest_depth)
            self._prefix.release_payload = self._release_tier_payload
        if self._arena is not None:
            self._arena.close()
            self._arena = None
        self._block_tables[:] = 0
        self._update_kv_gauges()

    def _fail_all(self, err: BaseException):
        with self._cond:
            pf, self._prefill = self._prefill, None
            leftovers = self._scheduler.drain()
            self._committed_blocks = 0
            commands, self._commands = \
                list(self._commands), collections.deque()
            QUEUE_GAUGE.set(0, tags=self._tags)
        for _fn, fut in commands:
            if not fut.done():
                fut.set_exception(err)
        if pf is not None:
            pf.req.stream._finish(err, self._wakes)
        for req in leftovers:
            req.stream._finish(err, self._wakes)
        for s in range(self.num_slots):
            req = self._slots[s]
            if req is not None:
                self._slots[s] = None
                req.stream._finish(err, self._wakes)
        # Before the device state is rebuilt: a reader whose token was
        # pushed ahead of the fault finds it, then the error.
        self._flush_emits()
        self._pos[:] = 0
        self._tok[:] = 0
        # Rebuild device state: the donated cache may be mid-flight.
        self._cache = decode.init_paged_cache(
            self.cfg, self.kv_pages + 1, self.page_size, self.num_slots)
        self._reset_paging()
        self._update_occupancy()
