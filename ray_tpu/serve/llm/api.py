"""LLMServer: the serve deployment wrapping a GenerationEngine.

One replica = one engine = one chip's KV-slot pool.  Three surfaces:

  * handle.generate.remote(tokens, ...)          -> full token list
  * handle.options("stream").stream(tokens, ...) -> ServeResponseStream
    (token at a time, through the replica streaming transport; the
    options() spelling is needed because the method is literally named
    "stream", which shadows DeploymentHandle.stream)
  * HTTP POST {route}/  body {"tokens": [...], ...}  -> JSON; with
    Accept: text/event-stream (or "stream": true) the proxy emits SSE
    events, one token per event, as they are generated.

Engine overload surfaces as EngineOverloadedError on handles and as
HTTP 503 with Retry-After through the proxy (backpressure, not
buffering).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence

import ray_tpu
from ray_tpu._private import tracing as _tracing
from ray_tpu._private.config import GLOBAL_CONFIG as _cfg
from ray_tpu._private.jax_utils import (device_facts, open_backend,
                                        tree_nbytes)
from ray_tpu.serve.exceptions import resumable
from ray_tpu.serve.llm import kv_transfer
from ray_tpu.serve.llm.engine import GenerationEngine
from ray_tpu.serve.llm.scheduler import EngineOverloadedError

_GEN_KEYS = ("max_new_tokens", "temperature", "top_k", "eos_token",
             "seed")


def _resume_tokens(items) -> List[int]:
    """Delivered items from a failover cursor -> token ints (handle
    streams yield bare ints, the SSE path yields {"token": t} events)."""
    out = []
    for it in items or []:
        out.append(int(it["token"]) if isinstance(it, dict) else int(it))
    return out


class LLMServer:
    """Deployment class hosting one continuous-batching engine.

    `model_loader` is a zero-arg callable returning (params, cfg) —
    a callable (not the weights) so the deployment pickles small and
    the params are materialized inside the replica process, resident
    next to its chip.  `engine_config` feeds GenerationEngine knobs
    (num_slots, max_seq, prefill_chunk, max_queue_len, ...)."""

    def __init__(self, model_loader, engine_config: Optional[Dict] = None,
                 default_generation: Optional[Dict] = None):
        with _tracing.start_span("llm", "llm.load_model") as span:
            # The chip is opened here, by name (a jax.backend_init
            # span), not somewhere inside the loader's first jax call.
            open_backend()
            params, cfg = model_loader()
            span.args["param_bytes"] = tree_nbytes(params)
        self._defaults = dict(default_generation or {})
        self.engine = GenerationEngine(params, cfg,
                                       **(engine_config or {}))
        self.engine.start()

    def _gen_kwargs(self, overrides: Dict[str, Any]) -> Dict[str, Any]:
        kw = dict(self._defaults)
        kw.update({k: v for k, v in overrides.items() if k in _GEN_KEYS})
        unknown = set(overrides) - set(_GEN_KEYS)
        if unknown:
            raise TypeError(f"unknown generation options: {sorted(unknown)}")
        return kw

    async def generate(self, tokens: Sequence[int], **overrides
                       ) -> List[int]:
        """Full generation for one prompt (continuous-batched under the
        hood with every other in-flight request)."""
        return await self.engine.generate(
            tokens, **self._gen_kwargs(overrides))

    def _trim_for_resume(self, tokens: Sequence[int], kw: Dict,
                         _resume: Optional[Dict]):
        """Failover resume: re-anchor the prompt at the cursor — prompt
        becomes original + delivered tokens (the prefix cache makes the
        re-prefill cheap) and the token budget shrinks by what was
        already delivered, so a greedy resumed stream yields EXACTLY
        the remaining tokens of the uninterrupted stream.  Returns
        (tokens, remaining_budget); remaining <= 0 means the stream was
        already complete at the cursor."""
        delivered = _resume_tokens((_resume or {}).get("items"))
        if not delivered:
            return list(tokens), 1
        max_new = kw.get("max_new_tokens")
        if max_new is None:
            max_new = self.engine.default_max_new_tokens
        remaining = int(max_new) - len(delivered)
        eos = kw.get("eos_token")
        if eos is not None and delivered[-1] == int(eos):
            remaining = 0  # the stream had already hit EOS
        kw["max_new_tokens"] = max(1, remaining)
        return list(tokens) + delivered, remaining

    @resumable
    async def stream(self, tokens: Sequence[int], _resume=None,
                     **overrides):
        """Token-streaming generation: an async generator, consumed
        through the serve streaming transport
        (handle.options("stream").stream(...) client-side, SSE over
        HTTP).

        Resumable (`_resume` carries the router's failover cursor):
        after a replica death the stream continues on a healthy replica
        with only the undelivered suffix — bit-identical for greedy
        (temperature=0) requests; sampled requests resume on a fresh
        RNG stream past the cursor (documented parity caveat)."""
        session = overrides.pop("session", None) \
            or (_resume or {}).get("session")
        kw = self._gen_kwargs(overrides)
        tokens, remaining = self._trim_for_resume(tokens, kw, _resume)
        if remaining <= 0:
            return
        rng_state = await self._prepare_kv(_resume, tokens, session)
        stream = self.engine.submit(tokens, session_id=session,
                                    rng_state=rng_state, **kw)
        try:
            async for tok in stream:
                yield int(tok)
        finally:
            # Early close (client cancelled / disconnected): free the
            # engine slot instead of generating into a dead buffer.
            stream.cancel()

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats().to_dict()

    def replica_info(self) -> Dict[str, Any]:
        """Which process and device answered, and how much it has
        served: jax's own report of the replica's device
        (jax_utils.device_facts — platform, kind, count, peak bytes,
        pid, open chip device files), its leased chip ids, the engine's
        completed-request count, and under "start" where this replica's
        start went: its trace id (`rt trace <id>`) and each phase's
        seconds (tracing.start_seconds; a phase still running, as the
        warm-up is right after HEALTHY, reads None).  A caller that
        must stay off jax itself (a driver next to TPU workers) checks
        here that the replica really is on the chip."""
        return {**device_facts(), "tpu_ids": ray_tpu.get_tpu_ids(),
                "completed": self.engine.stats().requests_completed,
                "start": _tracing.start_books()}

    def autoscale_metrics(self) -> Dict[str, Any]:
        """Saturation gauges for the serve controller's autoscaler
        (picked up via the replica's get_autoscale_metrics): decode
        queue depth, slot occupancy, and KV page headroom — so scaling
        tracks what the ENGINE is actually short of, not just the
        request count.  With affinity on the gauges also carry the
        engine's prefix digest (kv_digest, set by load_info) and this
        replica's migration pull address (kv_rdv) — the broadcast that
        already reaches the router teaches it both WHERE prefixes live
        and how to ship their pages, with zero extra RPCs."""
        info = self.engine.load_info()
        if _cfg.serve_affinity:
            rdv = kv_transfer.rendezvous(self.engine)
            if rdv is not None:
                info["kv_rdv"] = rdv
        return info

    async def _maybe_pull_kv(self, _resume: Optional[Dict],
                             tokens: Sequence[int]) -> int:
        """A failover cursor names the dead stream's origin replica
        (kv_origin): pull its committed pages for prompt + delivered
        tokens before submitting, so the resume's prefill collapses to
        a prefix-cache hit.  Best-effort by design — any failure means
        re-prefill, never a corrupt cache (pull_kv_pages's contract).
        Trust: kv_origin only ever arrives via the router, which
        validates client-replayed cursors against its own membership
        view (ReplicaSet._trusted_rdv) — this replica never dials an
        address a client invented."""
        rdv = (_resume or {}).get("kv_origin")
        if not rdv or not _cfg.serve_affinity:
            return 0
        mine = kv_transfer.rendezvous(self.engine)
        if mine is not None and mine == rdv:
            return 0  # resumed onto the origin itself: pages already here
        return await kv_transfer.pull_kv_pages(rdv, tokens, self.engine)

    async def _prepare_kv(self, _resume: Optional[Dict],
                          tokens: Sequence[int],
                          session: Optional[str]) -> Optional[Dict]:
        """Pre-submit KV warm-up, cheapest source first: a live origin
        pull (failover cursor), then the durable-session store.  The
        store path is what makes a session resurrect ANYWHERE — the
        origin can be minutes dead, any replica on the host imports its
        pages from T2 and the rest re-prefills bit-identically.
        Returns the session's checkpointed sampler state (None for
        greedy sessions or when nothing resurrected)."""
        try:
            await self._maybe_pull_kv(_resume, tokens)
        except Exception:
            pass  # best-effort: re-prefill covers it
        rng_state = None
        if session and _cfg.serve_kv_tiering:
            try:
                res = await kv_transfer._on_worker(
                    self.engine,
                    lambda: self.engine.session_resurrect(session,
                                                          tokens))
            except Exception:
                res = None
            if res is not None:
                rng_state = res.get("rng_state")
        return rng_state

    # -- KV migration control surface (router / controller RPCs) -------

    def kv_rendezvous(self) -> Optional[Dict]:
        """Where a peer can pull this replica's KV pages from."""
        return kv_transfer.rendezvous(self.engine)

    def kv_drain_manifest(self, top_k: int = 8) -> Optional[Dict]:
        """Drain handoff, origin side: this replica's pull address plus
        the token paths of its hottest cached prefixes.  The controller
        fetches this from a DRAINING replica and hands it to the chosen
        survivor's kv_pull_from — the survivor pulls, so teardown
        ordering stays trivial (the origin just keeps serving exports
        until its pages have been copied out).

        With tiering on, every demotable page is flushed to the store
        FIRST: a dying replica demotes instead of dropping, so even if
        no survivor ever pulls (or this process is killed mid-drain
        afterwards), its sessions resurrect anywhere from T2."""
        try:
            self.engine.run_on_worker(self.engine.kv_flush_to_store,
                                      timeout=10.0)
        except Exception:
            pass  # flush is belt-and-braces; the pull path still runs
        rdv = kv_transfer.rendezvous(self.engine)
        if rdv is None:
            return None
        prefixes = self.engine.run_on_worker(
            lambda: self.engine.kv_hot_prefixes(top_k))
        prefixes = [p for p in prefixes
                    if len(p) >= _cfg.serve_kv_min_migrate_pages
                    * self.engine.page_size]
        if not prefixes:
            return None
        return {"rdv": rdv, "prefixes": prefixes}

    async def kv_pull_from(self, manifest: Dict) -> int:
        """Drain handoff, survivor side: pull each offered prefix from
        the draining origin.  Copies, not moves — the origin's pages
        are untouched, so an un-drain mid-flight cannot double-count
        anything; its copies simply age out of both caches normally."""
        total = 0
        for toks in (manifest or {}).get("prefixes", []):
            total += await kv_transfer.pull_kv_pages(
                manifest["rdv"], toks, self.engine)
        return total

    def trace_spans(self, prefix: str = "engine.") -> List[Dict]:
        """Spans from THIS replica process's trace ring (the bench's
        TTFT-attribution probe: engine.queue / engine.prefill /
        engine.first_tick live here, not in the client process), and
        behind them the engine's capture log of the last profiler
        capture (`tpu_profiler.start()` / `stop()`, or running now):
        `engine.phase.<name>`, `engine.dispatch`, `engine.compile`,
        `engine.capture_log` (GenerationEngine.capture_events), in the
        ring's own form."""
        return [e for e in _tracing.ring().snapshot(clear=False)
                + self.engine.capture_events()
                if str(e.get("name", "")).startswith(prefix)]

    def check_health(self):
        if not self.engine.running:
            raise RuntimeError("generation engine worker is not running")

    def __del__(self):
        try:
            self.engine.stop(timeout=5.0)
        except Exception:
            pass

    # -- HTTP entry point (proxy) --------------------------------------

    @resumable
    async def __call__(self, request, _resume=None):
        """POST JSON {"tokens": [ints], "max_new_tokens"?, "temperature"?,
        "top_k"?, "eos_token"?, "seed"?}.

        Plain: {"tokens": [...]} JSON in one shot.  With
        `Accept: text/event-stream` or `?stream=1` the PROXY routes the
        call through the streaming transport and this returns an async
        generator — one `data: {"token": t}` SSE event per generated
        token (the detection rule here must mirror the proxy's, which
        decides before the replica is ever called).  SSE requests are
        resumable: on replica death the proxy's router re-submits here
        with the delivered-token cursor and only the remaining events
        are produced."""
        try:
            body = request.json()
        except Exception:
            return _http_error(400, "body must be JSON")
        if not isinstance(body, dict) or "tokens" not in body:
            return _http_error(400, 'body must be {"tokens": [...]}')
        wants_sse = _wants_stream(request)
        overrides = {k: body[k] for k in _GEN_KEYS if k in body}
        session = body.get("session") or (_resume or {}).get("session")
        try:
            kw = self._gen_kwargs(overrides)
            if wants_sse:
                toks, remaining = self._trim_for_resume(
                    body["tokens"], kw, _resume)
                if remaining <= 0:
                    return self._no_events()
                rng_state = await self._prepare_kv(_resume, toks,
                                                   session)
                stream = self.engine.submit(toks, session_id=session,
                                            rng_state=rng_state, **kw)
                return self._sse_events(stream)
            toks = [int(t) for t in body["tokens"]]
            rng_state = await self._prepare_kv(None, toks, session)
            out = await self.engine.generate(
                toks, session_id=session, rng_state=rng_state, **kw)
        except EngineOverloadedError as e:
            # Retry-After tracks WHAT saturated: a full waiting line
            # drains at admission speed (short), an exhausted KV pool
            # drains at generation speed (longer).  Seconds as a FLOAT:
            # the engine's tier-aware hint can be sub-second — one
            # demotion sweep away — and the old max(1, int(...))
            # rounding turned 0.25s of backoff into a full second of
            # idle client on every retry.
            retry = f"{max(0.05, float(getattr(e, 'retry_after_s', 1.0))):.3f}"
            return _http_error(503, str(e),
                               headers=[("Retry-After", retry)])
        except (TypeError, ValueError) as e:
            return _http_error(400, str(e))
        return {"tokens": out}

    async def _sse_events(self, stream):
        try:
            async for tok in stream:
                yield {"token": int(tok)}
        finally:
            stream.cancel()  # client went away mid-generation: free the slot

    async def _no_events(self):
        """A resumed stream whose cursor already covers the whole
        generation: stream transport, zero remaining events."""
        return
        yield  # pragma: no cover — marks this as a generator function


def _wants_stream(request) -> bool:
    """THE streaming-detection predicate — literally the proxy's own
    (HTTPProxy.wants_stream), so the replica's choice of generator vs
    unary can never drift from the transport the proxy picked."""
    from ray_tpu.serve._private.http_proxy import HTTPProxy
    return HTTPProxy.wants_stream(getattr(request, "query", None) or {},
                                  getattr(request, "headers", None) or {})


def _http_error(status: int, message: str, headers=None) -> Dict:
    """Structured response the HTTP proxy unwraps (same contract as the
    ASGI ingress path)."""
    return {"__http__": True, "status": status,
            "content_type": "application/json",
            "headers": list(headers or []),
            "body": json.dumps({"error": message}).encode()}


def llm_deployment(model_loader, *, name: str = "llm",
                   num_replicas: int = 1,
                   engine_config: Optional[Dict] = None,
                   default_generation: Optional[Dict] = None,
                   route_prefix: Optional[str] = None,
                   max_concurrent_queries: int = 256,
                   ray_actor_options: Optional[Dict] = None):
    """Build a ready-to-deploy LLMServer Deployment.

        handle = llm_deployment(loader, engine_config={"num_slots": 8}
                                ).deploy()
        tokens = handle.generate.remote([1, 2, 3]).result()
        for tok in handle.options("stream").stream([1, 2, 3]):
            ...
    """
    from ray_tpu.serve.api import deployment
    dep = deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        max_concurrent_queries=max_concurrent_queries,
        ray_actor_options=ray_actor_options, route_prefix=route_prefix)
    return dep.options(init_args=(model_loader, engine_config,
                                  default_generation))
