"""KV page accounting for the paged continuous-batching engine.

Host-side bookkeeping only — the device never sees these objects.  The
engine's KV memory is a pool of fixed-size pages (decode.init_paged_cache);
what lives here is who owns which page:

  * BlockAllocator — refcounted free-list over page ids.  A page is
    held by every block table that references it PLUS the radix tree if
    a prefix node points at it; it returns to the free list only when
    the last holder drops it.  Refcounts are what make prefix sharing
    safe: evicting one sharer can never free a page another request's
    attention still gathers through.
  * RadixPrefixCache — a radix/trie over token prefixes at PAGE
    granularity (SGLang's RadixAttention at block granularity, the same
    choice vLLM's prefix caching makes): each node is one FULL page of
    `page_size` prompt tokens and owns one allocator reference on the
    page holding that chunk's K/V.  match() walks the longest cached
    prefix; insert() adds nodes for pages not yet present; evict()
    drops least-recently-used LEAVES until enough pages are free
    (dropping a leaf only decrefs — sharers keep the page alive).

Single-owner discipline: every method is called from the engine's
worker thread (admission/eviction), never concurrently.
"""

from __future__ import annotations

import collections
import hashlib
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

# Tier ids for _RadixNode.tier: the decode pool (pages live on device,
# node.page is a pool page id), the host shared-memory arena, and the
# file-backed page store.  A node's K/V bytes live in EXACTLY one tier;
# demotion/promotion moves them, never copies them live in two places
# (the store is the exception by design: a T2 entry persists on disk
# even after its node is promoted or evicted — that persistence IS the
# durability the session-resurrect path relies on).
TIER_POOL = 0
TIER_HOST = 1
TIER_STORE = 2
# In flight: the node's pool page was handed back when the gather of its
# bytes was DISPATCHED, and the bytes have not landed in a tier yet (the
# engine's lander thread is copying them).  Not in the pool, not yet in
# a tier: a match stops here, the sweeper skips it, and only
# apply_demote / insert's adoption / eviction take it out of the state.
TIER_FLIGHT = 3


def _chunk_fp(parent_fp: str, key: Sequence[int]) -> str:
    """Fingerprint of one full page of tokens, chained off the parent
    page's fingerprint — so one fingerprint names an entire prefix, and
    two processes that never exchanged state agree on it.  blake2b (not
    Python hash(): that is salted per process) over little-endian token
    ids; 8-byte digests keep a whole top-K digest under ~1 KB."""
    h = hashlib.blake2b(parent_fp.encode("ascii"), digest_size=8)
    for t in key:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.hexdigest()


def prefix_fingerprints(tokens: Sequence[int], page_size: int,
                        max_depth: int) -> List[str]:
    """Fingerprints of a prompt's full-page prefixes, shallowest first:
    out[d-1] names tokens[: d * page_size].  The router computes these
    for an incoming prompt and intersects them with replicas' published
    digests; the radix cache computes the same chain incrementally at
    insert time, so equality means the replica holds that prefix."""
    out: List[str] = []
    fp = ""
    for i in range(min(max_depth, len(tokens) // page_size)):
        fp = _chunk_fp(fp, tokens[i * page_size:(i + 1) * page_size])
        out.append(fp)
    return out


class BlockAllocator:
    """Refcounted allocator over page ids [first_page, first_page+num).

    The engine reserves page id 0 as the TRASH page (inactive batch
    rows scatter their garbage writes there), so it allocates ids
    starting at 1 — hence `first_page`.
    """

    def __init__(self, num_pages: int, first_page: int = 1):
        if num_pages < 1:
            raise ValueError("num_pages must be >= 1")
        self.num_pages = num_pages
        self.first_page = first_page
        # LIFO free list: recently freed pages are re-handed first (their
        # stale K/V is overwritten before any unmasked read — see the
        # engine's no-zeroing note).
        self._free: List[int] = list(
            range(first_page + num_pages - 1, first_page - 1, -1))
        self._refs: Dict[int, int] = {}

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Allocate n pages (refcount 1 each) or None — all or nothing,
        so a half-admitted request can never strand pages."""
        if n < 0:
            raise ValueError("n must be >= 0")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def incref(self, page: int) -> None:
        self._refs[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; True when this freed the page."""
        r = self._refs.get(page)
        if r is None:
            raise ValueError(f"decref of unheld page {page}")
        r -= 1
        if r == 0:
            del self._refs[page]
            self._free.append(page)
            return True
        self._refs[page] = r
        return False

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)


class _RadixNode:
    __slots__ = ("children", "page", "parent", "key", "last_used",
                 "fp", "depth", "tier", "payload", "last_used_t")

    def __init__(self, key, page, parent):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.key = key
        self.page = page
        self.parent = parent
        self.last_used = 0
        self.fp = ""      # chained prefix fingerprint (root: "")
        self.depth = 0    # pages from root (root: 0)
        # Tier state: TIER_POOL means `page` is a live pool page id;
        # TIER_HOST/TIER_STORE mean `page` is None and `payload` names
        # where the bytes went — ("t1", slot, crc, nbytes) for an arena
        # slot, ("t2", key, crc, nbytes) for a store entry; TIER_FLIGHT
        # means `payload` is the ticket begin_demote() issued, by whose
        # identity the landing finds its node unchanged.  last_used_t
        # is the wall-clock twin of the LRU logical clock; the demotion
        # sweeper compares it against the idle knobs.
        self.tier = TIER_POOL
        self.payload: Optional[tuple] = None
        self.last_used_t = 0.0


class RadixPrefixCache:
    """Page-granularity prefix trie with LRU leaf eviction.

    Keys are tuples of `page_size` token ids; a path root->node spells a
    prompt prefix and node.page holds that chunk's K/V.  Only FULL pages
    are shareable — a partially filled page is private to its request
    (decode writes land in it).
    """

    def __init__(self, page_size: int, allocator: BlockAllocator,
                 digest_depth: int = 8):
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.page_size = page_size
        self._alloc = allocator
        self._root = _RadixNode(None, None, None)
        self._clock = 0
        self.nodes = 0
        # Affinity digest index: fingerprint -> node, maintained
        # incrementally for nodes at depth <= digest_depth (fingerprints
        # chain off the parent, so one entry names a whole prefix).  The
        # depth cap bounds the index — and the digest the router sees —
        # independent of how deep the trie grows.
        self.digest_depth = digest_depth
        self._fp_index: Dict[str, _RadixNode] = {}
        # Nodes per tier, maintained incrementally (load_info polls
        # this every autoscale tick — never a tree walk on that path).
        self.tier_nodes: List[int] = [0, 0, 0]
        self.inflight_nodes = 0     # ...and those in TIER_FLIGHT
        # The POOL-tier nodes, coldest first: the order demotion takes
        # them in (last touch, and within one touched path the deepest
        # first), kept as the paths are touched, so admission under
        # pressure finds its victims without walking a tree that also
        # holds every demoted node.
        self._pool_lru: "collections.OrderedDict[_RadixNode, None]" = \
            collections.OrderedDict()
        # ...and the arena's (at most its slots), for the sweep's
        # second stage.
        self._host_nodes: Dict[_RadixNode, None] = {}
        self._tickets = 0
        # Called with a node's payload whenever the tree stops owning
        # it (promotion, adoption by insert, eviction, clear).  The
        # engine points this at the arena's slot-free; T2 payloads are
        # deliberately NOT deleted from the store here (persistence is
        # the point — the store's TTL sweep owns their lifetime).
        self.release_payload: Optional[Callable[[tuple], None]] = None

    def _retier(self, node: _RadixNode, tier: Optional[int]) -> None:
        """Move `node` to `tier` in the per-tier counts (None: the node
        leaves the tree)."""
        if node.tier == TIER_FLIGHT:
            self.inflight_nodes -= 1
        else:
            self.tier_nodes[node.tier] -= 1
        if node.tier == TIER_POOL:
            del self._pool_lru[node]
        elif node.tier == TIER_HOST:
            del self._host_nodes[node]
        if tier == TIER_FLIGHT:
            self.inflight_nodes += 1
        elif tier is not None:
            self.tier_nodes[tier] += 1
        if tier == TIER_POOL:
            self._pool_lru[node] = None
        elif tier == TIER_HOST:
            self._host_nodes[node] = None
        if tier is not None:
            node.tier = tier

    def _touch(self, path: Sequence[_RadixNode]) -> None:
        """`path` (root to leaf) was just used: its pool nodes become
        the warmest, the deepest of them the coldest among those."""
        for n in reversed(path):
            if n.tier == TIER_POOL:
                self._pool_lru.move_to_end(n)

    def _drop_payload(self, node: _RadixNode) -> None:
        if node.payload is not None and self.release_payload is not None:
            try:
                self.release_payload(node.payload)
            except Exception:
                pass  # a leaked arena slot must never poison the trie
        node.payload = None

    def match(self, tokens: Sequence[int], max_tokens: Optional[int] = None
              ) -> Tuple[List[int], int]:
        """Longest cached POOL-TIER prefix of `tokens` in full pages.

        Returns (pages, matched_token_count).  `max_tokens` caps the
        match (the engine passes len(prompt)-1: at least one prompt
        token must run through tail prefill to produce the logits the
        first sampled token comes from — a pure cache hit yields K/V,
        never logits).  The walk stops at the first demoted node: a
        T1/T2 node has no pool page to hand out — callers that can
        promote use match_nodes() instead.  Matched nodes are touched
        for LRU; the CALLER must incref the returned pages before
        relying on them (a later evict() may drop the nodes)."""
        nodes, _ = self.match_nodes(tokens, max_tokens)
        pages: List[int] = []
        for n in nodes:
            if n.tier != TIER_POOL:
                break
            pages.append(n.page)
        return pages, len(pages) * self.page_size

    def match_nodes(self, tokens: Sequence[int],
                    max_tokens: Optional[int] = None
                    ) -> Tuple[List["_RadixNode"], int]:
        """Longest cached prefix of `tokens` as the NODE path, any
        tier.  The engine's reservation path walks this to promote
        demoted nodes back into the pool in the same all-or-nothing
        reservation that admits the request.  Touches LRU (logical
        clock and wall time) for every matched node."""
        psz = self.page_size
        limit = len(tokens) if max_tokens is None else min(
            max_tokens, len(tokens))
        self._clock += 1
        now = time.monotonic()
        node = self._root
        out: List[_RadixNode] = []
        for i in range(limit // psz):
            child = node.children.get(tuple(tokens[i * psz:(i + 1) * psz]))
            if child is None:
                break
            child.last_used = self._clock
            child.last_used_t = now
            out.append(child)
            node = child
        self._touch(out)
        return out, len(out) * psz

    def insert(self, tokens: Sequence[int], pages: Sequence[int]) -> int:
        """Record that pages[i] holds the K/V of tokens[i*psz:(i+1)*psz].

        Walks/creates the path; each NEW node increfs its page.  Where a
        node already exists (another request cached the same chunk
        first) the existing page is kept and the duplicate is ignored —
        the caller keeps its own reference on the duplicate and frees it
        with the request.  A DEMOTED node on the path instead ADOPTS the
        caller's page (incref'd for the tree, old payload released):
        the caller just computed or imported bit-identical K/V for that
        chunk, so this is a free promotion.  Returns the number of new
        nodes."""
        psz = self.page_size
        self._clock += 1
        now = time.monotonic()
        node = self._root
        added = 0
        path: List[_RadixNode] = []
        for i, page in enumerate(pages):
            key = tuple(tokens[i * psz:(i + 1) * psz])
            child = node.children.get(key)
            if child is None:
                if page is None:
                    # A placeholder for a path node that vanished
                    # between the caller's match and this insert; a
                    # node cannot exist without bytes, so the rest of
                    # the path is unpublishable too.
                    break
                child = _RadixNode(key, page, node)
                child.depth = node.depth + 1
                if child.depth <= self.digest_depth:
                    child.fp = _chunk_fp(node.fp, key)
                    self._fp_index[child.fp] = child
                node.children[key] = child
                self._alloc.incref(page)
                self.nodes += 1
                self.tier_nodes[TIER_POOL] += 1
                self._pool_lru[child] = None
                added += 1
            elif child.tier != TIER_POOL and page is not None:
                # Adoption: deterministic prefill/import reproduced this
                # chunk's K/V bit-identically in the caller's page.  A
                # None page means the caller is extending BELOW a
                # demoted ancestor without re-materializing it (store
                # import); the ancestor keeps its tier payload.
                # (An in-flight node's ticket goes with its payload, so
                # its landing finds the node changed and is discarded.)
                self._retier(child, TIER_POOL)
                child.page = page
                self._drop_payload(child)
                self._alloc.incref(page)
            child.last_used = self._clock
            child.last_used_t = now
            path.append(child)
            node = child
        self._touch(path)
        return added

    # -- tier transitions (engine worker thread only) -------------------

    def path_fp(self, node: _RadixNode) -> str:
        """Full-depth chained fingerprint of the prefix this node caps.
        The digest index only computes fingerprints to digest_depth;
        store-tier keys need them at ANY depth, so this chains down
        from the nearest ancestor that has one and KEEPS each on its
        node (`fp`: the same chain, never indexed past digest_depth) —
        one hash a node over its life, on the demotion path only."""
        chain: List[_RadixNode] = []
        n = node
        while n is not self._root and n is not None and not n.fp:
            chain.append(n)
            n = n.parent
        fp = n.fp if n is not None else ""
        for n in reversed(chain):
            fp = n.fp = _chunk_fp(fp, n.key)
        return fp

    def demote_candidates(self, min_idle_s: float,
                          tier: int = TIER_POOL,
                          limit: Optional[int] = None
                          ) -> List["_RadixNode"]:
        """Nodes eligible to leave `tier`, coldest first.  T0 eligibility
        is tree-only pages (refcount 1 — a page a live request still
        gathers through is NEVER demoted) idle at least min_idle_s; T1
        eligibility is idle time alone.  min_idle_s=0 is the pressure
        path: anything tree-only is fair game, LRU order."""
        now = time.monotonic()
        out: List[_RadixNode] = []
        if tier == TIER_POOL:
            # _pool_lru IS the order (last touch, deepest first), and a
            # node's last touch is no later than its successor's: the
            # scan ends at the first one too fresh, or at `limit`.
            for n in self._pool_lru:
                if now - n.last_used_t < min_idle_s \
                        or (limit is not None and len(out) >= limit):
                    break
                if self._alloc.refcount(n.page) == 1:
                    out.append(n)
            return out
        if tier != TIER_HOST:
            raise ValueError("only pool and arena nodes demote")
        out = [n for n in self._host_nodes
               if now - n.last_used_t >= min_idle_s]
        out.sort(key=lambda n: (n.last_used, -n.depth))
        return out if limit is None else out[:limit]

    def begin_demote(self, node: _RadixNode) -> tuple:
        """A tree-only pool page (caller guaranteed refcount 1) leaves
        the pool the moment the gather of its bytes is dispatched: the
        page is freed, the node is IN FLIGHT, and the ticket returned
        (also the node's payload) is what apply_demote's caller checks
        by identity when the bytes have landed — an eviction or an
        adoption in between clears it."""
        self._alloc.decref(node.page)
        node.page = None
        self._retier(node, TIER_FLIGHT)
        self._tickets += 1      # makes each ticket an object of its own
        node.payload = ("fl", self._tickets, 0, 0)
        return node.payload

    def apply_demote(self, node: _RadixNode, tier: int,
                     payload: tuple) -> None:
        """Commit one node's demotion AFTER its bytes landed in the
        destination tier: the pool page is freed (T0 source; caller
        guaranteed refcount 1), the arena slot released (T1 source) or
        the ticket retired (in flight), and the node now names
        `payload` instead."""
        if node.tier == TIER_POOL:
            self._alloc.decref(node.page)
            node.page = None
        else:
            self._drop_payload(node)
        self._retier(node, tier)
        node.payload = payload

    def drop(self, node: _RadixNode) -> int:
        """Take `node` and everything below it out of the tree (an
        in-flight page whose bytes found nowhere to land: the prefixes
        through it are unreachable without it).  Pool pages are
        decref'd, tier payloads released, in-flight tickets cleared so
        their landings are discarded.  Returns the nodes dropped."""
        parent = node.parent
        if parent is None or parent.children.get(node.key) is not node:
            return 0
        del parent.children[node.key]
        dropped, stack = 0, [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self._unindex(n)
            if n.tier == TIER_POOL:
                self._alloc.decref(n.page)
            else:
                self._drop_payload(n)
            self._retier(n, None)
            self.nodes -= 1
            dropped += 1
        return dropped

    def promote(self, node: _RadixNode, page: int) -> None:
        """Commit one node's promotion AFTER its bytes landed in pool
        page `page` (freshly alloc'd — its allocation ref becomes the
        tree's ref, mirroring insert()'s accounting)."""
        self._drop_payload(node)
        self._retier(node, TIER_POOL)
        node.page = page

    def _unindex(self, node: _RadixNode) -> None:
        if node.fp and self._fp_index.get(node.fp) is node:
            del self._fp_index[node.fp]

    def digest(self, top_k: int) -> List[Dict]:
        """The replica's affinity digest: the top_k most recently used
        MAXIMAL indexed prefixes as [{"fp", "d"}].  The router scores by
        the deepest request fingerprint present in the digest, and a
        depth-d entry implies the whole d-page prefix is cached — so an
        ancestor of an advertised node carries zero information and
        advertising it would waste a top_k slot (with 8-deep chains,
        raw-node top-K covers 8x fewer distinct prefixes).  Recency
        ties break deepest-first for the same reason as hot_prefixes:
        a path touched as one unit stamps every node the same clock.
        Bounded by both top_k and digest_depth, so it stays gauge-sized
        however big the trie is.  Each entry carries "t": the WORST
        tier on its root path — the router discounts T1/T2 hits against
        T0 hits (a promoted page costs a host->device splice a pool hit
        does not)."""
        return [{"fp": n.fp, "d": n.depth, "t": self._path_tier(n)}
                for n in self._pick_maximal(top_k)]

    def _path_tier(self, node: _RadixNode) -> int:
        worst = node.tier
        n = node.parent
        while n is not None and n.parent is not None:
            if n.tier > worst:
                worst = n.tier
            n = n.parent
        return min(worst, TIER_STORE)   # in flight: as good as demoted

    def _pick_maximal(self, top_k: int) -> List["_RadixNode"]:
        """Up to top_k indexed nodes, most recently used first, maximal
        paths only.  The forward pass skips a candidate implied by an
        ALREADY-picked descendant; the final pass drops a picked node
        whose descendant was picked LATER (an ancestor more recently
        used than its child gets selected first, and nothing in the
        forward pass revisits it) — without it the output would carry
        redundant ancestors, breaking the ancestor-deduped contract
        digest()/hot_prefixes() advertise."""
        picked: List[_RadixNode] = []
        for n in sorted(self._fp_index.values(),
                        key=lambda n: (-n.last_used, -n.depth)):
            if len(picked) >= top_k:
                break
            if any(self._is_ancestor(n, p) for p in picked):
                continue  # implied by a deeper advertised node
            picked.append(n)
        return [n for n in picked
                if not any(n is not p and self._is_ancestor(n, p)
                           for p in picked)]

    def prefix_tokens(self, node: _RadixNode) -> List[int]:
        out: List[int] = []
        while node is not self._root and node is not None:
            out[:0] = node.key
            node = node.parent
        return out

    def hot_prefixes(self, top_k: int) -> List[List[int]]:
        """Token sequences of the hottest cached prefixes, maximal
        paths only (a selected node's ancestors are implied — the
        destination's longest-prefix match recovers them for free).
        Drain migration walks these to re-home still-referenced pages
        before teardown."""
        return [self.prefix_tokens(n)
                for n in self._pick_maximal(top_k)]

    @staticmethod
    def _is_ancestor(a: _RadixNode, b: _RadixNode) -> bool:
        while b is not None:
            if b is a:
                return True
            b = b.parent
        return False

    def releasable(self) -> int:
        """POOL pages the tree could actually FREE by evicting
        everything: T0 nodes whose page has no holder besides the tree
        itself.  Tier-aware on purpose — a demoted node holds no pool
        page, so counting it would overstate what eviction can reclaim
        and let an unsatisfiable reservation wipe the cache for
        nothing.  The engine checks this before evicting; when even a
        full wipe cannot cover a reservation, the request waits for
        residents to finish instead and future prefix hits survive."""
        refcount = self._alloc.refcount
        return sum(1 for n in self._pool_lru if refcount(n.page) == 1)

    def _leaves(self) -> List[_RadixNode]:
        out, stack = [], list(self._root.children.values())
        while stack:
            n = stack.pop()
            if n.children:
                stack.extend(n.children.values())
            else:
                out.append(n)
        return out

    def evict(self, need_free: int) -> int:
        """Drop LRU leaves until the allocator has `need_free` free
        pages or nothing is evictable.  Dropping a leaf decrefs its
        page — shared pages survive until their sharers finish.  Returns
        the number of nodes dropped.

        One DFS seeds a heap of leaves; a drop that exposes its parent
        pushes the parent, so a whole cold branch unwinds in O(log n)
        per node instead of rescanning the trie per freed page.  A
        parent touched AFTER its leaf (heap entries are stale snapshots)
        re-enters the heap with its CURRENT last_used, so recency is
        honored at pop time."""
        import heapq
        if self._alloc.free_pages >= need_free:
            return 0
        heap = [(n.last_used, i, n)
                for i, n in enumerate(self._leaves())]
        heapq.heapify(heap)
        tick = len(heap)
        dropped = 0
        while self._alloc.free_pages < need_free and heap:
            seen, _, victim = heapq.heappop(heap)
            if victim.children \
                    or victim.parent.children.get(victim.key) is not victim:
                continue  # stale entry (no longer a leaf / already gone)
            if victim.last_used != seen:
                tick += 1
                heapq.heappush(heap, (victim.last_used, tick, victim))
                continue  # touched since snapshot: re-sort by recency
            parent = victim.parent
            del parent.children[victim.key]
            self._unindex(victim)
            if victim.tier == TIER_POOL:
                self._alloc.decref(victim.page)
            else:
                # A demoted leaf frees no pool page, but dropping it
                # exposes its (warmer, possibly T0) parent to the heap.
                # Its T2 copy persists in the store; a T1 payload's
                # arena slot is handed back through the release hook.
                self._drop_payload(victim)
            self._retier(victim, None)
            self.nodes -= 1
            dropped += 1
            if parent is not self._root and not parent.children:
                tick += 1
                heapq.heappush(heap, (parent.last_used, tick, parent))
        return dropped

    def clear(self) -> None:
        for child in list(self._root.children.values()):
            self.drop(child)
