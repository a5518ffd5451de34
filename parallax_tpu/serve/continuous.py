"""Slot-based continuous decoding (Orca-style, PAPERS.md) over a
paged KV pool, with chunked prefill and speculative decoding (ISSUE 6).

Static batching decodes a batch until its SLOWEST sequence finishes:
a 5-token reply waits for the 120-token one next to it, and the batch
slot it occupies does nothing in between. The continuous scheduler
keeps a fixed set of ``max_batch`` *slots* over one compiled KV-cached
decode step and treats membership as dynamic:

* every iteration runs ONE batched step for all slots (one signature,
  one executable — the step function takes per-slot positions, so
  slots at different depths coexist in one dispatch);
* a slot whose sequence just emitted EOS (or hit its token budget, or
  blew its deadline) RETIRES immediately — its request completes now,
  not when the batch's slowest member finishes;
* the freed slot REFILLS from the request queue — the batch never
  flushes, occupancy stays high under load.

Three throughput layers ride on top of the PR 4 scheduler:

* **paged KV** — a :class:`~parallax_tpu.serve.paging.PageAllocator`
  owns a fixed pool of fixed-size pages; a refill allocates
  ``ceil(cap / page_size)`` pages and a retire frees them, so slot
  count becomes a pure scheduling knob (8-64x the dense layout's) and
  admission is governed by pool memory. Exhaustion DEFERS the refill
  (the request stays queued, ``serve.kv_refill_deferred`` counts it)
  instead of failing it — pages free as sequences retire.
* **chunked prefill** — with a chunked program
  (``num_prefill_chunks > 1``) at most ONE prefill piece runs per
  scheduler iteration, so a long newcomer costs every decoding slot a
  bounded slice of latency per step instead of a whole prefill stall.
* **speculative decoding** — with ``spec_tokens = k`` the iteration
  becomes k small DRAFT steps + one target VERIFY dispatch; the
  longest agreeing prefix (plus the target's correction/bonus token)
  is emitted, 1..k+1 tokens per iteration. Exact under greedy: the
  verify step is bit-identical to k+1 single steps, so acceptance
  reproduces the plain greedy sequence token for token.

Correctness rides on per-slot independence: every per-token op
(projections, attention with per-slot position masks, layer norms,
argmax) is row-wise, so a slot's tokens are bit-identical to decoding
its request alone — tested against per-request standalone decode in
tests/test_serve.py and tests/test_paged_kv.py.

The model plugs in as a :class:`DecodeProgram` (duck-typed; see
serve/adapters.py for the NMT implementation). Every device callable
is warmed at construction, so serving never meets an XLA compile.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional

import jax
import numpy as np

from parallax_tpu.common.lib import parallax_log
from parallax_tpu.obs import trace
from parallax_tpu.serve.batcher import (DeadlineExceeded, Request,
                                        RequestQueue)
from parallax_tpu.serve.paging import PageAllocator, PagePoolExhausted


class DecodeProgram:
    """The interface a decode model exposes to the scheduler (duck
    typed — subclassing is optional; serve/adapters.py implements it
    for NMT). All shapes are FIXED per program instance so the whole
    serving loop runs on a closed signature set.

    Attributes: ``max_len`` (decode buffer length — the per-request
    token cap), ``bos_id`` / ``eos_id`` / ``pad_id``. Optional
    capability attributes (defaults in parentheses):

    * ``paged`` (False): self-KV lives in a page pool; the program
      additionally exposes ``page_size``, ``pool_pages``,
      ``pages_per_seq`` and ``pages_needed(cap)``, and ``step`` /
      ``spec_step`` take the ``[slots, pages_per_seq]`` int32 page
      table (unallocated entries hold the sentinel ``pool_pages``).
    * ``num_prefill_chunks`` (1): when > 1, prefill runs through
      ``prefill_chunk(params, carry, k)`` — carry is the prepared feed
      at k=0, the request state after the last chunk.
    * ``spec_tokens`` (0): when k >= 1, the scheduler calls
      ``spec_step(params, state, tok, t, prev_tok, pages) ->
      (y [S, k+1], proposals [S, k], state)`` instead of ``step`` and
      accepts the longest agreeing prefix (``prev_tok`` is the content
      at position t-1 — the draft's catch-up input).
    * ``insert_pages`` (False): decoder-only programs whose PROMPT KV
      lands in the slot's own paged decode buffer take the slot's page
      row too: ``insert(state, slot, request_state, pages_row)`` with
      ``pages_row`` the ``[pages_per_seq]`` int32 row (sentinel-filled
      past the allocation). The insert must route padded prompt rows
      through the sentinel (OOB -> dropped) so a prefix-mapped slot
      never writes garbage into shared pages.
    * ``kv_prefix_positions(feed) -> int`` (optional): how many decode
      buffer positions the PROMPT occupies before the first decoded
      token (0 for encoder-decoder programs, whose self-KV starts
      empty). The scheduler uses it to convert token counts into page
      offsets for prefix sharing and retire-time caching.

    Core callables (shapes fixed per instance):

    * ``example_feed() -> dict`` — one request's feed at the padded
      shapes ``prefill`` accepts (used for warmup and planning).
    * ``prepare_feed(feed) -> dict`` — validate/pad one request's raw
      feed onto the fixed prefill shapes.
    * ``init_state(params, slots) -> state`` — fresh device state for
      ``slots`` slots (KV caches/pool, encoder memory, masks).
    * ``prefill(params, feed) -> request_state`` — run the one-time
      per-request work (e.g. the encoder + cross-attention K/V) for a
      single request in one dispatch.
    * ``insert(state, slot, request_state) -> state`` — write one
      prefilled request into slot ``slot`` (an int32 scalar; traced,
      so any slot index shares one compiled insert).
    * ``step(params, state, tok, t) -> (next_tok, state)`` — one
      batched decode step: ``tok``/``t`` are ``[slots]`` int32 arrays
      of each slot's current token and position; returns each slot's
      next token. Inactive slots' lanes compute garbage the scheduler
      ignores — they must not affect other lanes (row-wise ops only).
    """


class _Slot:
    __slots__ = ("req", "tokens", "t", "cap", "pages", "rs", "key",
                 "entry", "replayed", "base")

    def __init__(self, req: Request, cap: int, pages: List[int]):
        self.req = req
        self.tokens: List[int] = []
        self.t = 0
        self.cap = cap
        self.pages = pages
        # decode-buffer positions the PROMPT occupies ahead of the
        # decoded tokens (kv_prefix_positions; 0 for encoder-decoder
        # programs) — page-occupancy math is in POSITIONS, not tokens
        self.base = 0
        # prefix-reuse bookkeeping (ISSUE 15): the prefill request
        # state (kept so a retiring sequence can be cached), the radix
        # key, the mapped cache entry (pinned while we run), and how
        # many of `tokens` were REPLAYED rather than decoded
        self.rs = None
        self.key = None
        self.entry = None
        self.replayed = 0


class _Prefill:
    """One in-flight chunked prefill: the reserved slot, its allocated
    pages, the carry between chunks and the next chunk index."""

    __slots__ = ("req", "slot", "pages", "carry", "k", "key")

    def __init__(self, req: Request, slot: int, pages: List[int],
                 key=None):
        self.req = req
        self.slot = slot
        self.pages = pages
        self.carry = req.feed
        self.k = 0
        self.key = key


class ContinuousScheduler:
    """Drives one :class:`DecodeProgram` over a request queue on a
    daemon thread; constructed (and owned) by
    :class:`~parallax_tpu.serve.session.ServeSession`."""

    TOKENS_PER_SEC_WINDOW = 50

    def __init__(self, program, params, serve_config, metrics,
                 queue: RequestQueue,
                 name: str = "parallax-serve-decode",
                 on_deadline_breach=None, replica_id=None,
                 faults=None, on_fatal=None, on_error=None, *,
                 state_sharding):
        self._program = program
        self._params = params
        # where the decode state (KV caches, page pool) lives: the
        # owning session's mesh. ``init_state`` builds on jax's default
        # device, so without this every replica's fresh pool sat on
        # device 0 until its first dispatch moved it.
        self._state_sharding = state_sharding
        self._sc = serve_config
        self._queue = queue
        self.metrics = metrics
        # fleet wiring (ISSUE 7): deterministic fault hooks consulted
        # once per loop pass, and death/error reporting for the router
        self._replica_id = replica_id
        self._faults = faults
        self._on_fatal = on_fatal
        self._on_error = on_error
        self.alive = True
        self.heartbeat = time.perf_counter()
        # SLO-breach hook for MID-DECODE expiries (queued expiries go
        # through the queue's own on_timeout); the serve session points
        # it at the flight recorder
        self._on_deadline_breach = on_deadline_breach
        self._S = int(serve_config.max_batch)
        self._ttft = metrics.histogram("serve.ttft_ms")
        self._latency = metrics.histogram("serve.request_latency_ms")
        self._occupancy = metrics.histogram("serve.batch_occupancy")
        self._step_ms = metrics.histogram("serve.step_ms")
        self._tokens = metrics.counter("serve.tokens")
        self._completed = metrics.counter("serve.completed")
        self._timeouts = metrics.counter("serve.timeouts")
        self._steps = metrics.counter("serve.decode_steps")
        self._tok_times: collections.deque = collections.deque(
            maxlen=self.TOKENS_PER_SEC_WINDOW)
        metrics.gauge("serve.tokens_per_sec").set_fn(self.tokens_per_sec)

        # capability probes (duck-typed; PR 4 programs keep defaults)
        self._paged = bool(getattr(program, "paged", False))
        self._chunks = int(getattr(program, "num_prefill_chunks", 1))
        self._spec = int(getattr(program, "spec_tokens", 0))
        self._insert_pages = bool(getattr(program, "insert_pages",
                                          False))
        self._kvpos = getattr(program, "kv_prefix_positions", None)
        if self._paged:
            self._alloc = PageAllocator(program.pool_pages)
            self._P = int(program.pages_per_seq)
            self._sentinel = int(program.pool_pages)
            self._pages = np.full((self._S, self._P), self._sentinel,
                                  np.int32)
            # serve.kv_pages_in_use counts each PHYSICAL page once
            # however many sequences/cache entries map it (the
            # allocator's distinct-page accounting, ISSUE 15 — naive
            # per-slot summing would double-count shared pages and
            # trip the leak checks); the sharing multiplier is its own
            # gauge family next to it
            self._pages_gauge = metrics.gauge("serve.kv_pages_in_use")
            self._pages_gauge.set(0)
            metrics.gauge("serve.kv_pool_pages").set(self._sentinel)
            self._defer = metrics.counter("serve.kv_refill_deferred")
            metrics.gauge("serve.kv_page_refs").set_fn(
                lambda: self._alloc.total_refs)
            metrics.gauge("serve.kv_shared_pages").set_fn(
                lambda: self._alloc.shared_pages)
            metrics.gauge("serve.kv_sharing_ratio").set_fn(
                lambda: round(self._alloc.sharing_ratio(), 4))
        else:
            self._pages = None
        # prefix-aware KV reuse (ISSUE 15, serve/prefixcache.py)
        self._prefix = None
        if bool(getattr(serve_config, "prefix_cache", False)):
            if not self._paged or not hasattr(program, "copy_page") \
                    or not hasattr(program, "prefix_key"):
                raise ValueError(
                    "ServeConfig.prefix_cache requires a PAGED "
                    "DecodeProgram exposing prefix_key/copy_page "
                    "(page-table indirection is what makes shared "
                    "read-only pages possible)")
            from parallax_tpu.serve.prefixcache import RadixPrefixCache
            self._ps = int(program.page_size)
            self._prefix = RadixPrefixCache(
                self._alloc,
                max_pages=getattr(serve_config,
                                  "prefix_cache_max_pages", None),
                max_entries=getattr(serve_config,
                                    "prefix_cache_max_entries", None))
            self._pfx_hits = metrics.counter("serve.prefix.hits")
            self._pfx_misses = metrics.counter("serve.prefix.misses")
            self._pfx_full = metrics.counter("serve.prefix.full_hits")
            self._pfx_cow = metrics.counter("serve.prefix.cow_copies")
            self._pfx_replayed = metrics.counter(
                "serve.prefix.replayed_tokens")
            self._pfx_skipped = metrics.counter(
                "serve.prefix.prefill_tokens_skipped")
            metrics.gauge("serve.prefix.hit_rate").set_fn(
                self.prefix_hit_rate)
            metrics.gauge("serve.prefix.evictions").set_fn(
                lambda: self._prefix.evictions)
            metrics.gauge("serve.prefix.cached_pages").set_fn(
                lambda: self._prefix.cached_pages)
            metrics.gauge("serve.prefix.entries").set_fn(
                lambda: self._prefix.num_entries)
            metrics.gauge("serve.prefix.shared_pages").set_fn(
                lambda: self._alloc.shared_pages)
        if self._chunks > 1:
            self._chunk_ctr = metrics.counter("serve.prefill_chunks")
        if self._spec:
            self._spec_proposed = metrics.counter("serve.spec_proposed")
            self._spec_accepted = metrics.counter("serve.spec_accepted")
            metrics.gauge("serve.spec_accept_rate").set_fn(
                self.spec_accept_rate)
        self._pending: List[_Prefill] = []
        # True while a request is popped-from-queue but not yet
        # activated into a slot (or parked in _pending): in that
        # window it is invisible to both len(queue) and _active(),
        # and idle() must NOT report quiesced — a hot-swap landing
        # there would mix weights mid-sequence
        self._refilling = False

        self._slots: List[Optional[_Slot]] = [None] * self._S
        self._tok = np.full((self._S,), program.pad_id, np.int32)
        # content at position t-1 per slot (the speculative catch-up
        # input; BOS right after a refill, where t == 0)
        self._prev = np.full((self._S,), program.pad_id, np.int32)
        self._t = np.zeros((self._S,), np.int32)
        self._stop = threading.Event()
        self._kick = threading.Event()
        self._warm()
        self._state = self._fresh_state()
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        self._thread.start()

    def _fresh_state(self):
        return jax.device_put(
            self._program.init_state(self._params, self._S),
            self._state_sharding)

    # -- insert dispatch ---------------------------------------------------

    def _insert(self, state, j: int, rs, pages: List[int]):
        """One compiled insert, routed by the program's capability: an
        ``insert_pages`` program scatters the prompt KV through the
        slot's page row (sentinel-filled past the allocation, so padded
        prompt rows drop OOB instead of landing in shared pages)."""
        if self._insert_pages:
            row = np.full((self._P,), self._sentinel, np.int32)
            row[:len(pages)] = pages
            return self._program.insert(state, np.int32(j), rs, row)
        return self._program.insert(state, np.int32(j), rs)

    # -- warmup ------------------------------------------------------------

    def _warm(self) -> None:
        """Execute every device callable the serving loop can dispatch
        once on dummy inputs — prefill (all chunks), insert, and the
        plain or speculative step — so the COMPLETE signature set is
        compiled before serving (the state this writes is discarded —
        a fresh one is built after)."""
        prog, params = self._program, self._params
        t0 = time.perf_counter()
        with trace.span("serve.warmup_compile", mode="decode"):
            state = self._fresh_state()
            feed = prog.prepare_feed(prog.example_feed())
            if self._chunks > 1:
                carry = feed
                for k in range(self._chunks):
                    carry = prog.prefill_chunk(params, carry, k)
                rs = carry
            else:
                rs = prog.prefill(params, feed)
            state = self._insert(state, 0, rs, [])
            tok = np.full((self._S,), prog.bos_id, np.int32)
            tz = np.zeros((self._S,), np.int32)
            pages = self._pages.copy() if self._paged else None
            if self._spec:
                y, _, state = prog.spec_step(params, state, tok, tz,
                                             tok, pages)
                jax.block_until_ready(y)
            else:
                if self._paged:
                    nxt, state = prog.step(params, state, tok, tz,
                                           pages)
                else:
                    nxt, state = prog.step(params, state, tok, tz)
                jax.block_until_ready(nxt)
            # one more insert against the POST-step state: step outputs
            # are committed device arrays whose jit signature differs
            # from the fresh init_state leaves the first insert saw —
            # without this, the first live retire-and-refill pays one
            # serve-time compile
            state = self._insert(state, 0, rs, [])
            if self._prefix is not None:
                # the copy-on-write page copy joins the closed
                # signature set: warmed against the post-insert state
                # (the state it runs on live, at a cache hit)
                state = prog.copy_page(state, np.int32(0), np.int32(0))
            jax.block_until_ready(jax.tree_util.tree_leaves(state)[0])
        dt = time.perf_counter() - t0
        self.metrics.histogram("serve.compile_seconds").record(dt)
        parallax_log.info(
            "serve decode warmup: prefill(%d chunk(s))/insert/%s "
            "compiled in %.2fs (%d slots%s)",
            self._chunks, "spec_step" if self._spec else "step", dt,
            self._S,
            f", {self._sentinel}-page pool" if self._paged else "")

    # -- admission hooks (called by ServeSession) --------------------------

    def make_request(self, feed, deadline,
                     max_new_tokens: Optional[int],
                     tenant=None, slo_rank: int = 0) -> Request:
        prog = self._program
        cap = int(max_new_tokens or prog.max_len)
        if cap < 1 or cap > prog.max_len:
            raise ValueError(
                f"max_new_tokens={max_new_tokens} outside [1, "
                f"{prog.max_len}] (the program's decode buffer)")
        return Request(prog.prepare_feed(feed), deadline=deadline,
                       max_new_tokens=cap, tenant=tenant,
                       slo_rank=slo_rank)

    def kick(self) -> None:
        self._kick.set()

    def tokens_per_sec(self) -> Optional[float]:
        window = list(self._tok_times)
        if len(window) < 2:
            return None
        dt = window[-1][0] - window[0][0]
        n = sum(c for _, c in window[1:])
        return n / dt if dt > 0 else None

    def spec_accept_rate(self) -> Optional[float]:
        if not self._spec:
            return None
        prop = self._spec_proposed.value
        return (self._spec_accepted.value / prop) if prop else None

    def prefix_hit_rate(self) -> Optional[float]:
        if self._prefix is None:
            return None
        hits = self._pfx_hits.value
        lookups = hits + self._pfx_misses.value
        return (hits / lookups) if lookups else None

    def prefix_stats(self) -> Optional[dict]:
        """The radix cache's own snapshot (entries / cached pages /
        pins / per-run insert+evict totals), None without the cache."""
        return None if self._prefix is None else self._prefix.stats()

    # -- paging ------------------------------------------------------------

    def _try_alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh pages, reclaiming from the prefix cache when
        the pool is exhausted: LRU *unpinned* cached prefixes are
        evicted until the grant fits (graceful degradation under
        pressure, ISSUE 15 — the cache is a scavenger of free memory,
        never a reason to stall admission). None when even eviction
        cannot free enough (defer)."""
        try:
            return self._alloc.alloc(n)
        except PagePoolExhausted:
            if self._prefix is not None \
                    and self._prefix.evict_for(n) > 0:
                try:
                    return self._alloc.alloc(n)
                except PagePoolExhausted:
                    return None
            return None

    def _alloc_pages(self, req: Request) -> Optional[List[int]]:
        """Pages for one refill, or None to DEFER (pool exhausted —
        retiring sequences will free pages; the request stays queued)."""
        if not self._paged:
            return []
        n = self._program.pages_needed(req.max_new_tokens)
        ids = self._try_alloc(n)
        if ids is None:
            self._defer.inc()
            return None
        self._pages_gauge.set(self._alloc.in_use)
        return ids

    def _release_pages(self, pages: List[int]) -> None:
        if self._paged and pages:
            self._alloc.free(pages)
            self._pages_gauge.set(self._alloc.in_use)

    def _clear_slot(self, j: int) -> None:
        self._tok[j] = self._program.pad_id
        self._prev[j] = self._program.pad_id
        self._t[j] = 0
        if self._paged:
            self._pages[j, :] = self._sentinel

    # -- refill / prefill --------------------------------------------------

    def _activate(self, j: int, req: Request, pages: List[int],
                  rs, key=None, entry=None, replay=()) -> None:
        if req.rec is not None:
            # prefill done, slot owned: everything from here to retire
            # is the decode phase of the request timeline
            req.rec.mark("decode")
            req.rec.kv_pages = len(pages)
        self._state = self._insert(self._state, j, rs, pages)
        slot = _Slot(req, req.max_new_tokens, pages)
        slot.key = key
        slot.entry = entry
        if self._kvpos is not None:
            slot.base = int(self._kvpos(req.feed))
        if self._prefix is not None:
            # kept so the retiring sequence can be cached (the entry's
            # prefill state); dropped at retire either way
            slot.rs = rs
        if replay:
            # prefix-cache replay: the slot resumes AFTER the cached
            # tokens — its next decode step continues at position
            # len(replay) on top of the mapped pages
            slot.tokens = [int(t) for t in replay]
            slot.t = len(slot.tokens)
            slot.replayed = slot.t
        self._slots[j] = slot
        self._tok[j] = (int(replay[-1]) if replay
                        else self._program.bos_id)
        self._prev[j] = (int(replay[-2]) if len(replay) >= 2
                         else self._program.bos_id)
        self._t[j] = slot.t
        if self._paged:
            self._pages[j, :] = self._sentinel
            self._pages[j, :len(pages)] = pages

    # -- prefix-aware admission (ISSUE 15) ---------------------------------

    def _try_prefix_admit(self, j: int, req: Request):
        """Try to serve ``req`` from the radix cache. Returns one of

        * ``("completed", None)`` — full hit: every token the request
          could emit is cached; it was completed with ZERO device
          dispatches and slot ``j`` stays free;
        * ``("activated", None)`` — partial hit: cached tokens
          replayed, shared pages mapped read-only (+ one COW copy at
          the divergence boundary), slot ``j`` now decodes the
          continuation;
        * ``("deferred", None)`` — hit, but the continuation's fresh
          pages are unavailable even after eviction (requeued);
        * ``("miss", key)`` — no entry; the caller runs the normal
          prefill and threads ``key`` through for retire-time insert.
        """
        prog = self._program
        key = prog.prefix_key(req.feed)
        tenant = getattr(req, "tenant", None)
        entry = self._prefix.lookup(tenant, key)
        if entry is None:
            self._pfx_misses.inc()
            return "miss", key
        cap = req.max_new_tokens
        toks = entry.tokens
        n_replay = min(len(toks), cap)
        eos = prog.eos_id
        if eos in toks[:n_replay]:
            n_replay = toks.index(eos) + 1
        # an IMPORTED entry (disaggregation: externally-prefilled
        # request state, no decoded tokens yet) replays nothing — it
        # exists purely to skip the local prefill, so n_replay may be 0
        full = (n_replay == cap) or (n_replay > 0
                                     and toks[n_replay - 1] == eos)
        skipped = (int(prog.prefill_tokens(req.feed))
                   if hasattr(prog, "prefill_tokens") else 0)
        base = (int(self._kvpos(req.feed))
                if self._kvpos is not None else 0)
        if not full:
            # continuation: map the cached FULL pages read-only, COW
            # the boundary page, own fresh pages for the rest. Sharing
            # is accounted in decode-buffer POSITIONS (prompt prefix +
            # replayed tokens), not tokens — for an encoder-decoder
            # program base == 0 and the two coincide
            p_need = prog.pages_needed(cap)
            shared_pos = min(int(entry.positions), base + n_replay)
            shared_full = shared_pos // self._ps
            partial = (shared_pos % self._ps) != 0
            # pin FIRST: the fresh-page grant below may evict LRU
            # cache entries to make room, and the entry being mapped
            # must never be its own eviction victim
            self._prefix.pin(entry)
            fresh = self._try_alloc(p_need - shared_full)
            if fresh is None:
                self._prefix.unpin(entry)
                self._defer.inc()
                if req.rec is not None:
                    req.rec.mark("slot_wait")
                self._queue.requeue_front(req)
                return "deferred", None
            shared = [int(p) for p in entry.pages[:shared_full]]
            if shared:
                self._alloc.share(shared)
            if partial:
                # copy-on-write: the first divergent write (position
                # n_replay, next step) lands inside a cached page —
                # device-copy it into a mapper-owned page FIRST, so
                # the cached original is never written again
                self._state = prog.copy_page(
                    self._state, np.int32(fresh[0]),
                    np.int32(entry.pages[shared_full]))
                self._pfx_cow.inc()
            self._pages_gauge.set(self._alloc.in_use)
        self._pfx_hits.inc()
        self._pfx_replayed.inc(n_replay)
        self._pfx_skipped.inc(skipped)
        rec = req.rec
        if rec is not None:
            # the explicit skipped-prefill attribution: the window a
            # cold request would spend in `prefill` shows up as a
            # (near-zero) `prefix_replay` phase plus the skipped-token
            # counts on the record
            rec.mark("prefix_replay")
            rec.prefill_tokens_skipped = skipped
            rec.prefix_hit_pages = (n_replay + self._ps - 1) // self._ps
        if full:
            self._pfx_full.inc()
            now = time.perf_counter()
            out = np.asarray(toks[:n_replay], np.int32)
            req.t_first_token = now
            self._ttft.record((now - req.t_enqueue) * 1e3)
            if rec is not None:
                rec.first_token(now)
                rec.tokens = n_replay
                rec.decode_steps = 0
            req._complete(out)
            self._completed.inc()
            self._latency.record((now - req.t_enqueue) * 1e3)
            trace.record_span(
                "serve.request", req.t_enqueue, now, id=req.id,
                tokens=n_replay, replica=self._replica_id,
                rid=(rec.key if rec is not None else req.id),
                hops=(len(rec.hops) if rec is not None else 1))
            return "completed", None
        with trace.span("serve.prefix_map", slot=j, id=req.id,
                        replay=n_replay):
            self._activate(j, req, shared + fresh, entry.request_state,
                           key=key, entry=entry, replay=toks[:n_replay])
        # the replayed tokens are client-visible NOW — TTFT is the
        # map latency, not a prefill + first decode step. An imported
        # entry replays NOTHING (it only skipped the prefill): no
        # token is visible yet, so TTFT waits for the first decode
        # step's _emit
        if n_replay > 0:
            now = time.perf_counter()
            req.t_first_token = now
            self._ttft.record((now - req.t_enqueue) * 1e3)
            if rec is not None:
                rec.first_token(now)
        return "activated", None

    def import_prefix(self, tenant, key, request_state,
                      positions: int = 0) -> bool:
        """Install an EXTERNALLY-prefilled request state (the
        disaggregation import path, serve/disagg.py) as a page-less
        prefix-cache entry: ``tokens=[]`` / ``pages=[]``, so a matching
        admission takes the hit path with ``n_replay == 0`` — it skips
        the local prefill entirely and the insert re-scatters the
        prompt KV from ``request_state`` into freshly-owned pages.
        Thread-safe (the radix cache locks internally); returns False
        when a longer local entry already covers the key (which is
        strictly better — nothing to do)."""
        if self._prefix is None:
            raise ValueError(
                "import_prefix requires ServeConfig.prefix_cache "
                "(the radix index is the import surface)")
        return self._prefix.insert(tenant, key, [], [], request_state,
                                   positions=positions)

    def _refill(self) -> None:
        """Unchunked path: fill free slots from the queue, one whole
        single-request prefill each (or a prefix-cache replay),
        inserted without touching the running slots. A FULL cache hit
        completes without consuming the slot — the loop keeps draining
        the queue through it, so a burst of fully-cached requests is
        answered in one pass instead of one per scheduler iteration."""
        for j in range(self._S):
            if self._slots[j] is not None:
                continue
            while self._slots[j] is None:
                req = self._queue.pop(timeout=0.0)
                if req is None:
                    return
                self._refilling = True
                try:
                    key = None
                    if self._prefix is not None:
                        outcome, key = self._try_prefix_admit(j, req)
                        if outcome == "deferred":
                            return
                        if outcome == "completed":
                            continue  # slot still free: keep draining
                        if outcome == "activated":
                            break
                    if req.rec is not None:
                        req.rec.mark("prefill")
                    pages = self._alloc_pages(req)
                    if pages is None:
                        if req.rec is not None:
                            # pool exhausted: the wait back at the
                            # queue head is slot/page pressure, not
                            # queue depth
                            req.rec.mark("slot_wait")
                        self._queue.requeue_front(req)
                        return
                    with trace.span("serve.prefill", slot=j, id=req.id):
                        rs = self._program.prefill(self._params,
                                                   req.feed)
                        self._activate(j, req, pages, rs, key=key)
                finally:
                    self._refilling = False

    def _free_slot(self) -> Optional[int]:
        reserved = {pp.slot for pp in self._pending}
        for j in range(self._S):
            if self._slots[j] is None and j not in reserved:
                return j
        return None

    def _advance_prefill(self) -> None:
        """Chunked path: run at most ONE prefill piece this iteration —
        start a new prefill when none is pending (slot + pages
        permitting), else advance the oldest by one chunk; the last
        chunk's output is inserted into the reserved slot."""
        if not self._pending:
            j = self._free_slot()
            if j is None:
                return
            while True:
                req = self._queue.pop(timeout=0.0)
                if req is None:
                    return
                self._refilling = True
                try:
                    key = None
                    if self._prefix is not None:
                        outcome, key = self._try_prefix_admit(j, req)
                        if outcome == "completed":
                            # full hit: the slot is still free — keep
                            # draining fully-cached requests this pass
                            continue
                        if outcome != "miss":
                            # activated (slot consumed, no chunks to
                            # run) or deferred (requeued)
                            return
                    if req.rec is not None:
                        req.rec.mark("prefill")
                    pages = self._alloc_pages(req)
                    if pages is None:
                        if req.rec is not None:
                            req.rec.mark("slot_wait")
                        self._queue.requeue_front(req)
                        return
                    self._pending.append(_Prefill(req, j, pages,
                                                  key=key))
                    break
                finally:
                    self._refilling = False
        pp = self._pending[0]
        t_chunk = time.perf_counter()
        with trace.span("serve.prefill_chunk", slot=pp.slot,
                        id=pp.req.id, k=pp.k):
            pp.carry = self._program.prefill_chunk(self._params,
                                                   pp.carry, pp.k)
        if pp.req.rec is not None:
            pp.req.rec.note_prefill_chunk(
                (time.perf_counter() - t_chunk) * 1e3)
        pp.k += 1
        self._chunk_ctr.inc()
        if pp.k == self._chunks:
            self._pending.pop(0)
            self._activate(pp.slot, pp.req, pp.pages, pp.carry,
                           key=pp.key)

    # -- retire / expire / fail --------------------------------------------

    def _teardown_slot(self, slot: _Slot, cache: bool) -> None:
        """Release one slot's page holdings. With ``cache`` (a clean
        retire under the prefix cache) the refs of the WRITTEN pages
        transfer to the radix index — the just-finished sequence
        becomes the next identical request's replay — and only the
        unwritten tail frees; otherwise (expiry, failure, cache off)
        every ref this slot holds is dropped. Either way the mapped
        entry's pin releases first, so LRU eviction sees the truth."""
        if slot.entry is not None:
            self._prefix.unpin(slot.entry)
            slot.entry = None
        if (cache and self._prefix is not None and slot.key is not None
                and slot.t > 0 and slot.pages):
            pos = slot.base + int(slot.t)
            used = min(-(-pos // self._ps), len(slot.pages))
            self._prefix.insert(getattr(slot.req, "tenant", None),
                                slot.key, slot.tokens,
                                slot.pages[:used], slot.rs,
                                positions=pos)
            tail = slot.pages[used:]
            if tail:
                self._alloc.free(tail)
            if self._paged:
                self._pages_gauge.set(self._alloc.in_use)
        else:
            self._release_pages(slot.pages)
        slot.rs = None

    def _retire(self, j: int, now: float) -> None:
        slot = self._slots[j]
        self._slots[j] = None
        self._teardown_slot(slot, cache=True)
        self._clear_slot(j)
        req = slot.req
        rec = req.rec
        if rec is not None:
            rec.tokens = len(slot.tokens)
            rec.decode_steps = int(slot.t) - int(slot.replayed)
        req._complete(np.asarray(slot.tokens, np.int32))
        self._completed.inc()
        self._latency.record((now - req.t_enqueue) * 1e3)
        # ONE span per logical request, emitted by the delivering
        # replica only (a crashed hop never retires), carrying the
        # final replica id and hop count — the failover-visibility
        # contract tests/test_fleet.py asserts
        trace.record_span(
            "serve.request", req.t_enqueue, now, id=req.id,
            tokens=len(slot.tokens), replica=self._replica_id,
            rid=(rec.key if rec is not None else req.id),
            hops=(len(rec.hops) if rec is not None else 1))

    def _expire_slots(self, now: float) -> None:
        n_expired = 0
        for j, slot in enumerate(self._slots):
            if slot is None or slot.req.deadline is None:
                continue
            if now > slot.req.deadline:
                self._slots[j] = None
                self._teardown_slot(slot, cache=False)
                self._clear_slot(j)
                self._timeouts.inc()
                n_expired += 1
                slot.req._fail(DeadlineExceeded(
                    f"request {slot.req.id} deadline expired mid-"
                    f"decode after {len(slot.tokens)} token(s)"))
        for pp in list(self._pending):
            if pp.req.deadline is not None and now > pp.req.deadline:
                self._pending.remove(pp)
                self._release_pages(pp.pages)
                self._timeouts.inc()
                n_expired += 1
                pp.req._fail(DeadlineExceeded(
                    f"request {pp.req.id} deadline expired mid-"
                    f"prefill after {pp.k} chunk(s)"))
        if n_expired and self._on_deadline_breach is not None:
            try:
                self._on_deadline_breach(n_expired, where="decode")
            except Exception:
                # forensics must never take the decode loop down
                pass

    def _fail_active(self, exc) -> None:
        """Fail every in-flight slot and pending prefill — called ONLY
        from the scheduler thread (slot state is single-owner; a
        cross-thread mutation here would race the decode loop)."""
        for j, slot in enumerate(self._slots):
            if slot is not None:
                self._slots[j] = None
                self._teardown_slot(slot, cache=False)
                self._clear_slot(j)
                slot.req._fail(exc)
        for pp in self._pending:
            self._release_pages(pp.pages)
            pp.req._fail(exc)
        self._pending = []

    # -- the scheduling loop ----------------------------------------------

    def _active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def _emit(self, j: int, token: int, now: float) -> bool:
        """Deliver one token to slot ``j``; True when the slot retired
        (EOS or cap)."""
        slot = self._slots[j]
        if slot.req.t_first_token is None:
            slot.req.t_first_token = now
            self._ttft.record((now - slot.req.t_enqueue) * 1e3)
            if slot.req.rec is not None:
                slot.req.rec.first_token(now)
        slot.tokens.append(token)
        slot.t += 1
        self._prev[j] = self._tok[j]
        self._tok[j] = token
        self._t[j] = slot.t
        if token == self._program.eos_id or len(slot.tokens) >= slot.cap:
            self._retire(j, now)
            return True
        return False

    def _plain_iteration(self, n_active: int) -> None:
        prog = self._program
        t0 = time.perf_counter()
        with trace.span("serve.step", active=n_active):
            if self._paged:
                nxt, self._state = prog.step(
                    self._params, self._state, self._tok, self._t,
                    self._pages.copy())
            else:
                nxt, self._state = prog.step(
                    self._params, self._state, self._tok, self._t)
            nxt = np.asarray(nxt)  # block: tokens ready
        now = time.perf_counter()
        self._step_ms.record((now - t0) * 1e3)
        self._steps.inc()
        self._occupancy.record(n_active / self._S)
        emitted = 0
        for j in range(self._S):
            if self._slots[j] is None:
                continue
            self._emit(j, int(nxt[j]), now)
            emitted += 1
        self._tokens.inc(emitted)
        self._tok_times.append((now, emitted))

    def _spec_iteration(self, n_active: int) -> None:
        """One speculative iteration: draft proposes k tokens, the
        target verifies k+1 in one dispatch, each slot accepts its
        longest agreeing prefix (1..k+1 tokens). Exact under greedy:
        proposal j is accepted iff it EQUALS the target's greedy
        choice, and the first disagreement is replaced by that greedy
        choice — the emitted stream is the plain greedy stream."""
        prog = self._program
        k = self._spec
        t0 = time.perf_counter()
        with trace.span("serve.spec_step", active=n_active, k=k):
            y, props, self._state = prog.spec_step(
                self._params, self._state, self._tok, self._t,
                self._prev,
                self._pages.copy() if self._paged else None)
            y = np.asarray(y)            # [S, k+1]; blocks
            props = np.asarray(props)    # [S, k]
        now = time.perf_counter()
        self._step_ms.record((now - t0) * 1e3)
        self._steps.inc()
        self._occupancy.record(n_active / self._S)
        emitted = 0
        for j in range(self._S):
            if self._slots[j] is None:
                continue
            n = 1
            while n <= k and props[j, n - 1] == y[j, n - 1]:
                n += 1
            self._spec_proposed.inc(k)
            self._spec_accepted.inc(n - 1)
            for g in range(n):
                emitted += 1
                if self._emit(j, int(y[j, g]), now):
                    break
        self._tokens.inc(emitted)
        self._tok_times.append((now, emitted))

    def _loop(self) -> None:
        try:
            self._run_loop()
        except BaseException as e:
            # replica death (injected crash, poisoned device state, a
            # bug in the program): a silently-dead daemon thread would
            # hang every client on result() — instead, fail everything
            # this replica holds NOW with the retryable wrapper and
            # report up, so a fleet can eject it and fail work over
            self._fatal(e)

    def _run_loop(self) -> None:
        from parallax_tpu.serve.batcher import ServeClosed
        while True:
            self.heartbeat = time.perf_counter()
            if self._stop.is_set():
                # fast close / drain window expired: in-flight decodes
                # are failed by THIS thread (single-owner slot state)
                self._fail_active(ServeClosed(
                    "session closed mid-decode"))
                return
            if self._faults is not None:
                # chaos hook: may raise ReplicaCrash (fatal path above)
                # or sleep through an injected stall
                self._faults.on_dispatch(self._replica_id)
            now = time.perf_counter()
            self._expire_slots(now)
            if self._chunks > 1:
                self._advance_prefill()
            else:
                self._refill()
            n_active = self._active()
            if n_active == 0:
                if self._pending:
                    continue  # keep prefill chunks flowing
                if self._queue.closed and len(self._queue) == 0:
                    return
                self._kick.wait(0.02)
                self._kick.clear()
                continue
            if self._spec:
                self._spec_iteration(n_active)
            else:
                self._plain_iteration(n_active)

    def _fatal(self, cause: BaseException) -> None:
        """The decode loop died: fail in-flight slots, pending
        prefills and the whole queue with ReplicaUnavailable (retryable
        — no request ever delivered a result, so failover cannot
        double-serve), close admission, report ``on_fatal``."""
        from parallax_tpu.serve.batcher import ReplicaUnavailable
        self.alive = False
        err = ReplicaUnavailable(
            f"decode replica died: {type(cause).__name__}: {cause}")
        err.__cause__ = cause
        try:
            self._fail_active(err)
        except Exception:
            pass
        self._queue.close()
        n = self._queue.fail_all(err)
        parallax_log.error(
            "serve decode loop died (%s); failed %d queued request(s)",
            cause, n)
        if self._on_error is not None:
            try:
                self._on_error(cause, n)
            except Exception:
                pass
        if self._on_fatal is not None:
            try:
                self._on_fatal(cause)
            except Exception:
                pass

    # -- fleet hooks -------------------------------------------------------

    def idle(self) -> bool:
        """No active slots, no pending prefills, nothing queued AND no
        request in the popped-but-not-yet-activated refill window —
        the quiesced state a weight hot-swap requires (a swap landing
        mid-prefill would compute the encoder under old weights and
        decode under new ones)."""
        return (not self._refilling and self._active() == 0
                and not self._pending and len(self._queue) == 0)

    def set_params(self, placed) -> None:
        """Swap the target params the decode step reads (live weight
        hot-swap). The reference is read once per iteration, so the
        swap is atomic at an iteration boundary; the caller quiesces
        the scheduler first (ServeFleet rotates the replica out) so no
        sequence mixes weights mid-decode. A speculative program's
        draft params live inside the program and are NOT swapped — a
        stale draft only lowers the acceptance rate, never correctness
        (verify is exact under greedy for ANY draft)."""
        self._params = placed

    def drain(self, timeout_s: float) -> None:
        """After ``queue.close()``: wait for in-flight + queued decodes
        to finish, hard-stopping at the timeout. Slot state is owned by
        the scheduler thread — undrained slots are failed by the loop
        itself when it observes the stop flag, never from here."""
        if timeout_s > 0:
            self._thread.join(timeout=timeout_s)
        self._stop.set()
        self._kick.set()
        self._thread.join(timeout=10.0)
        if self._thread.is_alive():
            parallax_log.warning(
                "serve decode thread did not stop within the drain "
                "window; in-flight requests may hang until their "
                "result() timeout")
        # the prefix cache intentionally holds pages while serving —
        # at close it releases everything evictable so the leak checks
        # ("0 pages in use after the last retire") stay meaningful
        if self._prefix is not None:
            self._prefix.clear()
            self._pages_gauge.set(self._alloc.in_use)
        # unhook the gauges: their set_fns pin this scheduler (and the
        # device KV caches) inside a possibly long-lived shared
        # registry; after close they must read as plain None, not
        # sample a dead scheduler
        self.metrics.gauge("serve.tokens_per_sec").set_fn(None)
        if self._spec:
            self.metrics.gauge("serve.spec_accept_rate").set_fn(None)
        if self._paged:
            for name in ("serve.kv_page_refs", "serve.kv_shared_pages",
                         "serve.kv_sharing_ratio"):
                self.metrics.gauge(name).set_fn(None)
        if self._prefix is not None:
            for name in ("serve.prefix.hit_rate",
                         "serve.prefix.evictions",
                         "serve.prefix.cached_pages",
                         "serve.prefix.entries",
                         "serve.prefix.shared_pages"):
                self.metrics.gauge(name).set_fn(None)
        self._state = None


__all__ = ["DecodeProgram", "ContinuousScheduler"]
