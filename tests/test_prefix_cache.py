"""Prefix-aware KV reuse, COW pages, LRU eviction, multi-tenant
admission (ISSUE 15).

Five layers of coverage:

* the ref-counted allocator as a PURE unit — share/free algebra under
  churn, distinct-page accounting (``in_use`` counts a k-mapped page
  once), over-release refusal;
* the radix cache as a PURE unit — insert/lookup/LRU order, pinned
  entries survive eviction pressure, longest-continuation-wins
  supersede, per-tenant namespacing, page-budget enforcement;
* the device-level visibility bar — the OOB-sentinel guarantees of
  tests/test_paged_kv.py extended to SHARED and COW pages: a mapper's
  divergent writes never land in a shared page, and a sibling reading
  through the same shared prefix is bit-unaffected by them;
* the scheduler acceptance bar — warm replays, COW continuations,
  eviction-under-pressure and chunked/speculative composition are all
  token-identical to standalone greedy decode, with zero leaked pages
  and an evicted prefix never readable by a later mapper;
* multi-tenant admission — tenant quotas shed loudly and release on
  completion, SLO classes order the queue, fleet model variants route
  and hot-swap per variant;

plus the tier-1 subprocess guard (tools/check_prefix_reuse.py) and
the ``serve.prefix`` regression-gate units.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu import ServeConfig
from parallax_tpu.models import nmt
from parallax_tpu.serve import (NMTDecodeProgram, PageAllocator,
                                PagePoolExhausted, RadixPrefixCache,
                                RequestQueue, Request, ServeSession,
                                TenantQuotaExceeded)
from test_compile import _run_driver_json
from test_paged_kv import _assert_greedy_identical
from test_serve import _nmt_params, nmt_cfg


# -- the ref-counted allocator as a pure unit -------------------------------


class TestRefCountedAllocator:
    def test_share_free_algebra(self):
        a = PageAllocator(8)
        pages = a.alloc(3)
        assert a.in_use == 3 and a.total_refs == 3
        a.share(pages)                      # second holder
        assert a.in_use == 3, "a shared page must count ONCE"
        assert a.total_refs == 6 and a.shared_pages == 3
        assert a.sharing_ratio() == pytest.approx(2.0)
        a.free(pages)                       # first holder releases
        assert a.in_use == 3 and a.free_pages == 5, \
            "pages with a surviving holder must not return to the pool"
        a.free(pages)                       # last holder releases
        assert a.in_use == 0 and a.free_pages == 8

    def test_over_release_refused(self):
        a = PageAllocator(4)
        pages = a.alloc(2)
        a.share(pages)
        a.free(pages)
        a.free(pages)
        with pytest.raises(ValueError, match="double-free"):
            a.free(pages)

    def test_share_of_free_page_refused(self):
        a = PageAllocator(4)
        pages = a.alloc(1)
        a.free(pages)
        with pytest.raises(ValueError, match="not currently allocated"):
            a.share(pages)
        got = a.alloc(1)
        with pytest.raises(ValueError, match="duplicate"):
            a.share([got[0], got[0]])

    def test_refcount_churn(self):
        """Random share/free churn with a shadow model: the allocator's
        accounting must match exact reference counting at every step,
        and every page must come home."""
        a = PageAllocator(6)
        shadow = {}
        rng = np.random.default_rng(3)
        for _ in range(400):
            op = rng.random()
            if op < 0.4 and a.can_alloc(1):
                (p,) = a.alloc(1)
                shadow[p] = 1
            elif op < 0.7 and shadow:
                p = int(rng.choice(list(shadow)))
                a.share([p])
                shadow[p] += 1
            elif shadow:
                p = int(rng.choice(list(shadow)))
                a.free([p])
                shadow[p] -= 1
                if shadow[p] == 0:
                    del shadow[p]
            assert a.in_use == len(shadow)
            assert a.total_refs == sum(shadow.values())
            assert a.shared_pages == sum(1 for c in shadow.values()
                                         if c > 1)
        for p, c in list(shadow.items()):
            for _ in range(c):
                a.free([p])
        assert a.in_use == 0 and a.free_pages == 6

    def test_alloc_still_all_or_nothing(self):
        a = PageAllocator(4)
        a.alloc(3)
        with pytest.raises(PagePoolExhausted):
            a.alloc(2)
        assert a.free_pages == 1


# -- the radix cache as a pure unit -----------------------------------------


def _cached_entry(cache, alloc, tenant, key, tokens, n_pages, rs=None):
    pages = alloc.alloc(n_pages)
    cache.insert(tenant, key, tokens, pages, rs)
    return pages


class TestRadixPrefixCache:
    def test_insert_lookup_exact_key(self):
        a = PageAllocator(16)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, (1, 2, 3), [7, 8], 1)
        assert c.lookup(None, (1, 2, 3)).tokens == [7, 8]
        assert c.lookup(None, (1, 2)) is None, \
            "partial source prefixes must NOT match (encoder " \
            "bidirectionality)"
        assert c.lookup(None, (1, 2, 3, 4)) is None

    def test_lru_eviction_order_and_pin(self):
        a = PageAllocator(6)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, (1,), [5], 2)
        _cached_entry(c, a, None, (2,), [6], 2)
        _cached_entry(c, a, None, (3,), [7], 2)
        # touch (1,) so (2,) is LRU; pin (2,) so (3,) is the victim
        c.lookup(None, (1,))
        e2 = c.lookup(None, (2,))
        c.pin(e2)
        assert not a.can_alloc(2)
        assert c.evict_for(2) == 1
        assert a.can_alloc(2)
        assert c.lookup(None, (2,)) is not None, "pinned entry evicted"
        assert c.lookup(None, (3,)) is None, \
            "expected the LRU unpinned entry to go first"
        # unpinned again, (2,) becomes evictable
        c.unpin(e2)
        assert c.evict_for(4) >= 1

    def test_evict_for_gives_up_when_all_pinned(self):
        a = PageAllocator(4)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, (1,), [5], 2)
        _cached_entry(c, a, None, (2,), [6], 2)
        for key in ((1,), (2,)):
            c.pin(c.lookup(None, key))
        assert c.evict_for(1) == 0, \
            "pinned pages must never be reclaimed for another tenant"
        assert c.num_entries == 2

    def test_supersede_keeps_longer_continuation(self):
        a = PageAllocator(8)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, (1,), [5, 6], 1)
        # shorter offer loses; its pages are released
        short = a.alloc(1)
        assert c.insert(None, (1,), [5], short, None) is False
        assert a.refcount(short[0]) == 0
        # longer offer wins; the old entry's pages release
        old = c.lookup(None, (1,)).pages
        longer = a.alloc(2)
        assert c.insert(None, (1,), [5, 6, 7], longer, None) is True
        assert c.lookup(None, (1,)).tokens == [5, 6, 7]
        assert a.refcount(old[0]) == 0

    def test_tenant_namespacing(self):
        a = PageAllocator(8)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, "a", (1, 2), [9], 1)
        assert c.lookup("b", (1, 2)) is None, \
            "tenant B must never see tenant A's entries"
        assert c.lookup("a", (1, 2)) is not None
        assert c.tenants() == ["a"]

    def test_page_budget_enforced(self):
        a = PageAllocator(16)
        c = RadixPrefixCache(a, max_pages=4)
        _cached_entry(c, a, None, (1,), [5], 2)
        _cached_entry(c, a, None, (2,), [6], 2)
        _cached_entry(c, a, None, (3,), [7], 2)
        assert c.cached_pages <= 4
        assert c.lookup(None, (1,)) is None, "LRU should have gone"

    def test_entry_budget_enforced(self):
        """max_entries caps the COUNT — the bound for the prefill
        request-state HBM the page accounting cannot see."""
        a = PageAllocator(16)
        c = RadixPrefixCache(a, max_entries=2)
        for k in range(4):
            _cached_entry(c, a, None, (k,), [5], 1)
        assert c.num_entries == 2
        assert c.lookup(None, (0,)) is None
        assert c.lookup(None, (3,)) is not None

    def test_trie_prunes_empty_branches(self):
        a = PageAllocator(8)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, tuple(range(30)), [5], 1)
        assert c.evict_for(8) == 1
        assert c.num_entries == 0
        assert c.tenants() == [], "empty trie branches must prune"

    def test_clear_releases_everything(self):
        a = PageAllocator(8)
        c = RadixPrefixCache(a)
        _cached_entry(c, a, None, (1,), [5], 2)
        _cached_entry(c, a, "t", (2,), [6], 2)
        assert c.clear() == 2
        assert a.in_use == 0 and c.num_entries == 0


# -- device-level visibility: shared + COW pages ----------------------------


class TestSharedPageVisibility:
    """The OOB-sentinel suite of tests/test_paged_kv.py, extended to
    SHARED pages: a mapper continuing past the replay boundary writes
    only into pages it owns, and a sibling mapping the same shared
    prefix reads bit-identical K/V regardless of the first mapper's
    divergent writes."""

    @pytest.fixture()
    def drig(self, rng):
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        S, T, Ts, ps, pool = 2, 16, 8, 4, 32
        src = rng.integers(3, 64, (S, Ts)).astype(np.int32)
        enc, sv = nmt._encode(cfg, params, src)
        ck, cv = nmt._cross_kv(cfg, params, enc)
        kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
        return dict(cfg=cfg, params=params, rng=rng, S=S, T=T, Ts=Ts,
                    ps=ps, pool=pool, ck=ck, cv=cv, sv=sv, kp=kp,
                    vp=vp)

    def test_divergent_writes_never_touch_shared_pages(self, drig):
        """Both slots' tables name the SAME pages for the replayed
        prefix (positions 0..7) and their OWN pages beyond; decoding
        at positions >= 8 must leave every shared page bit-untouched."""
        cfg, params = drig["cfg"], drig["params"]
        S, ps, pool = drig["S"], drig["ps"], drig["pool"]
        shared = [0, 1]                       # positions 0..7
        pages_np = np.full((S, 4), pool, np.int32)
        for s in range(S):
            pages_np[s, :2] = shared
            pages_np[s, 2:] = [2 + 2 * s, 3 + 2 * s]
        pages = jnp.asarray(pages_np)
        kp, vp = drig["kp"], drig["vp"]
        # write the shared prefix once (slot 0's table; the pages are
        # the same ids either way)
        toks = drig["rng"].integers(3, 64, (S, 1)).astype(np.int32)
        for step in range(8):
            t = jnp.full((S,), step, jnp.int32)
            _, kp, vp = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks), t, kp, vp,
                drig["ck"], drig["cv"], drig["sv"],
                pages=pages, page_size=ps)
        before_k = np.asarray(kp)[:, shared]
        before_v = np.asarray(vp)[:, shared]
        # divergent continuation: each slot writes at positions 8..11
        for step in range(8, 12):
            t = jnp.full((S,), step, jnp.int32)
            _, kp, vp = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks), t, kp, vp,
                drig["ck"], drig["cv"], drig["sv"],
                pages=pages, page_size=ps)
        assert np.array_equal(before_k, np.asarray(kp)[:, shared]), \
            "a divergent write landed in a SHARED page"
        assert np.array_equal(before_v, np.asarray(vp)[:, shared])

    def test_sibling_reads_unaffected_by_divergent_writes(self, drig):
        """Slot B's step output over a shared prefix must be
        bit-identical whether or not slot A has already written its
        own continuation — A's writes live in pages B's table never
        names (the COW'd-slot-cannot-read-sibling-writes bar)."""
        cfg, params = drig["cfg"], drig["params"]
        ps, pool = drig["ps"], drig["pool"]
        shared = [0, 1]
        toks8 = drig["rng"].integers(3, 64, (2, 8)).astype(np.int32)
        # build the shared prefix with A's table
        pages_a = jnp.asarray(np.array(
            [[0, 1, 2, 3], [0, 1, 4, 5]], np.int32))
        kp, vp = drig["kp"], drig["vp"]
        for step in range(8):
            t = jnp.full((2,), step, jnp.int32)
            _, kp, vp = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks8[:, step:step + 1]), t,
                kp, vp, drig["ck"], drig["cv"], drig["sv"],
                pages=pages_a, page_size=ps)
        tok_next = drig["rng"].integers(3, 64, (2, 1)).astype(np.int32)
        t8 = jnp.full((2,), 8, jnp.int32)
        # B's read BEFORE A diverges
        lb_before, _, _ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(tok_next), t8, kp, vp,
            drig["ck"], drig["cv"], drig["sv"],
            pages=pages_a, page_size=ps)
        # A writes four divergent positions into ITS pages (rows run
        # in lockstep; both rows' writes land outside `shared`)
        kp2, vp2 = kp, vp
        for step in range(8, 12):
            t = jnp.full((2,), step, jnp.int32)
            _, kp2, vp2 = nmt._decode_tokens_cached(
                cfg, params,
                drig["rng"].integers(3, 64, (2, 1)).astype(np.int32),
                t, kp2, vp2, drig["ck"], drig["cv"], drig["sv"],
                pages=pages_a, page_size=ps)
        # B's read AFTER: same logits bit for bit
        lb_after, _, _ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(tok_next), t8, kp2, vp2,
            drig["ck"], drig["cv"], drig["sv"],
            pages=pages_a, page_size=ps)
        assert np.array_equal(np.asarray(lb_before)[1],
                              np.asarray(lb_after)[1]), \
            "a sibling's divergent writes leaked into a shared read"


# -- scheduler acceptance: replay, COW, eviction under churn ----------------


def _prefix_rig(slots=3, T=12, Ts=8, pool_pages=36, **kw):
    cfg = nmt_cfg()
    params = _nmt_params(cfg)
    prog = NMTDecodeProgram(cfg, max_src_len=Ts, max_len=T,
                            page_size=4, pool_pages=pool_pages,
                            **{k: v for k, v in kw.items()
                               if k in ("prefill_chunk_layers",
                                        "spec_tokens", "draft_cfg",
                                        "draft_params")})
    sc_kw = {k: v for k, v in kw.items()
             if k in ("prefix_cache_max_pages", "tenant_quotas",
                      "default_tenant_quota", "slo_classes")}
    pcfg = parallax.Config(serve_config=ServeConfig(
        max_batch=slots, max_queue=64, prefix_cache=True, **sc_kw))
    sess = ServeSession(program=prog, params=params, config=pcfg)
    return sess, cfg, params


class TestPrefixCacheServing:
    def test_warm_replay_and_cow_token_identical(self, rng):
        """Cold round, warm full-hit round and an extended-cap COW
        round are all token-identical to standalone greedy decode;
        after close the pool is whole."""
        sess, cfg, params = _prefix_rig()
        try:
            srcs = [rng.integers(3, 64, (L,)).astype(np.int32)
                    for L in (6, 4, 8)]
            caps = [7, 5, 7]
            outs1 = [sess.submit({"src": s}, max_new_tokens=c)
                     .result(timeout=120.0)
                     for s, c in zip(srcs, caps)]
            outs2 = [sess.submit({"src": s}, max_new_tokens=c)
                     .result(timeout=120.0)
                     for s, c in zip(srcs, caps)]
            ext = [sess.submit({"src": s}, max_new_tokens=12)
                   .result(timeout=120.0) for s in srcs]
            stats = sess.stats()
            alloc = sess._scheduler._alloc
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs1)
        _assert_greedy_identical(params, cfg, srcs, caps, outs2)
        _assert_greedy_identical(params, cfg, srcs, [12] * 3, ext)
        assert stats["serve.prefix.hits"] >= 3
        assert stats["serve.prefix.full_hits"] >= 3
        assert alloc.in_use == 0, "pages leaked after close"

    def test_full_hit_completes_with_zero_decode_steps(self, rng):
        sess, cfg, params = _prefix_rig()
        try:
            src = rng.integers(3, 64, (6,)).astype(np.int32)
            sess.submit({"src": src},
                        max_new_tokens=8).result(timeout=120.0)
            steps_before = sess.stats()["serve.decode_steps"]
            out = sess.submit({"src": src},
                              max_new_tokens=8).result(timeout=120.0)
            stats = sess.stats()
        finally:
            sess.close()
        assert stats["serve.decode_steps"] == steps_before, \
            "a full cache hit must cost ZERO decode dispatches"
        assert stats["serve.prefix.full_hits"] == 1
        _assert_greedy_identical(params, cfg, [src], [8], [out])

    def test_eviction_under_pressure_and_no_stale_reads(self, rng):
        """A starved pool: the cache must evict LRU prefixes instead
        of deferring forever, an evicted prefix is a MISS for the next
        identical request (never a stale mapping), and every output
        stays greedy-identical throughout the churn."""
        sess, cfg, params = _prefix_rig(slots=2, pool_pages=8)
        try:
            srcs = [rng.integers(3, 64, (5,)).astype(np.int32)
                    for _ in range(6)]
            caps = [12] * 6
            outs = [sess.submit({"src": s}, max_new_tokens=c)
                    .result(timeout=120.0)
                    for s, c in zip(srcs, caps)]
            # resubmit the FIRST source: its entry was evicted by the
            # churn (8-page pool, 3 pages per seq) — must recompute
            # (miss) and still be identical
            hits_before = sess.stats()["serve.prefix.hits"]
            out0 = sess.submit({"src": srcs[0]},
                               max_new_tokens=12).result(timeout=120.0)
            stats = sess.stats()
            alloc = sess._scheduler._alloc
        finally:
            sess.close()
        assert stats["serve.prefix.evictions"] > 0
        assert stats["serve.prefix.hits"] == hits_before, \
            "an evicted prefix was readable by a later mapper"
        _assert_greedy_identical(params, cfg, srcs, caps, outs)
        _assert_greedy_identical(params, cfg, [srcs[0]], [12], [out0])
        assert alloc.in_use == 0

    def test_chunked_prefill_composes_with_prefix_cache(self, rng):
        sess, cfg, params = _prefix_rig(prefill_chunk_layers=1)
        try:
            srcs = [rng.integers(3, 64, (6,)).astype(np.int32)
                    for _ in range(2)]
            outs1 = [sess.submit({"src": s}, max_new_tokens=9)
                     .result(timeout=120.0) for s in srcs]
            chunks_cold = sess.stats()["serve.prefill_chunks"]
            outs2 = [sess.submit({"src": s}, max_new_tokens=9)
                     .result(timeout=120.0) for s in srcs]
            stats = sess.stats()
        finally:
            sess.close()
        assert stats["serve.prefill_chunks"] == chunks_cold, \
            "a cache hit must skip EVERY prefill chunk"
        _assert_greedy_identical(params, cfg, srcs, [9, 9], outs1)
        _assert_greedy_identical(params, cfg, srcs, [9, 9], outs2)

    def test_speculative_decode_composes_with_prefix_cache(self, rng):
        """Replay + continuation under speculative decoding stays
        EXACTLY greedy: the draft's cache is stale for replayed
        positions (only acceptance rate may suffer), the verify step
        reads the shared target pages and is exact regardless."""
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        from parallax_tpu.serve.adapters import layer_skip_draft
        dcfg, dparams = layer_skip_draft(cfg, params)
        sess, cfg, params = _prefix_rig(spec_tokens=2, draft_cfg=dcfg,
                                        draft_params=dparams)
        try:
            srcs = [rng.integers(3, 64, (6,)).astype(np.int32)
                    for _ in range(3)]
            caps = [7, 9, 12]
            outs1 = [sess.submit({"src": s}, max_new_tokens=c)
                     .result(timeout=120.0)
                     for s, c in zip(srcs, caps)]
            ext = [sess.submit({"src": s}, max_new_tokens=12)
                   .result(timeout=120.0) for s in srcs]
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs1)
        _assert_greedy_identical(params, cfg, srcs, [12] * 3, ext)

    def test_kv_accounting_counts_shared_pages_once(self, rng):
        """While a mapper shares cached pages, serve.kv_pages_in_use
        must equal the allocator's DISTINCT page count (< the naive
        per-holder sum), with the multiplicity in the refs/sharing
        gauges."""
        sess, _, _ = _prefix_rig()
        try:
            src = rng.integers(3, 64, (6,)).astype(np.int32)
            sess.submit({"src": src},
                        max_new_tokens=7).result(timeout=120.0)
            sess.submit({"src": src},
                        max_new_tokens=12).result(timeout=120.0)
            stats = sess.stats()
            alloc = sess._scheduler._alloc
            assert stats["serve.kv_pages_in_use"] == alloc.in_use
            assert stats["serve.kv_page_refs"] == alloc.total_refs
            assert stats["serve.kv_pages_in_use"] <= \
                stats["serve.kv_page_refs"]
            assert stats["serve.kv_sharing_ratio"] >= 1.0
        finally:
            sess.close()

    def test_tenant_isolation_in_serving(self, rng):
        """Tenant B submitting tenant A's exact source gets a MISS
        (cross-tenant reuse structurally impossible) while outputs
        stay identical (greedy determinism)."""
        sess, cfg, params = _prefix_rig()
        try:
            src = rng.integers(3, 64, (6,)).astype(np.int32)
            out_a = sess.submit({"src": src}, max_new_tokens=9,
                                tenant="a").result(timeout=120.0)
            hits = sess.stats()["serve.prefix.hits"]
            out_b = sess.submit({"src": src}, max_new_tokens=9,
                                tenant="b").result(timeout=120.0)
            assert sess.stats()["serve.prefix.hits"] == hits, \
                "tenant B hit tenant A's cached prefix"
            out_a2 = sess.submit({"src": src}, max_new_tokens=9,
                                 tenant="a").result(timeout=120.0)
            assert sess.stats()["serve.prefix.hits"] == hits + 1
            ps = sess.prefix_stats()
        finally:
            sess.close()
        assert list(out_a) == list(out_b) == list(out_a2)
        assert ps["tenants"] == 2

    def test_prefix_metrics_flow_through_exporter(self, rng):
        """The serve.prefix.* family reaches the PR-12 Prometheus
        exporter like every other registry metric."""
        import urllib.request

        from parallax_tpu.obs.export import TelemetryExporter

        sess, _, _ = _prefix_rig()
        exporter = None
        try:
            src = rng.integers(3, 64, (6,)).astype(np.int32)
            for _ in range(2):
                sess.submit({"src": src},
                            max_new_tokens=8).result(timeout=120.0)
            exporter = TelemetryExporter(
                lambda: {"replica0": sess.metrics.snapshot()})
            exporter.start()
            with urllib.request.urlopen(exporter.url,
                                        timeout=10.0) as resp:
                text = resp.read().decode()
        finally:
            if exporter is not None:
                exporter.stop()
            sess.close()
        assert "parallax_serve_prefix_hits" in text
        assert "parallax_serve_prefix_hit_rate" in text
        assert "parallax_serve_kv_sharing_ratio" in text

    def test_reqtrace_carries_prefix_fields(self, rng):
        """The lifecycle record of a hit request shows the
        prefix_replay phase and the skipped-prefill attribution."""
        sess, _, _ = _prefix_rig()
        try:
            src = rng.integers(3, 64, (6,)).astype(np.int32)
            sess.submit({"src": src},
                        max_new_tokens=8).result(timeout=120.0)
            sess.submit({"src": src},
                        max_new_tokens=8).result(timeout=120.0)
            recs = sess.request_records()
        finally:
            sess.close()
        cold, warm = recs[-2], recs[-1]
        assert cold["prefix_hit_pages"] == 0
        assert cold["prefill_tokens_skipped"] == 0
        assert "prefill_ms" in cold["phases_ms"]
        assert warm["prefix_hit_pages"] > 0
        assert warm["prefill_tokens_skipped"] == 6
        assert "prefix_replay_ms" in warm["phases_ms"], \
            "the skipped prefill must be attributed EXPLICITLY"
        assert "prefill_ms" not in warm["phases_ms"]
        if warm.get("ttft_decomp"):
            # the decomposition still partitions the client TTFT
            assert sum(warm["ttft_decomp"].values()) == \
                pytest.approx(warm["ttft_ms"], rel=0.05)

    def test_prefix_cache_requires_paged_program(self):
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        prog = NMTDecodeProgram(cfg, max_src_len=8, max_len=12)
        pcfg = parallax.Config(serve_config=ServeConfig(
            max_batch=2, prefix_cache=True))
        with pytest.raises(ValueError, match="PAGED"):
            ServeSession(program=prog, params=params, config=pcfg)


# -- multi-tenant admission: quotas + SLO classes ---------------------------


class TestTenantAdmission:
    def test_quota_sheds_and_releases(self):
        q = RequestQueue(max_queue=64, tenant_quotas={"a": 2})
        r1 = Request({}, tenant="a")
        r2 = Request({}, tenant="a")
        q.put(r1)
        q.put(r2)
        with pytest.raises(TenantQuotaExceeded, match="tenant 'a'"):
            q.put(Request({}, tenant="a"))
        # another tenant is NOT capped by a's quota
        q.put(Request({}, tenant="b"))
        # completion releases the allowance
        r1._complete(None)
        q.put(Request({}, tenant="a"))
        assert q.tenant_outstanding("a") == 2

    def test_default_quota_applies_to_unlisted_tenants(self):
        q = RequestQueue(max_queue=64, tenant_quotas={"a": 8},
                         default_tenant_quota=1)
        q.put(Request({}, tenant="x"))
        with pytest.raises(TenantQuotaExceeded):
            q.put(Request({}, tenant="x"))
        q.put(Request({}, tenant="a"))  # listed tenant: own quota

    def test_quota_released_on_failure_too(self):
        q = RequestQueue(max_queue=64, default_tenant_quota=1)
        r = Request({}, tenant="t")
        q.put(r)
        r._fail(RuntimeError("x"))
        q.put(Request({}, tenant="t"))  # allowance came back

    def test_slo_rank_orders_pop(self):
        q = RequestQueue(max_queue=64)
        batch1 = Request({}, slo_rank=2)
        batch2 = Request({}, slo_rank=2)
        rt = Request({}, slo_rank=0)
        q.put(batch1)
        q.put(batch2)
        q.put(rt)
        assert q.pop(timeout=0.0) is rt, "lower rank serves first"
        assert q.pop(timeout=0.0) is batch1, "FIFO within a rank"
        assert q.pop(timeout=0.0) is batch2

    def test_requeue_front_keeps_head_of_its_rank(self):
        q = RequestQueue(max_queue=64)
        a = Request({}, slo_rank=1)
        b = Request({}, slo_rank=1)
        q.put(a)
        q.put(b)
        got = q.pop(timeout=0.0)
        q.requeue_front(got)
        assert q.pop(timeout=0.0) is a

    def test_session_resolves_slo_class(self, rng):
        classes = {"realtime": {"priority": 0, "deadline_ms": 50.0},
                   "batch": {"priority": 9}}
        sess, _, _ = _prefix_rig(slo_classes=classes)
        try:
            src = rng.integers(3, 64, (5,)).astype(np.int32)
            req = sess.submit({"src": src}, max_new_tokens=4,
                              slo_class="batch")
            req.result(timeout=120.0)
            assert req.slo_rank == 9 and req.deadline is None
            req2 = sess.submit({"src": src}, max_new_tokens=4,
                               slo_class="realtime")
            assert req2.deadline is not None, \
                "the class deadline must apply when none is passed"
            with pytest.raises(ValueError, match="unknown slo_class"):
                sess.submit({"src": src}, slo_class="typo")
        finally:
            sess.close()

    def test_config_validation(self):
        with pytest.raises(ValueError, match="tenant quota"):
            ServeConfig(tenant_quotas={"a": 0})
        with pytest.raises(ValueError, match="default_tenant_quota"):
            ServeConfig(default_tenant_quota=0)
        with pytest.raises(ValueError, match="priority"):
            ServeConfig(slo_classes={"x": {}})
        with pytest.raises(ValueError, match="deadline_ms"):
            ServeConfig(slo_classes={"x": {"priority": 1,
                                           "deadline_ms": 0}})
        with pytest.raises(ValueError, match="prefix_cache_max_pages"):
            ServeConfig(prefix_cache_max_pages=-1)
        with pytest.raises(ValueError,
                           match="prefix_cache_max_entries"):
            ServeConfig(prefix_cache_max_entries=-1)


# -- fleet model variants ---------------------------------------------------


class TestFleetVariants:
    def _fleet(self):
        from tools import loadgen
        from parallax_tpu.serve import FleetConfig
        return loadgen.demo_decode_fleet(
            replicas=2, slots=2, T=8, Ts=6, model_dim=16, vocab=32,
            fleet_config=FleetConfig(num_replicas=2, max_replicas=3))

    def test_variant_routing_and_per_variant_push(self, rng):
        fleet, make_feed, params, cfg = self._fleet()
        try:
            # variant B: a genuinely different model (scaled output
            # projection changes greedy argmax ties deterministically)
            params_b = jax.tree.map(lambda x: x * 1.5, params)
            out = fleet.assign_variants({"base": params,
                                         "scaled": params_b})
            assert sorted(out.values()) == ["base", "scaled"]
            vm = fleet.variant_map()
            assert sorted(v for v in vm.values()) == ["base", "scaled"]
            feed = make_feed(0)
            ref_a = np.asarray(nmt.greedy_decode(
                params, cfg, feed["src"][None], max_len=8))[0]
            ref_b = np.asarray(nmt.greedy_decode(
                params_b, cfg, feed["src"][None], max_len=8))[0]

            def _trim(arr):
                toks = list(arr.tolist())
                if nmt.EOS_ID in toks:
                    toks = toks[:toks.index(nmt.EOS_ID) + 1]
                return toks

            got_a = fleet.submit(feed, max_new_tokens=8,
                                 variant="base").result(timeout=120.0)
            got_b = fleet.submit(feed, max_new_tokens=8,
                                 variant="scaled").result(
                                     timeout=120.0)
            assert list(got_a) == _trim(ref_a)
            assert list(got_b) == _trim(ref_b)
            with pytest.raises(ValueError, match="unknown model "
                                                 "variant"):
                fleet.submit(feed, variant="nope")
            with pytest.raises(ValueError, match="needs\\s+variant"):
                # unconstrained submit on a multiplexed fleet would be
                # served by WHICHEVER variant is least loaded
                fleet.submit(feed)
            with pytest.raises(ValueError, match="needs variant"):
                fleet.push_weights(params)
            # per-variant push rotates ONLY that variant's replica
            res = fleet.push_weights(params, variant="base")
            assert sorted(res.values()) == ["skipped (other variant)",
                                            "swapped"]
            assert fleet.recompiles() == 0, \
                "variant multiplexing must not recompile"
        finally:
            fleet.close()


# -- the tier-1 guard (subprocess driver) -----------------------------------


def test_prefix_reuse_guard():
    """tools/check_prefix_reuse.py end to end: >=50% shared-prefix
    load shows warm TTFT p50 measurably below the no-sharing A/B,
    bit-identical tokens in every round, zero serve-time compiles,
    zero leaked pages, and a cross-tenant sweep with zero foreign
    reads under eviction + COW churn. Subprocess for the same
    toolchain-crash isolation as the other tier-1 guards."""
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_prefix_reuse.py")
    result = _run_driver_json(
        [sys.executable, tool, "--requests", "30"],
        check_rc=False, timeout=600.0)
    assert result.get("ok"), result.get("violations")
    assert result["ttft_ms_p50_warm"] <= \
        0.8 * result["ttft_ms_p50_cold_nosharing"]
    assert result["token_mismatches"] == 0
    assert result["tenant_isolation"]["b_hits_delta"] == 0
