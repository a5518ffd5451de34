"""Paged KV cache, chunked prefill and speculative decoding (ISSUE 6).

Four layers of coverage:

* the page allocator as a PURE unit — alloc/free/reuse across
  retire-and-refill churn, deterministic refusal on pool exhaustion
  (state untouched), double-free/foreign-id refusal;
* the gather-based decode step math — paged attention and the G-token
  verify step are BIT-identical to the dense single-token step
  (models/nmt.py ``_decode_tokens_cached`` vs
  ``_decode_step_cached_multi``), including buffer-end overshoot
  (writes drop, foreign pages untouched) and chunked prefill vs the
  whole-prefill dispatch;
* the scheduler acceptance bar — paged + chunked-prefill continuous
  decode and speculative decoding are token-identical to standalone
  per-request greedy decode under mixed target lengths with mid-stream
  retire/refill, pool exhaustion defers refills (no stale-page
  visibility when pages are reused), and all pages return to the pool;
* the signature-set contract — the enlarged set (page tables, prefill
  chunks, draft + verify) is closed: zero XLA compiles under load
  after construction.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import parallax_tpu as parallax
from parallax_tpu import ServeConfig
from parallax_tpu.models import nmt
from parallax_tpu.serve import (NMTDecodeProgram, PageAllocator,
                                PagePoolExhausted, ServeSession,
                                pages_for)
from test_compile import _CompileCounter
from test_serve import _nmt_params, nmt_cfg


# -- the page allocator as a pure unit --------------------------------------


class TestPageAllocator:
    def test_alloc_free_reuse_churn(self):
        """Retire-and-refill churn: pages hand out, return, and hand
        out again with exact accounting at every point."""
        a = PageAllocator(8)
        seqs = {}
        rng = np.random.default_rng(0)
        for step in range(200):
            if seqs and (a.free_pages == 0 or rng.random() < 0.5):
                key = rng.choice(list(seqs))
                a.free(seqs.pop(key))
            else:
                n = int(rng.integers(1, 4))
                if n <= a.free_pages:
                    pages = a.alloc(n)
                    assert len(set(pages)) == n
                    seqs[step] = pages
            live = [p for ps in seqs.values() for p in ps]
            assert len(set(live)) == len(live), "page double-granted"
            assert a.in_use == len(live)
            assert a.free_pages == 8 - len(live)
        for ps in seqs.values():
            a.free(ps)
        assert a.in_use == 0 and a.free_pages == 8
        assert a.high_water <= 8

    def test_exhaustion_refusal_is_deterministic_and_atomic(self):
        a = PageAllocator(4)
        got = a.alloc(3)
        for _ in range(3):  # refusal every time, nothing granted
            with pytest.raises(PagePoolExhausted, match="2 page"):
                a.alloc(2)
            assert a.free_pages == 1 and a.in_use == 3
        a.free(got[:1])
        assert a.alloc(2) is not None  # freed pages make it grantable

    def test_double_free_and_foreign_ids_refused(self):
        a = PageAllocator(4)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(ValueError, match="double-free"):
            a.free(pages)  # already returned
        b = a.alloc(1)
        with pytest.raises(ValueError, match="double-free"):
            a.free([b[0], 99])
        with pytest.raises(ValueError, match="duplicate"):
            a.free([b[0], b[0]])

    def test_pages_for(self):
        assert pages_for(1, 4) == 1
        assert pages_for(4, 4) == 1
        assert pages_for(5, 4) == 2
        assert pages_for(16, 4) == 4
        with pytest.raises(ValueError):
            pages_for(0, 4)

    def test_bad_pool_size(self):
        with pytest.raises(ValueError, match="pool_pages"):
            PageAllocator(0)


# -- step-math bit-identity -------------------------------------------------


@pytest.fixture(scope="module")
def rig():
    cfg = nmt_cfg()
    params = _nmt_params(cfg)
    rng = np.random.default_rng(7)
    S, T, Ts = 3, 16, 8
    src = rng.integers(3, 64, (S, Ts)).astype(np.int32)
    enc, sv = nmt._encode(cfg, params, src)
    ck, cv = nmt._cross_kv(cfg, params, enc)
    kc, vc = nmt._init_self_cache(cfg, S, T)
    return dict(cfg=cfg, params=params, rng=rng, S=S, T=T, Ts=Ts,
                ck=ck, cv=cv, sv=sv, kc=kc, vc=vc)


def _fresh_pages(S, P, pool, start=0):
    """Distinct page ids per slot, sentinel-free."""
    pages = np.full((S, P), pool, np.int32)
    ids = iter(range(start, pool))
    for s in range(S):
        for k in range(P):
            pages[s, k] = next(ids)
    return pages


class TestPagedStepMath:
    def test_paged_step_bit_identical_to_dense(self, rig):
        cfg, params = rig["cfg"], rig["params"]
        S, T = rig["S"], rig["T"]
        ps, pool = 4, 32
        kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
        pages = jnp.asarray(_fresh_pages(S, T // ps, pool))
        toks = rig["rng"].integers(3, 64, (S, T)).astype(np.int32)
        kc, vc = rig["kc"], rig["vc"]
        for step in range(T):
            t = jnp.full((S,), step, jnp.int32)
            ld, kc, vc = nmt._decode_step_cached_multi(
                cfg, params, jnp.asarray(toks[:, step]), t, kc, vc,
                rig["ck"], rig["cv"], rig["sv"])
            lp, kp, vp = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks[:, step:step + 1]), t,
                kp, vp, rig["ck"], rig["cv"], rig["sv"],
                pages=pages, page_size=ps)
            assert np.array_equal(np.asarray(ld), np.asarray(lp[:, 0])), \
                f"paged logits diverged at step {step}"

    def test_verify_bit_identical_to_single_steps(self, rig):
        """The exact-under-greedy foundation: G-token verify logits ==
        G successive single-token steps, dense AND paged."""
        cfg, params = rig["cfg"], rig["params"]
        S, T, G = rig["S"], rig["T"], 4
        toks = rig["rng"].integers(3, 64, (S, G)).astype(np.int32)
        kc, vc = rig["kc"], rig["vc"]
        singles = []
        for g in range(G):
            t = jnp.full((S,), g, jnp.int32)
            lg, kc, vc = nmt._decode_step_cached_multi(
                cfg, params, jnp.asarray(toks[:, g]), t, kc, vc,
                rig["ck"], rig["cv"], rig["sv"])
            singles.append(np.asarray(lg))
        t0 = jnp.zeros((S,), jnp.int32)
        ld, *_ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), t0, rig["kc"], rig["vc"],
            rig["ck"], rig["cv"], rig["sv"])
        ps, pool = 4, 32
        kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
        pages = jnp.asarray(_fresh_pages(S, T // ps, pool))
        lp, *_ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), t0, kp, vp,
            rig["ck"], rig["cv"], rig["sv"], pages=pages, page_size=ps)
        for g in range(G):
            assert np.array_equal(singles[g], np.asarray(ld[:, g]))
            assert np.array_equal(singles[g], np.asarray(lp[:, g]))

    def test_overshoot_writes_drop_not_corrupt(self, rig):
        """A verify window past the buffer end stays finite and NEVER
        writes into pages the slot does not own."""
        cfg, params = rig["cfg"], rig["params"]
        S, T, G = rig["S"], rig["T"], 4
        ps, pool = 4, 32
        kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
        pages_np = _fresh_pages(S, T // ps, pool)
        pages = jnp.asarray(pages_np)
        toks = rig["rng"].integers(3, 64, (S, G)).astype(np.int32)
        t = jnp.asarray(np.array([T - 2, T - 1, T - 3], np.int32))
        before_k = np.asarray(kp)
        lg, kp2, _ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), t, kp, vp,
            rig["ck"], rig["cv"], rig["sv"], pages=pages, page_size=ps)
        # finite (clip, not NaN-fill, on the positional table)
        assert np.isfinite(np.asarray(lg)).all()
        owned = set(pages_np.flatten().tolist())
        foreign = [p for p in range(pool) if p not in owned]
        assert np.array_equal(before_k[:, foreign],
                              np.asarray(kp2)[:, foreign]), \
            "an overshooting write landed in a foreign page"

    def test_sentinel_page_table_rows_never_write(self, rig):
        """An inactive slot (all-sentinel page row) cannot touch the
        pool at all — the no-stale-visibility guarantee's other half."""
        cfg, params = rig["cfg"], rig["params"]
        S, T = rig["S"], rig["T"]
        ps, pool = 4, 32
        kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
        pages = jnp.asarray(np.full((S, T // ps), pool, np.int32))
        toks = rig["rng"].integers(3, 64, (S, 1)).astype(np.int32)
        before = np.asarray(kp)
        _, kp2, vp2 = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), jnp.zeros((S,), jnp.int32),
            kp, vp, rig["ck"], rig["cv"], rig["sv"],
            pages=pages, page_size=ps)
        assert np.array_equal(before, np.asarray(kp2))
        assert np.array_equal(before, np.asarray(vp2))


# -- chunked prefill --------------------------------------------------------


class TestChunkedPrefill:
    def test_chunks_reproduce_whole_prefill(self):
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        whole = NMTDecodeProgram(cfg, max_src_len=8, max_len=12)
        chunked = NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                                   prefill_chunk_layers=1)
        assert chunked.num_prefill_chunks == cfg.num_layers + 1
        feed = whole.prepare_feed(
            {"src": np.arange(3, 9, dtype=np.int32)})
        rs = whole.prefill(params, feed)
        carry = feed
        for k in range(chunked.num_prefill_chunks):
            carry = chunked.prefill_chunk(params, carry, k)
        np.testing.assert_array_equal(np.asarray(rs["src_valid"]),
                                      np.asarray(carry["src_valid"]))
        # whole and chunked prefill are two differently fused float32
        # programs, so their sums associate differently: equal to a few
        # ulps, not to the bit (observed 3.6e-7 on values of order 1)
        for key in ("ck", "cv"):
            np.testing.assert_allclose(np.asarray(rs[key]),
                                       np.asarray(carry[key]),
                                       rtol=0, atol=2e-6)

    def test_chunk_layer_validation(self):
        cfg = nmt_cfg()
        with pytest.raises(ValueError, match="prefill_chunk_layers"):
            NMTDecodeProgram(cfg, max_src_len=8,
                             prefill_chunk_layers=0)
        with pytest.raises(ValueError, match="prefill_chunk_layers"):
            NMTDecodeProgram(cfg, max_src_len=8,
                             prefill_chunk_layers=cfg.num_layers + 1)


# -- program config validation ---------------------------------------------


class TestProgramValidation:
    def test_page_geometry(self):
        cfg = nmt_cfg()
        with pytest.raises(ValueError, match="divide"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                             page_size=5, pool_pages=16)
        with pytest.raises(ValueError, match="pool_pages"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                             page_size=4)
        with pytest.raises(ValueError, match="without page_size"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                             pool_pages=16)
        with pytest.raises(ValueError, match="hold even one"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=16,
                             page_size=4, pool_pages=3)

    def test_spec_requires_draft(self):
        cfg = nmt_cfg()
        with pytest.raises(ValueError, match="draft"):
            NMTDecodeProgram(cfg, max_src_len=8, spec_tokens=3)

    def test_pages_needed(self):
        cfg = nmt_cfg()
        prog = NMTDecodeProgram(cfg, max_src_len=8, max_len=16,
                                page_size=4, pool_pages=16)
        assert prog.pages_per_seq == 4
        assert prog.pages_needed(1) == 1
        assert prog.pages_needed(5) == 2
        assert prog.pages_needed(16) == 4


# -- scheduler acceptance: token identity under churn -----------------------


def _serve_rig(slots, T=12, Ts=8, **prog_kw):
    cfg = nmt_cfg()
    params = _nmt_params(cfg)
    prog = NMTDecodeProgram(cfg, max_src_len=Ts, max_len=T, **prog_kw)
    pcfg = parallax.Config(serve_config=ServeConfig(max_batch=slots,
                                                    max_queue=64))
    sess = ServeSession(program=prog, params=params, config=pcfg)
    return sess, cfg, params


def _truncated_draft(cfg, params, layers=1):
    """A layer-skip draft: the target's first ``layers`` blocks with
    the shared embedding/positional/output tables — a real draft-model
    shape (cheap, correlated with the target, never trusted)."""
    from parallax_tpu.serve.adapters import layer_skip_draft
    return layer_skip_draft(cfg, params, layers)


def _assert_greedy_identical(params, cfg, srcs, caps, outs):
    for src, cap, out in zip(srcs, caps, outs):
        ref = np.asarray(nmt.greedy_decode(
            params, cfg, src[None], max_len=cap))[0].tolist()
        if nmt.EOS_ID in ref:
            ref = ref[:ref.index(nmt.EOS_ID) + 1]
        assert list(out) == ref, (src, list(out), ref)


class TestPagedContinuousDecode:
    def test_paged_refill_token_identical(self, rng):
        """The ISSUE 6 acceptance bar: paged continuous decode with
        retire-and-refill churn (6 requests over 3 slots, reused
        pages) is token-identical to standalone greedy decode."""
        sess, cfg, params = _serve_rig(slots=3, page_size=4,
                                       pool_pages=12)
        try:
            srcs = [rng.integers(3, 64, (L,)).astype(np.int32)
                    for L in (6, 4, 8, 5, 7, 3)]
            caps = [12, 5, 9, 12, 4, 8]
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
            stats = sess.stats()
            assert stats["serve.completed"] == 6
            assert stats["serve.kv_pages_in_use"] == 0, \
                "pages leaked after all sequences retired"
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs)

    def test_pool_exhaustion_defers_then_recovers(self, rng):
        """A pool that fits only ~2 max-cap sequences: refills DEFER
        (never fail), pages from retiring sequences are REUSED, and
        every output stays token-identical — the no-stale-visibility
        test under real churn."""
        sess, cfg, params = _serve_rig(slots=4, page_size=4,
                                       pool_pages=6)
        try:
            srcs = [rng.integers(3, 64, (5,)).astype(np.int32)
                    for _ in range(6)]
            caps = [12, 9, 12, 10, 12, 11]  # 3 pages each; pool = 6
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
            stats = sess.stats()
            assert stats["serve.completed"] == 6
            assert stats["serve.kv_refill_deferred"] > 0, \
                "the pool never saturated — the rig is too big"
            assert stats["serve.kv_pages_in_use"] == 0
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs)

    def test_chunked_prefill_token_identical(self, rng):
        sess, cfg, params = _serve_rig(slots=3, page_size=4,
                                       pool_pages=12,
                                       prefill_chunk_layers=1)
        try:
            srcs = [rng.integers(3, 64, (L,)).astype(np.int32)
                    for L in (6, 4, 8, 5)]
            caps = [12, 6, 9, 8]
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
            assert sess.stats()["serve.prefill_chunks"] == \
                4 * (cfg.num_layers + 1)
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs)


class TestSpeculativeDecode:
    def test_spec_exact_greedy_with_truncated_draft(self, rng):
        """Speculative decoding with a layer-skip draft emits the
        EXACT greedy sequence under mixed target lengths with
        mid-stream retire/refill — the draft is never trusted, only
        verified."""
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        dcfg, dparams = _truncated_draft(cfg, params)
        prog = NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                                page_size=4, pool_pages=12,
                                spec_tokens=3, draft_cfg=dcfg,
                                draft_params=dparams)
        pcfg = parallax.Config(serve_config=ServeConfig(max_batch=3,
                                                        max_queue=64))
        sess = ServeSession(program=prog, params=params, config=pcfg)
        try:
            srcs = [rng.integers(3, 64, (L,)).astype(np.int32)
                    for L in (6, 4, 8, 5, 7, 3)]
            caps = [12, 5, 9, 12, 4, 8]
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
            stats = sess.stats()
            assert stats["serve.completed"] == 6
            assert stats["serve.spec_proposed"] > 0
            assert stats["serve.kv_pages_in_use"] == 0
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs)

    def test_spec_with_perfect_draft_multiplies_tokens_per_step(
            self, rng):
        """draft == target: every proposal verifies, so each iteration
        emits spec_tokens + 1 tokens — decode_steps must come in well
        under total tokens (the count that would multiply tokens/sec;
        the rate itself is not measured here)."""
        cfg = nmt_cfg()
        params = _nmt_params(cfg)
        prog = NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                                spec_tokens=3, draft_cfg=cfg,
                                draft_params=params)
        pcfg = parallax.Config(serve_config=ServeConfig(max_batch=2,
                                                        max_queue=64))
        sess = ServeSession(program=prog, params=params, config=pcfg)
        try:
            srcs = [rng.integers(3, 64, (6,)).astype(np.int32)
                    for _ in range(3)]
            caps = [12, 12, 10]
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
            stats = sess.stats()
            # a perfect draft accepts everything
            assert stats["serve.spec_accept_rate"] == pytest.approx(1.0)
            # 34 tokens in at most ~ceil(12/4)+ceil(12/4)+ceil(10/4)
            # iterations plus refill slack — far under 1 step/token
            assert stats["serve.decode_steps"] * 2 < \
                stats["serve.tokens"]
        finally:
            sess.close()
        _assert_greedy_identical(params, cfg, srcs, caps, outs)


# -- the signature-set contract ---------------------------------------------


def test_enlarged_signature_set_closed_no_recompiles(rng):
    """Page tables, prefill chunks, draft + verify: the whole enlarged
    signature set is AOT-warmed at construction — mixed-length traffic
    with retire/refill and pool churn never triggers an XLA compile
    (the subprocess SLO guard enforces the same thing in
    tools/check_serve_slo.py with the jax.monitoring witness)."""
    cfg = nmt_cfg()
    params = _nmt_params(cfg)
    dcfg, dparams = _truncated_draft(cfg, params)
    prog = NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                            page_size=4, pool_pages=9,
                            prefill_chunk_layers=1,
                            spec_tokens=2, draft_cfg=dcfg,
                            draft_params=dparams)
    pcfg = parallax.Config(serve_config=ServeConfig(max_batch=3,
                                                    max_queue=64))
    sess = ServeSession(program=prog, params=params, config=pcfg)
    try:
        with _CompileCounter() as cc:
            srcs = [rng.integers(3, 64,
                                 (int(rng.integers(3, 9)),))
                    .astype(np.int32) for _ in range(8)]
            caps = [int(rng.integers(4, 13)) for _ in range(8)]
            reqs = [sess.submit({"src": s}, max_new_tokens=c)
                    for s, c in zip(srcs, caps)]
            outs = [r.result(timeout=120.0) for r in reqs]
        assert cc.count == 0, (
            f"{cc.count} XLA compile(s) during paged/chunked/spec "
            f"serving — the signature set leaked")
    finally:
        sess.close()
    _assert_greedy_identical(params, cfg, srcs, caps, outs)
