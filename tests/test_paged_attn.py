"""Fused Pallas paged-attention decode kernel (ISSUE 16).

Five layers of coverage over ``ops/pallas_paged_attention``:

* executor-switch semantics as a pure unit — ``resolve_impl`` arg/env
  precedence, loud refusals (unknown impl, kernel past the VMEM
  budget on a real TensorCore), the budget env var, and the adapter's
  constructor validation (``attn_impl='kernel'`` without paging);
* sentinel ownership — ``sentinel_write_coords`` /``paged_gather`` are
  the one owner both executors share: OOB and sentinel positions map
  to the dropping page id, gathers clip;
* token-level greedy identity of the kernel vs the einsum executor —
  single-token steps and the G-wide spec-decode verify, against the
  paged einsum path AND the dense path, on float32 where the contract
  is exact (the einsum path's own bitwise guarantees stay covered by
  tests/test_paged_kv.py);
* page-sharing safety — a page-table row referencing a sibling's page
  (the prefix-cache shared/COW layout) reads it bit-identically under
  both executors, never writes it, and post-churn page recycling
  (the eviction case) stays invisible; plus the ragged-occupancy
  sweep including the zero-allocated-pages edge, where the kernel's
  contract is finite zeros, never NaN;
* the serve-level guard (tools/check_paged_attn_serve.py, subprocess):
  kernel-executor session == einsum-executor session token for token
  over the full paged+chunked+speculative rig with zero serve-time
  compiles and zero leaked pages.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.models import nmt
from parallax_tpu.ops import pallas_paged_attention as ppa
from test_compile import _run_driver_json
from test_serve import _nmt_params, nmt_cfg


# -- executor switch ---------------------------------------------------------


class TestResolveImpl:
    KW = dict(G=3, D=64, page_size=8, num_heads=4, itemsize=2)

    def test_unknown_impl_refused(self):
        with pytest.raises(ValueError, match="unknown paged-attention"):
            ppa.resolve_impl("bogus", **self.KW)

    def test_auto_is_einsum_off_tpu(self):
        assert ppa.resolve_impl("auto", **self.KW) == "einsum"
        assert ppa.resolve_impl(None, **self.KW) == "einsum"

    def test_auto_is_kernel_on_tpu_when_fit(self):
        assert ppa.resolve_impl("auto", interpret=False,
                                **self.KW) == "kernel"

    def test_explicit_kernel_honored_in_interpret(self):
        assert ppa.resolve_impl("kernel", **self.KW) == "kernel"

    def test_kernel_past_budget_refuses_loudly(self):
        os.environ["PARALLAX_PAGED_ATTN_VMEM_BUDGET"] = "256"
        try:
            with pytest.raises(ValueError, match="VMEM budget"):
                ppa.resolve_impl("kernel", interpret=False, **self.KW)
            # auto degrades to einsum instead of refusing
            assert ppa.resolve_impl("auto", interpret=False,
                                    **self.KW) == "einsum"
            # interpret mode runs any size (the CPU-parity escape)
            assert ppa.resolve_impl("kernel", interpret=True,
                                    **self.KW) == "kernel"
        finally:
            del os.environ["PARALLAX_PAGED_ATTN_VMEM_BUDGET"]

    def test_env_override_outranks_argument(self):
        os.environ["PARALLAX_PAGED_ATTN"] = "einsum"
        try:
            assert ppa.resolve_impl("kernel", **self.KW) == "einsum"
        finally:
            del os.environ["PARALLAX_PAGED_ATTN"]

    def test_adapter_validates_attn_impl(self):
        from parallax_tpu.serve import NMTDecodeProgram
        cfg = nmt_cfg()
        with pytest.raises(ValueError, match="attn_impl"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                             attn_impl="bogus")
        with pytest.raises(ValueError, match="paged KV layout"):
            NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                             attn_impl="kernel")  # dense layout
        # einsum/auto are fine without paging (no-ops on dense)
        NMTDecodeProgram(cfg, max_src_len=8, max_len=12,
                         attn_impl="einsum")


# -- sentinel ownership ------------------------------------------------------


class TestSentinelHelpers:
    def test_write_coords_drop_semantics(self):
        pool, ps = 8, 4
        pages = jnp.asarray([[0, 2, pool, pool]], jnp.int32)  # P=4
        pos = jnp.asarray([[1, 5, 9, 17]], jnp.int32)
        pg, off = ppa.sentinel_write_coords(pages, pos, ps, pool)
        pg, off = np.asarray(pg)[0], np.asarray(off)[0]
        assert pg[0] == 0 and off[0] == 1      # live page 0
        assert pg[1] == 2 and off[1] == 1      # live page 2
        assert pg[2] == pool                   # sentinel entry -> drop
        assert pg[3] == pool                   # beyond table -> drop
        assert off[3] == 1                     # offset stays in range

    def test_gather_clips_and_reshapes(self):
        pool, ps, D = 6, 2, 4
        layer = jnp.arange(pool * ps * D,
                           dtype=jnp.float32).reshape(pool, ps, D)
        pages = jnp.asarray([[1, pool], [3, 0]], jnp.int32)
        out = ppa.paged_gather(layer, pages)
        assert out.shape == (2, 2 * ps, D)
        assert np.array_equal(np.asarray(out[0, :ps]),
                              np.asarray(layer[1]))
        # sentinel CLIPS to the last pool page — callers must mask
        assert np.array_equal(np.asarray(out[0, ps:]),
                              np.asarray(layer[pool - 1]))


# -- token-level kernel/einsum identity --------------------------------------


@pytest.fixture(scope="module")
def rig():
    cfg = nmt_cfg()    # float32: the exact-identity regime
    params = _nmt_params(cfg)
    rng = np.random.default_rng(7)
    S, T, Ts = 3, 16, 8
    src = rng.integers(3, 64, (S, Ts)).astype(np.int32)
    enc, sv = nmt._encode(cfg, params, src)
    ck, cv = nmt._cross_kv(cfg, params, enc)
    return dict(cfg=cfg, params=params, rng=rng, S=S, T=T, Ts=Ts,
                ck=ck, cv=cv, sv=sv)


def _fresh_pages(S, P, pool, start=0):
    pages = np.full((S, P), pool, np.int32)
    ids = iter(range(start, pool))
    for s in range(S):
        for k in range(P):
            pages[s, k] = next(ids)
    return pages


def _greedy_paged(rig, attn_impl, steps=10, ps=4, pool=32):
    cfg, params, S = rig["cfg"], rig["params"], rig["S"]
    kp, vp = nmt._init_paged_self_cache(cfg, pool, ps)
    pages = jnp.asarray(_fresh_pages(S, rig["T"] // ps, pool))
    tok = jnp.full((S, 1), nmt.BOS_ID, jnp.int32)
    t = jnp.zeros((S,), jnp.int32)
    out = []
    for _ in range(steps):
        logits, kp, vp = nmt._decode_tokens_cached(
            cfg, params, tok, t, kp, vp, rig["ck"], rig["cv"],
            rig["sv"], pages=pages, page_size=ps, attn_impl=attn_impl)
        tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
        out.append(np.asarray(tok[:, 0]))
        t = t + 1
    return np.stack(out, 1), kp, vp, pages


def _greedy_dense(rig, steps=10):
    cfg, params, S = rig["cfg"], rig["params"], rig["S"]
    kc, vc = nmt._init_self_cache(cfg, S, rig["T"])
    tok = jnp.full((S,), nmt.BOS_ID, jnp.int32)
    t = jnp.zeros((S,), jnp.int32)
    out = []
    for _ in range(steps):
        logits, kc, vc = nmt._decode_step_cached_multi(
            cfg, params, tok, t, kc, vc, rig["ck"], rig["cv"],
            rig["sv"])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(np.asarray(tok))
        t = t + 1
    return np.stack(out, 1)


class TestTokenIdentity:
    def test_greedy_tokens_kernel_vs_einsum_vs_dense(self, rig):
        """Single-token greedy decode: the kernel path's tokens equal
        the paged einsum path's AND the dense path's, step for step —
        the executor is a traffic optimization, never a result
        change."""
        te, kpe, vpe, _ = _greedy_paged(rig, "einsum")
        tk, kpk, vpk, _ = _greedy_paged(rig, "kernel")
        td = _greedy_dense(rig)
        assert np.array_equal(te, tk), "kernel diverged from einsum"
        assert np.array_equal(te, td), "paged diverged from dense"
        # layer-0 writes are pre-attention (bit-equal); deeper layers
        # inherit the executor's float-level drift through the layer-0
        # attention output — float-close, never token-visible above
        np.testing.assert_allclose(np.asarray(kpe), np.asarray(kpk),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(vpe), np.asarray(vpk),
                                   atol=1e-5)

    def test_verify_tokens_kernel_vs_einsum(self, rig):
        """The G-wide spec-decode verify dispatch: greedy argmax per
        verify position identical under both executors, on a mid-
        stream cache (pages partially filled)."""
        cfg, params, S = rig["cfg"], rig["params"], rig["S"]
        _, kp, vp, pages = _greedy_paged(rig, "einsum", steps=6)
        toks = rig["rng"].integers(3, 64, (S, 3)).astype(np.int32)
        t = jnp.full((S,), 6, jnp.int32)
        le, *_ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), t, kp, vp, rig["ck"],
            rig["cv"], rig["sv"], pages=pages, page_size=4,
            attn_impl="einsum")
        lk, *_ = nmt._decode_tokens_cached(
            cfg, params, jnp.asarray(toks), t, kp, vp, rig["ck"],
            rig["cv"], rig["sv"], pages=pages, page_size=4,
            attn_impl="kernel")
        assert np.array_equal(np.asarray(jnp.argmax(le, -1)),
                              np.asarray(jnp.argmax(lk, -1)))

    def test_op_level_outputs_match_reference(self):
        """paged_decode_attention itself: kernel vs einsum reference
        on random paged data with a ragged (sentinel-tailed) table —
        f32 outputs agree to float tolerance on every live slot."""
        rng = np.random.default_rng(0)
        S, G, D, H, ps, P, pool = 4, 3, 32, 2, 4, 4, 12
        q = jnp.asarray(rng.standard_normal((S, G, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pool, ps, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pool, ps, D)),
                         jnp.float32)
        pages = np.full((S, P), pool, np.int32)
        pages[0, :4] = [0, 1, 2, 3]
        pages[1, :2] = [4, 5]
        pages[2, :1] = [6]
        pos = np.asarray([[13, 14, 15], [5, 6, 7], [1, 2, 3],
                          [0, 1, 2]], np.int32)
        args = (q, kp, vp, jnp.asarray(pages), jnp.asarray(pos))
        kw = dict(num_heads=H, page_size=ps)
        ein = ppa.paged_decode_attention(*args, impl="einsum", **kw)
        ker = ppa.paged_decode_attention(*args, impl="kernel", **kw)
        np.testing.assert_allclose(np.asarray(ein[:3]),
                                   np.asarray(ker[:3]), atol=2e-5)
        # slot 3 has ZERO live pages: the kernel contract is finite
        # zeros (the einsum side reads clipped garbage there — both
        # are discarded host-side; see the module docstring)
        assert np.array_equal(np.asarray(ker[3]),
                              np.zeros_like(np.asarray(ker[3])))


# -- page sharing, churn, ragged occupancy -----------------------------------


class TestSharedPagesAndChurn:
    def test_shared_page_read_identical_never_written(self, rig):
        """The prefix-cache layout: slot 1's table references slot 0's
        first page (a shared full prefix page, read-only by
        convention). Both executors read it bit-identically; a decode
        step writing BEYOND it leaves the shared page untouched."""
        cfg, params, S = rig["cfg"], rig["params"], rig["S"]
        ps, pool = 4, 32
        # seed slot caches by decoding 6 steps through DISTINCT pages
        _, kp, vp, pages_np = _greedy_paged(rig, "einsum", steps=6)
        pages = np.asarray(pages_np).copy()
        shared = pages[0, 0]
        pages[1, 0] = shared          # slot 1 now shares slot 0's page
        pages = jnp.asarray(pages)
        toks = rig["rng"].integers(3, 64, (S, 1)).astype(np.int32)
        t = jnp.full((S,), 6, jnp.int32)   # position in page 1, not 0
        before = np.asarray(kp)[:, shared].copy()
        outs = {}
        for impl in ("einsum", "kernel"):
            l, kp2, vp2 = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks), t, kp, vp, rig["ck"],
                rig["cv"], rig["sv"], pages=pages, page_size=ps,
                attn_impl=impl)
            outs[impl] = np.asarray(jnp.argmax(l[:, 0], -1))
            assert np.array_equal(np.asarray(kp2)[:, shared], before), \
                f"{impl}: a write landed in the shared page"
        assert np.array_equal(outs["einsum"], outs["kernel"])

    def test_sibling_unaffected_by_sharing_and_churn(self, rig):
        """Slot 2's step result is bit-identical whether or not other
        slots share pages — and after churn (a freed page recycled
        with new content under a DIFFERENT slot), the sibling's
        tokens are unchanged: foreign pages are invisible whatever
        their content."""
        cfg, params, S = rig["cfg"], rig["params"], rig["S"]
        ps = 4
        _, kp, vp, pages_np = _greedy_paged(rig, "kernel", steps=6)
        base = np.asarray(pages_np).copy()
        toks = rig["rng"].integers(3, 64, (S, 1)).astype(np.int32)
        t = jnp.full((S,), 6, jnp.int32)

        def slot2_logits(pages, kpool, vpool):
            l, *_ = nmt._decode_tokens_cached(
                cfg, params, jnp.asarray(toks), t, kpool, vpool,
                rig["ck"], rig["cv"], rig["sv"],
                pages=jnp.asarray(pages), page_size=ps,
                attn_impl="kernel")
            return np.asarray(l[2])

        ref = slot2_logits(base, kp, vp)
        # sharing: slot 1 maps slot 0's page — slot 2 must not care
        shared = base.copy()
        shared[1, 0] = shared[0, 0]
        assert np.array_equal(slot2_logits(shared, kp, vp), ref)
        # churn: scribble over a page slot 2 does NOT own (a recycled
        # page now holding another slot's fresh KV)
        foreign = base[0, 1]
        kp2 = kp.at[:, foreign].set(9.0)
        vp2 = vp.at[:, foreign].set(-9.0)
        assert np.array_equal(slot2_logits(base, kp2, vp2), ref)

    def test_ragged_occupancy_sweep(self):
        """Occupancies from full table down to ZERO live pages in one
        batch: every live slot agrees kernel-vs-einsum; the
        zero-pages slot is finite zeros from the kernel and cannot
        perturb its neighbors."""
        rng = np.random.default_rng(3)
        S, G, D, H, ps, P, pool = 5, 2, 32, 2, 4, 4, 24
        q = jnp.asarray(rng.standard_normal((S, G, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pool, ps, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((pool, ps, D)),
                         jnp.float32)
        pages = np.full((S, P), pool, np.int32)
        next_id = 0
        for s, n_live in enumerate((4, 3, 2, 1, 0)):
            for k in range(n_live):
                pages[s, k] = next_id
                next_id += 1
        pos = np.zeros((S, G), np.int32)
        for s, n_live in enumerate((4, 3, 2, 1, 0)):
            hi = max(n_live * ps - 1, 0)
            pos[s] = [max(hi - 1, 0), hi]
        args = (q, kp, vp, jnp.asarray(pages), jnp.asarray(pos))
        kw = dict(num_heads=H, page_size=ps)
        ein = ppa.paged_decode_attention(*args, impl="einsum", **kw)
        ker = ppa.paged_decode_attention(*args, impl="kernel", **kw)
        np.testing.assert_allclose(np.asarray(ein[:4]),
                                   np.asarray(ker[:4]), atol=2e-5)
        assert np.isfinite(np.asarray(ker)).all()
        assert np.array_equal(np.asarray(ker[4]),
                              np.zeros_like(np.asarray(ker[4])))


# -- analytic accounting -----------------------------------------------------


class TestHbmAccounting:
    def test_kernel_bytes_scale_with_occupancy_gather_flat(self):
        F = ppa.FLAGSHIP_DECODE
        S, G, D, ps, P = F["S"], F["G"], F["D"], F["page_size"], F["P"]
        full = ppa.kernel_hbm_bytes(S, G, D, ps, S * P, 2)
        half = ppa.kernel_hbm_bytes(S, G, D, ps, S * P // 2, 2)
        gather = ppa.gather_hbm_bytes(S, G, D, ps, P, 2)
        # stream term halves with occupancy (q/out floor stays)
        assert half["stream_bytes"] * 2 == full["stream_bytes"]
        assert half["qout_bytes"] == full["qout_bytes"]
        # even at FULL occupancy the kernel beats the gather: the
        # gather pays the materialized view write + re-read on top
        assert full["total_bytes"] < gather["total_bytes"]

    def test_trace_records_note_executor(self):
        ppa.reset_trace_records()
        rng = np.random.default_rng(0)
        S, G, D, H, ps, P, pool = 2, 1, 16, 2, 2, 2, 6
        q = jnp.asarray(rng.standard_normal((S, G, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((pool, ps, D)),
                         jnp.float32)
        pages = jnp.zeros((S, P), jnp.int32)
        pos = jnp.zeros((S, G), jnp.int32)
        for impl in ("einsum", "kernel"):
            ppa.paged_decode_attention(q, kp, kp, pages, pos,
                                       num_heads=H, page_size=ps,
                                       impl=impl)
        impls = {r["impl"] for r in ppa.trace_records()}
        assert impls == {"einsum", "kernel"}
        ppa.reset_trace_records()


# -- the tier-1 serve guard (subprocess driver) ------------------------------


def test_paged_attn_serve_guard():
    """tools/check_paged_attn_serve.py end to end: the kernel-executor
    session equals the einsum-executor session token for token over
    the full paged+chunked+speculative rig (including the page-recycle
    churn round), with zero serve-time compiles and zero leaked pages
    on both. Subprocess for the same toolchain-crash isolation as the
    other tier-1 guards."""
    tool = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "check_paged_attn_serve.py")
    result = _run_driver_json(
        [sys.executable, tool, "--requests", "8"],
        check_rc=False, timeout=600.0)
    assert result.get("ok"), result.get("violations")
    assert result["token_mismatches"] == 0
    assert result["token_mismatches_churn"] == 0
    assert result["kernel"]["compiles"] == 0
    assert result["kernel"]["pages_in_use_after_close"] == 0
