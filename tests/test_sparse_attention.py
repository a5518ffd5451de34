"""ops/sparse_attention: the exact top-k selection (ties, short rows),
GQA without repeated keys, the dense limit, the indexer's loss and who
receives which gradient, against straightforward jax.numpy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.ops import sparse_attention as sa

B, T, HQ, HKV, D, HI, DI = 2, 24, 4, 2, 8, 2, 4


def _inputs(seed=0, t=T):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    return (n(B, t, HQ, D), n(B, t, HKV, D), n(B, t, HKV, D),
            n(B, t, HI, DI), n(B, t, DI), n(B, t, HI))


def _plain(q, k, v, qi, ki, wi, topk):
    """Dense [T, T] scores, lax.top_k, K and V repeated per query head."""
    t = q.shape[1]
    z = jnp.einsum("bqjd,bsd->bqjs", qi, ki)
    scores = jnp.sum(wi[..., None] * jax.nn.relu(z), 2) \
        * (HI ** -0.5 * DI ** -0.5)
    scores = jnp.where(scores == 0, 0.0, scores)
    causal = jnp.tril(jnp.ones((t, t), bool))[None]
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, t))
    sel = jnp.zeros((B, t, t), bool).at[
        jnp.arange(B)[:, None, None], jnp.arange(t)[None, :, None],
        idx].set(True) & causal
    kr, vr = (jnp.repeat(a, HQ // HKV, axis=2) for a in (k, v))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kr) * D ** -0.5
    probs = jax.nn.softmax(jnp.where(sel[:, None], logits, -1e30), -1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs, vr)
    target = jax.lax.stop_gradient(probs.sum(1) / HQ)
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, -1e30), -1)
    kl = jnp.sum(jnp.where(sel & (target > 0), target * (
        jnp.log(jnp.maximum(target, 1e-37)) - log_q), 0.0))
    return out, kl, sel


@pytest.mark.parametrize("topk,q_chunk,band", [(5, 4, 2), (5, 8, 4),
                                               (7, 24, 1), (1, 4, 3)])
def test_matches_plain_attention(topk, q_chunk, band):
    args = _inputs()
    got = sa.sparse_attention(*args, topk=topk, q_chunk=q_chunk,
                              chunks_per_band=band, return_selection=True)
    out, kl, sel = _plain(*args, topk)
    np.testing.assert_array_equal(np.asarray(got.selection), np.asarray(sel))
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(out),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got.indexer_loss), float(kl), rtol=1e-4)
    assert float(got.selected) == float(sel.sum())
    assert float(got.causal) == B * T * (T + 1) / 2


def test_selection_is_exact_under_ties_and_for_short_rows():
    """Rows of equal scores take the LOWEST keys, as lax.top_k does; a
    query with fewer than topk causal keys takes them all."""
    scores = jnp.zeros((1, 12, 12))
    scores = scores.at[0, 9, :].set(jnp.asarray(
        [1., 3, 3, 3, 0, 3, 3, -0.0, 0, 0, 9, 9]))
    causal = jnp.tril(jnp.ones((12, 12), bool))[None]
    sel = np.asarray(sa.select_topk(scores, causal, 4))
    for t in range(12):
        want = np.zeros(12, bool)
        want[:min(t + 1, 4)] = True
        if t == 9:
            want = np.zeros(12, bool)
            want[[1, 2, 3, 5]] = True
        np.testing.assert_array_equal(sel[0, t], want, err_msg=str(t))
    # negative scores and a score of -0.0 order as numbers do
    row = jnp.asarray([[[-2., -1, -0.0, 0, -3, 5]]])
    got = np.asarray(sa.select_topk(row, jnp.ones((1, 1, 6), bool), 3))
    np.testing.assert_array_equal(got[0, 0], [0, 0, 1, 1, 0, 1])


def test_zero_indexer_selects_the_first_keys():
    q, k, v, qi, ki, wi = _inputs()
    got = sa.sparse_attention(q, k, v, qi * 0, ki, wi, topk=3, q_chunk=8,
                              return_selection=True)
    sel = np.asarray(got.selection)
    for t in range(T):
        assert sel[0, t, :min(t + 1, 3)].all() and sel[0, t].sum() \
            == min(t + 1, 3)


def test_topk_of_the_sequence_is_dense_attention():
    q, k, v, qi, ki, wi = _inputs()
    got = sa.sparse_attention(q, k, v, qi, ki, wi, topk=T, q_chunk=8)
    kr, vr = (jnp.repeat(a, HQ // HKV, axis=2) for a in (k, v))
    logits = jnp.einsum("bqhd,bshd->bhqs", q, kr) * D ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    dense = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(
        jnp.where(causal, logits, -1e30), -1), vr)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(dense),
                               rtol=2e-5, atol=2e-6)
    assert float(got.selected) == float(got.causal)


def test_query_heads_read_their_own_group():
    """Changing one key/value head moves only its group's query heads."""
    q, k, v, qi, ki, wi = _inputs()
    base = sa.sparse_attention(q, k, v, qi, ki, wi, topk=6, q_chunk=8).out
    moved = sa.sparse_attention(q, k.at[:, :, 1].add(1.0),
                                v.at[:, :, 1].add(1.0), qi, ki, wi,
                                topk=6, q_chunk=8).out
    group = HQ // HKV
    np.testing.assert_array_equal(np.asarray(moved[:, :, :group]),
                                  np.asarray(base[:, :, :group]))
    assert not np.allclose(np.asarray(moved[:, :, group:]),
                           np.asarray(base[:, :, group:]))


def test_gradients_match_and_stay_on_their_side():
    """q, k, v learn from the output only; the indexer from its loss
    only (the selection is a constant of both)."""
    args = _inputs()

    def both(fn):
        def f(*a):
            out, kl = fn(*a)[:2]
            return jnp.sum(out ** 2) + 0.7 * kl
        return jax.grad(f, argnums=tuple(range(6)))(*args)

    got = both(lambda *a: sa.sparse_attention(*a, topk=5, q_chunk=8,
                                              chunks_per_band=2))
    want = both(lambda *a: _plain(*a, 5))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)
    out_only = jax.grad(lambda *a: jnp.sum(sa.sparse_attention(
        *a, topk=5, q_chunk=8).out ** 2), argnums=(3, 4, 5))(*args)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in out_only)
    loss_only = jax.grad(lambda *a: sa.sparse_attention(
        *a, topk=5, q_chunk=8).indexer_loss, argnums=(0, 1, 2))(*args)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in loss_only)


def test_shapes_that_do_not_divide_are_refused():
    q, k, v, qi, ki, wi = _inputs()
    with pytest.raises(ValueError, match="multiple"):
        sa.sparse_attention(q, k, v, qi, ki, wi, topk=4, q_chunk=7)
    with pytest.raises(ValueError, match="group"):
        sa.sparse_attention(q[:, :, :3], k, v, qi, ki, wi, topk=4,
                            q_chunk=8)


@pytest.mark.parametrize("topk,band", [(5, 2), (24, 3), (3, 1), (12, 2)])
def test_kernels_interpreted_match_the_xla_executor(topk, band, monkeypatch):
    """The four Mosaic kernels the TPU runs, interpreted here, against
    the einsum executor: output, loss and every gradient (the indexer's
    through the target ``sparse_attn_bwd`` hands back); key tiles of 8,
    so that a chunk walks several and skips those above its diagonal."""
    monkeypatch.setattr(sa, "_KEY_TILE", 8)
    args = _inputs(seed=3)

    def run(impl):
        def f(*a):
            o = sa.sparse_attention(*a, topk=topk, q_chunk=8,
                                    chunks_per_band=band, impl=impl)
            return jnp.sum(o.out ** 2) + 0.7 * o.indexer_loss, o
        (_, o), grads = jax.value_and_grad(
            f, argnums=tuple(range(6)), has_aux=True)(*args)
        return o, grads

    want, want_grads = run("xla")
    got, got_grads = run("kernel_interpret")
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want.out),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(got.indexer_loss),
                               float(want.indexer_loss), rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="impl"):
        sa.sparse_attention(*args, topk=4, q_chunk=8, impl="mosaic")


@pytest.mark.parametrize("q_start,dtype,rtol,atol", [
    (0, jnp.float32, 1e-5, 1e-5), (8, jnp.float32, 1e-5, 1e-5),
    (0, jnp.bfloat16, 2e-2, 2e-2), (8, jnp.bfloat16, 2e-2, 2e-2)])
def test_indexer_bwd_kernel_matches_the_vjp_of_the_scores(
        q_start, dtype, rtol, atol, monkeypatch):
    """The kernel ``indexer_bwd``, interpreted, against
    ``jax.vjp(indexer_scores)`` for one chunk of 8 queries over 24 keys
    in tiles of 8, under a ``ds`` that is zero off a random causal
    selection: a chunk at 0 sees one tile, one at 8 two, and the tiles
    above the diagonal get ``dki`` rows of exact zeros."""
    monkeypatch.setattr(sa, "_KEY_TILE", 8)
    C, hi, di = 8, 3, 4
    rng = np.random.default_rng(11 + q_start)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    qi, ki, wi = (n(B, C, hi, di).astype(dtype), n(B, T, di).astype(dtype),
                  n(B, C, hi).astype(dtype))
    causal = np.arange(T)[None] <= q_start + np.arange(C)[:, None]
    ds = jnp.where(causal & (rng.random((B, C, T)) < 0.6), n(B, C, T), 0.0)
    want = jax.vjp(sa.indexer_scores, qi, ki, wi)[1](ds)
    got = sa._indexer_bwd_call(qi, ki, wi, ds,
                                jnp.full((1,), q_start, jnp.int32), True)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)
    above = q_start + C
    assert float(jnp.abs(got[1][:, :above].astype(jnp.float32)).max()) > 0
    assert float(jnp.abs(got[1][:, above:].astype(jnp.float32)).max()) == 0.0


@pytest.mark.parametrize("keys,q_start,dtype,rtol,atol", [
    (16, 0, jnp.float32, 1e-5, 1e-5), (16, 8, jnp.float32, 1e-5, 1e-5),
    (32, 8, jnp.float32, 1e-5, 1e-5), (32, 24, jnp.float32, 1e-5, 1e-5),
    (32, 0, jnp.bfloat16, 2e-2, 2e-2), (32, 16, jnp.bfloat16, 2e-2, 2e-2)])
def test_bwd_kernel_hands_back_the_target_of_the_probs_kernel(
        keys, q_start, dtype, rtol, atol, monkeypatch):
    """The kernel ``sparse_attn_bwd``, interpreted, for one chunk of 8
    queries over 2 or 4 key tiles of 8 and 2 key/value heads of 3 query
    heads: the indexer's target it sums from its own probabilities is
    ``sparse_attn_probs``' on the same operands, bit for bit (the same
    sums in the same order), zero on the tiles past the chunk's last
    and one a row; its dq, dk and dv are the einsum executor's
    gradient."""
    monkeypatch.setattr(sa, "_KEY_TILE", 8)
    C, R = 8, 3
    rng = np.random.default_rng(17 + keys + q_start)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    q, k, v, do = (n(*shape).astype(dtype) for shape in (
        (B, HKV, R, C, D), (B, HKV, keys, D), (B, HKV, keys, D),
        (B, HKV, R, C, D)))
    t = q_start + np.arange(C)[:, None]
    s = np.arange(keys)[None]
    sel = jnp.asarray(((s <= t) & (rng.random((B, C, keys)) < 0.5))
                      | (s == t))
    qs, mask, start = sa._kernel_operands(q, sel, jnp.int32(q_start))
    out, lse = sa._fwd_call(qs, k, v, mask, start, True)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, delta.shape[:-1] + (sa._LANES,))
    dqs, dk, dv, target = sa._bwd_call(qs, k, v, mask, start, do, lse,
                                       delta, True)
    want = sa._probs_call(qs, k, mask, start, lse, True)
    assert target.dtype == jnp.float32 and target.shape == (B, C, keys)
    np.testing.assert_array_equal(np.asarray(target), np.asarray(want))
    past = (q_start // 8 + 1) * 8
    assert past == keys or float(jnp.abs(target[..., past:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(target.sum(-1)), 1.0, rtol=1e-5)

    _, pull = jax.vjp(lambda q, k, v: sa._attend_xla(q, k, v, sel)[0],
                      q, k, v)
    for g, w in zip((sa._scaled(dqs), dk, dv), pull(do)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


def _check_fwd_kernel(q, k, v, sel, q_start, rtol, atol):
    """``sparse_attn_fwd`` interpreted against the einsum executor's
    output and the logsumexp over the selection; returns the scaled
    logits."""
    qs, mask, start = sa._kernel_operands(q, sel, jnp.int32(q_start))
    out, lse = sa._fwd_call(qs, k, v, mask, start, True)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert lse.dtype == jnp.float32 \
        and lse.shape == q.shape[:-1] + (sa._LANES,)
    want, _ = sa._attend_xla(q, k, v, sel)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)
    logits = jnp.einsum("bgrqd,bgsd->bgrqs", qs, k,
                        preferred_element_type=jnp.float32)
    want_lse = jax.nn.logsumexp(
        jnp.where(sel[:, None, None], logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(want_lse),
                               rtol=1e-5, atol=1e-5)
    # every lane holds the row's one value
    np.testing.assert_array_equal(
        np.asarray(lse), np.broadcast_to(np.asarray(lse[..., :1]), lse.shape))
    return logits


@pytest.mark.parametrize("dtype,rtol,atol", [(jnp.float32, 1e-5, 1e-5),
                                             (jnp.bfloat16, 2e-2, 2e-2)])
@pytest.mark.parametrize("q_start", [0, 8, 16])
def test_fwd_kernel_keeps_its_running_state_exactly(q_start, dtype, rtol,
                                                    atol, monkeypatch):
    """The kernel ``sparse_attn_fwd``, interpreted, for one chunk of 8
    queries over 24 keys in tiles of 8 (a chunk at 0 walks one tile, at
    8 two, at 16 three), against the einsum executor's output and the
    logsumexp over the selection. Row 0's logits rise with the key, so
    its maximum moves in every tile; row 1's fall, so it never moves
    again; row 2 meets its first selected key in the second tile it
    walks, row 3 only in its own (the last), row 4 reads its own key
    alone; the rest select at random. The state lies across the lanes:
    every lane of ``lse`` holds the row's one value."""
    monkeypatch.setattr(sa, "_KEY_TILE", 8)
    C, R = 8, 3
    rng = np.random.default_rng(5 + q_start)

    def n(*shape):
        return rng.standard_normal(shape).astype(np.float32)

    q, k, v = n(B, HKV, R, C, D), n(B, HKV, T, D), n(B, HKV, T, D)
    # rows 0 and 1 look along one direction, along which the keys grow
    u = np.zeros(D, np.float32)
    u[0] = 4.0
    q[:, :, :, 0], q[:, :, :, 1] = u, -u
    k[..., 0] = 0.5 * (1 + np.arange(T))
    t = q_start + np.arange(C)[:, None]
    s = np.arange(T)[None]
    sel = (s <= t) & (rng.random((B, C, T)) < 0.5)
    sel[:, :2] = s <= t[:2]
    sel[:, 2] = (s >= 8) & (s <= t[2])
    sel[:, 3] = (s >= q_start) & (s <= t[3])
    sel[:, 4] = False
    sel |= s == t                       # every row has its own key
    sel = jnp.asarray(sel)
    q, k, v = (jnp.asarray(a).astype(dtype) for a in (q, k, v))

    logits = _check_fwd_kernel(q, k, v, sel, q_start, rtol, atol)
    # the cases the state must get right are in the selection
    first = np.argmax(np.asarray(sel), axis=-1)             # [B, C]
    if q_start >= 8:
        assert (first[:, 2] == 8).all() and (first[:, 3] == q_start).all()
    rising = np.asarray(logits[0, 0, 0, 0], np.float32)[:q_start + 1]
    assert (np.diff(rising) > 0).all()


@pytest.mark.parametrize("keys", [24, 192, 256])
def test_fwd_kernel_state_follows_the_key_tile(keys):
    """The state's width follows the key tile: one tile of 24 or of 192
    keys holds it over its own width, one of 256 over 128 lanes (the
    row sum as two lane tiles added, the maximum laid twice side by
    side), as the tiles of 512 on the chip do."""
    C, R = 8, 2
    rng = np.random.default_rng(keys)
    q, k, v = (jnp.asarray(rng.standard_normal(shape).astype(np.float32))
               for shape in ((B, HKV, R, C, D), (B, HKV, keys, D),
                             (B, HKV, keys, D)))
    q_start = keys - C
    t = q_start + np.arange(C)[:, None]
    s = np.arange(keys)[None]
    sel = jnp.asarray(((s <= t) & (rng.random((B, C, keys)) < 0.4))
                      | (s == t))
    assert sa._state_lanes(sa._tile(keys)) == (128 if keys == 256 else keys)
    _check_fwd_kernel(q, k, v, sel, q_start, 1e-5, 1e-5)
