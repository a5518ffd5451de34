"""Expert-parallel MoE tests: sharded dispatch/combine matches the
unsharded reference path; gradients flow; capacity drops are bounded
and ACCOUNTED (never silent); top-2 (GShard) routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.core import mesh as mesh_lib
from parallax_tpu.ops import moe


B, D, F, E = 64, 16, 32, 8


@pytest.fixture
def weights(rng):
    return (
        jnp.asarray(rng.standard_normal((D, E)).astype(np.float32)) * 0.5,
        jnp.asarray(rng.standard_normal((E, D, F)).astype(np.float32))
        * 0.1,
        jnp.asarray(rng.standard_normal((E, F, D)).astype(np.float32))
        * 0.1,
    )


@pytest.fixture
def tokens(rng):
    return jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("k", [1, 2])
def test_sharded_matches_dense_path(tokens, weights, p, k):
    router, w1, w2 = weights
    mesh = mesh_lib.build_mesh(num_partitions=p)
    # generous capacity so nothing is dropped -> exact match
    ref, aux_ref, drop_ref = moe.switch_moe(
        tokens, router, w1, w2, None, capacity_factor=float(E), top_k=k)
    got, aux, dropped = moe.switch_moe(
        tokens, router, w1, w2, mesh, capacity_factor=float(E), top_k=k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-6)
    assert float(dropped) == 0.0 and float(drop_ref) == 0.0


@pytest.mark.parametrize("k", [1, 2])
def test_gradients_flow_through_dispatch(tokens, weights, k):
    router, w1, w2 = weights
    mesh = mesh_lib.build_mesh(num_partitions=4)

    def loss(w1, w2, tokens):
        out, aux, _ = moe.switch_moe(tokens, router, w1, w2, mesh,
                                     capacity_factor=float(E), top_k=k)
        return jnp.sum(out ** 2) + 0.01 * aux

    g1, g2 = jax.jit(jax.grad(loss, argnums=(0, 1)))(w1, w2, tokens)

    def ref_loss(w1, w2, tokens):
        out, aux, _ = moe.switch_moe(tokens, router, w1, w2, None,
                                     capacity_factor=float(E), top_k=k)
        return jnp.sum(out ** 2) + 0.01 * aux

    e1, e2 = jax.grad(ref_loss, argnums=(0, 1))(w1, w2, tokens)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(e1), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(e2), rtol=1e-4,
                               atol=1e-6)


def test_capacity_drops_are_accounted(tokens, weights):
    """With tight capacity some tokens drop (zero output) — and the
    dropped fraction REPORTS it (silent drops were VERDICT weak #8)."""
    router, w1, w2 = weights
    mesh = mesh_lib.build_mesh(num_partitions=4)
    out, aux, dropped = moe.switch_moe(tokens, router, w1, w2, mesh,
                                       capacity_factor=0.5)
    assert out.shape == (B, D)
    assert np.isfinite(np.asarray(out)).all()
    # at least one token dropped given the skewed router
    zero_rows = np.asarray((jnp.sum(jnp.abs(out), axis=1) == 0))
    assert zero_rows.any()
    assert float(dropped) > 0.0
    # the accounting matches the observable zero rows at k=1: a dropped
    # (token, choice) IS a zeroed token output
    np.testing.assert_allclose(float(dropped), zero_rows.mean(),
                               atol=0.02)


def test_top2_gates_renormalized(weights):
    """Top-2 output = g1*f(e1) + g2*f(e2) with g1+g2 = 1."""
    rng = np.random.default_rng(7)
    router, w1, w2 = weights
    toks = jnp.asarray(rng.standard_normal((4, D)).astype(np.float32))
    out, _, _ = moe.switch_moe(toks, router, w1, w2, None, top_k=2)
    probs = jax.nn.softmax(toks @ router, axis=-1)
    tp, ti = jax.lax.top_k(probs, 2)
    g = tp / tp.sum(-1, keepdims=True)

    def f(e, x):
        return jax.nn.relu(x @ w1[e]) @ w2[e]
    expect = np.stack([
        np.asarray(g[i, 0] * f(int(ti[i, 0]), toks[i])
                   + g[i, 1] * f(int(ti[i, 1]), toks[i]))
        for i in range(4)])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-5,
                               atol=2e-6)


def test_first_choice_has_capacity_priority(weights):
    """When capacity is scarce, first choices must win slots over
    second choices (GShard priority)."""
    rng = np.random.default_rng(3)
    router, w1, w2 = weights
    toks = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    mesh = mesh_lib.build_mesh(num_partitions=4)
    # same tokens, k=1 vs k=2 at the k-scaled same capacity: every slot a
    # first choice occupies at k=1 must still be served at k=2
    out1, _, drop1 = moe.switch_moe(toks, router, w1, w2, mesh,
                                    capacity_factor=1.0, top_k=1)
    out2, _, drop2 = moe.switch_moe(toks, router, w1, w2, mesh,
                                    capacity_factor=1.0, top_k=2)
    served1 = np.asarray(jnp.sum(jnp.abs(out1), axis=1) > 0)
    served2 = np.asarray(jnp.sum(jnp.abs(out2), axis=1) > 0)
    # a token served at k=1 keeps (at least) its first-choice service
    assert (served2 >= served1).all()


def test_aux_loss_uniform_router_is_one():
    """With a uniform router, E * sum f_e p_e == 1 (balanced)."""
    tokens = jnp.ones((32, D))
    router = jnp.zeros((D, E))
    w1 = jnp.zeros((E, D, F))
    w2 = jnp.zeros((E, F, D))
    _, aux, _ = moe.switch_moe(tokens, router, w1, w2, None)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)


def test_bad_top_k_rejected(tokens, weights):
    router, w1, w2 = weights
    with pytest.raises(ValueError, match="top_k"):
        moe.switch_moe(tokens, router, w1, w2, None, top_k=0)
    with pytest.raises(ValueError, match="top_k"):
        moe.switch_moe(tokens, router, w1, w2, None, top_k=E + 1)


# ---- routed_experts: the dropless layer of a chip's share ----------------

RD, RF, RE = 16, 24, 8


def _routed_weights(rng, held=RE):
    def n(*shape, scale=0.2):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ) * scale
    return n(RD, RE, scale=0.7), n(held, RD, RF), n(held, RD, RF), \
        n(held, RF, RD)


def _routed(tokens, router, w_gate, w_up, w_down, top_k, first_expert=0,
            impl=None):
    """``linear_router`` and ``routed_experts`` under its choice: the
    layer as ``models/keye_vl2`` calls it."""
    import types
    route = moe.linear_router(tokens, router, top_k)
    got = moe.routed_experts(tokens, route.choice, route.gate, w_gate, w_up,
                             w_down, num_experts=router.shape[1],
                             first_expert=first_expert, impl=impl)
    return types.SimpleNamespace(aux_loss=route.aux_loss, choice=route.choice,
                                 gate=route.gate, **got._asdict())


def _routed_plain(tokens, router, w_gate, w_up, w_down, k, first=0):
    """Every held expert for every token, then the token's own gates."""
    probs = jax.nn.softmax(tokens @ router, -1)
    top_p, top_i = jax.lax.top_k(probs, k)
    gates = top_p / top_p.sum(-1, keepdims=True)
    act = jax.nn.silu(jnp.einsum("nd,edf->nef", tokens, w_gate)) \
        * jnp.einsum("nd,edf->nef", tokens, w_up)
    each = jnp.einsum("nef,efd->ned", act, w_down)
    weight = (jax.nn.one_hot(top_i - first, w_gate.shape[0])
              * gates[..., None]).sum(1)
    return jnp.einsum("ned,ne->nd", each, weight)


@pytest.mark.parametrize("k", [1, 2, 4])
def test_routed_experts_match_every_expert_for_every_token(tokens, rng, k):
    router, wg, wu, wd = _routed_weights(rng)
    got = _routed(tokens, router, wg, wu, wd, top_k=k)
    want = _routed_plain(tokens, router, wg, wu, wd, k)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert float(got.dropped) == 0.0
    assert float(got.rows_here) == B * k      # all experts held here


def test_the_shares_of_a_layer_add_up_to_the_whole(tokens, rng):
    """Four chips holding two experts each: their parts sum to what one
    chip holding all eight computes; the router is counted once."""
    router, wg, wu, wd = _routed_weights(rng)
    whole = _routed(tokens, router, wg, wu, wd, top_k=3)
    parts = [_routed(tokens, router, wg[f:f + 2], wu[f:f + 2],
                                wd[f:f + 2], top_k=3, first_expert=f)
             for f in range(0, RE, 2)]
    np.testing.assert_allclose(
        np.asarray(sum(p.out for p in parts)), np.asarray(whole.out),
        rtol=2e-5, atol=2e-6)
    assert sum(float(p.rows_here) for p in parts) == B * 3
    assert all(float(p.aux_loss) == float(whole.aux_loss) for p in parts)
    # a traced first_expert (a shard's index) gives the same part
    traced = jax.jit(lambda f: _routed(
        tokens, router, wg[2:4], wu[2:4], wd[2:4], top_k=3,
        first_expert=f).out)(jnp.int32(2))
    np.testing.assert_allclose(np.asarray(traced), np.asarray(parts[1].out),
                               rtol=1e-6, atol=1e-7)


def test_routed_experts_drop_nothing_under_a_skewed_router(tokens, rng):
    """A router that sends every token to the same two experts: both
    are computed for all B tokens (switch_moe's capacity would drop)."""
    router, wg, wu, wd = _routed_weights(rng)
    bias_tokens = jnp.concatenate([tokens, jnp.ones((B, 1))], axis=1)
    skew = jnp.zeros((RD + 1, RE)).at[RD, 1].set(30.0).at[RD, 5].set(29.0)
    pad = lambda w: jnp.concatenate(
        [w, jnp.zeros((w.shape[0], 1, w.shape[2]))], axis=1)
    wd_p = jnp.concatenate([wd, jnp.zeros((RE, RF, 1))], axis=2)
    got = _routed(bias_tokens, skew, pad(wg), pad(wu), wd_p,
                             top_k=2)
    want = _routed_plain(bias_tokens, skew, pad(wg), pad(wu), wd_p, 2)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert float(got.dropped) == 0.0
    assert float(got.rows_here) == 2 * B
    assert float(got.load_max_over_mean) == pytest.approx(RE / 2)
    # the chip that holds neither of the two computes nothing, drops nothing
    idle = _routed(bias_tokens, skew, pad(wg)[2:4], pad(wu)[2:4],
                              wd_p[2:4], top_k=2, first_expert=2)
    assert float(idle.rows_here) == 0.0 and float(idle.dropped) == 0.0
    assert float(jnp.abs(idle.out).max()) == 0.0


def test_routed_experts_gradients_match(tokens, rng):
    router, wg, wu, wd = _routed_weights(rng, held=4)

    def loss(fn):
        def f(t, r, a, b, c):
            return jnp.sum(fn(t, r, a, b, c) ** 2)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(tokens, router, wg, wu,
                                                    wd)

    got = loss(lambda t, r, a, b, c: _routed(
        t, r, a, b, c, top_k=3, first_expert=2).out)
    want = loss(lambda t, r, a, b, c: _routed_plain(t, r, a, b, c, 3, 2))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


def test_routed_experts_kernel_path_interpreted(rng):
    """The megablox kernel the TPU runs, interpreted here, against
    XLA's ragged dot, rows of absent experts included."""
    toks = jnp.asarray(rng.standard_normal((64, RD)).astype(np.float32))
    router, wg, wu, wd = _routed_weights(rng, held=4)
    args = (toks, router, wg, wu, wd)
    ref = _routed(*args, top_k=2, first_expert=4,
                             impl="ragged_dot")
    got = _routed(*args, top_k=2, first_expert=4,
                             impl="gmm_interpret")
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(ref.out),
                               rtol=2e-5, atol=2e-6)
    assert 0 < float(got.rows_here) < 128      # 128 rows: one tile


def test_routed_experts_reject_bad_arguments(tokens, rng):
    router, wg, wu, wd = _routed_weights(rng)
    with pytest.raises(ValueError, match="top_k"):
        _routed(tokens, router, wg, wu, wd, top_k=RE + 1)
    with pytest.raises(ValueError, match="impl"):
        _routed(tokens, router, wg, wu, wd, top_k=2, impl="x")


@pytest.mark.parametrize("skewed", [False, True])
def test_routed_experts_rows_past_the_fast_part(rng, skewed):
    """1,024 tokens top-2 on one held expert of eight: a balanced
    router's share is 256 rows and the fast part 512. A router skewed
    onto the held expert sends it all 1,024, which the second part
    takes: the result and the gradients are the plain ones either way,
    and nothing drops."""
    n = 1024
    toks = jnp.asarray(rng.standard_normal((n, RD)).astype(np.float32))
    router, wg, wu, wd = _routed_weights(rng, held=1)
    if skewed:
        toks = toks.at[:, 0].set(4.0)
        router = router.at[0, 3].set(9.0)
    args = (toks, router, wg, wu, wd)
    got = _routed(*args, top_k=2, first_expert=3)
    want = _routed_plain(*args, 2, 3)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    assert float(got.dropped) == 0.0
    assert (float(got.rows_here) > 512) == skewed
    grads = jax.grad(lambda *a: jnp.sum(_routed(
        *a, top_k=2, first_expert=3).out ** 2), argnums=(0, 1, 2, 4))(*args)
    plain = jax.grad(lambda *a: jnp.sum(_routed_plain(*a, 2, 3) ** 2),
                     argnums=(0, 1, 2, 4))(*args)
    for g, w in zip(grads, plain):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5)


def test_fast_rows_and_the_two_parts_cover_every_row(rng):
    """The first part's size, and that the two parts' group sizes add
    up to every held expert's rows wherever the split falls."""
    assert moe.fast_rows(8192, 8, 16, 128) == 16384
    assert moe.fast_rows(1024, 2, 1, 8) == 512
    assert moe.fast_rows(64, 2, 4, 8) == 128          # all of them
    sizes = jnp.asarray(rng.integers(0, 300, 6), jnp.int32)
    ends = jnp.cumsum(sizes)
    for fast in (0, 128, 512, 2048):
        a = moe._part_sizes(sizes, ends, 0, fast)
        b = moe._part_sizes(sizes, ends, fast, 2048 - fast)
        np.testing.assert_array_equal(np.asarray(a + b), np.asarray(sizes))


def test_dropped_counts_rows_that_no_part_covered(rng, monkeypatch):
    """``dropped`` is read off the parts' own group sizes and the
    second part's predicate: a split that loses rows shows in it."""
    n = 1024
    toks = jnp.asarray(rng.standard_normal((n, RD)).astype(np.float32))
    toks = toks.at[:, 0].set(4.0)
    router, wg, wu, wd = _routed_weights(rng, held=1)
    router = router.at[0, 3].set(9.0)
    args = (toks, router, wg, wu, wd)
    sound = _routed(*args, top_k=2, first_expert=3)
    assert float(sound.rows_here) > 512 and float(sound.dropped) == 0.0
    # each part leaves the last 128 of its rows out: the first part's
    # [384, 512) are live and lost, the second's lie past the live rows
    whole = moe._part_sizes
    monkeypatch.setattr(
        moe, "_part_sizes",
        lambda sizes, ends, lo, n: whole(sizes, ends, lo, max(n - 128, 0)))
    lossy = _routed(*args, top_k=2, first_expert=3)
    assert float(lossy.dropped) == 128.0


def test_routed_experts_under_a_given_choice_are_the_old_entry(rng):
    """``routed_experts`` used to route for itself (softmax of one
    matrix, top-k, renormalised gates). Under that routing, handed in,
    it gives what it gave: bit for bit the old prologue's numbers here,
    and the parent commit's own outputs on these inputs as recorded."""
    gen = np.random.default_rng(31)
    n, first, k = 96, 2, 2

    def draw(*shape, scale=0.2):
        return jnp.asarray(gen.standard_normal(shape).astype(np.float32)
                           ) * scale
    toks, router = draw(n, RD, scale=1.0), draw(RD, RE, scale=0.7)
    wg, wu, wd = draw(4, RD, RF), draw(4, RD, RF), draw(4, RF, RD)

    def value(t, r, a, b, c, by_hand):
        if by_hand:
            # the four lines the old entry began with
            probs = jax.nn.softmax(t.astype(jnp.float32)
                                   @ r.astype(jnp.float32), axis=-1)
            top_p, choice = jax.lax.top_k(probs, k)
            gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
            aux = moe.load_balance_loss(probs, choice)
        else:
            choice, gate, aux = moe.linear_router(t, r, k)
        out = moe.routed_experts(t, choice, gate, a, b, c, num_experts=RE,
                                 first_expert=first)
        return jnp.sum(out.out * jnp.cos(jnp.arange(RD))) + 0.1 * aux, out

    run = jax.jit(jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4),
                                     has_aux=True), static_argnums=5)
    (v_new, out_new), g_new = run(toks, router, wg, wu, wd, False)
    (v_old, out_old), g_old = run(toks, router, wg, wu, wd, True)
    assert float(v_new) == float(v_old)
    np.testing.assert_array_equal(np.asarray(out_new.out),
                                  np.asarray(out_old.out))
    for a, b in zip(g_new, g_old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the parent commit's entry on the same inputs (bit-equal on the
    # machine that recorded them; another CPU may add in another order)
    assert float(v_new) == pytest.approx(7.438641548156738, rel=1e-6)
    assert float(out_new.rows_here) == 105.0
    assert float(out_new.load_max_over_mean) == pytest.approx(
        1.409523844718933, rel=1e-6)
    sums = [float(jnp.sum(g)) for g in g_new]
    np.testing.assert_allclose(
        sums, [-5.355449676513672, -2.652406692504883e-06,
               1.0701560974121094, -46.37874984741211, -0.9740736484527588],
        rtol=2e-5, atol=2e-6)


def test_routed_experts_take_an_unrenormalised_top1_gate(tokens, rng):
    """A caller's own routing (``models/zaya``: one expert a token, the
    gate its probability as it is): the gate scales the expert's output
    and takes its gradient; a choice carries none."""
    router, wg, wu, wd = _routed_weights(rng)
    probs = jax.nn.softmax(tokens @ router, -1)
    choice = jnp.argmax(probs, -1, keepdims=True).astype(jnp.int32)

    def f(scale):
        gate = jnp.take_along_axis(probs, choice, -1) * scale
        return moe.routed_experts(tokens, choice, gate, wg, wu, wd,
                                  num_experts=RE)
    one, half = f(1.0), f(0.5)
    np.testing.assert_allclose(np.asarray(half.out), 0.5 * np.asarray(one.out),
                               rtol=1e-6, atol=1e-7)
    assert float(one.rows_here) == B and float(one.dropped) == 0.0
    act = jax.nn.silu(jnp.einsum("nd,edf->nef", tokens, wg)) \
        * jnp.einsum("nd,edf->nef", tokens, wu)
    each = jnp.einsum("nef,efd->ned", act, wd)
    want = jnp.take_along_axis(each, choice[..., None], 1)[:, 0] \
        * jnp.take_along_axis(probs, choice, -1)
    np.testing.assert_allclose(np.asarray(one.out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    d_scale = jax.grad(lambda s: jnp.sum(f(s).out))(1.0)
    assert float(d_scale) == pytest.approx(float(jnp.sum(one.out)), rel=1e-4)


def _sorted_rows(choice, held, lo, m):
    """The sorted rows ``[lo, lo + m)`` of ``choice`` as ``routed_experts``
    takes them: (the pair of every row, the live rows among them)."""
    group = jnp.where(choice < held, choice, held).reshape(-1)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    rows_here = int(jnp.sum(choice < held))
    return order[lo:lo + m], min(max(rows_here - lo, 0), m)


def _draw_choice(rng, b, k, num_experts):
    return jnp.asarray(np.stack([rng.permutation(num_experts)[:k]
                                 for _ in range(b)]), jnp.int32)


# token 5 is the last row of expert 0's group and the first of expert
# 1's: two updates in a row of one accumulator row
_NEIGHBOURS = np.asarray([[0, 2]] * 5 + [[0, 1]] + [[1, 2]] * 2, np.int32)

# b tokens choosing k of e experts, `held` of them here; the sorted rows
# [lo, lo + m) of width d
_SUM_ROWS_CASE = dict(lo=0, scaled=True, dtype=jnp.float32, d=128,
                      acc_bytes=None, choice=None)


@pytest.mark.parametrize("case", [
    pytest.param(dict(b=256, k=1, e=16, held=8, m=256, dtype=jnp.bfloat16,
                      d=256), id="k1-pairs-are-rows"),
    pytest.param(dict(b=128, k=8, e=64, held=16, m=512, dtype=jnp.bfloat16,
                      d=256), id="k8-a-quarter-live"),
    pytest.param(dict(b=128, k=8, e=64, held=48, lo=512, m=512),
                 id="second-part-lo-512"),
    pytest.param(dict(b=64, k=2, e=8, held=0, m=128), id="no-live-row"),
    pytest.param(dict(b=96, k=8, e=64, held=24, m=768, dtype=jnp.bfloat16),
                 id="live-rows-end-inside-a-block"),
    pytest.param(dict(b=8, k=2, e=4, held=4, m=16, choice=_NEIGHBOURS),
                 id="one-token-ends-and-starts-groups"),
    pytest.param(dict(b=128, k=8, e=64, held=16, m=512, scaled=False,
                      dtype=jnp.bfloat16, d=256),
                 id="no-scale-dispatch-backward"),
    pytest.param(dict(b=96, k=8, e=64, held=24, m=768, scaled=False, d=384,
                      acc_bytes=4 * 96 * 128), id="three-column-chunks"),
])
def test_sum_rows_by_token_kernel_against_segment_sum(rng, case,
                                                      monkeypatch):
    """The Mosaic kernel interpreted against ``jax.ops.segment_sum`` over
    the live rows, and the path off the TPU against the same; what lies
    past the live rows is NaN and must not be added."""
    c = {**_SUM_ROWS_CASE, **case}
    b, k, m, d = c["b"], c["k"], c["m"], c["d"]
    choice = _draw_choice(rng, b, k, c["e"]) if c["choice"] is None \
        else jnp.asarray(c["choice"])
    mine, n_live = _sorted_rows(choice, c["held"], c["lo"], m)
    token_of_row = mine // k
    if c["acc_bytes"] is not None:
        monkeypatch.setattr(moe, "_SUM_ROWS_ACC_BYTES", c["acc_bytes"])
    assert d // moe._sum_rows_chunk(b, d) == (3 if c["acc_bytes"] else 1)
    if c["lo"]:
        assert 0 < n_live < m
    rows = rng.standard_normal((m, d)).astype(np.float32)
    rows[n_live:] = np.nan
    rows = jnp.asarray(rows, c["dtype"])
    scale = jnp.asarray(rng.standard_normal(m), jnp.float32) \
        if c["scaled"] else None
    wide = rows.astype(jnp.float32)[:n_live]
    if scale is not None:
        wide = wide * scale[:n_live, None]
    want = jax.ops.segment_sum(wide, token_of_row[:n_live], num_segments=b)
    for impl in ("gmm_interpret", "ragged_dot"):
        got = jax.jit(lambda r, s, t, n: moe.sum_rows_by_token(
            r, s, t, n, b, impl))(rows, scale, token_of_row,
                                  jnp.int32(n_live))
        assert got.dtype == jnp.float32 and got.shape == (b, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6, err_msg=impl)
        # as `_combine` and `_dispatch` call it: a part with one row
        # of every token (the first case) gathers, the rest is the same
        got = jax.jit(lambda r, s, p, n: moe._sum_by_token(
            r, s, p, n, b, k, impl))(rows, scale, mine, jnp.int32(n_live))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6, err_msg=impl)
    one_row = moe._one_row_a_token(m, b, k)
    assert one_row == (k == 1)
    tm = moe._sum_rows_block(m)
    walked = {impl: int(moe._rows_walked(jnp.int32(n_live), m, b, k, impl))
              for impl in ("gmm_interpret", "ragged_dot")}
    assert walked == ({"gmm_interpret": m, "ragged_dot": m} if one_row else
                      {"gmm_interpret": -(-n_live // tm) * tm,
                       "ragged_dot": n_live})


def test_sum_rows_by_token_cases_are_what_they_say(rng):
    """The shapes the cases above stand for: a token in the last row of
    one group and the first of the next, a part whose live rows end
    inside a row block, a part without a live row; and the rows the
    kernel refuses."""
    mine, n_live = _sorted_rows(jnp.asarray(_NEIGHBOURS), 4, 0, 16)
    assert n_live == 16 and int(mine[5] // 2) == int(mine[6] // 2) == 5
    _, n_live = _sorted_rows(_draw_choice(rng, 96, 8, 64), 24, 0, 768)
    tm = moe._sum_rows_block(768)
    assert tm == 256 and 0 < n_live % tm and n_live < 768 - tm
    assert _sorted_rows(_draw_choice(rng, 64, 2, 8), 0, 0, 128)[1] == 0
    with pytest.raises(ValueError, match="multiple of 8"):
        moe.sum_rows_by_token(jnp.zeros((12, 128)), None,
                              jnp.zeros(12, jnp.int32), 3, 4, "gmm_interpret")


@pytest.mark.parametrize("part", ["first", "second"])
def test_gate_cotangent_by_rows_is_plain_ad_of_the_pair_formula(rng, part):
    """``_combine``'s cotangents against plain AD of what it used to be:
    every (token, choice) pair's row gathered through the inverse of the
    sort into ``[B, k, D]`` and summed over ``k`` under the gates."""
    b, k, held, e, d = 128, 4, 6, 8, 16
    choice = _draw_choice(rng, b, k, e)
    fast = 256                                  # of 512 pairs, ~384 live
    assert int(jnp.sum(choice < held)) > fast
    lo, m = (0, fast) if part == "first" else (fast, b * k - fast)
    mine, n_live = _sorted_rows(choice, held, lo, m)
    assert 0 < n_live and (part == "first" or n_live < m)
    group = jnp.where(choice < held, choice, held).reshape(-1)
    inv = jnp.argsort(jnp.argsort(group, stable=True))
    y = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
    y = jnp.where((jnp.arange(m) < n_live)[:, None], y, 0.0)
    weight = jnp.where(choice < held, jnp.asarray(
        rng.uniform(0.1, 1.0, (b, k)), jnp.float32), 0.0)
    g = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)

    def pairs(y, weight):
        pos = inv - lo
        valid = (pos >= 0) & (pos < m)
        rows = jnp.where(valid[:, None], y[jnp.clip(pos, 0, m - 1)], 0.0)
        return jnp.einsum("bkd,bk->bd", rows.reshape(b, k, d), weight)

    want_out, want_vjp = jax.vjp(pairs, y, weight)
    got_out, got_vjp = jax.vjp(
        lambda y, w: moe._combine(y, w, mine, jnp.int32(n_live), k,
                                  "ragged_dot"), y, weight)
    np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                               rtol=2e-4, atol=2e-5)
    for got, want in zip(got_vjp(g), want_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(want_vjp(g)[1]).max()) > 0.1


@pytest.mark.parametrize("shape,want", [
    # Mellum2's experts (width 896 = 7 x 128): the up and gate products'
    # n and the down product's k take the width whole, not 128
    ((16384, 2304, 896), (512, 768, 896)),
    ((16384, 896, 2304), (512, 896, 768)),
    # Keye's (2048 x 768) and ZAYA's (2048 x 2048) as before 896 was a
    # choice
    ((32768, 2048, 768), (512, 1024, 768)),
    ((32768, 768, 2048), (512, 768, 1024)),
    ((8192, 2048, 2048), (512, 1024, 1024)),
    # Trinity-Mini's (2048 x 1024)
    ((8192, 2048, 1024), (512, 1024, 1024)),
    # the transposed products of Mellum2's gate and up (k and n
    # swapped) and of its down product, at the cell's 32,768 rows
    ((32768, 896, 2304), (512, 896, 768)),
    ((32768, 2304, 896), (512, 768, 896)),
    # GLM-4.7-Flash's (2048 x 1536): forward and transposed
    ((8192, 2048, 1536), (512, 1024, 768)),
    ((8192, 1536, 2048), (512, 768, 1024)),
    # a width only the rule covers: 640 = 5 x 128, and 1920 = 3 x 640
    ((4096, 1920, 640), (512, 640, 640)),
    # no choice divides: the size itself
    ((96, 200, 72), (96, 200, 72)),
])
def test_gmm_tiling_takes_the_widest_choice_that_divides(shape, want):
    assert moe._gmm_tiling(*shape) == want


def _per_group_dot(lhs, rhs, group_sizes, transpose_rhs):
    """``_grouped_dot`` written out: each row times its group's matrix
    in float32 at the highest precision, zeros past the groups."""
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    rows = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(jnp.cumsum(group_sizes), rows, side="right")
    onehot = jax.nn.one_hot(group, rhs.shape[0], dtype=lhs.dtype)
    return jnp.einsum("mk,gkn,mg->mn", lhs, rhs, onehot,
                      precision=jax.lax.Precision.HIGHEST)


# k 384 and n 256: the forward is tiled (128, 384, 256), the transposed
# product (128, 256, 384); the forward's tuple would not divide it
GK, GN, GM = 384, 256, 384


@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("sizes", [
    # an empty group, one under a row tile (128), one across two row
    # tiles, and 64 rows past the groups that are nobody's
    (0, 50, 200, 70),
    # every row live, the first group across a tile's edge, the last
    # group empty
    (130, 126, 128, 0),
])
def test_grouped_dot_and_its_gradients_match_a_per_group_product(
        rng, transpose_rhs, sizes):
    """The megablox products, interpreted, at shapes where the
    transposed product's tiles differ from the forward's: the output,
    the rows' cotangent (the transposed product) and the matrices'
    (``tgmm``) against the per-group product and its plain AD."""
    assert moe._gmm_tiling(GM, GK, GN) != moe._gmm_tiling(GM, GN, GK)
    groups = len(sizes)
    lhs = jnp.asarray(rng.standard_normal((GM, GK)).astype(np.float32))
    shape = (groups, GN, GK) if transpose_rhs else (groups, GK, GN)
    rhs = jnp.asarray(rng.standard_normal(shape).astype(np.float32)) * 0.1
    ct = jnp.asarray(rng.standard_normal((GM, GN)).astype(np.float32))
    group_sizes = jnp.asarray(sizes, jnp.int32)
    live = sum(sizes)

    def run(fn):
        out, vjp = jax.vjp(lambda a, b: fn(a, b, group_sizes, transpose_rhs),
                           lhs, rhs)
        return (out,) + vjp(ct)

    got_out, got_dlhs, got_drhs = run(
        lambda a, b, g, t: moe._grouped_dot(a, b, g, "gmm_interpret", t))
    want_out, want_dlhs, want_drhs = run(_per_group_dot)
    tol = dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_out)[:live],
                               np.asarray(want_out)[:live], **tol)
    np.testing.assert_allclose(np.asarray(got_dlhs)[:live],
                               np.asarray(want_dlhs)[:live], **tol)
    # the rows past the groups do not reach the matrices' cotangent,
    # and an empty group's is zero
    np.testing.assert_allclose(np.asarray(got_drhs), np.asarray(want_drhs),
                               **tol)
    for g, size in enumerate(sizes):
        assert (float(jnp.abs(got_drhs[g]).max()) == 0) == (size == 0)


def _kernel_tiles(fn, *args):
    """The (rows, contraction, columns) tiles of each megablox kernel
    that tracing ``fn`` on ``args`` calls, in order, read off the block
    shapes of its ``pallas_call``: ``[("gmm", (tm, tk, tn)), ...]``."""
    from jax.extend import core as jcore
    found = []

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                blocks = [[getattr(b, "block_size", None)
                           for b in m.block_shape]
                          for m in eqn.params["grid_mapping"].block_mappings]
                if kernel == "gmm":       # lhs (tm, tk), out (tm, tn)
                    tiles = (blocks[0][0], blocks[0][1], blocks[2][1])
                else:                     # rhs (tm, tn), out (., tk, tn)
                    tiles = (blocks[1][0], blocks[2][1], blocks[2][2])
                found.append((kernel, tiles))
                continue
            inner = eqn.params.get("name") \
                if eqn.params.get("name") in ("gmm", "tgmm") else kernel
            for v in eqn.params.values():
                for j in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(j, jcore.ClosedJaxpr):
                        walk(j.jaxpr, inner)
                    elif isinstance(j, jcore.Jaxpr):
                        walk(j, inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("rows,dim,width,transposed", [
    # (the cell's fast rows, model width, expert width), and what the
    # transposed products get on the forward's tiles: Mellum2 2.0 x the
    # MXU passes they need, Keye and GLM 1.5 x; ZAYA and Trinity-Mini
    # the same tiles either way
    pytest.param(32768, 2304, 896, True, id="mellum2"),
    pytest.param(16384, 2048, 768, True, id="keye"),
    pytest.param(8192, 2048, 1536, True, id="glm"),
    pytest.param(8192, 2048, 2048, False, id="zaya"),
    pytest.param(16384, 2048, 1024, False, id="trinity"),
])
@pytest.mark.parametrize("product", ["gate_up", "down"])
def test_each_grouped_product_is_tiled_for_its_own_shape(
        rows, dim, width, transposed, product):
    """At each cell's shapes the forward product and ``tgmm`` keep the
    tiles the forward's shape gives, and megablox's VJP gives the
    transposed product (k and n swapped) the tiles of its own shape."""
    k, n = (dim, width) if product == "gate_up" else (width, dim)
    groups = 16

    def fwd_bwd(x, w, group_sizes, ct):
        out, vjp = jax.vjp(
            lambda a, b: moe._grouped_dot(a, b, group_sizes, "gmm"), x, w)
        return out, vjp(ct)

    found = _kernel_tiles(
        fwd_bwd, jax.ShapeDtypeStruct((rows, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups,), jnp.int32),
        jax.ShapeDtypeStruct((rows, n), jnp.bfloat16))
    forward = moe._gmm_tiling(rows, k, n)
    own = moe._gmm_tiling(rows, n, k)
    assert found == [("gmm", forward), ("gmm", own), ("tgmm", forward)]
    # each product's tiles divide it: no tile is partly padding
    for size, tile in zip((rows, n, k), own):
        assert size % tile == 0
    assert (own != forward) == transposed


def test_linear_router_under_a_given_choice(tokens, rng):
    """A fed choice takes the top-k's place: its experts' probabilities
    renormalised to one, its loads in the loss; the router's own top-k
    fed back is the router's own route."""
    router = jnp.asarray(rng.standard_normal((tokens.shape[1], RE)),
                         jnp.float32)
    own = moe.linear_router(tokens, router, 2)
    same = moe.linear_router(tokens, router, 2, choice=own.choice)
    np.testing.assert_array_equal(np.asarray(same.choice),
                                  np.asarray(own.choice))
    np.testing.assert_allclose(np.asarray(same.gate), np.asarray(own.gate),
                               rtol=1e-6)
    assert float(same.aux_loss) == pytest.approx(float(own.aux_loss),
                                                 rel=1e-6)
    other = (own.choice + 1) % RE
    fed = moe.linear_router(tokens, router, 2, choice=other)
    probs = jax.nn.softmax(tokens @ router, -1)
    picked = jnp.take_along_axis(probs, other, -1)
    np.testing.assert_allclose(
        np.asarray(fed.gate),
        np.asarray(picked / picked.sum(-1, keepdims=True)), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(fed.gate.sum(-1)), 1.0, rtol=1e-6)
    assert float(fed.aux_loss) != pytest.approx(float(own.aux_loss),
                                                rel=1e-6)


# ---------------------------------------------------------------------------
# The sigmoid router under balancing biases, its rule, the shared expert.
# ---------------------------------------------------------------------------

def _sigmoid_case(tokens, rng, k=3):
    router = jnp.asarray(rng.standard_normal((tokens.shape[1], RE)),
                         jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(RE), jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(tokens @ router))
    return router, bias, scores, k


def test_sigmoid_router_chooses_by_the_bias_and_weighs_without_it(tokens,
                                                                  rng):
    """The choice is the top-k of ``s + b``; the gates are ``s`` of the
    chosen WITHOUT ``b``, over their sum, times ``route_scale``: they
    sum to ``route_scale``. A bias large enough decides the choice and
    leaves the weights the scores'."""
    router, bias, scores, k = _sigmoid_case(tokens, rng)
    route = moe.sigmoid_router(tokens, router, bias, k, True, 2.5)
    want = np.argsort(-(scores + np.asarray(bias)), axis=-1)[:, :k]
    np.testing.assert_array_equal(np.sort(np.asarray(route.choice), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(route.choice), -1)
    np.testing.assert_allclose(
        np.asarray(route.gate),
        2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(route.gate.sum(-1)), 2.5,
                               rtol=1e-5)
    assert float(route.gate_sum_mean) == pytest.approx(
        picked.sum(-1).mean(), rel=1e-5)
    # the biases alone move the choice, not a weight
    unbiased = moe.sigmoid_router(tokens, router, jnp.zeros(RE), k, True,
                                  2.5)
    assert (np.sort(np.asarray(unbiased.choice), -1)
            != np.sort(np.asarray(route.choice), -1)).any()
    pushed = jnp.zeros(RE).at[5].set(10.0)
    forced = moe.sigmoid_router(tokens, router, pushed, k, True, 2.5)
    assert (np.asarray(forced.choice) == 5).any(axis=-1).all()
    at5 = np.take_along_axis(scores, np.asarray(forced.choice), -1)
    np.testing.assert_allclose(
        np.asarray(forced.gate), 2.5 * at5 / at5.sum(-1, keepdims=True),
        rtol=1e-5)


def test_sigmoid_router_without_the_norm_and_under_a_given_choice(tokens,
                                                                  rng):
    """``route_norm`` off: the raw scores times the scale. A fed choice
    takes the top-k's place, gates and loads with it, and
    ``own_choice`` stays the router's; the router's own top-k fed back
    is the router's own route."""
    router, bias, scores, k = _sigmoid_case(tokens, rng)
    raw = moe.sigmoid_router(tokens, router, bias, k, False, 2.0)
    np.testing.assert_allclose(
        np.asarray(raw.gate),
        2.0 * np.take_along_axis(scores, np.asarray(raw.choice), -1),
        rtol=1e-5)
    own = moe.sigmoid_router(tokens, router, bias, k, True, 1.0)
    same = moe.sigmoid_router(tokens, router, bias, k, True, 1.0,
                              choice=own.choice)
    np.testing.assert_array_equal(np.asarray(same.choice),
                                  np.asarray(own.choice))
    np.testing.assert_allclose(np.asarray(same.gate), np.asarray(own.gate),
                               rtol=1e-6)
    other = (own.choice + 1) % RE
    fed = moe.sigmoid_router(tokens, router, bias, k, True, 1.0,
                             choice=other)
    np.testing.assert_array_equal(np.asarray(fed.choice), np.asarray(other))
    # the router's own top-k is told whatever was fed
    for route in (own, same, fed):
        np.testing.assert_array_equal(np.asarray(route.own_choice),
                                      np.asarray(own.choice))
    picked = np.take_along_axis(scores, np.asarray(other), -1)
    np.testing.assert_allclose(np.asarray(fed.gate),
                               picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(fed.load),
        np.bincount(np.asarray(other).ravel(), minlength=RE))
    with pytest.raises(ValueError):
        moe.sigmoid_router(tokens, router, bias, RE + 1)


def test_sigmoid_router_loads_count_every_expert_and_pass_no_gradient(
        tokens, rng):
    """``load [E]`` counts the (token, choice) pairs of ALL the experts;
    the gates carry the router's gradient, the bias none."""
    router, bias, _, k = _sigmoid_case(tokens, rng)
    route = moe.sigmoid_router(tokens, router, bias, k)
    assert float(route.load.sum()) == tokens.shape[0] * k
    np.testing.assert_array_equal(
        np.asarray(route.load),
        np.bincount(np.asarray(route.choice).ravel(), minlength=RE))

    def weight(router, bias):
        gate = moe.sigmoid_router(tokens, router, bias, k, True, 1.0).gate
        return jnp.sum(gate[:, 0])

    d_router, d_bias = jax.grad(weight, argnums=(0, 1))(router, bias)
    assert float(jnp.abs(d_router).max()) > 0
    assert not np.asarray(d_bias).any()


def test_balance_step_moves_each_bias_against_its_load():
    """Every bias moves by ``rate`` against the sign of its expert's
    load over ITS layer's mean; an expert at the mean stays; ZAYA's rule
    is this one at its own rate."""
    from parallax_tpu.models import zaya
    load = jnp.asarray([[4.0, 0.0, 2.0, 2.0], [1.0, 1.0, 1.0, 5.0]])
    beta = jnp.asarray([[0.1, 0.1, 0.1, 0.1], [0.0, 0.2, -0.2, 0.0]])
    new = moe.balance_step(beta, load, 0.01)
    np.testing.assert_allclose(
        np.asarray(new), [[0.09, 0.11, 0.1, 0.1], [0.01, 0.21, -0.19, -0.01]],
        atol=1e-7)
    cfg = zaya.tiny_config(bias_update_rate=0.01)
    np.testing.assert_array_equal(
        np.asarray(zaya.balance_step(cfg, beta, load)), np.asarray(new))
    # no gradient reaches the loads
    assert not np.asarray(jax.grad(
        lambda x: jnp.sum(moe.balance_step(beta, x, 0.01)))(load)).any()


def test_balance_step_brings_a_sigmoid_routers_loads_together(tokens, rng):
    router, _, _, k = _sigmoid_case(tokens, rng)
    bias = jnp.zeros(RE)

    def spread(bias):
        load = moe.sigmoid_router(tokens, router, bias, k).load
        return float(load.max() / load.mean())

    before = spread(bias)
    for _ in range(60):
        bias = moe.balance_step(
            bias, moe.sigmoid_router(tokens, router, bias, k).load, 0.01)
    assert before > 1.25 and spread(bias) < min(before, 1.2)


def test_shared_expert_is_a_plain_swiglu_on_every_row(tokens, rng):
    """Every row, whatever the routing; value and gradients against the
    formula."""
    D_, F_ = tokens.shape[1], 24
    w_gate, w_up = (jnp.asarray(rng.standard_normal((D_, F_)), jnp.float32)
                    for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((F_, D_)), jnp.float32)

    def plain(t, g, u, d):
        return (jax.nn.silu(t @ g) * (t @ u)) @ d

    got = moe.shared_expert(tokens, w_gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(plain(tokens, w_gate, w_up, w_down)),
                               rtol=1e-5, atol=1e-5)
    grads = jax.grad(lambda *a: jnp.sum(moe.shared_expert(*a) ** 2),
                     argnums=(0, 1, 2, 3))(tokens, w_gate, w_up, w_down)
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) ** 2),
                     argnums=(0, 1, 2, 3))(tokens, w_gate, w_up, w_down)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4)
    # bfloat16 rows compute in bfloat16
    low = moe.shared_expert(tokens.astype(jnp.bfloat16), w_gate, w_up,
                            w_down)
    assert low.dtype == jnp.bfloat16
