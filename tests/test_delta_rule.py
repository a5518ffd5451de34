"""ops/delta_rule: the chunked gated delta rule, under XLA's scan and as
the interpreted Mosaic kernels, against the recurrence applied token by
token: values and all five gradients; the padding behind a sequence that
is no multiple of the chunk; the gates' corners; the chunk the module
takes by itself; the short convolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.ops import delta_rule as dr


def by_token(q, k, v, g, beta):
    """``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t
    k_t^T``, ``o_t = S_t q_t``, a token at a time."""
    B, T, H, dk = q.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        eye = jnp.eye(dk)
        kk = k_t[..., :, None] * k_t[..., None, :]
        S = jnp.exp(g_t)[..., None, None] * jnp.einsum(
            "bhvk,bhkj->bhvj", S, eye - b_t[..., None, None] * kk) \
            + b_t[..., None, None] * v_t[..., :, None] * k_t[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((B, H, v.shape[-1], dk)),
                        tuple(jnp.moveaxis(a, 1, 0)
                              for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def operands(T, seed=0, B=2, H=2, dk=8, dv=16, g=None, beta=None):
    r = np.random.default_rng(seed)
    q = r.normal(size=(B, T, H, dk))
    k = r.normal(size=(B, T, H, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    if g is None:
        g = -np.exp(2 * r.normal(size=(B, T, H)) - 2)
    if beta is None:
        beta = 2 / (1 + np.exp(-2 * r.normal(size=(B, T, H))))
    return tuple(jnp.asarray(np.broadcast_to(a, s), jnp.float32)
                 for a, s in ((q, q.shape), (k, k.shape),
                              (r.normal(size=(B, T, H, dv)), (B, T, H, dv)),
                              (g, (B, T, H)), (beta, (B, T, H))))


def _close(got, want, rtol, what, floor=0.0):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=rtol,
                               atol=max(rtol * scale, floor), err_msg=what)


def _both(fn, args, seed=1):
    """``fn``'s output and the gradients of a seeded weighted sum of it
    with respect to all five operands."""
    o = fn(*args)
    wo = jnp.asarray(np.random.default_rng(seed).normal(size=o.shape),
                     jnp.float32)
    return o, jax.grad(lambda *a: jnp.sum(fn(*a) * wo),
                       argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("T,chunk", [(64, 16), (128, 64), (50, 16)])
def test_values_and_five_gradients_match_the_recurrence(impl, T, chunk):
    """``T`` = 50 is no multiple of the chunk: the sequence is PADDED
    behind with tokens that leave the state alone and whose outputs are
    dropped."""
    args = operands(T)
    with jax.default_matmul_precision("highest"):
        o, grads = _both(
            lambda *a: dr.gated_delta_rule(*a, chunk=chunk, impl=impl),
            args)
        want_o, want = _both(by_token, args)
    assert o.shape == (2, T, 2, 16)
    _close(o, want_o, 2e-5, "o")
    for name, got, ref in zip(("dq", "dk", "dv", "dg", "dbeta"), grads,
                              want):
        _close(got, ref, 5e-5, name)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("what,g,beta", [
    ("beta near 2, no decay", 0.0, 1.999),
    ("alpha near 0", -30.0, 1.0),
    ("alpha near 1, beta near 0", -1e-6, 1e-3),
    ("alpha near 0 on every fourth token",
     np.where(np.arange(64) % 4 == 0, -40.0, -1e-3)[None, :, None], 1.9),
])
def test_the_gates_corners(impl, what, g, beta):
    """``beta`` near 2 (the eigenvalue near -1 that ``allow_neg_eigval``
    allows), ``alpha`` near 0 (a running decay of e^-1920 a chunk: the
    decays are differences before they are exponentials) and near 1."""
    args = operands(64, seed=3, g=g, beta=beta)
    with jax.default_matmul_precision("highest"):
        o, grads = _both(
            lambda *a: dr.gated_delta_rule(*a, chunk=64, impl=impl), args)
        want_o, want = _both(by_token, args)
    assert all(bool(jnp.isfinite(a).all()) for a in (o, *grads)), what
    _close(o, want_o, 1e-4, what)
    # (a decay of e^-30 leaves its own cotangent at 1e-14: held to the
    # float32 noise of the terms that cancel in it)
    for got, ref in zip(grads, want):
        _close(got, ref, 2e-4, what, floor=1e-6)


def test_the_kernels_and_the_scan_are_one_algebra():
    """Both executors call the same per-chunk functions: equal to
    rounding, at another chunk than the reference's tests."""
    args = operands(96, seed=4, dk=16, dv=8)
    outs = [_both(lambda *a: dr.gated_delta_rule(*a, chunk=32, impl=impl),
                  args) for impl in ("xla", "interpret")]
    for got, want in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(outs[1])):
        _close(got, want, 1e-5, "xla against interpret")


def test_what_a_remat_keeps_spares_the_forward_kernel():
    """Under a policy that keeps ``delta_rule``, a rematerialised
    caller's backward pass holds ONE ``delta_fwd`` (the forward pass's)
    and one ``delta_bwd``; without it the forward kernel runs again."""
    args = operands(32)

    def loss(policy):
        def fn(*a):
            o = dr.gated_delta_rule(*a, chunk=16, impl="interpret")
            return jnp.sum(jnp.tanh(o))
        return str(jax.make_jaxpr(jax.grad(
            jax.checkpoint(fn, policy=policy), argnums=(0, 1, 2, 3, 4)))(
                *args))

    kept = loss(jax.checkpoint_policies.save_only_these_names(dr.KEPT))
    assert kept.count("name=delta_fwd") == 1
    assert kept.count("name=delta_bwd") == 1
    assert loss(None).count("name=delta_fwd") == 2


@pytest.mark.parametrize("kw", [dict(chunk=48), dict(chunk=0),
                                dict(impl="triton")])
def test_a_chunk_or_an_executor_it_cannot_take_is_refused(kw):
    with pytest.raises(ValueError):
        dr.gated_delta_rule(*operands(32), **{"chunk": 16, **kw})


def test_the_chunk_is_the_modules_own_where_the_caller_names_none():
    """``CHUNK`` tokens a chunk: a sequence of two and a half of them
    holds three ``[CHUNK, CHUNK]`` systems a head, and reads as the
    recurrence does."""
    args = operands(2 * dr.CHUNK + dr.CHUNK // 2, seed=6)
    text = str(jax.make_jaxpr(
        lambda *a: dr.gated_delta_rule(*a, impl="interpret"))(*args))
    assert f"f32[2,2,3,{dr.CHUNK},{dr.CHUNK}]" in text
    with jax.default_matmul_precision("highest"):
        _close(dr.gated_delta_rule(*args, impl="xla"), by_token(*args),
               2e-5, "o")


def test_the_short_convolution_is_causal_shifts_and_silu():
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 9, 5)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    got = dr.causal_conv4_silu(x, w)
    want = np.zeros((2, 9, 5))
    for t in range(9):
        for i in range(4):
            if t - i >= 0:
                want[:, t] += np.asarray(w[i]) * np.asarray(x[:, t - i])
    want = want / (1 + np.exp(-want))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # position t sees nothing after t
    moved = dr.causal_conv4_silu(x.at[:, 5].add(1.0), w)
    assert float(jnp.abs(moved[:, :5] - got[:, :5]).max()) == 0.0
    assert dr.causal_conv4_silu(x.astype(jnp.bfloat16), w).dtype \
        == jnp.bfloat16
