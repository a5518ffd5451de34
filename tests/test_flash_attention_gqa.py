"""Grouped key/value heads in the dense flash kernels
(``ops/pallas_attention``): ``g`` query heads read one key/value head,
no K or V repeated in memory. The kernels, interpreted here, against the
einsum reference ``_xla_attention`` for ``g`` in {1, 4}: forward, all
three gradients, the padding mask, the ``(out, lse)`` surface, and the
names a trace reader finds the two calls by; the one backward kernel
against the einsum backward (``xla_backward=True``) at the heads' real
sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.ops import pallas_attention as pa

B, T, HKV, D = 2, 64, 2, 16


def _qkv(g, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    return n(B, T, HKV * g, D), n(B, T, HKV, D), n(B, T, HKV, D)


def _reference(q, k, v, causal, kv_mask=None):
    swap = lambda a: jnp.swapaxes(a, 1, 2)      # noqa: E731
    return swap(pa._xla_attention(swap(q), swap(k), swap(v), kv_mask,
                                  causal, D ** -0.5))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("g", [1, 4])
def test_forward_matches_the_einsum_reference(g, causal):
    q, k, v = _qkv(g)
    got = pa.flash_attention(q, k, v, causal=causal, q_tile=16, block_k=32)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_reference(q, k, v, causal)),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("g", [1, 4])
def test_gradients_match_the_einsum_reference(g, causal):
    """``dq`` a query head, ``dk`` and ``dv`` summed over the group
    inside the kernel."""
    q, k, v = _qkv(g, seed=1)
    weight = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    got = jax.grad(loss(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=causal, q_tile=32, block_k=16)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: _reference(q, k, v, causal)),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6, err_msg=name)


def test_a_group_is_what_repeating_its_key_value_head_gives():
    q, k, v = _qkv(4, seed=2)
    grouped = pa.flash_attention(q, k, v, causal=True, q_tile=16, block_k=16)
    repeated = pa.flash_attention(q, jnp.repeat(k, 4, axis=2),
                                  jnp.repeat(v, 4, axis=2), causal=True,
                                  q_tile=16, block_k=16)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(repeated),
                               rtol=1e-6, atol=1e-7)


def test_padding_mask_with_grouped_heads():
    q, k, v = _qkv(4, seed=3)
    kv_mask = jnp.asarray(np.arange(T)[None, :] < np.array([[T], [40]]),
                          jnp.int32)
    weight = jnp.sin(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    got = jax.value_and_grad(lambda q, k, v: jnp.sum(pa.flash_attention(
        q, k, v, kv_mask=kv_mask, q_tile=16, block_k=16) * weight),
        (0, 1, 2))(q, k, v)
    want = jax.value_and_grad(lambda q, k, v: jnp.sum(_reference(
        q, k, v, False, kv_mask) * weight), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6)
    # a masked key takes no gradient
    assert float(jnp.abs(got[1][1][1, 40:]).max()) == 0.0


def test_out_and_lse_with_grouped_heads():
    q, k, v = _qkv(4, seed=4)
    swap = lambda a: jnp.swapaxes(a, 1, 2)      # noqa: E731

    def both(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            return jnp.sum(out) + jnp.sum(jnp.sin(lse))
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)

    got = both(lambda q, k, v: pa.flash_attention_lse(
        q, k, v, causal=True, q_tile=16, block_k=16))

    def reference(q, k, v):
        out, lse = pa._xla_attention_lse(swap(q), swap(k), swap(v), None,
                                         True, D ** -0.5)
        return swap(out), lse
    want = both(reference)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("g", [1, 4])
def test_bfloat16_products_with_float32_sums(g, causal):
    """A bfloat16 caller: the logits take the inputs as they are, the
    other products widen ``k``, ``v`` and ``do`` to float32 beside the
    float32 probabilities, as before grouped heads (on the chip the
    MXU's default precision makes one bfloat16 pass of either form: the
    same time, the same output to the bit; PERF.md section 6, PR 31).
    Forward and all three gradients inside the tolerance the module's
    bfloat16 test has always held (``test_pallas_attention.test_bf16``:
    0.05), for the ungrouped callers (``g`` 1: ``bert``, ``nmt``,
    ``moe_lm``, ``long_context``) as for the grouped."""
    q, k, v = _qkv(g, seed=5, dtype=jnp.bfloat16)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    out = pa.flash_attention(q, k, v, causal=causal, q_tile=16, block_k=16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_reference(*f32, causal)),
                               rtol=0.05, atol=0.05)
    got = jax.grad(lambda q, k, v: jnp.sum(pa.flash_attention(
        q, k, v, causal=causal, q_tile=16, block_k=16).astype(jnp.float32)),
        (0, 1, 2))(q, k, v)
    want = jax.grad(lambda q, k, v: jnp.sum(_reference(q, k, v, causal)),
                    (0, 1, 2))(*f32)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=0.05, atol=0.05)


def test_heads_that_do_not_group_are_refused():
    q, k, v = _qkv(1)
    with pytest.raises(ValueError, match="group"):
        pa.flash_attention(jnp.concatenate([q, q[:, :, :1]], axis=2), k, v)


def test_the_three_calls_carry_their_names_and_grids():
    q, k, v = _qkv(4)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        pa.flash_attention(q, k, v, causal=True, q_tile=16, block_k=16)),
        (0, 1, 2)))(q, k, v)

    def calls(j, out):
        for e in j.eqns:
            if e.primitive.name == "pallas_call":
                out[e.params["name"]] = tuple(e.params["grid_mapping"].grid)
            for sub in e.params.values():
                if hasattr(sub, "jaxpr"):
                    calls(sub.jaxpr, out)
                elif hasattr(sub, "eqns"):
                    calls(sub, out)
        return out

    # forward a query tile of a query head; the backward a key tile of
    # a query head, the group's query heads one after another
    assert calls(jaxpr.jaxpr, {}) == {
        "flash_fwd": (B, HKV * 4, T // 16),
        "flash_bwd": (B, HKV * 4, T // 16)}


# (causal, group, head size, queries, keys, padding mask, lse cotangent)
FUSED_CASES = [
    pytest.param(True, 1, 256, 1024, 1024, False, False, id="causal-g1-d256"),
    pytest.param(True, 4, 128, 1024, 1024, False, False, id="causal-g4-d128"),
    pytest.param(True, 8, 128, 1024, 1024, False, False, id="causal-g8-d128"),
    pytest.param(False, 1, 128, 1024, 1024, True, False,
                 id="noncausal-g1-mask"),
    pytest.param(False, 4, 128, 512, 1024, True, False,
                 id="noncausal-g4-mask-tq<tk"),
    pytest.param(True, 4, 128, 512, 1024, False, False, id="causal-g4-tq<tk"),
    pytest.param(True, 4, 128, 1024, 1024, False, True, id="causal-g4-dlse"),
    pytest.param(True, 1, 256, 1024, 1024, True, True,
                 id="causal-g1-d256-mask-dlse"),
]


@pytest.mark.parametrize(
    "causal,g,d,tq,tk,masked,with_lse", FUSED_CASES)
def test_the_fused_backward_matches_the_einsum_backward(
        causal, g, d, tq, tk, masked, with_lse):
    """``flash_bwd`` (dq, dk and dv from one pass over the scores) against
    the same call's einsum backward, ``xla_backward=True``: the forward
    is the same kernel on both sides, so only the backward is compared.
    Tiles of 256 queries and 512 keys: a key tile spans two query
    blocks, the diagonal's range and the range past it both run."""
    rng = np.random.default_rng(d + g + tq)

    def n(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q, k, v = n(1, tq, 2 * g, d), n(1, tk, 2, d), n(1, tk, 2, d)
    do = n(1, tq, 2 * g, d)
    kv_mask = None
    if masked:
        kv_mask = jnp.asarray(np.arange(tk)[None, :] < tk - 200, jnp.int32)

    def pulled(xla_backward):
        def f(q, k, v):
            kw = dict(causal=causal, kv_mask=kv_mask, q_tile=256,
                      block_k=512, interpret=True, xla_backward=xla_backward)
            if with_lse:
                out, lse = pa.flash_attention_lse(q, k, v, **kw)
                return jnp.sum(out * do) + jnp.sum(jnp.sin(lse))
            return jnp.sum(pa.flash_attention(q, k, v, **kw) * do)
        return jax.grad(f, (0, 1, 2))(q, k, v)

    for name, a, b in zip("qkv", pulled(False), pulled(True)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6, err_msg=name)
