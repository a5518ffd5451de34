"""Pallas flash-attention kernel numerics (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.ops import pallas_attention as pa
from parallax_tpu.ops.ring_attention import full_attention_reference


B, T, H, D = 2, 64, 2, 16


def _qkv(rng, dim=D):
    def t():
        return jnp.asarray(
            rng.standard_normal((B, T, H, dim)).astype(np.float32))
    return t(), t(), t()


@pytest.fixture
def qkv(rng):
    return _qkv(rng)


# every head size the plain cases take, and latent attention's heads of
# 256 for q . k and v alike, as many key/value heads as query heads
BY_HEAD_SIZE = [pytest.param(False, D, id="False"),
                pytest.param(True, D, id="True"),
                pytest.param(True, 256, id="D256-group1")]


@pytest.mark.parametrize("causal,dim", BY_HEAD_SIZE)
def test_matches_reference(rng, causal, dim):
    q, k, v = _qkv(rng, dim)
    expected = full_attention_reference(q, k, v, causal=causal)
    got = pa.flash_attention(q, k, v, causal=causal, q_tile=16,
                             block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_uneven_tile_sizes_snap(qkv):
    q, k, v = qkv
    # q_tile=48 does not divide T=64 -> snapped down internally
    got = pa.flash_attention(q, k, v, causal=True, q_tile=48, block_k=40)
    expected = full_attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)


def test_gradients_match(qkv):
    q, k, v = qkv
    g = jnp.asarray(np.random.default_rng(5).standard_normal(
        (B, T, H, D)).astype(np.float32))

    def pallas_loss(q, k, v):
        return jnp.sum(pa.flash_attention(q, k, v, causal=True,
                                          q_tile=16, block_k=16) * g)

    def ref_loss(q, k, v):
        return jnp.sum(full_attention_reference(q, k, v, causal=True) * g)

    got = jax.grad(pallas_loss, argnums=(0, 1, 2))(q, k, v)
    exp = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, exp, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6, err_msg=name)


def test_bf16(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    got = pa.flash_attention(q, k, v, causal=False, q_tile=16, block_k=16)
    assert got.dtype == jnp.bfloat16
    expected = full_attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expected, np.float32),
                               rtol=0.05, atol=0.05)


@pytest.mark.slow
def test_flash_attention_through_engine(rng):
    """Model flag routes attention through the Pallas kernel inside the
    jitted train step; trajectory matches the XLA path."""
    import parallax_tpu as parallax
    from parallax_tpu.models import long_context as lc

    batches = [lc.make_batch(rng, 8, 32, 512) for _ in range(3)]

    def run(use_pallas):
        cfg = lc.tiny_config()
        cfg.parallelism = "data"
        cfg.use_pallas_attention = use_pallas
        sess, *_ = parallax.parallel_run(
            lc.build_model(cfg),
            parallax_config=parallax.Config(search_partitions=False),
            num_partitions=1)
        losses = [sess.run("loss", feed_dict=b) for b in batches]
        sess.close()
        return losses

    np.testing.assert_allclose(run(True), run(False), rtol=2e-3)


@pytest.mark.parametrize("causal,dim", BY_HEAD_SIZE)
def test_pallas_backward_matches_xla_backward(rng, causal, dim):
    """The fully-Pallas dq/dk/dv kernels agree with the einsum-recompute
    backward."""
    q, k, v = _qkv(rng, dim)
    g = jnp.asarray(np.random.default_rng(9).standard_normal(
        (B, T, H, dim)).astype(np.float32))

    def loss(xla_backward):
        def f(q, k, v):
            return jnp.sum(pa.flash_attention(
                q, k, v, causal=causal, q_tile=16, block_k=16,
                xla_backward=xla_backward) * g)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    pallas_grads = loss(False)
    xla_grads = loss(True)
    for a, b, name in zip(pallas_grads, xla_grads, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6, err_msg=name)


def test_kv_padding_mask(qkv):
    """Padding mask: masked keys get zero attention, grads flow."""
    q, k, v = qkv
    rng2 = np.random.default_rng(13)
    mask = jnp.asarray(rng2.integers(0, 2, (B, T)), jnp.int32
                       ).at[:, 0].set(1)  # keep >=1 key valid per row

    def xla_ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q / np.sqrt(D), k)
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    expected = xla_ref(q, k, v)
    got = pa.flash_attention(q, k, v, kv_mask=mask, q_tile=16,
                             block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-6)

    g = jnp.ones((B, T, H, D))
    grads_p = jax.grad(lambda q, k, v: jnp.sum(pa.flash_attention(
        q, k, v, kv_mask=mask, q_tile=16, block_k=16) * g),
        argnums=(0, 1, 2))(q, k, v)
    grads_x = jax.grad(lambda q, k, v: jnp.sum(xla_ref(q, k, v) * g),
                       argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(grads_p, grads_x, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-6, err_msg=name)


class TestFlashLse:
    """flash_attention_lse: the (out, lse) composition surface used by
    ring attention's pallas block path."""

    def test_out_and_lse_match_reference(self, qkv):
        q, k, v = qkv
        out, lse = jax.jit(lambda q, k, v: pa.flash_attention_lse(
            q, k, v, causal=True))(q, k, v)
        want = full_attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        # reference lse computed densely
        scale = 1.0 / np.sqrt(D)
        s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
        mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
        s = jnp.where(mask, s, -1e30)
        want_lse = jax.nn.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=1e-4, atol=1e-5)

    def test_lse_cotangent_reaches_inputs(self, qkv):
        """d(loss)/d(q,k) through BOTH outputs: the dlse term is the
        delta-shift in the backward kernels — compare against autodiff
        of the dense reference computing the same (out, lse) loss."""
        q, k, v = qkv
        r = np.random.default_rng(9)
        g_out = jnp.asarray(r.standard_normal(q.shape).astype(np.float32))
        g_lse = jnp.asarray(r.standard_normal((B, H, T)).astype(
            np.float32))
        scale = 1.0 / np.sqrt(D)

        def flash_loss(q, k, v):
            out, lse = pa.flash_attention_lse(q, k, v, causal=True)
            return jnp.sum(out * g_out) + jnp.sum(lse * g_lse)

        def dense_loss(q, k, v):
            s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
            mask = jnp.tril(jnp.ones((T, T), bool))[None, None]
            s = jnp.where(mask, s, -1e30)
            lse = jax.nn.logsumexp(s, axis=-1)
            p = jnp.exp(s - lse[..., None])
            out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            return jnp.sum(out * g_out) + jnp.sum(lse * g_lse)

        got = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for g, e, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                       rtol=5e-4, atol=5e-5,
                                       err_msg=name)
