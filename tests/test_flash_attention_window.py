"""A window in the dense flash kernels: query ``t`` reads key ``s`` iff
``0 <= t - s < window``. The kernels, interpreted, against an
einsum under the band's mask (outputs, ``dq``, ``dk``, ``dv``), over
windows smaller than a tile, a tile, between tiles, no multiple of
either tile and past the sequence, over group sizes and tiles; the
traced ``window_on`` that lets one loop body serve both kinds of layer;
the one backward kernel against the einsum backward at the cells'
windows and tiles; and that a call without a window is traced as before
this argument existed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.ops import pallas_attention as pa

T, D, HKV = 64, 16, 1


def _qkv(g, seed=0, dtype=jnp.float32, hkv=HKV):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (2, T, hkv * g, D), dtype)
    k = jax.random.normal(ks[1], (2, T, hkv, D), dtype)
    v = jax.random.normal(ks[2], (2, T, hkv, D), dtype)
    do = jax.random.normal(ks[3], (2, T, hkv * g, D), dtype)
    return q, k, v, do


def _reference(q, k, v, window):
    """Plain float32 attention under the band's ``[T, T]`` mask."""
    g = q.shape[2] // k.shape[2]
    kr, vr = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bshd->bhqs", q, kr) / np.sqrt(q.shape[-1])
    behind = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
    seen = (behind >= 0) & (behind < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, vr)


# smaller than a tile, a tile, between tiles, a multiple of neither
# tile, the sequence, past it
WINDOWS = [1, 5, 16, 24, 37, 64, 100]
TILES = [(16, 16), (32, 16), (16, 32)]


@pytest.mark.parametrize("tiles", TILES)
@pytest.mark.parametrize("g", [1, 2, 8])
@pytest.mark.parametrize("window", WINDOWS)
def test_outputs_and_gradients_match_the_banded_einsum(window, g, tiles):
    q, k, v, do = _qkv(g, seed=window)

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, window=window,
                                  q_tile=tiles[0], block_k=tiles[1],
                                  interpret=True)

    got, pull = jax.vjp(flash, q, k, v)
    want, pull_ref = jax.vjp(lambda *a: _reference(*a, window), q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), pull(do), pull_ref(do)):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("window", [5, 24, 100])
def test_out_and_lse_under_a_window(window):
    q, k, v, do = _qkv(2, seed=3)

    def flash(q, k, v):
        out, lse = pa.flash_attention_lse(
            q, k, v, causal=True, window=window, q_tile=16, block_k=16,
            interpret=True)
        return jnp.sum(out * do) + jnp.sum(jnp.sin(lse))

    def plain(q, k, v):
        swap = lambda a: jnp.swapaxes(a, 1, 2)      # noqa: E731
        out, lse = pa._xla_attention_lse(swap(q), swap(k), swap(v), None,
                                         True, D ** -0.5, window)
        return jnp.sum(swap(out) * do) + jnp.sum(jnp.sin(lse))

    got = jax.value_and_grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("xla_backward", [False, True])
@pytest.mark.parametrize("on", [False, True])
def test_a_traced_flag_says_whether_this_call_applies_the_window(
        on, xla_backward):
    """One traced program for both kinds of layer: ``window_on`` picks
    the windowed calls or the plain ones, forward and backward."""
    q, k, v, do = _qkv(2, seed=7)

    @jax.jit
    def both(flag, q, k, v):
        return jax.vjp(lambda *a: pa.flash_attention(
            *a, causal=True, window=24, window_on=flag, q_tile=16,
            block_k=32, interpret=True, xla_backward=xla_backward),
            q, k, v)[1](do)

    want = jax.vjp(lambda *a: _reference(*a, 24 if on else T),
                   q, k, v)[1](do)
    for a, b in zip(both(jnp.asarray(on), q, k, v), want):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("window,on,g", [
    (1024, True, 4), (1024, False, 4), (2048, True, 4), (2048, False, 4),
    (1024, None, 8), (2048, None, 1),
])
def test_the_fused_backward_matches_the_einsum_backward_at_the_cells_windows(
        window, on, g):
    """``flash_bwd_win`` (and ``flash_bwd`` where a traced ``window_on``
    is off) against the same call's einsum backward, ``xla_backward=True``,
    at Mellum2's and Trinity-Mini's windows over tiles of 512 and heads of
    128: a key tile's query blocks in all three ranges, the diagonal's,
    the whole ones and the band's edge. ``on`` None: always windowed."""
    t = 2560
    ks = jax.random.split(jax.random.PRNGKey(window + g), 4)
    q, do = (jax.random.normal(key, (1, t, g, 128)) for key in ks[:2])
    k, v = (jax.random.normal(key, (1, t, 1, 128)) for key in ks[2:])
    flag = None if on is None else jnp.asarray(on)

    def pulled(xla_backward):
        return jax.vjp(lambda *a: pa.flash_attention(
            *a, causal=True, window=window, window_on=flag, q_tile=512,
            block_k=512, interpret=True, xla_backward=xla_backward),
            q, k, v)[1](do)

    for name, a, b in zip(("dq", "dk", "dv"), pulled(False), pulled(True)):
        np.testing.assert_allclose(a, b, atol=5e-5, err_msg=name)


def test_a_padding_mask_composes_with_the_window():
    q, k, v, _ = _qkv(2, seed=11)
    mask = jnp.ones((2, T), jnp.int32).at[1, 40:].set(0)
    got = pa.flash_attention(q, k, v, causal=True, window=24, kv_mask=mask,
                             q_tile=16, block_k=16, interpret=True)
    swap = lambda a: jnp.swapaxes(a, 1, 2)          # noqa: E731
    want = swap(pa._xla_attention(swap(q), swap(k), swap(v), mask, True,
                                  D ** -0.5, 24))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_bfloat16_products_under_a_window():
    q, k, v, do = _qkv(8, seed=5, dtype=jnp.bfloat16)
    got, pull = jax.vjp(lambda *a: pa.flash_attention(
        *a, causal=True, window=24, q_tile=16, block_k=16, interpret=True),
        q, k, v)
    f32 = lambda a: a.astype(jnp.float32)           # noqa: E731
    want, pull_ref = jax.vjp(lambda *a: _reference(*a, 24), f32(q), f32(k),
                             f32(v))
    np.testing.assert_allclose(f32(got), want, atol=0.03)
    for a, b in zip(pull(do), pull_ref(f32(do))):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(f32(a), b, atol=0.08, rtol=0.05)


def _outline(jaxpr, out=None):
    """The kernels' names and the ``cond``s of a traced program, in
    order, not looking inside a kernel (``pl.when`` is a ``cond``
    there)."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(str(eqn.params["name"]))
            continue
        if eqn.primitive.name == "cond":
            out.append("cond")
        for value in eqn.params.values():
            for x in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    _outline(inner, out)
    return out


def _traced(flag, q, k, v, do, **kw):
    def run(flag, q, k, v):
        return jax.vjp(lambda *b: pa.flash_attention(
            *b, causal=True, window_on=flag, q_tile=16, block_k=16,
            interpret=True, **kw), q, k, v)[1](do)

    if flag is None:
        return jax.make_jaxpr(lambda *a: run(None, *a))(q, k, v)
    return jax.make_jaxpr(run)(jnp.asarray(flag), q, k, v)


def test_no_window_lowers_as_before():
    """The windowless call's jaxpr holds exactly ``flash_fwd`` and
    ``flash_bwd``, no ``cond`` and no band scope."""
    traced = _traced(None, *_qkv(2))
    assert _outline(traced.jaxpr) == ["flash_fwd", "flash_bwd"]
    assert pa.WINDOW_SCOPE not in str(traced)


def test_the_windowed_calls_carry_their_names():
    """Always windowed: the two ``_win`` calls alone, no ``cond``."""
    traced = _traced(None, *_qkv(2), window=24)
    assert _outline(traced.jaxpr) == ["flash_fwd_win", "flash_bwd_win"]


def test_a_traced_flag_is_one_cond_a_pass_holding_both_kinds_once():
    traced = _traced(True, *_qkv(2), window=24)
    assert _outline(traced.jaxpr) == [
        "cond", "flash_fwd", "flash_fwd_win",
        "cond", "flash_bwd", "flash_bwd_win"]


@pytest.mark.parametrize("kw", [
    dict(causal=False, window=8),
    dict(causal=True, window=0),
    dict(causal=True, window_on=True),
])
def test_a_window_without_its_diagonal_is_refused(kw):
    q, k, v, _ = _qkv(1)
    with pytest.raises(ValueError):
        pa.flash_attention(q, k, v, interpret=True, **kw)


@pytest.mark.parametrize("q0,window,want", [
    # 16 x 16 tiles, 4 key tiles under the diagonal of the last query tile
    (48, 24, (1, 3, 3)),     # keys 25..63: tiles 1, 2 the edge, 3 the diagonal
    (48, 16, (2, 3, 3)),     # keys 33..63: tile 2 the edge, none whole
    (48, 5, (2, 4, 4)),      # a window under a tile: tile 3 edge AND diag
    (48, 100, (0, 0, 3)),    # past the sequence: no edge, 0-2 whole
    (0, 24, (0, 0, 0)),      # the first tile: the diagonal alone
])
def test_the_key_ranges_of_a_query_tile(q0, window, want):
    num_k = (q0 + 16 + 15) // 16
    got = pa._key_ranges(jnp.int32(q0), 16, 16, window, num_k)
    assert tuple(int(x) for x in got) == want
