"""The gated delta rule (Yang, Kautz, Hatamizadeh, "Gated Delta
Networks", arXiv:2412.06464) in its chunked form, with a written-out
backward pass, and the causal depthwise convolution that feeds it.

A head carries a matrix along the sequence. With keys ``k_t`` (unit
length by the caller), values ``v_t``, a log decay ``g_t <= 0`` (``alpha_t
= exp(g_t)``) and a step ``beta_t``, the state ``S [dv, dk]``, zero before
the first token, and the output are::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

**The chunked form.** Write ``Z = S^T [dk, dv]`` and take ``C`` tokens at
a time, ``Z0`` the state before them, ``G_i = g_1 + .. + g_i`` inside the
chunk, ``gam_i = exp(G_i)``, ``D[i, j] = exp(G_i - G_j)`` for ``j <= i``
(a difference first: ``alpha`` near 0 overflows a quotient). The rule
is ``S_i = alpha_i S_{i-1} + u_i k_i^T`` with ``u_i = beta_i (v_i -
alpha_i S_{i-1} k_i)``, and unrolled over the chunk the ``u`` solve a unit
lower-triangular system::

    A  = strict_lower(beta_i (k_i . k_j) D[i, j])      T = (I + A)^-1
    W  = T (beta gam k)         U0 = T (beta v)
    U  = U0 - W Z0                                     [C, dv]
    O  = (gam q) Z0 + lower((q_i . k_j) D[i, j]) U
    Z1 = gam_C Z0 + (exp(G_C - G_i) k_i)^T U

so inside a chunk everything is a matrix product, and ONE ``[dk, dv]``
state a head crosses from chunk to chunk. ``T`` comes by blocks: the
inverse of a block of ``2b`` from its two halves' (``T <- T - T A_off
T``, ``A_off`` the sub-diagonal blocks of size ``b``), ``log2 C`` rounds
of two ``[C, C]`` products in float32, which is forward substitution by
blocks and as stable. The state, the running decays and that system are
float32; the other products take their operands in the dtype of ``q``
and accumulate in float32.

**The backward pass** is written out (``_step_bwd``, ``_chunk_bwd``): the
chunks again in reverse with the state's cotangent carried, from each
chunk's ``Z0`` and ``T`` as the forward pass left them (``[dk, dv]`` and
``[C, C]`` floats a chunk and head: 71 MB and 63 MB a layer at 8,192 x
15 x 96 x 192 in chunks of 128; a forward pass run again would cost the
system's float32 products, two a round of the inverse, a second time).
Both are ``checkpoint_name``d ``delta_rule`` with the outputs, so that a
rematerialised layer whose policy keeps that name runs no kernel twice.

**Executors** (``impl``): ``kernel``, two Mosaic calls named
``delta_fwd`` and ``delta_bwd``, a grid step a (batch, head, chunk) with
the chunks in order and the state in VMEM (the default on the TPU);
``interpret``, the same kernels interpreted (tests); ``xla``, the same
per-chunk algebra (the very functions the kernels call) with everything
that needs no state made for all chunks at once and a ``lax.scan`` over
the chunks for the four products that do (the default elsewhere).

``causal_conv4_silu`` is the short convolution in front of the rule, as
shifts and multiply-adds (``models/zaya.cca_mix``'s way; no convolution
primitive).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a caller's checkpoint policy keeps so that no kernel runs twice
KEPT = "delta_rule"
# the tokens a chunk takes where the caller names none: the executors'
# own tile and no size of a model. On the chip at 8,192 x 15 x 96 x 192 a
# layer's rule, forward and backward, took 13.45 / 8.69 / 6.55 ms at 32 /
# 64 / 128 (PERF.md section 6, PR 37)
CHUNK = 128
# per-token scalars cross a kernel's boundary as a column over a few
# lanes (``ops/pallas_attention._LANES``, and why): lane 0 the running
# log decay, lane 1 beta; and back: lane 0 the decay's cotangent by
# rows, lane 1 beta's
_LANES = 8
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_F32 = jnp.float32


def causal_conv4_silu(x, w):
    """``silu(sum_i w[i] * x[t - i])`` along the sequence of ``x [B, T,
    N]``, a weight a channel and tap (``w [taps, N]``, tap 0 the token
    itself; four in the published layer), zeros before position 0.
    Multiply-adds in float32, the result in ``x``'s dtype."""
    xf = x.astype(_F32)
    T = x.shape[1]
    taps = [jnp.pad(xf, ((0, 0), (i, 0), (0, 0)))[:, :T] * w[i].astype(_F32)
            for i in range(w.shape[0])]
    return jax.nn.silu(sum(taps)).astype(x.dtype)


def _mm(a, b, dims, dtype, precision=None):
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        precision=precision, preferred_element_type=_F32)


def _total(x):
    """The sum of a 2-D tile as ``[1, 1]`` (an axis at a time: Mosaic
    reduces along one)."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _scaled(x, col):
    """``x [m, n]`` times the LAST entry of the column ``col [C, 1]``:
    the column spread over the lanes, its last row kept and summed into
    a row, the row spread over ``x`` (Mosaic broadcasts and reduces
    along one axis at a time, and no ``[1, 1]`` over both)."""
    C, n = col.shape[0], x.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (C, n), 0) == C - 1
    last = jnp.sum(jnp.where(at, jnp.broadcast_to(col, (C, n)), 0.0),
                   axis=0, keepdims=True)
    return x * last


def _triangles(C: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row, col, row >= col, row > col


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of a strictly lower-triangular ``A [C, C]``, ``C``
    a power of two, float32 (the module's docstring)."""
    C = A.shape[0]
    row, col, _, _ = _triangles(C)
    inv = (row == col).astype(_F32)
    hi = jax.lax.Precision.HIGHEST
    for level in range(C.bit_length() - 1):
        # the sub-diagonal block of size b = 2^level of every block of 2b
        off = ((row >> (level + 1)) == (col >> (level + 1))) \
            & (((row >> level) & 1) == 1) & (((col >> level) & 1) == 0)
        a_off = jnp.where(off, A, 0.0)
        inv = inv - _mm(_mm(inv, a_off, _NN, _F32, hi), inv, _NN, _F32, hi)
    return inv


def _prep(q, k, v, Gc, Gr, bc, inv=None):
    """What a chunk needs that no state enters: ``q, k [C, dk]``, ``v [C,
    dv]``; ``Gc [C, 1]`` and ``Gr [1, C]`` the running log decay as a
    column and as a row, ``bc [C, 1]`` beta; ``inv`` the system's inverse
    where the forward pass kept it."""
    dt = q.dtype
    C = q.shape[0]
    _, _, lower, strict = _triangles(C)
    decay = jnp.exp(jnp.where(lower, Gc - Gr, -1e30))
    kk = _mm(k, k, _NT, dt)
    A = jnp.where(strict, bc * kk * decay, 0.0)
    if inv is None:
        inv = _unit_lower_inverse(A)
    gam = jnp.exp(Gc)
    kf, vf, qf = k.astype(_F32), v.astype(_F32), q.astype(_F32)
    end = jnp.exp(Gc[C - 1:] - Gc)                      # [C, 1]
    return {
        "decay": decay, "kk": kk, "A": A, "inv": inv, "gam": gam,
        "end": end, "gam_end": gam[C - 1:],             # [1, 1]
        "W": _mm(inv, bc * gam * kf, _NN, dt),
        "U0": _mm(inv, bc * vf, _NN, dt),
        "P": jnp.where(lower, _mm(q, k, _NT, dt) * decay, 0.0),
        "Qg": gam * qf, "Kd": end * kf}


def _step(p, Z0, dt):
    """``(O, Z1)`` of a chunk from its state before, ``Z0 [dk, dv]``."""
    U = p["U0"] - _mm(p["W"], Z0, _NN, dt)
    O = _mm(p["Qg"], Z0, _NN, dt) + _mm(p["P"], U, _NN, dt)
    Z1 = _scaled(Z0, p["gam"]) + _mm(p["Kd"], U, _TN, dt)
    return O, Z1


def _step_bwd(p, dO, dZ1, dt):
    """``(dU, dZ0)``: what the reverse pass carries from chunk to
    chunk."""
    dU = _mm(p["P"], dO, _TN, dt) + _mm(p["Kd"], dZ1, _NN, dt)
    dZ0 = _scaled(dZ1, p["gam"]) + _mm(p["Qg"], dO, _TN, dt) \
        - _mm(p["W"], dU, _TN, dt)
    return dU, dZ0


def _chunk_bwd(p, q, k, v, bc, Z0, dO, dZ1, dU):
    """The operands' cotangents of one chunk, given the state's at its
    end and ``dU`` (``_step_bwd``): ``(dq, dk, dv, dG [C, 1], drow [1,
    C], dbeta [C, 1])``; ``dG`` less ``drow`` is the running log decay's
    cotangent."""
    dt = q.dtype
    C = q.shape[0]
    row, _, lower, strict = _triangles(C)
    kf, vf, qf = k.astype(_F32), v.astype(_F32), q.astype(_F32)
    gam, end, inv = p["gam"], p["end"], p["inv"]
    U = p["U0"] - _mm(p["W"], Z0, _NN, dt)
    # the step
    dP = jnp.where(lower, _mm(dO, U, _NT, dt), 0.0)
    dQg = _mm(dO, Z0, _NT, dt)
    dKd = _mm(U, dZ1, _NT, dt)
    d_gam_end = _total(dZ1 * Z0)
    dW = -_mm(dU, Z0, _NT, dt)
    # W = T (beta gam k), U0 = T (beta v), T = (I + A)^-1
    dYk = _mm(inv, dW, _TN, dt)
    dYv = _mm(inv, dU, _TN, dt)
    dA = -jnp.where(strict, _mm(dYk, p["W"], _NT, dt)
                    + _mm(dYv, p["U0"], _NT, dt), 0.0)
    dv = bc * dYv
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)      # noqa: E731
    dyk_k = rows(dYk * kf)
    dbeta = rows(dYv * vf) + gam * dyk_k
    d_gam = bc * dyk_k + rows(dQg * qf)
    d_end = rows(dKd * kf)
    # A = beta (k k^T) D, P = (q k^T) D
    dkk = bc * dA * p["decay"]
    dbeta = dbeta + rows(dA * p["decay"] * p["kk"])
    dqk = dP * p["decay"]
    dq = gam * dQg + _mm(dqk, k, _NN, dt)
    dk = bc * gam * dYk + end * dKd + _mm(dkk, k, _NN, dt) \
        + _mm(dkk, k, _TN, dt) + _mm(dqk, q, _TN, dt)
    # the running log decay: D[i, j] = exp(G_i - G_j), gam, end, gam_end
    both = dA * p["A"] + dP * p["P"]
    at_end = _total(d_end * end) + d_gam_end * p["gam_end"]
    dG = rows(both) + d_gam * gam - d_end * end \
        + jnp.where(row[:, :1] == C - 1, at_end, 0.0)
    drow = jnp.sum(both, axis=0, keepdims=True)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dG, drow, dbeta)


# -- plain XLA: the state's four products under a scan ------------------


def _over_chunks(fn):
    """``fn`` on 2-D tiles, over the leading ``[B, H, NC]``."""
    return jax.vmap(jax.vmap(jax.vmap(fn)))


def _columns(G, beta):
    return G[..., :, None], G[..., None, :], beta[..., :, None]


def _xla_forward(q, k, v, G, beta):
    dt = q.dtype
    Gc, Gr, bc = _columns(G, beta)
    p = _over_chunks(_prep)(q, k, v, Gc, Gr, bc)
    B, H, _, _, dk = q.shape
    heads = jax.vmap(jax.vmap(functools.partial(_step, dt=dt)))

    def body(Z0, p_c):
        O, Z1 = heads(p_c, Z0)
        return Z1, (O, Z0)

    by_chunk = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), p)
    _, (o, z0s) = jax.lax.scan(
        body, jnp.zeros((B, H, dk, v.shape[-1]), _F32), by_chunk)
    return (jnp.moveaxis(o, 0, 2).astype(v.dtype), jnp.moveaxis(z0s, 0, 2),
            p["inv"])


def _xla_backward(q, k, v, G, beta, z0s, inv, do):
    dt = q.dtype
    Gc, Gr, bc = _columns(G, beta)
    p = _over_chunks(_prep)(q, k, v, Gc, Gr, bc, inv)
    heads = jax.vmap(jax.vmap(functools.partial(_step_bwd, dt=dt)))

    def body(dZ1, xs):
        p_c, dO = xs
        dU, dZ0 = heads(p_c, dO, dZ1)
        return dZ0, (dU, dZ1)

    by_chunk = jax.tree.map(lambda a: jnp.moveaxis(a, 2, 0), (p, do))
    # nothing reads the state after the last token: its cotangent is 0
    _, (dU, dZ1s) = jax.lax.scan(body, jnp.zeros_like(z0s[:, :, 0]),
                                 by_chunk, reverse=True)
    dq, dk, dv, dG, drow, dbeta = _over_chunks(_chunk_bwd)(
        p, q, k, v, bc, z0s, do, jnp.moveaxis(dZ1s, 0, 2),
        jnp.moveaxis(dU, 0, 2))
    return dq, dk, dv, dG[..., 0] - drow[..., 0, :], dbeta[..., 0]


# -- the Mosaic kernels -------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, col_ref, row_ref,
                o_ref, z0_ref, inv_ref, z_scr):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        z_scr[...] = jnp.zeros_like(z_scr)

    q, k, v = q_ref[0, 0, 0], k_ref[0, 0, 0], v_ref[0, 0, 0]
    col = col_ref[0, 0, 0]
    p = _prep(q, k, v, col[:, 0:1], row_ref[0, 0, 0], col[:, 1:2])
    Z0 = z_scr[...]
    O, Z1 = _step(p, Z0, q.dtype)
    o_ref[0, 0, 0] = O.astype(o_ref.dtype)
    z0_ref[0, 0, 0] = Z0
    inv_ref[0, 0, 0] = p["inv"]
    z_scr[...] = Z1


def _bwd_kernel(q_ref, k_ref, v_ref, col_ref, row_ref, z0_ref, inv_ref,
                do_ref, dq_ref, dk_ref, dv_ref, dcol_ref, drow_ref, dz_scr):
    # nothing reads the state after the last token: its cotangent is 0
    @pl.when(pl.program_id(2) == 0)
    def _():
        dz_scr[...] = jnp.zeros_like(dz_scr)

    q, k, v = q_ref[0, 0, 0], k_ref[0, 0, 0], v_ref[0, 0, 0]
    col = col_ref[0, 0, 0]
    bc = col[:, 1:2]
    p = _prep(q, k, v, col[:, 0:1], row_ref[0, 0, 0], bc, inv_ref[0, 0, 0])
    dO, dZ1 = do_ref[0, 0, 0], dz_scr[...]
    dU, dZ0 = _step_bwd(p, dO, dZ1, q.dtype)
    dq, dk, dv, dG, drow, dbeta = _chunk_bwd(
        p, q, k, v, bc, z0_ref[0, 0, 0], dO, dZ1, dU)
    dq_ref[0, 0, 0] = dq
    dk_ref[0, 0, 0] = dk
    dv_ref[0, 0, 0] = dv
    lane = jax.lax.broadcasted_iota(jnp.int32, (dG.shape[0], _LANES), 1)
    dcol_ref[0, 0, 0] = jnp.where(lane == 0, dG,
                                  jnp.where(lane == 1, dbeta, 0.0))
    drow_ref[0, 0, 0] = drow
    dz_scr[...] = dZ0


def _specs(shapes, order):
    """A block a (batch, head, chunk) of arrays ``[B, H, NC, ...]``, the
    chunks in ``order`` (a map of the grid's third index)."""
    def spec(shape):
        rest = tuple(shape[3:])
        return pl.BlockSpec(
            (1, 1, 1) + rest,
            lambda b, h, c: (b, h, order(c)) + (0,) * len(rest))
    return [spec(s) for s in shapes]


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _scalars(G, beta):
    col = jnp.stack([G, beta], axis=-1)
    col = jnp.pad(col, [(0, 0)] * (col.ndim - 1) + [(0, _LANES - 2)])
    return col, G[..., None, :]


def _kernel_forward(q, k, v, G, beta, interpret):
    B, H, NC, C, dk = q.shape
    dv = v.shape[-1]
    col, row = _scalars(G, beta)
    ins = (q, k, v, col, row)
    outs = [jax.ShapeDtypeStruct((B, H, NC, C, dv), v.dtype),
            jax.ShapeDtypeStruct((B, H, NC, dk, dv), _F32),
            jax.ShapeDtypeStruct((B, H, NC, C, C), _F32)]
    return pl.pallas_call(
        _fwd_kernel, grid=(B, H, NC),
        in_specs=_specs([a.shape for a in ins], lambda c: c),
        out_specs=_specs([s.shape for s in outs], lambda c: c),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_params(), name="delta_fwd", interpret=interpret,
    )(*ins)


def _kernel_backward(q, k, v, G, beta, z0s, inv, do, interpret):
    B, H, NC, C, dk = q.shape
    dv = v.shape[-1]
    col, row = _scalars(G, beta)
    ins = (q, k, v, col, row, z0s, inv, do)
    outs = [jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(col.shape, _F32),
            jax.ShapeDtypeStruct(row.shape, _F32)]
    backwards = lambda c: NC - 1 - c                    # noqa: E731
    dq, dk_, dv_, dcol, drow = pl.pallas_call(
        _bwd_kernel, grid=(B, H, NC),
        in_specs=_specs([a.shape for a in ins], backwards),
        out_specs=_specs([s.shape for s in outs], backwards),
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_params(), name="delta_bwd", interpret=interpret,
    )(*ins)
    return dq, dk_, dv_, dcol[..., 0] - drow[..., 0, :], dcol[..., 1]


# -- the rule on chunked arrays, with its own backward ------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, G, beta, impl):
    """``o [B, H, NC, C, dv]`` from ``q, k [B, H, NC, C, dk]``, ``v [..,
    dv]`` and float32 ``G`` (the running log decay inside each chunk) and
    ``beta [B, H, NC, C]``."""
    return _rule_fwd(q, k, v, G, beta, impl)[0]


def _rule_fwd(q, k, v, G, beta, impl):
    if impl == "xla":
        out = _xla_forward(q, k, v, G, beta)
    else:
        out = _kernel_forward(q, k, v, G, beta, impl == "interpret")
    o, z0s, inv = (checkpoint_name(a, KEPT) for a in out)
    return o, (q, k, v, G, beta, z0s, inv)


def _rule_bwd(impl, res, do):
    if impl == "xla":
        return _xla_backward(*res, do)
    return _kernel_backward(*res, do, impl == "interpret")


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q, k, v, g, beta, *, chunk: Optional[int] = None,
                     impl: Optional[str] = None):
    """The gated delta rule over a sequence: ``q, k [B, T, H, dk]``, ``v
    [B, T, H, dv]``, the log decay ``g <= 0`` and the step ``beta [B, T,
    H]`` -> ``o [B, T, H, dv]`` in ``v``'s dtype. The caller normalises
    ``q`` and ``k`` and scales ``q``. (The state starts at zero and the
    one after the last token is not returned: both come with the cache
    that carries a state between calls.)

    ``chunk`` (None: ``CHUNK``) is a power of two; a sequence that is no
    multiple of it is PADDED behind with tokens that leave the state
    alone (``beta`` 0, ``g`` 0, a zero key) and whose outputs are
    dropped. ``impl``: the module's docstring."""
    if impl is None:
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("kernel", "interpret", "xla"):
        raise ValueError(f"unknown delta-rule impl {impl!r}")
    C = CHUNK if chunk is None else int(chunk)
    if C < 1 or C & (C - 1):
        raise ValueError(f"the chunk is a power of two, not {chunk}")
    B, T, H, _ = q.shape
    NC = -(-T // C)

    def chunked(a):
        """``[B, T, H, ...]`` -> ``[B, H, NC, C, ...]``, zeros behind."""
        a = jnp.pad(a, [(0, 0), (0, NC * C - T)] + [(0, 0)] * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(B, H, NC, C, *a.shape[3:])

    G = jnp.cumsum(chunked(g.astype(_F32)), axis=-1)
    o = _rule(chunked(q), chunked(k), chunked(v), G,
              chunked(beta.astype(_F32)), impl)
    return jnp.moveaxis(o.reshape(B, H, NC * C, -1), 1, 2)[:, :T]
