"""Pallas LSTM scan — the flagship LM1B's hot op, VMEM-resident
forward AND backward.

The LM1B forward is dominated by the recurrent gate matmul
[B, E+P] x [E+P, 4H] under `lax.scan` (models/lm1b.py). XLA compiles the
scan body once and re-fetches the gate matrix from HBM every time step:
at the flagship size that is 16.8 MB (bf16, [1024, 8192]) x T=20 steps
= 335 MB of HBM traffic per step for 16.8 MB of actual weights.

**Flagship-capable design (r5; lifts r4's one-block ~12 MB refusal —
VERDICT r4 item 2).** The gate matrix w = [w_x; w_h] splits by row into
the input projection w_x [E, 4H] and the recurrent matrix w_h [P, 4H],
and the two halves want opposite treatments:

- ``x @ w_x``: every timestep's input is known up front, so the whole
  [T·B, E] x [E, 4H] product is hoisted OUT of the recurrence into one
  large batched XLA matmul — MXU-optimal, w_x fetched from HBM once
  per step-batch instead of once per timestep.
- ``h @ w_h`` is the true recurrence and is what this kernel fuses: the
  entire time loop runs inside one pallas program with w_h, w_proj and
  the fp32 (c, h) carry RESIDENT in VMEM. w_h is a quarter of w's rows
  at the flagship (P=512 of E+P=1024... bf16 [512, 8192] = 8.4 MB), so
  the flagship now fits the VMEM budget with room for the streamed
  xw/out tiles — no gate-dimension streaming needed, which would have
  re-fetched the column tiles every timestep (the XLA scan's traffic
  pattern all over again).

**Backward (r14; closes ROADMAP open item 1).** The same split, AD'd
by hand: ``_lstm_bwd_kernel`` is ONE time-reversed pallas program —
w_h and w_proj resident, the fp32 (dc, dh) cotangent carries in VMEM
scratch — that streams the saved per-step residuals in and streams
``d_gates`` (which IS ``d_xw``) and ``dh_total`` out. Every weight
gradient then leaves the recurrence entirely and becomes one batched
fp32-accumulating XLA matmul, the mirror image of the forward's hoist:

    dx      = d_xw @ w_x^T                      (batched over T)
    dW_x    = x^T @ d_xw          (contract T·B)
    dW_h    = h_prev^T @ d_xw     (h_prev = hs shifted one step)
    db      = sum_{T,B} d_xw
    dW_proj = h_full^T @ dh_total (h_full recomputed elementwise)

so the backward neither recomputes the forward nor re-fetches a weight
per timestep. The forward (under differentiation only — the primal
path pays nothing) saves two cheap residuals at the COMPUTE dtype:
the gate activations [T, B, 4H] and the c trajectory [T, B, H]; the
h trajectory is the forward's own output hs, free. Residual memory at
the flagship per chip (bf16, B=128, T=20): gates 41.9 MB + c 10.5 MB.

Per-device recurrence HBM traffic per step-batch (flagship, dp=8,
per-chip B=128, bf16 — the numbers below ARE `kernel_hbm_bytes` /
`scan_hbm_bytes` evaluated at this shape; both sides exclude the
dW-accumulation streams each path additionally pays, per-step
scatter-adds inside the transposed scan vs the batched epilogue
matmuls here, and the hoisted x@w_x both paths share):

    pallas fwd (primal):   xw 42 + out 2.6 + weights 10.5  = ~55 MB
    pallas fwd (training): + residuals (gates 42 + c 10.5) = ~108 MB
    pallas bwd kernel:     g 5.2 + gates 42 + c 2x10.5 + weights
                           10.5 + d_xw 42 + dh_total 5.2   = ~126 MB
    pallas fwd+bwd total                                   = ~233 MB

    XLA scan fwd:          T x 9.4 MB weight re-fetch 377
                           + xw/out activations 45         = ~422 MB
    XLA scan + recompute VJP (training: fwd, recomputed fwd,
    transposed scan)       3 x 422                         = ~1266 MB

`tune/costmodel.py` consumes the kernel accounting via
`trace_records` so scored plans price the kernel's custom-call
traffic — which XLA's cost_analysis reads as ~zero — instead of
treating the recurrence as free.

Numerics contract: the (dc, dh) carries and every dW accumulation are
fp32; cotangents are never downcast on entry (the r13 `_bwd` rounded
``g`` to the input dtype before the VJP — fixed here for BOTH paths).
The two in-recurrence matmuls round ``d_gates`` / ``dh_total`` to the
weight dtype (the same single rounding the forward applies to h), and
the streamed ``d_xw`` is stored at the compute dtype — the identical
rounding the reference VJP itself applies at the stored-xw boundary.
At fp32 compute both backward paths match the XLA-scan VJP to
reassociation (~1e-5); at bf16 they differ from it by bf16 rounding
(budget pinned at 2e-2 in tests/test_pallas_lstm.py — note the
XLA-scan VJP accumulates dW in *bf16* across steps, so the kernel's
fp32 accumulation is the strictly better-conditioned side).

Size guard and executors: the forward refuses only when the RESIDENT
set (w_h + w_proj + carry + streamed tiles at the smallest batch
tile) cannot fit the VMEM budget; `lstm_scan` auto-shrinks
``batch_tile`` before refusing. The backward's larger streamed set
gets its own fit; when it cannot fit — and on every off-TPU
(interpret) run, where pallas emulation would only pay the
interpreter tax — ``bwd_impl='auto'`` drops to the **residual-scan
executor**: the identical time-reversed recurrence run as a native
XLA ``lax.scan`` over the same saved residuals with the same hoisted
epilogue (no forward recompute; on TPU it pays the scan's per-step
w_h re-fetch, which is exactly what the resident kernel removes).
``bwd_impl='recompute'`` keeps the r13 recompute-XLA VJP available —
it saves NO residuals (the memory-lean remat trade) and
differentiates the identical pure-XLA scan (`lstm_scan_reference`)
at the same inputs, widened to fp32 weights so its dW accumulation
is fp32 too.

Reference parity: the cell math is models/lm1b.py's fused-gate LSTM
(reference examples/lm1b/language_model.py LSTM with projection);
enable per model via ``LM1BConfig.lstm_impl='pallas'``.
"""

from __future__ import annotations

import collections
import functools
import os
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallax_tpu.ops.pallas_attention import _sds


def _split_w(w, w_proj):
    """w [E+P, 4H] -> (w_x [E, 4H], w_h [P, 4H]); E = rows - P."""
    P = w_proj.shape[1]
    return w[:-P], w[-P:]


def _hoisted_xw(x_seq, w_x, b, matmul_dtype=None, store_dtype=None):
    """The input-projection half of the gate pre-activation for ALL
    timesteps as one batched matmul: [T, B, E] -> [T, B, 4H] in the
    COMPUTE dtype (x_seq's). The matmul itself accumulates in fp32; the
    result is stored at the input precision because this buffer is the
    dominant HBM traffic of the whole op (written once, re-read every
    timestep) — keeping it fp32 doubled it and erased half the
    documented ~3.3x HBM win (ADVICE r5). Inside the recurrence it is
    widened back to fp32 before the add, so the only precision cost is
    the one storage rounding of xw.

    ``matmul_dtype`` / ``store_dtype`` default to w_x.dtype / x_seq's
    dtype (bit-identical to the historical behavior); the fp32-widened
    backward fallback passes the ORIGINAL dtypes explicitly so fp32
    inputs reproduce the original rounding points exactly."""
    md = jnp.dtype(matmul_dtype) if matmul_dtype is not None \
        else w_x.dtype
    sd = jnp.dtype(store_dtype) if store_dtype is not None \
        else x_seq.dtype
    xw = jax.lax.dot_general(
        x_seq.astype(md), w_x.astype(md), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    return xw.astype(sd)


def lstm_scan_reference(x_seq, w, b, w_proj, *, out_dtype=None,
                        matmul_dtype=None, store_dtype=None):
    """Pure-XLA scan with the KERNEL's exact numerics: the x-projection
    is hoisted (matmuls take the weights' dtype with fp32 accumulation)
    and the (c, h) carry stays fp32 whatever the input dtype. This is
    the function the custom_vjp fallback backward differentiates, so it
    must match the Pallas forward bit-for-bit in semantics — it
    deliberately differs from models/lm1b.lstm_scan's plain
    compute-dtype scan (bf16 carries there; the kernel's fp32 carry is
    strictly more precise).

    The keyword-only dtype hooks exist for the fp32-widened backward
    fallback (`_bwd_recompute`): ``matmul_dtype``/``store_dtype`` pin
    the rounding points to the ORIGINAL compute dtypes when the inputs
    arrive pre-widened to fp32 (so the primal values are bit-identical
    while every cotangent accumulates in fp32), and ``out_dtype=fp32``
    skips the per-step output cast so an fp32 cotangent enters the
    transposed scan unrounded. Defaults reproduce the historical
    behavior exactly."""
    T, B, _ = x_seq.shape
    H = w.shape[1] // 4
    P = w_proj.shape[1]
    md = jnp.dtype(matmul_dtype) if matmul_dtype is not None \
        else w.dtype
    od = jnp.dtype(out_dtype) if out_dtype is not None \
        else x_seq.dtype
    w_x, w_h = _split_w(w, w_proj)
    xw = _hoisted_xw(x_seq, w_x, b, matmul_dtype=md,
                     store_dtype=store_dtype)   # [T, B, 4H] x dtype

    def cell(carry, xw_t):
        c, h = carry                                   # fp32
        gates = xw_t.astype(jnp.float32) + jax.lax.dot_general(
            h.astype(md), w_h.astype(md), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h_full = jax.nn.sigmoid(o) * jnp.tanh(c)
        h = jax.lax.dot_general(
            h_full.astype(md), w_proj.astype(md),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (c, h), h.astype(od)

    c0 = jnp.zeros((B, H), jnp.float32)
    h0 = jnp.zeros((B, P), jnp.float32)
    (_, _), hs = jax.lax.scan(cell, (c0, h0), xw)
    return hs


def _lstm_kernel(xw_ref, wh_ref, wp_ref, out_ref, c_ref, h_ref):
    """Grid (batch_tiles, T), t innermost. w_h/w_proj blocks have a
    constant index map so pallas keeps them VMEM-resident across the
    whole time loop; the fp32 carry lives in scratch, which persists
    across grid steps on TPU (and in interpret mode)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    w_h = wh_ref[...]                                 # [P, 4H] resident
    wp = wp_ref[...]                                  # [H, P]  resident
    c, h = c_ref[...], h_ref[...]                     # fp32
    gates = xw_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h.astype(w_h.dtype), w_h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_full = jax.nn.sigmoid(o) * jnp.tanh(c)
    h = jax.lax.dot_general(
        h_full.astype(wp.dtype), wp, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    c_ref[...], h_ref[...] = c, h
    out_ref[0] = h.astype(out_ref.dtype)


def _lstm_kernel_res(xw_ref, wh_ref, wp_ref, out_ref, gates_ref,
                     cseq_ref, c_ref, h_ref):
    """The forward under differentiation: identical cell math, plus
    the two backward residual streams — POST-activation gates
    [i|f|g|o] and the c trajectory, both stored at the compute dtype
    (the same storage-rounding decision as xw; see module docstring
    for the residual-memory cost)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        h_ref[...] = jnp.zeros_like(h_ref)

    w_h = wh_ref[...]
    wp = wp_ref[...]
    c, h = c_ref[...], h_ref[...]
    gates = xw_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h.astype(w_h.dtype), w_h, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + 1.0)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c = f * c + i * g
    h_full = o * jnp.tanh(c)
    h = jax.lax.dot_general(
        h_full.astype(wp.dtype), wp, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    c_ref[...], h_ref[...] = c, h
    out_ref[0] = h.astype(out_ref.dtype)
    gates_ref[0] = jnp.concatenate([i, f, g, o],
                                   axis=-1).astype(gates_ref.dtype)
    cseq_ref[0] = c.astype(cseq_ref.dtype)


def _compiler_params(vmem_limit):
    """Mosaic's scoped-VMEM cap for one kernel: the byte count of the
    fit that chose its tile (`_fwd_vmem_bytes`/`_bwd_vmem_bytes`), so
    the tile and the limit can never disagree. None (direct callers,
    interpret mode) keeps the compiler's default."""
    if vmem_limit is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=int(vmem_limit))


def _forward(x_seq, w, b, w_proj, batch_tile: int, interpret: bool,
             save_residuals: bool = False, vmem_limit=None):
    T, B, _ = x_seq.shape
    H = w.shape[1] // 4
    P = w_proj.shape[1]
    w_x, w_h = _split_w(w, w_proj)
    xw = _hoisted_xw(x_seq, w_x, b)              # [T, B, 4H] x dtype
    bt = min(batch_tile, B)
    while B % bt:
        bt -= 1
    grid = (B // bt, T)
    params = _compiler_params(vmem_limit)
    in_specs = [
        pl.BlockSpec((1, bt, 4 * H), lambda i, t: (t, i, 0)),
        pl.BlockSpec(w_h.shape, lambda i, t: (0, 0)),
        pl.BlockSpec(w_proj.shape, lambda i, t: (0, 0)),
    ]
    scratch = [
        pltpu.VMEM((bt, H), jnp.float32),          # c carry
        pltpu.VMEM((bt, P), jnp.float32),          # h carry
    ]
    if not save_residuals:
        return pl.pallas_call(
            _lstm_kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bt, P), lambda i, t: (t, i, 0)),
            out_shape=_sds((T, B, P), x_seq.dtype, xw),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
            name="lstm_fwd",
        )(xw, w_h, w_proj)
    return pl.pallas_call(
        _lstm_kernel_res,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bt, P), lambda i, t: (t, i, 0)),
            pl.BlockSpec((1, bt, 4 * H), lambda i, t: (t, i, 0)),
            pl.BlockSpec((1, bt, H), lambda i, t: (t, i, 0)),
        ],
        # under the mesh wrap (shard_map, VMA checker on for compiled
        # kernels) the outputs vary over the batch axes exactly as the
        # streamed input does
        out_shape=[
            _sds((T, B, P), x_seq.dtype, xw),
            _sds((T, B, 4 * H), x_seq.dtype, xw),
            _sds((T, B, H), x_seq.dtype, xw),
        ],
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
        name="lstm_fwd_res",
    )(xw, w_h, w_proj)


def _lstm_bwd_kernel(g_ref, gates_ref, c_ref, cprev_ref, wh_ref,
                     wp_ref, dxw_ref, dhtot_ref, dc_ref, dh_ref):
    """Time-reversed recurrence: grid (batch_tiles, T) with t innermost
    and every streamed index map running T-1 -> 0. w_h/w_proj stay
    VMEM-resident (constant index maps); the (dc, dh) cotangent
    carries are fp32 scratch, reset at each batch tile's first grid
    step (t == 0, i.e. timestep s = T-1). The two resident matmuls
    round their activation operand to the weight dtype — the same
    single rounding the forward applies to h — and everything else is
    fp32."""
    t = pl.program_id(1)
    n_t = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        dc_ref[...] = jnp.zeros_like(dc_ref)
        dh_ref[...] = jnp.zeros_like(dh_ref)

    w_h = wh_ref[...]                                 # [P, 4H] resident
    wp = wp_ref[...]                                  # [H, P]  resident
    H = wp.shape[0]
    gates = gates_ref[0].astype(jnp.float32)          # [bt, 4H]
    i, f, g_act, o = jnp.split(gates, 4, axis=-1)
    c_t = c_ref[0].astype(jnp.float32)
    # the s==0 step (t == n_t-1) has no predecessor: its c_prev block
    # index is clamped to 0 by the index map and zeroed here
    live = jnp.where(t == n_t - 1, 0.0, 1.0)
    c_prev = cprev_ref[0].astype(jnp.float32) * live

    dh_tot = g_ref[0].astype(jnp.float32) + dh_ref[...]
    dhtot_ref[0] = dh_tot.astype(dhtot_ref.dtype)     # fp32 stream
    # through the projection h = h_full @ w_proj (contract P)
    d_hfull = jax.lax.dot_general(
        dh_tot.astype(wp.dtype), wp, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    tc = jnp.tanh(c_t)
    d_o = d_hfull * tc
    dc_tot = dc_ref[...] + d_hfull * o * (1.0 - tc * tc)
    d_i = dc_tot * g_act
    d_f = dc_tot * c_prev
    d_g = dc_tot * i
    dc_ref[...] = dc_tot * f                          # -> step s-1
    d_gates = jnp.concatenate([
        d_i * i * (1.0 - i),
        d_f * f * (1.0 - f),
        d_g * (1.0 - g_act * g_act),
        d_o * o * (1.0 - o)], axis=-1)                # [bt, 4H] fp32
    dxw_ref[0] = d_gates.astype(dxw_ref.dtype)
    # through the recurrent matmul gates += h_prev @ w_h (contract 4H)
    dh_ref[...] = jax.lax.dot_general(
        d_gates.astype(w_h.dtype), w_h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_epilogue(x_seq, w, b, w_proj, gates, cseq, hs, dxw, dhtot):
    """The hoisted half of the residual backward, shared by the pallas
    kernel and the XLA residual-scan executor: one batched matmul per
    weight gradient, fp32 accumulation, cotangents cast to the input
    dtypes exactly once at the end. Operand castings mirror the
    forward's (activations rounded to the weight dtype before the
    MXU), so at matching dtypes they are no-ops and at fp32 the whole
    path is exact."""
    f32 = jnp.float32
    H = w.shape[1] // 4
    w_x, _w_h = _split_w(w, w_proj)
    wd = w.dtype
    dxw_m = dxw.astype(wd)
    dx = jax.lax.dot_general(
        dxw_m, w_x, (((2,), (1,)), ((), ())),
        preferred_element_type=f32).astype(x_seq.dtype)
    dw_x = jax.lax.dot_general(
        x_seq.astype(wd), dxw_m, (((0, 1), (0, 1)), ((), ())),
        preferred_element_type=f32)                    # [E, 4H] fp32
    h_prev = jnp.concatenate([jnp.zeros_like(hs[:1]), hs[:-1]], axis=0)
    dw_h = jax.lax.dot_general(
        h_prev.astype(wd), dxw_m, (((0, 1), (0, 1)), ((), ())),
        preferred_element_type=f32)                    # [P, 4H] fp32
    db = dxw.astype(f32).sum(axis=(0, 1))
    # h_full = o * tanh(c), recomputed elementwise from the residuals
    # and rounded to the projection dtype exactly as the forward did
    o = gates[..., 3 * H:].astype(f32)
    h_full = (o * jnp.tanh(cseq.astype(f32))).astype(
        w_proj.dtype).astype(f32)
    dw_proj = jax.lax.dot_general(
        h_full, dhtot, (((0, 1), (0, 1)), ((), ())),
        preferred_element_type=f32)                    # [H, P] fp32
    dw = jnp.concatenate([dw_x, dw_h], axis=0).astype(w.dtype)
    return (dx, dw, db.astype(b.dtype), dw_proj.astype(w_proj.dtype))


def _bwd_scan_path(x_seq, w, b, w_proj, gates, cseq, hs, g):
    """The residual backward executed as a native XLA reversed
    lax.scan — the SAME algorithm as the pallas kernel (identical
    per-step math, fp32 (dc, dh) carries, d_gates stored at the
    compute dtype, shared hoisted epilogue) with XLA owning the time
    loop. This is the refusal/off-TPU executor: no forward recompute
    (strictly less work than the recompute-VJP it replaced), and on
    TPU it pays the scan's per-step w_h re-fetch — which is exactly
    what the resident pallas kernel exists to remove."""
    f32 = jnp.float32
    T, B, _E = x_seq.shape
    H = w.shape[1] // 4
    P = w_proj.shape[1]
    _w_x, w_h = _split_w(w, w_proj)
    md = w.dtype
    c_prev_seq = jnp.concatenate([jnp.zeros_like(cseq[:1]), cseq[:-1]],
                                 axis=0)

    def cell(carry, inp):
        dc, dh = carry                                 # fp32
        g_t, gates_t, c_t, c_prev = inp
        i, f, g_act, o = jnp.split(gates_t.astype(f32), 4, axis=-1)
        dh_tot = g_t.astype(f32) + dh
        d_hfull = jax.lax.dot_general(
            dh_tot.astype(md), w_proj.astype(md),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
        tc = jnp.tanh(c_t.astype(f32))
        d_o = d_hfull * tc
        dc_tot = dc + d_hfull * o * (1.0 - tc * tc)
        d_i = dc_tot * g_act
        d_f = dc_tot * c_prev.astype(f32)
        d_g = dc_tot * i
        d_gates = jnp.concatenate([
            d_i * i * (1.0 - i),
            d_f * f * (1.0 - f),
            d_g * (1.0 - g_act * g_act),
            d_o * o * (1.0 - o)], axis=-1)
        dh_new = jax.lax.dot_general(
            d_gates.astype(md), w_h.astype(md),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)
        return (dc_tot * f, dh_new), (d_gates.astype(x_seq.dtype),
                                      dh_tot)

    dc0 = jnp.zeros((B, H), f32)
    dh0 = jnp.zeros((B, P), f32)
    (_, _), (dxw, dhtot) = jax.lax.scan(
        cell, (dc0, dh0), (g, gates, cseq, c_prev_seq), reverse=True)
    return _bwd_epilogue(x_seq, w, b, w_proj, gates, cseq, hs, dxw,
                         dhtot)


def _bwd_kernel_path(x_seq, w, b, w_proj, gates, cseq, hs, g,
                     bwd_batch_tile: int, interpret: bool,
                     vmem_limit=None):
    """The kernel backward: the time-reversed pallas recurrence streams
    d_xw / dh_total out, then every weight gradient is ONE batched
    fp32-accumulating XLA matmul — the mirror image of the forward's
    hoisted x @ w_x. Returned cotangents are cast to the input dtypes
    exactly once, at the end."""
    T, B, _E = x_seq.shape
    H = w.shape[1] // 4
    P = w_proj.shape[1]
    f32 = jnp.float32
    w_x, w_h = _split_w(w, w_proj)
    bt = min(bwd_batch_tile, B)
    while B % bt:
        bt -= 1
    grid = (B // bt, T)
    rev = lambda i, t: (T - 1 - t, i, 0)               # noqa: E731
    prev = lambda i, t: (jnp.maximum(T - 2 - t, 0), i, 0)  # noqa: E731
    dxw, dhtot = pl.pallas_call(
        _lstm_bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, P), rev),             # g (cotangent)
            pl.BlockSpec((1, bt, 4 * H), rev),         # gate acts
            pl.BlockSpec((1, bt, H), rev),             # c_t
            pl.BlockSpec((1, bt, H), prev),            # c_{t-1}
            pl.BlockSpec(w_h.shape, lambda i, t: (0, 0)),
            pl.BlockSpec(w_proj.shape, lambda i, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, 4 * H), rev),         # d_xw
            pl.BlockSpec((1, bt, P), rev),             # dh_total
        ],
        out_shape=[
            _sds((T, B, 4 * H), x_seq.dtype, gates),
            _sds((T, B, P), f32, gates),
        ],
        scratch_shapes=[
            pltpu.VMEM((bt, H), f32),                  # dc carry
            pltpu.VMEM((bt, P), f32),                  # dh carry
        ],
        compiler_params=_compiler_params(vmem_limit),
        interpret=interpret,
        name="lstm_bwd",
    )(g, gates, cseq, cseq, w_h, w_proj)
    return _bwd_epilogue(x_seq, w, b, w_proj, gates, cseq, hs, dxw,
                         dhtot)


def _bwd_recompute(x_seq, w, b, w_proj, g):
    """Recompute-XLA fallback (the refusal/size-guard path): one extra
    forward, gradients from the XLA-transposed scan. The inputs are
    widened to fp32 with the rounding points pinned to the ORIGINAL
    dtypes (matmul_dtype/store_dtype), so the primal math is
    bit-identical while every dW accumulates across timesteps in fp32
    — and the incoming cotangent enters unrounded via the fp32 output
    (the r13 path downcast g to the input dtype first, losing
    sub-input-precision cotangent structure and accumulating dW at the
    weight dtype). Returned cotangents cast to input dtypes once."""
    f32 = jnp.float32

    def wide(x32, w32, b32, wp32):
        return lstm_scan_reference(
            x32, w32, b32, wp32, out_dtype=f32,
            matmul_dtype=w.dtype, store_dtype=x_seq.dtype)

    _, vjp = jax.vjp(wide, x_seq.astype(f32), w.astype(f32),
                     b.astype(f32), w_proj.astype(f32))
    dx, dw, db, dwp = vjp(g.astype(f32))
    return (dx.astype(x_seq.dtype), dw.astype(w.dtype),
            db.astype(b.dtype), dwp.astype(w_proj.dtype))


# fwd_plan (static): (batch tile, vmem limit) from the forward fit.
# bwd_mode (static): None -> recompute-XLA (no residuals saved);
# "scan" -> residual backward via the XLA reversed scan;
# ("kernel", bt, limit) -> the time-reversed pallas kernel at batch
# tile bt under its own fit's vmem limit
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _lstm_scan_pallas(x_seq, w, b, w_proj, fwd_plan,
                      bwd_mode, interpret):
    bt, limit = fwd_plan
    return _forward(x_seq, w, b, w_proj, bt, interpret,
                    vmem_limit=limit)


def _fwd(x_seq, w, b, w_proj, fwd_plan, bwd_mode, interpret):
    bt, limit = fwd_plan
    if bwd_mode is None:
        # recompute backward: save no residuals (the primal inputs are
        # enough to re-run the reference scan)
        out = _forward(x_seq, w, b, w_proj, bt, interpret,
                       vmem_limit=limit)
        return out, (x_seq, w, b, w_proj, None, None, None)
    out, gates, cseq = _forward(x_seq, w, b, w_proj, bt, interpret,
                                save_residuals=True, vmem_limit=limit)
    return out, (x_seq, w, b, w_proj, gates, cseq, out)


def _bwd(fwd_plan, bwd_mode, interpret, res, g):
    x_seq, w, b, w_proj, gates, cseq, hs = res
    if gates is None:
        return _bwd_recompute(x_seq, w, b, w_proj, g)
    if bwd_mode == "scan":
        return _bwd_scan_path(x_seq, w, b, w_proj, gates, cseq, hs, g)
    _, bwd_bt, bwd_limit = bwd_mode
    return _bwd_kernel_path(x_seq, w, b, w_proj, gates, cseq, hs, g,
                            bwd_bt, interpret, vmem_limit=bwd_limit)


_lstm_scan_pallas.defvjp(_fwd, _bwd)


# -- the VMEM fit: one model chooses the tile AND the compiler's limit ------
# What Mosaic allocates per BlockSpec, read off the compiler's own
# "Scoped allocation with size ..." messages on a v5e target (jax
# 0.9.0 / libtpu 0.0.34, PR 22): a block whose index map is constant
# over the grid (w_h, w_proj) gets ONE buffer; every block whose index
# moves with the grid gets TWO (the pipeline prefetches step t+1 while
# step t computes); scratch is allocated as declared. On top of the
# buffers the body materializes fp32 [bt, 4H] gate tiles.

_VMEM_BUDGET = 64 * 1024 * 1024     # half a v5e TensorCore's 128 MiB


def _vmem_budget() -> int:
    return int(os.environ.get("PARALLAX_LSTM_VMEM_BUDGET", _VMEM_BUDGET))


def _fwd_vmem_bytes(bt, H, P, wsz, xsz, residuals: bool) -> int:
    """Bytes the FORWARD kernel holds in VMEM at batch tile ``bt``."""
    resident = (P * 4 * H + H * P) * wsz               # w_h + w_proj, x1
    streamed = bt * 4 * H * xsz + bt * P * xsz         # xw in, out
    if residuals:
        streamed += bt * 4 * H * xsz + bt * H * xsz    # gate acts, c traj
    scratch = bt * H * 4 + bt * P * 4                  # fp32 c + h carry
    body = 2 * bt * 4 * H * 4                          # gates pre/post act
    return resident + 2 * streamed + scratch + body


def _bwd_vmem_bytes(bt, H, P, wsz, xsz) -> int:
    """Bytes the BACKWARD kernel holds in VMEM at batch tile ``bt``.
    The cotangent ``g`` is sized at the output dtype: JAX types a
    cotangent by its primal, so it arrives as hs's dtype (x_seq's)."""
    resident = (P * 4 * H + H * P) * wsz               # w_h + w_proj, x1
    streamed = (bt * P * xsz                           # g
                + bt * 4 * H * xsz                     # gate acts
                + 2 * bt * H * xsz                     # c_t + c_{t-1}
                + bt * 4 * H * xsz                     # d_xw out
                + bt * P * 4)                          # dh_total out
    scratch = bt * H * 4 + bt * P * 4                  # fp32 dc + dh carry
    body = 2 * bt * 4 * H * 4                          # gates + d_gates
    return resident + 2 * streamed + scratch + body


def _largest_fitting_tile(batch_tile, B, vmem_bytes, budget):
    """(bt, bytes) for the largest divisor ``bt <= batch_tile`` of B
    with ``vmem_bytes(bt) <= budget``, or None when even bt=1 cannot
    fit. ``bytes`` is what the kernel is then compiled under
    (``vmem_limit_bytes``)."""
    for bt in range(min(batch_tile, B), 0, -1):
        if B % bt == 0:
            need = vmem_bytes(bt)
            if need <= budget:
                return bt, need
    return None


def _vmem_fit_batch_tile(batch_tile, B, H, P, w_dtype, x_dtype, budget,
                         *, residuals: bool = False):
    """FORWARD fit -> (bt, vmem bytes) or None (-> refusal)."""
    wsz = jnp.dtype(w_dtype).itemsize
    xsz = jnp.dtype(x_dtype).itemsize
    return _largest_fitting_tile(
        batch_tile, B,
        lambda bt: _fwd_vmem_bytes(bt, H, P, wsz, xsz, residuals),
        budget)


def _vmem_fit_batch_tile_bwd(batch_tile, B, H, P, w_dtype, x_dtype,
                             budget):
    """BACKWARD fit -> (bt, vmem bytes) or None (-> the residual-scan
    executor under ``bwd_impl='auto'``)."""
    wsz = jnp.dtype(w_dtype).itemsize
    xsz = jnp.dtype(x_dtype).itemsize
    return _largest_fitting_tile(
        batch_tile, B,
        lambda bt: _bwd_vmem_bytes(bt, H, P, wsz, xsz), budget)


# -- trace records for the cost model ---------------------------------------
# Every `lstm_scan(impl='pallas')` call records its static signature
# here at trace time (the embedding _lookup_records pattern, op-side):
# XLA's cost_analysis prices a pallas custom call at ~zero bytes, so
# without these the tuner would score a kernel-served model as if the
# recurrence were HBM-free. `tune/costmodel.inputs_from_engine` reads
# the records for its engine's mesh and adds the analytic kernel bytes
# (kernel_hbm_bytes) to the HBM roofline term. Records are deduped by
# (mesh, signature) — two same-shape LSTM layers on one mesh collapse
# to one record (document-level caveat; the flagship has one).
_TRACE_RECORDS: "collections.OrderedDict" = collections.OrderedDict()
_TRACE_RECORDS_MAX = 64


def _record_call(mesh, T, B, E, H, P, x_dtype, w_dtype, n_shards,
                 bwd):
    info = {"T": int(T), "B": int(B), "E": int(E), "H": int(H),
            "P": int(P),
            "x_itemsize": int(jnp.dtype(x_dtype).itemsize),
            "w_itemsize": int(jnp.dtype(w_dtype).itemsize),
            "n_shards": int(n_shards), "bwd": str(bwd)}
    key = (id(mesh) if mesh is not None else None,
           tuple(sorted(info.items())))
    try:
        ref = weakref.ref(mesh) if mesh is not None else None
    except TypeError:                       # mesh not weakref-able
        ref = (lambda m: (lambda: m))(mesh)
    _TRACE_RECORDS[key] = (ref, info)
    while len(_TRACE_RECORDS) > _TRACE_RECORDS_MAX:
        _TRACE_RECORDS.popitem(last=False)


def trace_records(mesh=None):
    """The recorded pallas-LSTM call signatures for ``mesh`` (None:
    records made outside any mesh). Each is a dict with T/B/E/H/P,
    x/w itemsizes, n_shards and ``bwd`` — which backward serves the
    call ('kernel' | 'scan' | 'recompute'; for the latter two only
    the forward is a custom call and cost_analysis prices the XLA
    backward itself)."""
    out = []
    for ref, info in _TRACE_RECORDS.values():
        m = ref() if ref is not None else None
        if (mesh is None and ref is None) or (m is mesh
                                              and m is not None):
            out.append(dict(info))
    return out


def reset_trace_records():
    _TRACE_RECORDS.clear()


def kernel_hbm_bytes(T, B, E, H, P, x_itemsize, w_itemsize, *,
                     bwd="kernel", g_itemsize=4):
    """Analytic per-step-batch HBM bytes of the pallas CUSTOM CALLS
    under training (forward, residual streams, and — when ``bwd`` is
    'kernel' — the backward program). ``stream_bytes`` scale with the
    GLOBAL batch (fixed total traffic however the batch is sharded);
    ``resident_bytes_per_device`` is the once-per-call weight fetch
    each device pays. Everything XLA executes (the hoisted/epilogue
    matmuls, the 'scan' backward, the 'recompute' re-forward) is NOT
    counted here — cost_analysis prices those; this accounts only the
    custom-call traffic XLA cannot see."""
    wbytes = (P * 4 * H + H * P) * w_itemsize          # w_h + w_proj
    # fwd: xw read + out write (+ residual writes when a residual
    # backward will consume them; the recompute fallback saves none)
    stream = T * B * (4 * H + P) * x_itemsize
    resident = wbytes
    if bwd in ("kernel", "scan"):
        stream += T * B * (4 * H + H) * x_itemsize     # gates + c traj
    if bwd == "kernel":
        stream += T * B * (P * g_itemsize              # g read
                           + 4 * H * x_itemsize        # gates read
                           + 2 * H * x_itemsize        # c + c_prev
                           + 4 * H * x_itemsize        # d_xw write
                           + P * 4)                    # dh_total write
        resident += wbytes
    return {"stream_bytes": int(stream),
            "resident_bytes_per_device": int(resident)}


def scan_hbm_bytes(T, B, E, H, P, x_itemsize, w_itemsize, *,
                   training=True):
    """The XLA-scan alternative's analytic bytes for the same shapes —
    the T x weight re-fetch story the kernel removes (README): the
    scan body re-reads the full [E+P, 4H] gate matrix and w_proj every
    timestep, forward and (training) again in the transposed backward
    plus the recompute-fallback's extra forward."""
    wfetch = T * ((E + P) * 4 * H + H * P) * w_itemsize
    act = T * B * (4 * H + P) * x_itemsize             # xw + out
    total = wfetch + act
    if training:
        total += 2 * (wfetch + act)    # recomputed fwd + transposed scan
    return int(total)


def lstm_scan(x_seq, w, b, w_proj, *, impl: str = "xla",
              batch_tile: int = 128,
              bwd_impl: str = "auto",
              interpret: Optional[bool] = None,
              mesh=None, batch_axes=None):
    """Fused-gate LSTM scan, x_seq [T, B, E] -> hs [T, B, P].

    ``impl='pallas'`` hoists the input projection into one batched XLA
    matmul and runs the recurrence as the VMEM-resident kernel,
    forward AND backward; ``'xla'`` is the plain scan. ``interpret``
    defaults to True off-TPU so CPU tests exercise the kernels.

    ``bwd_impl`` selects the backward: ``'auto'`` (default) uses the
    time-reversed pallas kernel when its resident set fits the VMEM
    budget on a real TensorCore run, and the XLA residual-scan
    executor otherwise (off-TPU interpret, or an unfittable size —
    the same algorithm over the same saved residuals, no forward
    recompute); ``'kernel'`` requires the pallas kernel (loud
    ValueError on an unfittable size, except under interpret where
    any size runs); ``'scan'`` forces the residual-scan executor;
    ``'recompute'`` forces the r13 recompute-XLA VJP (saves no
    residuals — the memory-lean remat trade, and the A/B baseline).
    The PARALLAX_LSTM_BWD env var overrides the argument (operational
    escape hatch; same four values).

    Under GSPMD a pallas custom call does not partition — pass ``mesh``
    + ``batch_axes`` (the mesh axes B is sharded over) and the kernel
    runs per-device under shard_map (weights replicated in, gradients
    psum'd by the transpose), keeping the batch sharding intact."""
    # the layer's name on the hoisted product, both kernels and the
    # backward's weight products (obs/xprof.LAYER_SCOPES)
    with jax.named_scope("lstm"):
        return _scan(x_seq, w, b, w_proj, impl, batch_tile, bwd_impl,
                     interpret, mesh, batch_axes)


def _scan(x_seq, w, b, w_proj, impl, batch_tile, bwd_impl, interpret,
          mesh, batch_axes):
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown lstm impl {impl!r}")
    if impl == "xla":
        return lstm_scan_reference(x_seq, w, b, w_proj)
    bwd_impl = os.environ.get("PARALLAX_LSTM_BWD") or bwd_impl
    if bwd_impl not in ("auto", "kernel", "scan", "recompute"):
        raise ValueError(f"unknown lstm bwd_impl {bwd_impl!r}; "
                         f"expected 'auto', 'kernel', 'scan' or "
                         f"'recompute'")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T, B, E = x_seq.shape
    H = w.shape[1] // 4
    P = w_proj.shape[1]
    budget = _vmem_budget()
    # refuse sizes that cannot compile on hardware instead of failing
    # deep inside Mosaic; only the RECURRENT matrix must be resident
    # (batch size is divided across devices by the shard_map wrap below,
    # so size the tile to the per-device batch)
    n_shards = 1
    if mesh is not None and batch_axes is not None:
        axes = ((batch_axes,) if isinstance(batch_axes, str)
                else tuple(batch_axes))
        n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    B_dev = max(1, B // n_shards)
    # interpret mode runs any size at the requested tile, no limit
    any_size = (min(batch_tile, B_dev), None)
    # backward mode first: whether residuals are saved decides the
    # forward's own tile fit. 'auto' picks the pallas kernel when its
    # resident set fits a real TensorCore run, and the XLA
    # residual-scan executor otherwise (off-TPU interpret, or a
    # VMEM-unfittable size) — same algorithm, no forward recompute.
    if bwd_impl == "recompute":
        bwd_mode = None
    elif bwd_impl == "scan":
        bwd_mode = "scan"
    else:
        bwd_fit = _vmem_fit_batch_tile_bwd(batch_tile, B_dev, H, P,
                                           w.dtype, x_seq.dtype, budget)
        if bwd_impl == "kernel":
            if bwd_fit is None:
                if not interpret:
                    wh_bytes = P * 4 * H * jnp.dtype(w.dtype).itemsize
                    raise ValueError(
                        f"pallas lstm backward: resident set "
                        f"(recurrent matrix {wh_bytes / 1e6:.1f} MB + "
                        f"proj + carries + streams) exceeds the "
                        f"{budget / 1e6:.0f} MB VMEM budget at every "
                        f"batch tile — use bwd_impl='scan' (the "
                        f"residual fallback) or 'recompute'")
                bwd_fit = any_size
            bwd_mode = ("kernel", *bwd_fit)
        elif interpret or bwd_fit is None:             # auto
            bwd_mode = "scan"
        else:
            bwd_mode = ("kernel", *bwd_fit)
    fwd_fit = _vmem_fit_batch_tile(batch_tile, B_dev, H, P,
                                   w.dtype, x_seq.dtype, budget,
                                   residuals=bwd_mode is not None)
    if fwd_fit is None and bwd_mode is not None and bwd_impl == "auto":
        # the residual streams are what broke the forward fit: drop to
        # the recompute backward rather than refusing outright
        bwd_mode = None
        fwd_fit = _vmem_fit_batch_tile(batch_tile, B_dev, H, P,
                                       w.dtype, x_seq.dtype, budget)
    if not interpret and fwd_fit is None:
        wh_bytes = P * 4 * H * jnp.dtype(w.dtype).itemsize
        raise ValueError(
            f"pallas lstm: resident set (recurrent matrix "
            f"{wh_bytes / 1e6:.1f} MB + proj + carry) exceeds the "
            f"{budget / 1e6:.0f} MB VMEM budget at every batch tile — "
            f"use impl='xla' (or a smaller hidden/projection size)")
    if fwd_fit is None:
        fwd_fit = any_size
    bwd_name = ("recompute" if bwd_mode is None
                else "scan" if bwd_mode == "scan" else "kernel")
    _record_call(mesh, T, B, E, H, P, x_seq.dtype, w.dtype, n_shards,
                 bwd_name)

    def run(x_seq, w, b, w_proj):
        return _lstm_scan_pallas(x_seq, w, b, w_proj, fwd_fit,
                                 bwd_mode, bool(interpret))

    if mesh is None or batch_axes is None:
        return run(x_seq, w, b, w_proj)
    from jax.sharding import PartitionSpec as P_

    def run_checked(x_seq, w, b, w_proj):
        # Compiled kernels run with the VMA checker on, and a
        # custom_vjp must return cotangents typed like its primals:
        # each device's dW is device-varying, the replicated weights
        # are not. Mark the weights varying on the way in — the
        # transpose of that cast IS the gradient psum over the batch
        # axes (first trace for a TensorCore, PR 22: "the varying
        # manual axes do not match").
        w, b, w_proj = (jax.lax.pcast(a, axes, to="varying")
                        for a in (w, b, w_proj))
        return run(x_seq, w, b, w_proj)

    return jax.shard_map(
        # pallas interpret mode trips the VMA checker (see
        # ops/ring_attention.py — jax's own suggested workaround), and
        # no pcast may be emitted with the checker off
        run if interpret else run_checked, mesh=mesh,
        in_specs=(P_(None, batch_axes, None), P_(), P_(), P_()),
        out_specs=P_(None, batch_axes, None),
        check_vma=not interpret)(x_seq, w, b, w_proj)
