"""Pipeline parallelism: GPipe-style microbatch pipelining over the mesh.

Absent from the reference (SURVEY.md §2.5) and from round-1 scope until
now: layer *stages* are sharded over the mesh's pipeline axis — the
dedicated ``'pipe'`` axis when the mesh was built from a 3-D
``(dp, tp, pp)`` plan (ISSUE 18), else the legacy ``'shard'`` axis
(stage s's parameters live only on ring position s via a stacked
leading axis) — and microbatches flow through the stage ring with one
`ppermute` hop per tick. All devices execute the same SPMD program; a
device is "active" for tick t iff its stage s has a microbatch in
flight (0 <= t - s < M).

Two schedules:

* `pipeline_apply` — GPipe. Differentiable end-to-end: the tick loop is
  a `lax.scan` and activation hops are `ppermute`, both transposable, so
  reverse-mode AD runs the pipeline backwards. Memory: the scan stores
  every tick's residuals, i.e. O(M) in-flight microbatch activations per
  stage.
* `pipeline_value_and_grad` — 1F1B with recompute. The loss is fused
  into the last stage so microbatch m's backward starts the moment it
  clears stage S-1; in-flight activation storage is a ring buffer of
  min(M, 2S-1) stage *inputs* per device (O(S), independent of M), at
  the cost of one extra stage forward per microbatch (rematerialized in
  the backward tick — the Megatron-LM "full recompute" tradeoff).

Cost model (both): wall-clock ticks scale as M + O(S) with bubble
fraction (S-1)/(M+S-1); per-tick comm = one activation microbatch (plus,
for 1F1B, one cotangent microbatch) per ICI hop.

Interleaved (virtual-stage) scheduling — ``virtual_stages=V > 1``: each
device holds V non-adjacent chunks of the layer stack (device s owns
global stages s, S+s, ..., (V-1)S+s), so a tick's work shrinks to 1/V of
a non-interleaved stage and the bubble fraction drops V-fold to
(S-1)/(V·M+S-1). The schedule is the Megatron-LM round-robin order —
each device runs chunk v for S consecutive microbatches, then rotates —
which has the property that EVERY activation dependency (including the
device S-1 -> device 0 chunk-advance wrap) is produced exactly one tick
before its consumption one ppermute hop away, so the SPMD formulation
needs no activation buffering beyond the single in-flight carry. Device
s's entry at tick t is k = t - s, decoded as
    round r = k // (V·S), chunk v = (k % (V·S)) // S,
    microbatch m = r·S + k % S,
and the backward stream (1F1B) mirrors it with per-device offset C - s,
C = 2(S-1) + (V-1)S, chunks reversed. M is rounded up to whole rounds
of S — a ragged final round just runs masked bubble entries (prefer
M % S == 0 to avoid the waste). V=1 reduces to the schedules above.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from parallax_tpu.core.mesh import AXIS_REPL, pipeline_axis
from parallax_tpu.common.lib import parallax_log


def _rounded_microbatches(M: int, S: int, V: int) -> int:
    """Schedule entries per chunk: M, rounded up to whole rounds of S
    when interleaving (ragged rounds become masked bubble entries)."""
    return M if V == 1 else -(-M // S) * S


_ragged_warned = set()


def _warn_ragged(M: int, S: int, V: int) -> None:
    """Warn ONCE per (M, S, V) that an interleaved schedule with a
    ragged final round (M % S != 0) executes padded bubble entries —
    real ticks of pure waste. The cost model prices the same rounded M
    (tune/costmodel.py uses `_rounded_microbatches`), so the predicted
    bubble matches what actually runs."""
    if V == 1 or M % S == 0:
        return
    key = (int(M), int(S), int(V))
    if key in _ragged_warned:
        return
    _ragged_warned.add(key)
    Mr = _rounded_microbatches(M, S, V)
    parallax_log.warning(
        "interleaved pipeline: num_microbatches=%d is not a multiple "
        "of num_stages=%d; the schedule pads to %d entries per chunk "
        "(%d masked bubble entries of pure waste at V=%d). Prefer "
        "M %% S == 0.", M, S, Mr, Mr - M, V)


def _decode_entry(k, S: int, V: int, M: int, reverse: bool = False):
    """(active, chunk, microbatch) for schedule entry ``k`` (traced).

    Entries follow the round-robin chunk order (S consecutive
    microbatches per chunk, then rotate); ``reverse=True`` mirrors the
    chunk order for the 1F1B backward stream (last chunk first)."""
    Mr = _rounded_microbatches(M, S, V)
    n_entries = V * Mr
    kc = jnp.clip(k, 0, n_entries - 1)
    if V == 1:
        v = jnp.zeros((), kc.dtype)
        m = kc
    else:
        v = (kc % (V * S)) // S
        if reverse:
            v = (V - 1) - v
        m = (kc // (V * S)) * S + kc % S
    active = (k >= 0) & (k < n_entries) & (m < M)
    return active, v, jnp.clip(m, 0, M - 1)


def _to_device_major(stage_params, S: int, V: int):
    """View [S*V, ...] device-major-stacked leaves as [S, V, ...].

    Device-major order means ``p[s*V + v]`` holds global stage
    ``v*S + s`` — each device's V chunks are CONTIGUOUS rows, so with
    the leading axis sharded over 'shard' this reshape moves no data
    across devices (an interleaved gather here would collective-permute
    the parameters every step)."""
    def tx(p):
        if p.shape[0] != S * V:
            raise ValueError(
                f"stage param leaf has leading dim {p.shape[0]}; "
                f"expected num_stages*virtual_stages = {S}*{V}")
        return p.reshape((S, V) + p.shape[1:])
    return jax.tree.map(tx, stage_params)


def stage_order_permutation(S: int, V: int):
    """Global-stage index held at device-major slot q = s*V + v.

    Models storing layers in natural order apply this permutation ONCE
    at init (and its inverse when exporting) so the pipeline's sharded
    stage axis never needs an in-graph cross-device gather."""
    return [(q % V) * S + q // V for q in range(S * V)]


def pipeline_apply(stage_fn: Callable,
                   stage_params,
                   x: jax.Array,
                   mesh: Mesh,
                   num_microbatches: int,
                   virtual_stages: int = 1) -> jax.Array:
    """Run ``x`` through S*virtual_stages pipelined stages.

    * ``stage_fn(params_one_stage, activation) -> activation`` — one
      stage's computation; activation shapes must match across stages.
    * ``stage_params`` — pytree whose leaves have a leading stage axis
      [S*V, ...] in DEVICE-MAJOR order (``p[s*V + v]`` = global stage
      ``v*S + s``; see `stage_order_permutation`), sharded
      P('shard', ...) so each device owns its V contiguous chunk rows.
      With V=1 this is the plain [S, ...] stage stack.
    * ``x`` — [B, ...] batch (replicated over 'shard'; 'repl' may carry
      data parallelism on dim 0). B must divide into
      ``num_microbatches``.

    Returns [B, ...] outputs (replicated over 'shard').
    """
    stage_axis = pipeline_axis(mesh)
    S = mesh.shape[stage_axis]
    V = int(virtual_stages)
    M = num_microbatches
    B = x.shape[0]
    repl = mesh.shape[AXIS_REPL]
    if (B // max(repl, 1)) % M or B % max(repl, 1):
        raise ValueError(
            f"per-replica batch {B}/{repl} must be divisible by "
            f"num_microbatches={M}")
    _warn_ragged(M, S, V)
    stage_params = _to_device_major(stage_params, S, V)
    n_entries = V * _rounded_microbatches(M, S, V)

    def local(params_local, x_local):
        # params_local leaves: [1, V, ...] (this device's chunks);
        # x_local: [B/repl, ...] — full batch slice for this repl row.
        s = jax.lax.axis_index(stage_axis)
        mb = x_local.shape[0] // M
        xm = x_local.reshape((M, mb) + x_local.shape[1:])
        my_params = jax.tree.map(lambda p: p[0], params_local)

        def run_chunk(v, xx):
            pv = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, v, 0, keepdims=False), my_params)
            return stage_fn(pv, xx)

        act0 = jnp.zeros_like(xm[0])
        outs0 = jax.lax.pcast(
            jnp.zeros_like(xm), (stage_axis,), to="varying")
        act0 = jax.lax.pcast(act0, (stage_axis,), to="varying")

        def tick(carry, t):
            act, outs = carry
            # entry k = t - s: every dependency — device s-1's same
            # entry, or (chunk-advance wrap) device S-1's entry k-S —
            # was produced exactly one tick ago, one ppermute hop away,
            # so the single carried activation suffices for any V.
            active, v, m = _decode_entry(t - s, S, V, M)
            # the first global stage pulls fresh input; all others use
            # the received activation
            inp = jnp.where((s == 0) & (v == 0),
                            jax.lax.dynamic_index_in_dim(
                                xm, m, axis=0, keepdims=False), act)
            out = run_chunk(v, inp)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # the last global stage records its finished microbatch
            record = (s == S - 1) & (v == V - 1) & active
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(record,
                                out,
                                jax.lax.dynamic_index_in_dim(
                                    outs, m, 0, keepdims=False)),
                m, axis=0)
            # hop to the next stage
            perm = [(i, (i + 1) % S) for i in range(S)]
            act_next = jax.lax.ppermute(out, stage_axis, perm)
            return (act_next, outs), None

        (_, outs), _ = jax.lax.scan(tick, (act0, outs0),
                                    jnp.arange(n_entries + S - 1))
        # only the last stage holds real outputs; broadcast them
        outs = jnp.where(s == S - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, stage_axis)
        return outs.reshape(x_local.shape)

    spec_params = jax.tree.map(
        lambda p: P(*((stage_axis,) + (None,) * (p.ndim - 1))),
        stage_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_params, P(AXIS_REPL)),
        out_specs=P(AXIS_REPL),
    )(stage_params, x)


def inflight_buffer_size(num_stages: int, num_microbatches: int,
                         virtual_stages: int = 1) -> int:
    """Per-chunk in-flight activation slots under the 1F1B schedule.

    V=1: stage s forwards microbatch m at tick m+s and backwards it at
    tick m + 2(S-1) - s, so at most 2(S-1-s)+1 microbatch inputs are
    live at once — bounded by 2S-1 regardless of M (GPipe stores all M).

    V>1: a chunk's forward-to-backward gap is G = C - 2s + (V-1-2v)S
    ticks (C = 2(S-1) + (V-1)S), at most 2VS-2; forwards of one chunk
    occupy S of every VS ticks, so live inputs per chunk never exceed
    ceil(G/VS)·S + S <= 3S — slots are whole rounds of S so the ring
    index ((m//S) mod rounds)·S + m%S never collides while live."""
    S, M, V = num_stages, num_microbatches, virtual_stages
    if V == 1:
        return min(M, 2 * S - 1)
    rounds = min(-(-M // S), 3)
    return rounds * S


def pipeline_value_and_grad(stage_fn: Callable,
                            loss_fn: Callable,
                            stage_params,
                            x: jax.Array,
                            y,
                            mesh: Mesh,
                            num_microbatches: int,
                            head_params=None,
                            virtual_stages: int = 1):
    """Fused forward+backward 1F1B pipeline training step.

    * ``stage_fn(params_one_stage, activation) -> activation`` — as in
      `pipeline_apply`; activation shapes match across stages.
    * ``loss_fn(head_params, out_mb, y_mb) -> scalar`` — mean-style loss
      on one microbatch of last-stage outputs; ``head_params`` holds any
      loss-side weights (e.g. the output projection), replicated across
      the mesh. The returned loss is the mean over microbatches (== the
      full-batch mean for equal microbatches).
    * ``stage_params`` — stacked [S*V, ...] leaves in device-major order
      (see `pipeline_apply`), sharded P('shard', ...).
    * ``x`` [B, ...], ``y`` pytree of [B, ...] — batch, split over
      'repl' (data parallel) then into M microbatches.

    Returns ``(loss, (g_stage, g_head, g_x))``: gradients for the
    stacked stage params, the head params, and the pipeline input ``x``
    (the cotangent to chain into whatever produced ``x`` — e.g. an
    embedding lookup — via its own vjp). All are gradients of the
    returned (global-mean) loss; math matches sequential execution.

    Backward rematerializes each stage forward from the buffered stage
    input, so peak activation memory is O(V·min(M, 3S)) microbatches
    per device instead of GPipe's O(M).

    Schedule: the forward stream runs entry kf = t - s and the backward
    stream entry kb = t - (C - s), C = 2(S-1) + (V-1)S, each decoded by
    the round-robin order (`_decode_entry`; backward with chunks
    reversed). The offsets make every activation and cotangent
    dependency land exactly one tick and one `ppermute` hop away (fwd
    hops s -> s+1, cotangents s -> s-1), and the last global stage
    computes its loss cotangent in the same tick its forward completes —
    the defining 1F1B property, now with a V-fold smaller bubble.
    """
    stage_axis = pipeline_axis(mesh)
    S = mesh.shape[stage_axis]
    # axes that replicate the pipeline's SPMD program: 'repl' carries
    # data parallelism, any other non-stage axis (e.g. 'shard' on a
    # 3-axis mesh) runs identical copies of the ring
    data_axes = tuple(a for a in mesh.axis_names if a != stage_axis)
    V = int(virtual_stages)
    M = num_microbatches
    B = x.shape[0]
    repl = mesh.shape[AXIS_REPL]
    if (B // max(repl, 1)) % M or B % max(repl, 1):
        raise ValueError(
            f"per-replica batch {B}/{repl} must be divisible by "
            f"num_microbatches={M}")
    _warn_ragged(M, S, V)
    Bbuf = inflight_buffer_size(S, M, V)
    stage_params = _to_device_major(stage_params, S, V)
    n_entries = V * _rounded_microbatches(M, S, V)
    C = 2 * (S - 1) + (V - 1) * S
    if head_params is None:
        head_params = {}

    def _slot(m):
        """Buffer slot for microbatch m (per chunk): whole rounds of S
        ring-indexed so slots never collide while in flight."""
        if V == 1:
            return jnp.mod(m, Bbuf)
        return jnp.mod(m // S, Bbuf // S) * S + jnp.mod(m, S)

    def local(params_local, head_local, x_local, y_local):
        s = jax.lax.axis_index(stage_axis)
        mb = x_local.shape[0] // M
        xm = x_local.reshape((M, mb) + x_local.shape[1:])
        ym = jax.tree.map(
            lambda a: a.reshape((M, mb) + a.shape[1:]), y_local)
        my_params = jax.tree.map(lambda p: p[0], params_local)
        # Declare params varying over the axes they are invariant on:
        # otherwise every tick's pullback gets an automatic psum over
        # those axes inserted by the transpose — a per-tick collective,
        # and a double-count with the one reduction we do at the end.
        my_params = jax.tree.map(
            lambda p: jax.lax.pcast(p, data_axes, to="varying"),
            my_params)

        def vary_all(a):
            for ax in mesh.axis_names:
                a = jax.lax.pcast(a, (ax,), to="varying")
            return a

        head_v = jax.tree.map(vary_all, head_local)

        def run_chunk(chunk_tree, v, xx):
            pv = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, v, 0, keepdims=False), chunk_tree)
            return stage_fn(pv, xx)

        act0 = vary_all(jnp.zeros(xm.shape[1:], xm.dtype))
        ct0 = vary_all(jnp.zeros(xm.shape[1:], xm.dtype))
        buf0 = vary_all(jnp.zeros((V, Bbuf) + xm.shape[1:], xm.dtype))
        gacc0 = jax.tree.map(
            lambda p: vary_all(jnp.zeros(p.shape, p.dtype)), my_params)
        hacc0 = jax.tree.map(
            lambda p: vary_all(jnp.zeros(p.shape, p.dtype)), head_v)
        xg0 = vary_all(jnp.zeros(xm.shape, xm.dtype))
        lacc0 = vary_all(jnp.zeros((), jnp.float32))

        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        bwd_perm = [(i, (i - 1) % S) for i in range(S)]

        def tick(carry, t):
            act_in, ct_in, buf, gacc, hacc, xg, lacc = carry
            # ---- forward stream: entry kf = t - s ----
            fwd_active, v_f, mf = _decode_entry(t - s, S, V, M)
            inp = jnp.where((s == 0) & (v_f == 0),
                            jax.lax.dynamic_index_in_dim(
                                xm, mf, axis=0, keepdims=False), act_in)
            slot_f = _slot(mf)
            buf = buf.at[v_f, slot_f].set(
                jnp.where(fwd_active, inp, buf[v_f, slot_f]))
            out = run_chunk(my_params, v_f, inp)
            # ---- backward stream: entry kb = t - (C - s),
            #      rematerialized from the buffered chunk input ----
            bwd_active, v_b, mb_i = _decode_entry(
                t - (C - s), S, V, M, reverse=True)
            inp_b = buf[v_b, _slot(mb_i)]
            out_b, pull = jax.vjp(
                lambda pt, xx: run_chunk(pt, v_b, xx), my_params, inp_b)
            y_mb = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, mb_i, 0, keepdims=False), ym)
            loss_m, (g_head, ct_loss) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(head_v, out_b, y_mb)
            is_last = (s == S - 1) & (v_b == V - 1)
            last_b = bwd_active & is_last
            hacc = jax.tree.map(
                lambda h, g: h + jnp.where(last_b, g / M,
                                           jnp.zeros_like(g)),
                hacc, g_head)
            ct = jnp.where(is_last,
                           ct_loss.astype(ct_in.dtype) / M, ct_in)
            dparams, dinp = pull(ct)
            dparams = jax.tree.map(
                lambda g: jnp.where(bwd_active, g, jnp.zeros_like(g)),
                dparams)
            gacc = jax.tree.map(jnp.add, gacc, dparams)
            lacc = lacc + jnp.where(last_b, loss_m / M, 0.0)
            # the first global stage's input cotangent is d loss / d x[mb]
            rec_x = bwd_active & (s == 0) & (v_b == 0)
            old_xg = jax.lax.dynamic_index_in_dim(xg, mb_i, 0,
                                                  keepdims=False)
            xg = jax.lax.dynamic_update_index_in_dim(
                xg, jnp.where(rec_x, dinp.astype(xg.dtype), old_xg),
                mb_i, axis=0)
            # ---- hops ----
            out = jnp.where(fwd_active, out, jnp.zeros_like(out))
            act_next = jax.lax.ppermute(out, stage_axis, fwd_perm)
            dinp = jnp.where(bwd_active, dinp, jnp.zeros_like(dinp))
            ct_next = jax.lax.ppermute(dinp, stage_axis, bwd_perm)
            return (act_next, ct_next, buf, gacc, hacc, xg, lacc), None

        n_ticks = n_entries + C
        (_, _, _, gacc, hacc, xg, lacc), _ = jax.lax.scan(
            tick, (act0, ct0, buf0, gacc0, hacc0, xg0, lacc0),
            jnp.arange(n_ticks))

        def mean_data(a):
            # average over the data axes: 'repl' rows each saw a real
            # batch slice; any other non-stage axis ran an identical
            # copy, so its pmean is numerically a no-op that restores
            # axis-invariance for the out_specs
            for ax in data_axes:
                a = jax.lax.pmean(a, ax)
            return a

        # loss lives on the last stage; data-parallel rows average
        loss = mean_data(jax.lax.psum(lacc, stage_axis))
        g_stage = jax.tree.map(lambda g: mean_data(g)[None], gacc)
        # head grads live on the last stage only (masked elsewhere)
        g_head = jax.tree.map(
            lambda g: mean_data(jax.lax.psum(g, stage_axis)), hacc)
        # x cotangent lives on stage 0; scale to the global-mean loss
        # (each row accumulated d(row-mean)/dx; loss averages the rows)
        xg = jax.lax.psum(xg, stage_axis) / repl
        for ax in data_axes:
            if ax != AXIS_REPL:
                xg = jax.lax.pmean(xg, ax)
        g_x = xg.reshape(x_local.shape)
        return loss, g_stage, g_head, g_x

    spec_params = jax.tree.map(
        lambda p: P(*((stage_axis,) + (None,) * (p.ndim - 1))),
        stage_params)
    head_specs = jax.tree.map(lambda _: P(), head_params)
    y_specs = jax.tree.map(lambda _: P(AXIS_REPL), y)
    loss, g_stage, g_head, g_x = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_params, head_specs, P(AXIS_REPL), y_specs),
        out_specs=(P(), spec_params, head_specs, P(AXIS_REPL)),
    )(stage_params, head_params, x, y)
    # [S, V, ...] -> the caller's device-major [S*V, ...] stacking
    # (contiguous merge along the sharded axis: no data movement)
    g_stage = jax.tree.map(
        lambda g: g.reshape((S * V,) + g.shape[2:]), g_stage)
    return loss, (g_stage, g_head, g_x)
