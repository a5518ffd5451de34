#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, ``python chip_smoke.py``, every device ``jax.devices()``
returns. It drives the two main paths through the entry points a user
calls, at the full width of the models (weights random from a seed):

1. **train** — LM1B at the reference widths (793,470-word vocabulary,
   emb 512 / hidden 2048 / proj 512, 8,192 samples, bf16 compute)
   through ``parallax.parallel_run`` -> ``sess.warmup`` ->
   ``sess.run_iter``: hybrid plan, slices sparse grads, the Pallas LSTM
   forward and backward, and on one chip the in-place row kernel of
   the two wide tables' update (``ops/sparse_optim``).
2. **serve** — NMT at its default widths (vocab 32,000, dim 512, 8
   heads, 6 layers, max_len 128) behind ``NMTDecodeProgram`` (paged KV,
   chunked prefill) -> one ``ServeSession`` per device, each on its own
   one-device mesh, behind one ``ServeFleet`` when there are several.
3. **kernels** — every Pallas family compiled by Mosaic
   (``interpret=False``) and compared with its XLA reference.

It exits non-zero, before compiling anything, when the platform is not
``tpu``. ``--cpu-rehearsal`` runs the same code at tiny sizes off the
chip (Pallas in interpret mode) and stamps itself as a rehearsal; it is
never what the default invocation falls into. Every phase runs even
after an earlier one failed (a chip run is too dear to stop at the
first refusal), and any failure makes the exit code 1.

The last two lines of stdout are JSON objects. First the summary:
device stamp, per-phase pass/fail, the executor each kernel site used,
cold compile seconds per phase (set-up information), peak
``bytes_in_use``; it prints no rate, utilization or speed-up, and ends
with ``"claim": null``. Then, as the last line, the verdict the driver
reads, with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

# bf16 budget: one bf16 ulp is 2^-8 = 3.9e-3 of the value; both sides of
# every comparison below round to bf16 a handful of times (inputs,
# stored activations, outputs) and re-associate T- or page-long fp32
# reductions, so five ulp of the reference's peak is the bound — the
# same 2e-2 tests/test_pallas_lstm.py pins for the LSTM backward.
BF16_TOL = 2e-2
# The training step against the lstm_impl='xla' session stacks two such
# comparisons: that scan carries (c, h) in bf16 where the kernel (and
# the kernels phase's reference) carries fp32, and its VJP accumulates
# dW in bf16 across the T steps where the kernel accumulates fp32.
UPDATE_TOL = 2 * BF16_TOL


def _sizes(rehearsal: bool, n: int) -> dict:
    """Full widths on the chip; a CPU rehearsal keeps every code path
    and shrinks every dimension."""
    if not rehearsal:
        return dict(
            lm1b=dict(), B=128 * n, T=20, steps=12,
            nmt=dict(), max_src_len=64, max_len=128, page_size=16,
            slots=8, requests=8 * max(n, 4), max_new=(8, 32),
            flash=[(2, 2048, 8, 64), (8, 128, 8, 64)],
            lstm=[(20, 128, 512, 2048, 512), (4, 128, 512, 2048, 512)],
            paged=[dict(S=64, D=512, num_heads=8, page_size=128, P=16,
                        pool_pages=1024),
                   dict(S=8, D=512, num_heads=8, page_size=16, P=8,
                        pool_pages=64)],
            table=dict(V=793470, D=512, B=128, T=20, samples=8192),
            ring=(2, 2048, 8, 64))
    return dict(
        lm1b=dict(vocab_size=1000, emb_dim=32, hidden_dim=64,
                  proj_dim=32, num_samples=64),
        B=8 * n, T=4, steps=12,
        nmt=dict(vocab_size=512, model_dim=32, num_heads=2, mlp_dim=64,
                 num_layers=2, max_len=32),
        max_src_len=8, max_len=32, page_size=16, slots=4,
        requests=8, max_new=(3, 6),
        flash=[(1, 32, 2, 16)], lstm=[(3, 8, 16, 32, 16)],
        paged=[dict(S=2, D=32, num_heads=2, page_size=16, P=2,
                    pool_pages=4)],
        table=dict(V=1006, D=128, B=4, T=4, samples=32),
        ring=(1, 16 * n, 2, 16))


def _peak_bytes():
    """Largest ``peak_bytes_in_use`` over the devices, or None where
    the backend reports nothing (XLA:CPU)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _rel_peak_err(got, want) -> float:
    """max |got - want| over the reference's peak magnitude, in fp32."""
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    if g.shape != w.shape:
        raise AssertionError(f"shape {g.shape} != reference {w.shape}")
    if not np.isfinite(g).all():
        raise AssertionError("non-finite values")
    return float(np.abs(g - w).max() / max(float(np.abs(w).max()), 1e-30))


def _mosaic_calls(fn, *args) -> int:
    """Mosaic custom calls in ``fn``'s lowering: an interpreted Pallas
    call lowers to plain HLO and counts 0."""
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


# -- phase 1: train ---------------------------------------------------------


def phase_train(sz: dict, rehearsal: bool) -> dict:
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.models import lm1b
    from parallax_tpu.ops import pallas_lstm, sparse_optim

    n = jax.device_count()
    B, T = sz["B"], sz["T"]

    def cfg_for(impl):
        return lm1b.LM1BConfig(num_partitions=n, sparse_grad_mode="slices",
                               lstm_impl=impl, **sz["lm1b"])

    def session(impl):
        sess, *_ = parallax.parallel_run(
            lm1b.build_model(cfg_for(impl)),
            parallax_config=parallax.Config(
                run_option="HYBRID", sparse_grad_mode="slices",
                search_partitions=False, shape_buckets=[B]),
            num_partitions=n)
        return sess

    cfg = cfg_for("pallas")
    rng = np.random.default_rng(0)
    batches = [lm1b.make_batch(rng, B, T, cfg.vocab_size)
               for _ in range(4)]

    def two_steps(sess):
        """Losses of the first two steps and how far they moved the
        LSTM weights (host copies)."""
        before = jax.device_get(sess.state.params["lstm"])
        losses = [float(sess.run("loss", feed_dict=batches[i]))
                  for i in range(2)]
        after = jax.device_get(sess.state.params["lstm"])
        return losses, jax.tree.map(np.subtract, after, before)

    # the reference first (and closed before the kernel session: two
    # sets of 793k-row tables and accumulators do not share one chip)
    ref = session("xla")
    try:
        ref.warmup(feed_dict=batches[0])
        loss_xla, moved_xla = two_steps(ref)
    finally:
        ref.close()
        del ref
        gc.collect()

    pallas_lstm.reset_trace_records()
    sparse_optim.reset_trace_records()
    sess = session("pallas")
    try:
        t0 = time.perf_counter()
        sess.warmup(feed_dict=batches[0])
        compile_s = time.perf_counter() - t0
        losses, moved = two_steps(sess)
        losses += [float(x) for x in list(sess.run_iter(
            (batches[i % 4] for i in range(2, sz["steps"])),
            fetches="loss"))]
        jax.block_until_ready(sess.state.params)

        out = {"compile_seconds": round(compile_s, 1),
               "losses": [round(x, 4) for x in losses],
               "losses_xla": [round(x, 4) for x in loss_xla]}
        if not np.isfinite(losses).all():
            raise AssertionError(f"non-finite loss: {losses}")
        # the feed cycles four batches: compare one whole cycle at the
        # end with the first one, batch for batch
        first, last = np.mean(losses[:4]), np.mean(losses[-4:])
        if not last < first:
            raise AssertionError(
                f"loss did not fall over {len(losses)} steps: first "
                f"cycle {first:.4f}, last cycle {last:.4f}")
        recompiles = int(sess.metrics_snapshot().get(
            "engine.recompiles", 0))
        out["engine.recompiles"] = recompiles
        if recompiles:
            raise AssertionError(f"engine.recompiles == {recompiles}")

        # the three tables row-sharded padded_vocab / n over all n
        # devices
        rows = cfg.padded_vocab // n
        tables = {}
        for name in ("emb", "softmax_w", "softmax_b"):
            arr = sess.state.params[name]
            shard_rows = arr.sharding.shard_shape(arr.shape)[0]
            on = {s.device for s in arr.addressable_shards}
            tables[name] = {"shard_rows": shard_rows,
                            "devices": len(on)}
            if shard_rows != rows or len(on) != n:
                raise AssertionError(
                    f"{name}: {shard_rows} rows/shard on {len(on)} "
                    f"device(s), want {rows} on {n}")
        out["tables"] = tables

        # which executor served the recurrence
        recs = pallas_lstm.trace_records(sess.engine.mesh)
        out["lstm_bwd"] = sorted({r["bwd"] for r in recs})
        want_bwd = ["scan"] if rehearsal else ["kernel"]
        if out["lstm_bwd"] != want_bwd:
            raise AssertionError(
                f"lstm backward executors {out['lstm_bwd']}, want "
                f"{want_bwd} (records: {recs})")
        step_text = next(iter(
            sess.engine._executables.values())).as_text()
        out["step_mosaic_calls"] = step_text.count("tpu_custom_call")
        if not rehearsal and out["step_mosaic_calls"] < 2:
            raise AssertionError(
                "compiled training step holds "
                f"{out['step_mosaic_calls']} Mosaic custom call(s); "
                "the LSTM forward and backward kernels are two")
        out["lstm_fwd"] = "interpret" if rehearsal else "kernel"

        # which executor updated each table's rows: the in-place kernel
        # where a lane-aligned f32 table sits whole on the one chip
        out["table_update"] = {r["table"]: r["executor"]
                               for r in sparse_optim.trace_records()}
        wide = "kernel" if n == 1 and not rehearsal else "xla"
        want_rows = {"emb": wide, "softmax_w": wide, "softmax_b": "xla"}
        if out["table_update"] != want_rows:
            raise AssertionError(
                f"table update executors {out['table_update']}, want "
                f"{want_rows}")
        table = f"f32[{cfg.padded_vocab // n},{cfg.emb_dim}]"
        out["step_table_copies"] = sum(
            1 for line in step_text.splitlines()
            if " copy(" in line and f"= {table}" in line)
        if wide == "kernel" and (out["step_table_copies"]
                                 or out["step_mosaic_calls"] < 4):
            raise AssertionError(
                f"compiled step copies a {table} table "
                f"{out['step_table_copies']} time(s) and holds "
                f"{out['step_mosaic_calls']} Mosaic call(s); the row "
                "kernels update two tables in place next to the LSTM's "
                "two")

        # same seed, same params, same batches, same dropout and sample
        # draws; only the recurrence's executor differs. The losses
        # barely see it (at initialization the sampled-softmax loss is
        # its sampling correction); the two steps' LSTM weight UPDATES
        # do — they are the kernel backward's gradients after the
        # mesh-wide reduction, the clip and adagrad, so a wrong psum
        # over the batch axes would be off by a factor of n here.
        errs = [abs(a - b) / abs(b) for a, b in zip(losses, loss_xla)]
        out["loss_vs_xla_rel"] = [float(f"{e:.3g}") for e in errs]
        out["lstm_update_vs_xla"] = {
            k: float(f"{_rel_peak_err(moved[k], moved_xla[k]):.3g}")
            for k in sorted(moved)}
        if max(errs) > BF16_TOL:
            raise AssertionError(
                f"losses {losses[:2]} vs lstm_impl='xla' {loss_xla}: "
                f"rel {errs} > {BF16_TOL}")
        worst = max(out["lstm_update_vs_xla"].values())
        if worst > UPDATE_TOL:
            raise AssertionError(
                f"LSTM weight updates after two steps vs "
                f"lstm_impl='xla': {out['lstm_update_vs_xla']} of the "
                f"peak update > {UPDATE_TOL}")
        return out
    finally:
        sess.close()
        del sess
        gc.collect()


# -- phase 2: serve ---------------------------------------------------------


def phase_serve(sz: dict, rehearsal: bool) -> dict:
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.core import mesh as mesh_lib
    from parallax_tpu.models import nmt
    from parallax_tpu.ops import pallas_paged_attention as ppa
    from parallax_tpu.serve import (FleetConfig, NMTDecodeProgram,
                                    ServeFleet, ServeSession)

    devs = jax.devices()
    n = len(devs)
    cfg = nmt.NMTConfig(num_partitions=1, **sz["nmt"])
    # host copy: each replica places its own, nothing stays on device 0
    params = jax.device_get(
        nmt.build_model(cfg).init_fn(jax.random.PRNGKey(0)))
    slots, ps = sz["slots"], sz["page_size"]
    prog = NMTDecodeProgram(
        cfg, max_src_len=sz["max_src_len"], max_len=sz["max_len"],
        page_size=ps, pool_pages=slots * (sz["max_len"] // ps),
        prefill_chunk_layers=max(1, cfg.num_layers // 3))
    pcfg = parallax.Config(serve_config=parallax.ServeConfig(
        max_batch=slots, max_queue=4096))
    ppa.reset_trace_records()
    replicas = []

    def make_replica(rid, **serve_kw):
        sess = ServeSession(
            program=prog, params=params, config=pcfg,
            mesh=mesh_lib.build_mesh(devices=[devs[int(rid) % n]],
                                     num_partitions=1),
            **serve_kw)
        replicas.append(sess)
        return sess

    t0 = time.perf_counter()
    if n > 1:
        front = ServeFleet(make_replica, config=FleetConfig(
            num_replicas=n, max_replicas=max(n, 4)))
    else:
        front = make_replica(0)
    compile_s = time.perf_counter() - t0
    out = {"compile_seconds": round(compile_s, 1), "replicas": n}

    rng = np.random.default_rng(1)
    lo, hi = sz["max_new"]
    work = []
    for i in range(sz["requests"]):
        src = rng.integers(3, cfg.vocab_size,
                           size=int(rng.integers(2, sz["max_src_len"] + 1)),
                           dtype=np.int32)
        work.append(({"src": src}, int(rng.integers(lo, hi + 1))))
    try:
        pending = [front.submit(feed, max_new_tokens=cap)
                   for feed, cap in work]
        results = [np.asarray(r.result(timeout=600)) for r in pending]

        # placement, read while the sessions still hold their state
        placed = []
        for i, sess in enumerate(replicas):
            on = set()
            for leaf in jax.tree_util.tree_leaves(
                    (sess._params, sess._scheduler._state)):
                on |= set(leaf.devices())
            placed.append(sorted(d.id for d in on))
            if on != {devs[i]}:
                raise AssertionError(
                    f"replica {i}: params/page pool on device(s) "
                    f"{placed[-1]}, want [{devs[i].id}]")
        out["replica_devices"] = placed
    finally:
        front.close()

    for (feed, cap), toks in zip(work, results):
        if not (1 <= toks.shape[0] <= cap and toks.ndim == 1
                and ((0 <= toks) & (toks < cfg.vocab_size)).all()):
            raise AssertionError(
                f"bad tokens for cap {cap}: {toks.tolist()}")
    out["completed"] = len(results)
    out["tokens"] = int(sum(len(t) for t in results))

    stats = [s.stats() for s in replicas]
    out["serve.recompiles"] = sum(
        int(s.get("serve.recompiles", 0)) for s in stats)
    out["serve.kv_pages_in_use"] = sum(
        int(s.get("serve.kv_pages_in_use", 0)) for s in stats)
    out["completed_per_replica"] = [
        int(s.get("serve.completed", 0)) for s in stats]
    if out["serve.recompiles"]:
        raise AssertionError(
            f"serve.recompiles == {out['serve.recompiles']}")
    if out["serve.kv_pages_in_use"]:
        raise AssertionError(
            f"{out['serve.kv_pages_in_use']} page(s) leaked after close")
    if n > 1 and not all(out["completed_per_replica"]):
        raise AssertionError(
            f"idle replica: {out['completed_per_replica']}")

    out["paged_impl"] = sorted(
        {r["impl"] for r in ppa.trace_records()})
    # (the einsum executor gathers in models/nmt.py and records no call)
    want_impl = [] if rehearsal else ["kernel"]
    if out["paged_impl"] != want_impl:
        raise AssertionError(
            f"paged attention executors {out['paged_impl']}, want "
            f"{want_impl}")

    out["oracle"] = _serve_oracle(prog, cfg, jax.device_put(
        params, devs[0]), list(zip(work, results))[:3])
    return out


def _serve_oracle(prog, cfg, params, served) -> dict:
    """Served tokens against reference logits: each request replayed
    alone — one slot, fresh pool, whole (unchunked) prefill, the einsum
    gather executor — with the SERVED tokens teacher-forced, so one
    flip cannot compound. Every served token must be the reference's
    argmax or tie with it inside the bf16 budget: exact token identity
    is an fp32 contract (tools/loadgen.py pins fp32 for it); under
    bf16 the 8-slot kernel step and the one-slot einsum step round
    differently and near-tied logits may swap."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.models import nmt

    @jax.jit
    def step_logits(state, tok, t, pages):
        logits, kc, vc = nmt._decode_tokens_cached(
            cfg, params, tok[:, None], t, state["kc"], state["vc"],
            state["ck"], state["cv"], state["src_valid"], pages=pages,
            page_size=prog.page_size, attn_impl="einsum")
        return logits[:, 0], dict(state, kc=kc, vc=vc)

    positions = equal = 0
    worst_gap = 0.0
    for (feed, cap), toks in served:
        rs = prog.prefill(params, prog.prepare_feed(feed))
        state = prog.insert(prog.init_state(params, 1), np.int32(0), rs)
        row = np.full((1, prog.pages_per_seq), prog.pool_pages, np.int32)
        need = prog.pages_needed(cap)
        row[0, :need] = np.arange(need)
        tok = prog.bos_id
        for t, served_tok in enumerate(toks.tolist()):
            logits, state = step_logits(
                state, jnp.full((1,), tok, jnp.int32),
                jnp.full((1,), t, jnp.int32), jnp.asarray(row))
            logits = np.asarray(logits[0, :cfg.vocab_size], np.float32)
            gap = float(logits.max() - logits[served_tok]) \
                / float(np.abs(logits).max())
            worst_gap = max(worst_gap, gap)
            positions += 1
            equal += int(served_tok == int(logits.argmax()))
            if gap > BF16_TOL:
                raise AssertionError(
                    f"served token {served_tok} at position {t} sits "
                    f"{gap:.3g} of the logit scale under the "
                    f"reference argmax {int(logits.argmax())} "
                    f"(> {BF16_TOL})")
            tok = served_tok
    return {"requests": len(served), "positions": positions,
            "argmax_equal": equal,
            "worst_gap_of_logit_scale": float(f"{worst_gap:.3g}")}


# -- phase 3: kernels -------------------------------------------------------


def _check(rows, name, shape, err, tol, executor):
    rows.append({"kernel": name, "shape": list(shape),
                 "err": float(f"{err:.3g}"), "tol": tol,
                 "executor": executor, "ok": bool(err <= tol)})


def _flash_checks(rows, shape, executor):
    """Forward, dq and dk/dv, the (out, lse) variant with an lse
    cotangent, and the kv_mask variant, against the einsum references
    kept beside the kernels."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.ops import pallas_attention as pa

    B, T, H, D = shape
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q, k, v, g = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
                  for kk in ks[:4])
    mask = (jax.random.uniform(ks[4], (B, T)) < 0.8).astype(jnp.int32)
    mask = mask.at[:, 0].set(1)
    scale = 1.0 / np.sqrt(D)
    tr = lambda x: x.transpose(0, 2, 1, 3)                  # noqa: E731

    def kern(q, k, v, mask=None, causal=True):
        return pa.flash_attention(q, k, v, causal=causal, kv_mask=mask)

    def ref(q, k, v, mask=None, causal=True):
        return tr(pa._xla_attention(tr(q), tr(k), tr(v), mask, causal,
                                    scale))

    def kern_lse(q, k, v):
        return pa.flash_attention_lse(q, k, v, causal=True)

    def ref_lse(q, k, v):
        out, lse = pa._xla_attention_lse(tr(q), tr(k), tr(v), None, True,
                                         scale)
        return tr(out), lse

    gf = g.astype(jnp.float32)

    def vjp_of(fn, *extra, **kw):
        return jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(
                fn(q, k, v, *extra, **kw).astype(jnp.float32) * gf),
            argnums=(0, 1, 2)))(q, k, v)

    if executor == "kernel":
        calls = _mosaic_calls(kern, q, k, v)
        if calls != 1:
            raise AssertionError(
                f"flash forward lowered to {calls} Mosaic call(s)")
    _check(rows, "flash_fwd", shape,
           _rel_peak_err(jax.jit(kern)(q, k, v), jax.jit(ref)(q, k, v)),
           BF16_TOL, executor)
    for name, a, b in zip(("flash_dq", "flash_dk", "flash_dv"),
                          vjp_of(kern), vjp_of(ref)):
        _check(rows, name, shape, _rel_peak_err(a, b), BF16_TOL,
               executor)

    (o_k, l_k), (o_r, l_r) = jax.jit(kern_lse)(q, k, v), \
        jax.jit(ref_lse)(q, k, v)
    _check(rows, "flash_lse_out", shape, _rel_peak_err(o_k, o_r),
           BF16_TOL, executor)
    _check(rows, "flash_lse", shape, _rel_peak_err(l_k, l_r), BF16_TOL,
           executor)

    def lse_loss(fn):
        def loss(q, k, v):
            out, lse = fn(q, k, v)
            return (jnp.sum(out.astype(jnp.float32) * gf)
                    + jnp.sum(lse * gf[..., 0].transpose(0, 2, 1)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for name, a, b in zip(("flash_lse_dq", "flash_lse_dk",
                           "flash_lse_dv"),
                          lse_loss(kern_lse), lse_loss(ref_lse)):
        _check(rows, name, shape, _rel_peak_err(a, b), BF16_TOL,
               executor)

    _check(rows, "flash_mask_fwd", shape,
           _rel_peak_err(jax.jit(kern, static_argnames="causal")(
               q, k, v, mask, causal=False),
               jax.jit(ref, static_argnames="causal")(
                   q, k, v, mask, causal=False)),
           BF16_TOL, executor)
    for name, a, b in zip(("flash_mask_dq", "flash_mask_dk",
                           "flash_mask_dv"),
                          vjp_of(kern, mask, causal=False),
                          vjp_of(ref, mask, causal=False)):
        _check(rows, name, shape, _rel_peak_err(a, b), BF16_TOL,
               executor)


def _lstm_checks(rows, shape, executor):
    """The primal forward, the forward under differentiation (residual
    streams) and the time-reversed backward kernel, against the pure-XLA
    scan with the kernel's numerics and its XLA VJP."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.ops import pallas_lstm

    T, B, E, H, P = shape
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    bf = jnp.bfloat16
    x = (jax.random.normal(ks[0], (T, B, E)) * 0.05).astype(bf)
    w = (jax.random.normal(ks[1], (E + P, 4 * H))
         / np.sqrt(E + P)).astype(bf)
    b = jnp.zeros((4 * H,), bf)
    wp = (jax.random.normal(ks[2], (H, P)) / np.sqrt(H)).astype(bf)
    g = jax.random.normal(ks[3], (T, B, P), jnp.float32)

    def kern(x, w, b, wp):
        # 'kernel', not 'auto': a backward that does not fit must
        # refuse here, not drop to the scan in silence
        return pallas_lstm.lstm_scan(x, w, b, wp, impl="pallas",
                                     bwd_impl="kernel")

    def grads(fn):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * g),
            argnums=(0, 1, 2, 3)))(x, w, b, wp)

    if executor == "kernel":
        calls = _mosaic_calls(jax.grad(
            lambda *a: jnp.sum(kern(*a).astype(jnp.float32) * g),
            argnums=(0, 1, 2, 3)), x, w, b, wp)
        if calls != 2:
            raise AssertionError(
                f"lstm fwd+bwd lowered to {calls} Mosaic call(s), "
                "want 2")
    _check(rows, "lstm_fwd", shape,
           _rel_peak_err(jax.jit(kern)(x, w, b, wp),
                         jax.jit(pallas_lstm.lstm_scan_reference)(
                             x, w, b, wp)),
           BF16_TOL, executor)
    for name, a, r in zip(("lstm_dx", "lstm_dw", "lstm_db",
                           "lstm_dwproj"),
                          grads(kern),
                          grads(pallas_lstm.lstm_scan_reference)):
        _check(rows, name, shape, _rel_peak_err(a, r), BF16_TOL,
               executor)


def _paged_checks(rows, geo, executor):
    """Paged decode at G=1 (a plain step) and G=3 (the verify width)
    against the clip-then-mask einsum gather, over page tables with
    live prefixes and sentinel tails."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.ops import pallas_paged_attention as ppa

    S, D, nh = geo["S"], geo["D"], geo["num_heads"]
    ps, P, pool = geo["page_size"], geo["P"], geo["pool_pages"]
    rng = np.random.default_rng(4)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    kp, vp = (jax.random.normal(kk, (pool, ps, D), jnp.bfloat16)
              for kk in ks[:2])
    per_slot = min(P, pool // S)
    live = rng.integers(1, per_slot + 1, size=S)
    pages = np.full((S, P), pool, np.int32)
    ids = rng.permutation(pool)
    for s in range(S):
        pages[s, :live[s]] = ids[s * per_slot:s * per_slot + live[s]]
    for G in (1, 3):
        q = jax.random.normal(ks[2], (S, G, D), jnp.bfloat16)
        last = rng.integers(G - 1, live * ps)               # [S]
        pos = (last[:, None] - np.arange(G - 1, -1, -1)[None, :]
               ).astype(np.int32)
        args = (q, kp, vp, jnp.asarray(pages), jnp.asarray(pos))

        def run(impl):
            return jax.jit(lambda *a: ppa.paged_decode_attention(
                *a, num_heads=nh, page_size=ps, impl=impl))(*args)

        if executor == "kernel":
            calls = _mosaic_calls(lambda *a: ppa.paged_decode_attention(
                *a, num_heads=nh, page_size=ps, impl="kernel"), *args)
            if calls != 1:
                raise AssertionError(
                    f"paged decode G={G} lowered to {calls} Mosaic "
                    "call(s)")
        _check(rows, f"paged_decode_G{G}", (S, G, D, ps, P, pool),
               _rel_peak_err(run("kernel"), run("einsum")), BF16_TOL,
               executor)


def _table_checks(rows, geo, executor):
    """SliceAdagrad's in-place row kernel against its scatter path on a
    table and accumulator at a cell's shapes, for the ids of an ``emb``
    update (a batch's tokens) and of a ``softmax_w`` update (its labels
    and the sampled candidates), the table's last row among them.
    Compared on the device: the touched rows in units in the last
    place of their values, every other row bit for bit with what
    went in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.models import lm1b
    from parallax_tpu.ops import sparse_optim as so
    from parallax_tpu.ops.sampled_softmax import log_uniform_candidates

    V, D = geo["V"], geo["D"]
    sl = so.SliceAdagrad(0.2)
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    param = jax.random.normal(ks[0], (V, D), jnp.float32)
    acc = jax.random.uniform(ks[1], (V, D), jnp.float32, 0.1, 2.0)
    batch = lm1b.make_batch(np.random.default_rng(6), geo["B"], geo["T"],
                            V)
    cands = np.asarray(log_uniform_candidates(ks[2], geo["samples"], V))
    id_lists = {"emb": batch["x"].reshape(-1),
                "softmax_w": np.concatenate([batch["y"].reshape(-1),
                                             cands])}

    def bits(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32)

    def ulps(got, want, was):
        # in units in the last place of the larger of what the row held
        # and what it holds now: where the step nearly cancels the
        # value, the result's own last place would be far finer than
        # anything that was added
        size = jnp.maximum(jnp.abs(want), jnp.abs(was))
        return jnp.abs(got - want) / (jnp.nextafter(size, jnp.inf) - size)

    @jax.jit
    def combine(ids, drows):
        return so._combine_slices(ids, drows, V, jnp.float32, False)

    @jax.jit
    def compare(param, acc, uids, gsum):
        want = sl._scatter_rows(param, acc, uids, gsum)
        got = sl._kernel_rows(param, acc, uids, gsum)
        touched = jnp.zeros((V, 1), bool).at[uids].set(True, mode="drop")
        off = jnp.maximum(*(
            jnp.max(jnp.where(touched, ulps(g, w, x), 0.0))
            for g, w, x in zip(got, want, (param, acc))))
        changed = sum(jnp.sum((bits(g) != bits(x)) & ~touched)
                      for g, x in zip(got, (param, acc)))
        moved = jnp.sum(bits(got[0]) != bits(param))
        return off, changed, moved, jnp.sum(uids < V)

    for name, ids in id_lists.items():
        ids = jnp.asarray(np.append(ids[:-1], V - 1), jnp.int32)
        drows = jax.random.normal(jax.random.PRNGKey(ids.shape[0]),
                                  (ids.shape[0], D), jnp.float32) * 0.05
        uids, gsum = combine(ids, drows)
        if executor == "kernel":
            calls = _mosaic_calls(sl._kernel_rows, param, acc, uids, gsum)
            if calls != 1:
                raise AssertionError(
                    f"row update of {name} lowered to {calls} Mosaic "
                    "call(s)")
        off, changed, moved, live = (int(x) for x in compare(
            param, acc, uids, gsum))
        if changed or not moved:
            raise AssertionError(
                f"row update of {name}: {changed} element(s) changed "
                f"outside the {live} touched rows, {moved} inside")
        _check(rows, f"adagrad_rows_{name}_ulp",
               (V, D, int(ids.shape[0]), live), off, 2, executor)


def _ring_check(rows, shape, executor):
    """One causal ring-attention pass with the flash kernels as block
    core and zig-zag placement, sequence split over every device,
    against unsharded attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parallax_tpu.ops import ring_attention as ring

    devs = jax.devices()
    n = len(devs)
    B, T, H, D = shape
    mesh = Mesh(np.array(devs), ("sp",))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
               for kk in ks)
    want = ring.full_attention_reference(q, k, v, causal=True)
    perm = ring.zigzag_permutation(T, n)
    inv = ring.inverse_zigzag_permutation(T, n)
    sh = NamedSharding(mesh, P(None, "sp", None, None))
    qz, kz, vz = (jax.device_put(a[:, perm], sh) for a in (q, k, v))
    got = jax.jit(lambda q, k, v: ring.ring_attention(
        q, k, v, mesh, "sp", causal=True, placement="zigzag",
        block_impl="pallas"))(qz, kz, vz)
    _check(rows, f"ring_zigzag_pallas_n{n}", shape,
           _rel_peak_err(np.asarray(got)[:, inv], want), BF16_TOL,
           executor)


def phase_kernels(sz: dict, rehearsal: bool) -> dict:
    import jax

    # off the chip every ``interpret`` default reads the backend and
    # interprets; on it none may — the Mosaic-call counts above prove
    # which happened
    executor = "interpret" if rehearsal else "kernel"
    rows = []
    t0 = time.perf_counter()
    for shape in sz["flash"]:
        _flash_checks(rows, shape, executor)
    for shape in sz["lstm"]:
        _lstm_checks(rows, shape, executor)
    for geo in sz["paged"]:
        _paged_checks(rows, geo, executor)
    _table_checks(rows, sz["table"], executor)
    if jax.device_count() > 1:
        _ring_check(rows, sz["ring"], executor)
    out = {"seconds_with_compiles": round(time.perf_counter() - t0, 1),
           "comparisons": len(rows),
           "worst": max(rows, key=lambda r: r["err"] / r["tol"]),
           "rows": rows}
    bad = [r for r in rows if not r["ok"]]
    if bad:
        raise AssertionError(f"{len(bad)} kernel comparison(s) outside "
                             f"tolerance: {bad}")
    return out


# -- driver -----------------------------------------------------------------


PHASES = (("train", phase_train), ("kernels", phase_kernels),
          ("serve", phase_serve))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes off the chip, Pallas interpreted; "
                         "stamped as a rehearsal, proves nothing about "
                         "the chip")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version}
    print(f"chip_smoke: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"jax={versions['jax']} jaxlib={versions['jaxlib']} "
          f"libtpu={versions['libtpu']}", flush=True)
    if device["platform"] != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: platform is {device['platform']!r}, not "
              f"'tpu' — refusing before any compile (a CPU rehearsal "
              f"is --cpu-rehearsal, and proves nothing about the chip)",
              file=sys.stderr)
        return 2
    if device["platform"] == "tpu" and args.cpu_rehearsal:
        print("chip_smoke: --cpu-rehearsal on a TPU would stamp a chip "
              "run as a rehearsal; run the default invocation",
              file=sys.stderr)
        return 2

    from parallax_tpu.common import flops
    from parallax_tpu.compile.cache import ensure_persistent_cache

    if device["platform"] == "tpu":
        # an unknown TPU kind raises here, before any phase
        flops.device_peak_flops("tpu", device["kind"])
    cache_dir = ensure_persistent_cache()

    def cache_entries():
        try:
            return len(os.listdir(cache_dir))
        except FileNotFoundError:
            return 0

    entries_before = cache_entries()
    sz = _sizes(args.cpu_rehearsal, len(devs))
    phases = {}
    t_start = time.perf_counter()
    for name, fn in PHASES:
        t0 = time.perf_counter()
        print(f"chip_smoke: phase {name} ...", flush=True)
        try:
            phases[name] = dict(fn(sz, args.cpu_rehearsal), ok=True)
        except Exception as e:  # recorded, and the exit code is 1
            traceback.print_exc()
            phases[name] = {"ok": False,
                            "error": f"{type(e).__name__}: {e}"[:2000]}
        phases[name]["seconds"] = round(time.perf_counter() - t0, 1)
        phases[name]["peak_bytes_in_use"] = _peak_bytes()
        print(f"chip_smoke: phase {name} "
              f"{'passed' if phases[name]['ok'] else 'FAILED'} in "
              f"{phases[name]['seconds']}s", flush=True)

    ok = all(p["ok"] for p in phases.values())
    kernel_rows = phases.get("kernels", {}).pop("rows", [])
    summary = {
        "ok": ok,
        "device": device,
        "rehearsal": bool(args.cpu_rehearsal),
        "versions": versions,
        "phases": phases,
        "executors": {
            "lstm_fwd": phases["train"].get("lstm_fwd"),
            "lstm_bwd": phases["train"].get("lstm_bwd"),
            "table_update": phases["train"].get("table_update"),
            "paged_decode": phases["serve"].get("paged_impl"),
            "kernels_phase": sorted({r["executor"]
                                     for r in kernel_rows}),
            "behind_the_valve": [],
        },
        "kernel_errors": {f"{r['kernel']}{r['shape']}": r["err"]
                          for r in kernel_rows},
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": cache_entries()},
        "seconds_total": round(time.perf_counter() - t_start, 1),
        "peak_bytes_in_use": _peak_bytes(),
        "claim": None,
    }
    print(json.dumps(summary))
    # the driver's contract: the last line holds these keys and no other
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
