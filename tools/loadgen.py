"""Synthetic load generator for the serving subsystem.

Drives a :class:`~parallax_tpu.serve.session.ServeSession` with
closed-loop clients (each thread submits, waits for the result, then
submits again — the standard saturating-load shape) over a caller-
supplied feed generator, and reports per-request outcomes (latency,
time-to-first-token, emitted tokens) alongside the session's own
``serve.*`` metrics. Used by ``tools/check_serve_slo.py`` (the tier-1
SLO contract) and the other ``tools/check_*.py`` rigs, and runnable
directly::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/loadgen.py

which serves a small MLP scorer under a mixed-length load and prints
one JSON report (``--mode decode`` serves the tiny-NMT continuous-decode
rig: paged KV + chunked prefill + speculative decoding by default).
Off the TPU its rates and latencies are the CPU rig's: they check
behaviour and counts, and are no statement about speed
(``benchmark/`` makes those).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _pct(sorted_ms, q):
    # one quantile rule repo-wide (obs/metrics.nearest_rank)
    from parallax_tpu.obs.metrics import nearest_rank
    v = nearest_rank(sorted_ms, q)
    return round(v, 3) if v is not None else None


def run_load(session, make_feed, n_requests: int, concurrency: int = 4,
             deadline_ms=None, max_new_tokens=None,
             result_timeout_s: float = 120.0,
             submit_kw=None) -> dict:
    """Submit ``n_requests`` through ``concurrency`` closed-loop client
    threads; ``make_feed(i)`` builds request ``i``'s feed. Returns the
    outcome/latency report (shed and timed-out requests are counted,
    not errors). ``submit_kw`` (e.g. ``{"tenant": "a"}``) is forwarded
    to every ``session.submit``. ``max_new_tokens`` may be a CALLABLE
    ``i -> int`` (per-request decode budgets — the mixed-regime rig's
    short-decode/long-decode split rides this)."""
    from parallax_tpu.serve import (DeadlineExceeded, ServeClosed,
                                    ServeOverloaded)

    submit_kw = submit_kw or {}

    lock = threading.Lock()
    counter = {"next": 0}
    outcomes = {"completed": 0, "shed": 0, "timeout": 0, "failed": 0}
    latencies = []
    ttfts = []
    tokens = [0]
    errors = []

    def client():
        while True:
            with lock:
                i = counter["next"]
                if i >= n_requests:
                    return
                counter["next"] = i + 1
            mnt = (max_new_tokens(i) if callable(max_new_tokens)
                   else max_new_tokens)
            try:
                req = session.submit(make_feed(i),
                                     deadline_ms=deadline_ms,
                                     max_new_tokens=mnt,
                                     **submit_kw)
            except ServeOverloaded:
                with lock:
                    outcomes["shed"] += 1
                continue
            try:
                res = req.result(timeout=result_timeout_s)
                n_tok = len(res) if hasattr(res, "__len__") else 0
                t_first = req.t_first_token or req.t_done
                with lock:
                    outcomes["completed"] += 1
                    latencies.append(req.latency_s())
                    tokens[0] += n_tok
                    if t_first is not None:
                        ttfts.append(t_first - req.t_enqueue)
            except DeadlineExceeded:
                with lock:
                    outcomes["timeout"] += 1
            except (ServeClosed, TimeoutError) as e:
                with lock:
                    outcomes["failed"] += 1
                    errors.append(f"{type(e).__name__}: {e}")

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, name=f"loadgen-{k}",
                                daemon=True)
               for k in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    lat_ms = sorted(v * 1e3 for v in latencies)
    ttft_ms = sorted(v * 1e3 for v in ttfts)
    return {
        "submitted": n_requests,
        "completed": outcomes["completed"],
        "shed": outcomes["shed"],
        "timeouts": outcomes["timeout"],
        "failed": outcomes["failed"],
        "errors": errors[:5],
        "wall_s": round(wall, 3),
        "qps": round(outcomes["completed"] / wall, 2) if wall > 0 else None,
        "latency_ms": {"p50": _pct(lat_ms, 0.50), "p95": _pct(lat_ms, 0.95),
                       "p99": _pct(lat_ms, 0.99),
                       "max": round(lat_ms[-1], 3) if lat_ms else None},
        # time-to-first-token, measured CLIENT-side per request (equals
        # full latency in one-shot mode, where the only token is the
        # result)
        "ttft_ms": {"p50": _pct(ttft_ms, 0.50), "p95": _pct(ttft_ms, 0.95),
                    "p99": _pct(ttft_ms, 0.99),
                    "max": round(ttft_ms[-1], 3) if ttft_ms else None},
        "tokens": tokens[0],
        "tokens_per_sec": (round(tokens[0] / wall, 2)
                           if wall > 0 and tokens[0] else None),
        "deadline_ms": deadline_ms,
        "concurrency": concurrency,
    }


def demo_session(max_batch: int = 8, length_buckets=(16, 32),
                 dim: int = 384, layers: int = 4, max_queue: int = 128,
                 max_wait_ms: float = 2.0, default_deadline_ms=None):
    """A small-MLP one-shot scorer behind a ServeSession — the shared
    rig of the CLI and the SLO tool. Returns
    ``(session, make_feed)``."""
    import jax
    import numpy as np

    import parallax_tpu as parallax

    rng = jax.random.PRNGKey(0)
    ws = []
    for i in range(layers):
        rng, k = jax.random.split(rng)
        ws.append(jax.random.normal(k, (dim, dim)) / np.sqrt(dim))
    params = {"w": ws}

    def infer_fn(params, batch):
        x = batch["x"]                       # [B, L, dim]
        for w in params["w"]:
            x = jax.nn.tanh(x @ w)
        return {"score": x.mean(axis=(1, 2))}

    cfg = parallax.Config(serve_config=parallax.ServeConfig(
        max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_queue=max_queue, length_buckets=list(length_buckets),
        default_deadline_ms=default_deadline_ms))
    sess = parallax.ServeSession(
        infer_fn, params,
        example_feed={"x": np.zeros((length_buckets[-1], dim),
                                    np.float32)},
        config=cfg, ragged_feeds=("x",))

    lo, hi = max(1, length_buckets[0] // 2), length_buckets[-1]

    def make_feed(i):
        # per-request generator: make_feed is called concurrently from
        # every client thread, and numpy Generators are not
        # thread-safe — a shared one would corrupt the mixed-length
        # coverage this rig exists to produce
        r = np.random.default_rng(1000 + i)
        L = int(r.integers(lo, hi + 1))
        return {"x": r.standard_normal((L, dim)).astype(np.float32)}

    return sess, make_feed


def shared_prefix_feed(Ts: int = 8, vocab: int = 256,
                       prefix_share: float = 0.5, pool_size: int = 4,
                       pool_seed: int = 777):
    """A ``make_feed(i)`` with a DETERMINISTIC shared-prefix pool
    (ISSUE 15): a ``prefix_share`` fraction of requests draw their
    source from ``pool_size`` fixed sequences (the system-prompt /
    template / retry population) and the rest are unique. Which
    requests are shared — and which pool member they draw — is a pure
    function of ``i``, so an A/B rig (sharing on vs off) and a
    bit-identity sweep replay the EXACT same request stream."""
    import numpy as np

    if not 0.0 <= float(prefix_share) <= 1.0:
        raise ValueError(
            f"prefix_share must be in [0, 1], got {prefix_share}")
    pr = np.random.default_rng(pool_seed)
    pool = [pr.integers(3, vocab, (Ts,)).astype(np.int32)
            for _ in range(max(1, int(pool_size)))]

    def make_feed(i):
        r = np.random.default_rng(3000 + i)
        if r.random() < prefix_share:
            return {"src": pool[int(r.integers(0, len(pool)))]}
        L = int(r.integers(max(2, Ts // 2), Ts + 1))
        return {"src": r.integers(3, vocab, (L,)).astype(np.int32)}

    return make_feed


def mixed_regime_feed(Ts: int = 8, vocab: int = 256,
                      long_prefill_share: float = 0.5,
                      short_decode: int = 2, long_decode: int = 8,
                      key: str = "src", seed: int = 4000):
    """The disaggregation traffic shape (ISSUE 19): a deterministic
    mix of the two regimes that pull a colocated replica in opposite
    directions — LONG-prefill/SHORT-decode requests (full-length
    source, ``short_decode`` new tokens: the prefill-bound half) and
    SHORT-prefill/LONG-decode requests (minimal source,
    ``long_decode`` new tokens: the decode-bound half). Which regime
    request ``i`` belongs to is a pure function of ``i``, so the
    colocated and disaggregated arms of an A/B replay the EXACT same
    request stream. Returns ``(make_feed, max_new_tokens)``; the
    second is the ``i -> int`` callable ``run_load`` resolves per
    request."""
    import numpy as np

    if not 0.0 <= float(long_prefill_share) <= 1.0:
        raise ValueError(f"long_prefill_share must be in [0, 1], "
                         f"got {long_prefill_share}")

    def _regime(r):
        # first draw from the per-i generator decides the regime, so
        # make_feed and max_new_tokens agree without shared state
        return r.random() < long_prefill_share

    def make_feed(i):
        r = np.random.default_rng(seed + i)
        L = Ts if _regime(r) else max(2, Ts // 4)
        return {key: r.integers(3, vocab, (L,)).astype(np.int32)}

    def max_new_tokens(i):
        r = np.random.default_rng(seed + i)
        return short_decode if _regime(r) else long_decode

    return make_feed, max_new_tokens


def demo_decode_session(slots: int = 16, T: int = 16, Ts: int = 8,
                        page_size: int = 4, pool_pages=None,
                        prefill_chunk_layers=1, spec_tokens: int = 2,
                        model_dim: int = 64, num_layers: int = 2,
                        vocab: int = 256, max_queue: int = 4096,
                        paged: bool = True, speculative: bool = True,
                        prefix_cache: bool = False,
                        prefix_cache_max_pages=None,
                        tenant_quotas=None, slo_classes=None,
                        metrics=None, attn_impl=None,
                        compute_dtype=None):
    """A tiny-NMT continuous-decode session with the full ISSUE 6
    stack on by default — paged KV pool, chunked prefill, layer-skip
    speculative draft — plus the ISSUE 15 knobs (prefix cache, tenant
    quotas, SLO classes) off by default. Returns ``(session,
    make_feed)``; ``make_feed`` produces mixed-length sources.
    ``paged=False`` / ``speculative=False`` select the dense / plain
    ablations (the A/B rigs of the ``tools/check_*.py`` guards)."""
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.models import nmt
    from parallax_tpu.serve import NMTDecodeProgram

    cfg_kw = dict(vocab_size=vocab, model_dim=model_dim,
                  num_heads=4, mlp_dim=2 * model_dim,
                  num_layers=num_layers, max_len=max(T, Ts),
                  num_partitions=1)
    if compute_dtype is not None:
        # executor A/B rigs pin float32: the kernel/einsum token-
        # identity contract is exact there (bf16 differs within
        # rounding noise — see ops/pallas_paged_attention)
        cfg_kw.update(compute_dtype=compute_dtype)
    cfg = nmt.tiny_config(**cfg_kw)
    params = nmt.build_model(cfg).init_fn(jax.random.PRNGKey(0))
    kw = {}
    if paged:
        if pool_pages is None:
            pool_pages = slots * (T // page_size)
        kw.update(page_size=page_size, pool_pages=pool_pages)
    if attn_impl is not None:
        # paged-attention executor A/B ('kernel' | 'einsum' | 'auto');
        # see ops/pallas_paged_attention and tools/check_paged_attn_serve
        kw.update(attn_impl=attn_impl)
    if prefill_chunk_layers:
        kw.update(prefill_chunk_layers=prefill_chunk_layers)
    if speculative and spec_tokens:
        from parallax_tpu.serve.adapters import layer_skip_draft
        dcfg, dparams = layer_skip_draft(cfg, params)
        kw.update(spec_tokens=spec_tokens, draft_cfg=dcfg,
                  draft_params=dparams)
    prog = NMTDecodeProgram(cfg, max_src_len=Ts, max_len=T, **kw)
    pcfg = parallax.Config(serve_config=parallax.ServeConfig(
        max_batch=slots, max_queue=max_queue,
        prefix_cache=prefix_cache,
        prefix_cache_max_pages=prefix_cache_max_pages,
        tenant_quotas=tenant_quotas, slo_classes=slo_classes))
    sess = parallax.ServeSession(program=prog, params=params,
                                 config=pcfg, metrics=metrics)

    def make_feed(i):
        r = np.random.default_rng(2000 + i)
        L = int(r.integers(max(2, Ts // 2), Ts + 1))
        return {"src": r.integers(3, vocab, (L,)).astype(np.int32)}

    return sess, make_feed


def demo_decode_fleet(replicas: int = 2, slots: int = 4, T: int = 12,
                      Ts: int = 8, model_dim: int = 32,
                      num_layers: int = 2, vocab: int = 64,
                      page_size: int = 4, paged: bool = True,
                      max_queue: int = 4096, submesh: bool = True,
                      fleet_config=None, faults=None, flight=None,
                      anomaly=None, metrics=None):
    """A replicated tiny-NMT continuous-decode :class:`ServeFleet` —
    the chaos-harness rig (tools/check_fleet_faults.py).

    Every replica is a full ServeSession (own scheduler thread, own
    queue) on its own submesh when the device count splits
    (``submesh=True``), else on one shared mesh. All replicas share
    ONE program instance and one host param pytree, so replica
    spin-up rides the jit caches — the first replica compiles, the
    rest come up compile-free (the PR 3 cache story at fleet scale).
    Greedy decode is deterministic, so every replica emits
    bit-identical tokens for the same request — the property failover
    retry leans on. Returns ``(fleet, make_feed, params, cfg)``;
    ``make_feed(i)`` is deterministic per ``i`` so an unfaulted
    baseline can replay the exact request set."""
    import jax
    import numpy as np

    import parallax_tpu as parallax
    from parallax_tpu.core import mesh as mesh_lib
    from parallax_tpu.models import nmt
    from parallax_tpu.serve import (FleetConfig, NMTDecodeProgram,
                                    ServeFleet, ServeSession)

    import jax.numpy as jnp
    # f32 compute: the bit-identity bar (failover retries vs standalone
    # greedy) holds exactly in f32; bf16 rounding differences between
    # the batched cached step and the reference decode can flip argmax
    # at near-ties, which is a dtype artifact, not a fleet bug
    cfg = nmt.tiny_config(vocab_size=vocab, model_dim=model_dim,
                          num_heads=4, mlp_dim=2 * model_dim,
                          num_layers=num_layers, max_len=max(T, Ts),
                          num_partitions=1,
                          compute_dtype=jnp.float32)
    params = nmt.build_model(cfg).init_fn(jax.random.PRNGKey(0))
    kw = {}
    if paged:
        kw.update(page_size=page_size,
                  pool_pages=slots * (T // page_size))
    prog = NMTDecodeProgram(cfg, max_src_len=Ts, max_len=T, **kw)
    pcfg = parallax.Config(serve_config=parallax.ServeConfig(
        max_batch=slots, max_queue=max_queue))

    fc = fleet_config or FleetConfig(num_replicas=replicas)
    devs = jax.devices()
    # split ALL devices across the INITIAL replica count (with 8 CPU
    # devices and 2 replicas: two 4-device submeshes, no idle devices);
    # replicas churned/scaled past that wrap onto existing groups —
    # sharing a submesh also means sharing its compiled executables
    groups = max(1, int(fc.num_replicas))
    per = len(devs) // groups
    meshes = {}

    def make_replica(rid, **serve_kw):
        if submesh and per >= 1 and groups > 1:
            g = int(rid) % groups
            mesh = meshes.get(g)
            if mesh is None:
                mesh = meshes[g] = mesh_lib.build_mesh(
                    devices=devs[g * per:(g + 1) * per],
                    num_partitions=1)
        else:
            mesh = meshes.setdefault(
                "shared", mesh_lib.build_mesh(num_partitions=1))
        return ServeSession(program=prog, params=params, config=pcfg,
                            mesh=mesh, **serve_kw)

    fleet = ServeFleet(make_replica, config=fc, metrics=metrics,
                       flight=flight, anomaly=anomaly, faults=faults)

    def make_feed(i):
        r = np.random.default_rng(2000 + i)
        L = int(r.integers(max(2, Ts // 2), Ts + 1))
        return {"src": r.integers(3, vocab, (L,)).astype(np.int32)}

    return fleet, make_feed, params, cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--mode", choices=("oneshot", "decode"),
                    default="oneshot")
    ap.add_argument("--prefix-share", type=float, default=None,
                    help="decode mode: fraction of requests drawing "
                         "their source from a deterministic shared "
                         "pool (e.g. 0.5); enables the prefix cache")
    ap.add_argument("--prefix-pool", type=int, default=4,
                    help="size of the shared-prefix pool")
    ap.add_argument("--mixed-regime", action="store_true",
                    help="decode mode: the disaggregation traffic "
                         "shape — a deterministic long-prefill/"
                         "short-decode vs short-prefill/long-decode "
                         "mix with per-request decode budgets")
    args = ap.parse_args(argv)
    if args.mixed_regime and args.prefix_share is not None:
        ap.error("--mixed-regime and --prefix-share are separate "
                 "traffic shapes; pick one")
    mnt = None
    if args.mode == "decode":
        sess, make_feed = demo_decode_session(
            prefix_cache=args.prefix_share is not None)
        if args.prefix_share is not None:
            make_feed = shared_prefix_feed(
                prefix_share=args.prefix_share,
                pool_size=args.prefix_pool)
        if args.mixed_regime:
            make_feed, mnt = mixed_regime_feed()
    else:
        if args.prefix_share is not None:
            ap.error("--prefix-share needs --mode decode (the prefix "
                     "cache lives on the continuous-decode path)")
        if args.mixed_regime:
            ap.error("--mixed-regime needs --mode decode (decode "
                     "budgets only exist on the continuous-decode "
                     "path)")
        sess, make_feed = demo_session()
    try:
        report = run_load(sess, make_feed, args.requests,
                          concurrency=args.concurrency,
                          deadline_ms=args.deadline_ms,
                          max_new_tokens=mnt)
        report["serve_metrics"] = sess.stats()
    finally:
        sess.close()
    print(json.dumps(report, indent=2, default=str))
    return 0 if report["failed"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
