"""Serving latency attribution: name the dominant cause per percentile.

Reads the per-request lifecycle records (obs/reqtrace.py) a serving
session or fleet collected and answers the question flat histograms
cannot: *which phase* makes p99 slow — "p99 is slot_wait-bound at 64
offered", not "p99 is 885 ms". Requests are bucketed by TTFT percentile
band (p50 = the typical half, p90 = the 50-90 band, p99 = the tail) and
each bucket reports its mean phase shares and the dominant phase.

Used three ways:

* ``analyze(records)`` — pure function over record snapshots
  (``session.request_records()`` / ``fleet.request_records()`` / the
  ``request_records`` section of a flight artifact).
* ``measure(level=64, ...)`` — bring up the tiny-NMT continuous-decode
  rig at one offered-concurrency level and report attribution for it
  (the tier-1 acceptance path: the 64-offered level must name a
  dominant p99 cause).
* CLI::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/serve_report.py --level 64

Off the TPU every latency here is the CPU rig's: the report checks
that the phases add up and that a dominant cause is named, and is no
statement about speed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from parallax_tpu.obs.metrics import nearest_rank  # noqa: E402

# percentile bands, keyed by their upper edge
BANDS = (("p50", 0.0, 0.50), ("p90", 0.50, 0.90), ("p99", 0.90, 1.01))


def ttft_shares(records: Sequence[Dict]) -> Optional[Dict[str, float]]:
    """Mean share of TTFT per phase across completed records (the
    ``ttft_decomp`` shares); None when no record
    carries a decomposition."""
    totals: Dict[str, float] = {}
    grand = 0.0
    for r in records:
        dec = r.get("ttft_decomp")
        if not dec:
            continue
        for k, v in dec.items():
            totals[k] = totals.get(k, 0.0) + v
            grand += v
    if grand <= 0:
        return None
    return {k.replace("_ms", "_share"): round(v / grand, 4)
            for k, v in sorted(totals.items())}


def deadline_miss_budget_consumed(records: Sequence[Dict],
                                  budget: float = 0.01
                                  ) -> Optional[float]:
    """Window deadline-miss rate over the SLO budget (1.0 = the whole
    budget burned); None when no record carried a deadline."""
    with_ddl = [r for r in records if r.get("deadline_ms") is not None]
    if not with_ddl:
        return None
    missed = sum(
        1 for r in with_ddl
        if r.get("outcome") == "deadline_exceeded"
        or (r.get("total_ms") or 0) > r["deadline_ms"])
    return round((missed / len(with_ddl)) / budget, 4)


def analyze(records: Sequence[Dict], metric: str = "ttft_ms") -> Dict:
    """Bucket records by ``metric`` percentile band; per bucket, the
    mean phase shares (from each record's TTFT decomposition) and the
    DOMINANT phase. Returns a JSON-ready report; ``dominant_p99`` is
    the headline ("p99 is <phase>-bound")."""
    rows = [r for r in records
            if r.get(metric) is not None and r.get("ttft_decomp")]
    rows.sort(key=lambda r: r[metric])
    vals = [r[metric] for r in rows]
    buckets: Dict[str, Dict] = {}
    n = len(rows)
    for name, lo, hi in BANDS:
        lo_i, hi_i = int(math.floor(lo * n)), int(math.ceil(hi * n))
        band = rows[lo_i:min(hi_i, n)]
        if not band:
            buckets[name] = None
            continue
        shares = ttft_shares(band) or {}
        dominant = (max(shares, key=shares.get).replace("_share", "")
                    if shares else None)
        totals = sorted(v for r in band
                        if (v := r.get("total_ms")) is not None)
        buckets[name] = {
            "count": len(band),
            # the band's upper-edge latency (the gated key: p99 TTFT)
            "ttft_ms": round(nearest_rank(vals, min(hi, 1.0)), 3),
            "total_ms": (round(totals[-1], 3) if totals else None),
            "shares": shares,
            "dominant": dominant,
        }
    p99 = buckets.get("p99") or {}
    return {
        "metric": metric,
        "requests_analyzed": n,
        "buckets": buckets,
        "dominant_p99": p99.get("dominant"),
    }


def headline(report: Dict, offered: Optional[int] = None) -> str:
    """One sentence: 'p99 is <phase>-bound (...)'. """
    dom = report.get("dominant_p99")
    if dom is None:
        return "no completed requests to attribute"
    p99 = report["buckets"]["p99"]
    at = f" at {offered} offered" if offered else ""
    return (f"p99 is {dom}-bound{at} "
            f"({p99['shares'].get(dom + '_share', 0) * 100:.0f}% of "
            f"TTFT; p99 ttft {p99['ttft_ms']}ms)")


def measure(level: int = 64, requests: Optional[int] = None,
            slots: Optional[int] = None, T: int = 8, Ts: int = 6,
            model_dim: int = 16, vocab: int = 64,
            deadline_ms: Optional[float] = None,
            speculative: bool = False,
            prefill_chunk_layers=None) -> dict:
    """One offered-concurrency level end to end on the tiny-NMT
    continuous-decode rig; returns the attribution report plus the
    trace-derived serve keys. Small model defaults keep the 64-offered
    acceptance level tier-1-affordable on CPU."""
    from tools import loadgen

    n_req = requests or max(2 * level, 16)
    sess, make_feed = loadgen.demo_decode_session(
        slots=(slots or level), T=T, Ts=Ts, model_dim=model_dim,
        vocab=vocab, speculative=speculative,
        prefill_chunk_layers=prefill_chunk_layers)
    try:
        rep = loadgen.run_load(sess, make_feed, n_req,
                               concurrency=level,
                               deadline_ms=deadline_ms)
        records = sess.request_records()
    finally:
        sess.close()
    report = analyze(records)
    return {
        "offered_concurrency": level,
        "requests": n_req,
        "completed": rep["completed"],
        "ttft_ms": rep["ttft_ms"],
        "latency_ms": rep["latency_ms"],
        "report": report,
        "headline": headline(report, offered=level),
        "ttft_decomp": ttft_shares(records),
        "deadline_miss_budget_consumed":
            deadline_miss_budget_consumed(records),
        "records_sample": records[:3],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--level", type=int, default=64,
                    help="offered concurrency (slots == clients)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--records", type=str, default=None,
                    help="analyze a JSON file of record snapshots (a "
                         "flight artifact's request_records section) "
                         "instead of running the rig")
    args = ap.parse_args(argv)
    if args.records:
        with open(args.records) as f:
            doc = json.load(f)
        records = doc.get("request_records", doc) \
            if isinstance(doc, dict) else doc
        report = analyze(records)
        out = {"report": report, "headline": headline(report)}
    else:
        out = measure(level=args.level, requests=args.requests)
    print(json.dumps(out, indent=2, default=str))
    ok = (out["report"]["dominant_p99"] is not None)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
