"""Analytic step-time model over (mesh shape, run option) plans.

Pure and unit-testable: every function here maps plain numbers to plain
numbers — no jax import, no device touch — so a candidate plan can be
priced from *lowered-only* artifacts before anything compiles:

* XLA ``cost_analysis`` FLOPs / bytes-accessed of one step
  (``Engine.step_cost_analysis``),
* the dense-vs-IndexedSlices wire split from the engine's
  GradientsInfo-equivalent (``ShardingPlan.var_specs`` + the per-lookup
  trace records of ``ops/embedding.py`` — the paper's sparsity-aware
  core),
* ``common.flops.device_peak_flops`` for the chip's compute ceiling.

The prediction is a three-term roofline:

    step ~= max(compute, HBM) + interconnect

compute and HBM overlap inside the chip (whichever ceiling binds wins);
collective traffic is first-order serialized against them, except under
``sync=False`` bounded-staleness plans, where the delayed-gradient
exchange overlaps the next step's compute and only the excess bills.

Wire terms per plan (N = dp * tp devices, ring all-reduce moves
``2 * bytes * (k-1)/k``, a one-way gather/scatter ``bytes * (k-1)/k``):

* dense (non-table) grads all-reduce over the full mesh in every run
  option (the batch axis spans the whole mesh);
* ``SHARD`` additionally pays the ZeRO storage tax: sharded dense
  params are all-gathered for fwd+bwd consumption;
* tables: ``AR`` ships the full dense [V, D] gradient through the same
  ring; ``SHARD``/``HYBRID`` ship the sparse exchange — the probe
  trace's recorded (ids + row planes + counts) bytes rescaled to the
  candidate's shard width, plus the cross-replica combine rescaled to
  its replica count (estimated from the dense shard-grad psum when the
  probe mesh had a single replica row and recorded nothing).

HONESTY: absolute seconds are only as good as the bandwidth/peak
constants — on the CPU rig (unknown peak) the model falls back to
nominal TPU-class constants, so predictions are *ranking* devices, not
wall-clock oracles, and every predicted-vs-measured ratio downstream is
CPU-relative until captured on hardware. The per-term breakdown rides
into the flight-recorder artifacts so each tuner decision stays
explainable either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from parallax_tpu.common import consts
from parallax_tpu.common.config import normalize_run_option

# Nominal per-chip constants used when the running backend doesn't
# report real ones (CPU rig, unknown hardware): TPU-v4-class ballpark.
# They set the compute-vs-wire exchange rate of the model, i.e. how
# many wire bytes cost as much as a FLOP — the plan *ranking* is
# dominated by the byte terms, which are exact.
NOMINAL_PEAK_FLOPS = 275e12      # bf16 MXU peak, FLOP/s
NOMINAL_HBM_BPS = 1.2e12         # HBM bandwidth, bytes/s
NOMINAL_ICI_BPS = 100e9          # per-device interconnect, bytes/s


@dataclasses.dataclass(frozen=True)
class Plan:
    """One candidate configuration: mesh shape + run options.

    ``dp`` is the ``'repl'`` axis size (data-parallel replica rows),
    ``tp`` the ``'shard'`` axis size (row-shard width — the
    reference's embedding partition count), ``pp`` the ``'pipe'``
    axis size (pipeline stages, ISSUE 18; 1 means no pipe axis and
    the exact pre-PR-18 two-axis mesh). ``virtual_stages`` /
    ``microbatches`` are the pipeline schedule knobs a ``pp>1`` plan
    carries (the tuner copies them from the model's declared
    ``pipeline_info``); both stay at their neutral defaults on 2-D
    plans — validated, so a pp=1 plan can never smuggle schedule
    state into the cache key. ``sync`` / ``local_aggregation`` ride
    along from the session config (the search varies mesh shape and
    run option); they are part of the plan so the cache key, the
    cost breakdown, and the dryrun phase list all name the complete
    configuration.
    """

    dp: int
    tp: int
    run_option: str = consts.RUN_HYBRID
    sync: bool = True
    local_aggregation: bool = True
    pp: int = 1
    virtual_stages: int = 1
    microbatches: int = 0

    def __post_init__(self):
        if int(self.dp) < 1 or int(self.tp) < 1 or int(self.pp) < 1:
            raise ValueError(
                f"plan mesh axes must be >= 1, got dp={self.dp} "
                f"tp={self.tp} pp={self.pp}")
        if int(self.virtual_stages) < 1 or int(self.microbatches) < 0:
            raise ValueError(
                f"virtual_stages must be >= 1 and microbatches >= 0, "
                f"got virtual_stages={self.virtual_stages} "
                f"microbatches={self.microbatches}")
        if int(self.pp) == 1 and (int(self.virtual_stages) != 1
                                  or int(self.microbatches) != 0):
            raise ValueError(
                "pipeline knobs (virtual_stages/microbatches) require "
                "pp > 1")
        object.__setattr__(self, "dp", int(self.dp))
        object.__setattr__(self, "tp", int(self.tp))
        object.__setattr__(self, "pp", int(self.pp))
        object.__setattr__(self, "virtual_stages",
                           int(self.virtual_stages))
        object.__setattr__(self, "microbatches", int(self.microbatches))
        object.__setattr__(self, "run_option",
                           normalize_run_option(self.run_option))
        object.__setattr__(self, "sync", bool(self.sync))
        object.__setattr__(self, "local_aggregation",
                           bool(self.local_aggregation))

    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.pp

    def mesh_shape(self) -> Tuple[int, ...]:
        """The ``build_mesh(shape=...)`` tuple for this plan: the
        legacy 2-tuple at pp=1 (the exact pre-PR-18 mesh), the
        3-tuple otherwise."""
        if self.pp == 1:
            return (self.dp, self.tp)
        return (self.dp, self.tp, self.pp)

    def validate_for(self, num_devices: int) -> "Plan":
        """Refuse a plan whose dp*tp*pp product does not tile the
        mesh."""
        if self.num_devices != int(num_devices):
            raise ValueError(
                f"plan {self.describe()} covers {self.num_devices} "
                f"devices but the mesh has {num_devices}; dp*tp*pp "
                f"must equal the device count")
        return self

    def cache_key(self) -> Tuple:
        """The engine-cache key prefix: every field that changes the
        compiled program. Two plans with equal device counts but
        different mesh shape or run option MUST key apart (ISSUE 10
        bugfix — the old ``(num_partitions, sig)`` key collided them;
        ISSUE 18 extends the shape to the full 3-tuple plus schedule
        knobs for the same reason)."""
        return (self.dp, self.tp, self.run_option, self.sync,
                self.local_aggregation, self.pp, self.virtual_stages,
                self.microbatches)

    def describe(self) -> str:
        tags = [] if self.sync else ["async"]
        if not self.local_aggregation:
            tags.append("noagg")
        if self.pp > 1:
            if self.virtual_stages > 1:
                tags.append(f"v{self.virtual_stages}")
            if self.microbatches:
                tags.append(f"m{self.microbatches}")
            return (f"dp{self.dp}xtp{self.tp}xpp{self.pp}"
                    f"/{self.run_option}"
                    + ("".join("+" + t for t in tags)))
        return (f"dp{self.dp}xtp{self.tp}/{self.run_option}"
                + ("".join("+" + t for t in tags)))


@dataclasses.dataclass
class CostInputs:
    """Lowered-only artifacts one probe engine yields; the same inputs
    price every candidate plan (terms are rescaled analytically).

    All byte counts are per-step and mesh-global. ``probe_dp`` /
    ``probe_tp`` name the mesh the sparse terms were recorded on.
    """

    flops: float = 0.0            # per-step global FLOPs
    hbm_bytes: float = 0.0        # per-step bytes accessed (all devices)
    dense_grad_bytes: int = 0     # non-table gradient bytes per step
    table_grad_bytes: int = 0     # tables' dense [V, D] gradient bytes
    sparse_fwd_bytes: int = 0     # sparse shard-exchange bytes at probe
    sparse_repl_bytes: int = 0    # cross-replica combine bytes at probe
    # Pallas-LSTM kernel HBM traffic (ops/pallas_lstm.kernel_hbm_bytes
    # via its trace records): XLA's cost_analysis prices a pallas
    # custom call at ~zero bytes accessed, so a kernel-served
    # recurrence would otherwise score as HBM-free — exactly backwards
    # from the scan path, whose T x weight re-fetch cost_analysis DOES
    # price. ``lstm_stream_bytes`` is mesh-global and scales with the
    # global batch (fixed total traffic however B is sharded);
    # ``lstm_resident_bytes`` is the once-per-call weight fetch EVERY
    # device pays (total grows with the device count). Both fold into
    # the HBM roofline term, so PR 13's on_chip calibration sees the
    # kernel too.
    lstm_stream_bytes: float = 0.0
    lstm_resident_bytes: float = 0.0
    # Paged-attention kernel HBM traffic (same blind spot, same fix:
    # ops/pallas_paged_attention.kernel_hbm_bytes via its trace
    # records). Only impl='kernel' records are priced — the einsum
    # gather is ordinary XLA cost_analysis DOES see. Priced at the
    # table-width upper bound (all entries live): occupancy is
    # runtime-dynamic and invisible to a lowered-only probe, and an
    # upper bound keeps the roofline conservative. Mesh-global,
    # stream-like (splits across devices with the batch).
    attn_stream_bytes: float = 0.0
    probe_dp: int = 1
    probe_tp: int = 1
    num_devices: int = 1
    peak_flops: Optional[float] = None    # per device; None -> nominal
    hbm_bps: Optional[float] = None
    ici_bps: Optional[float] = None
    peak_is_nominal: bool = True  # False iff a real chip peak resolved
    # per-term predicted/measured ratios from a persisted calibration
    # file (tune/calibrate.py): {"on_chip": r, "wire": r}. Each
    # predicted term is divided by its ratio, replacing the nominal
    # exchange rates with measured ones — rig-relative by design.
    calibration: Optional[Dict[str, float]] = None
    # Pipeline capability record (ISSUE 18), present iff the probed
    # model declared ``Model.pipeline_info``. Keys: ``schedule``
    # ('gpipe'|'1f1b'), ``microbatches``, ``virtual_stages``,
    # ``pinned_stages`` (stage count baked into a V>1 layer storage
    # order, else None), ``num_layers``, ``act_bytes`` (global-batch
    # activation bytes at one stage boundary), ``global_batch``, and
    # optionally ``layer_costs`` (per-layer relative flop/byte
    # weights; None means uniform). pp>1 plans can only be priced —
    # and only get enumerated — when this record exists.
    pipeline: Optional[Dict[str, Any]] = None

    def resolved(self) -> "CostInputs":
        out = dataclasses.replace(self)
        if not out.peak_flops:
            out.peak_flops = NOMINAL_PEAK_FLOPS
            out.peak_is_nominal = True
        if not out.hbm_bps:
            out.hbm_bps = NOMINAL_HBM_BPS
        if not out.ici_bps:
            out.ici_bps = NOMINAL_ICI_BPS
        return out


@dataclasses.dataclass
class PlanCost:
    """Predicted step time for one plan, with the per-term breakdown
    that makes the decision explainable (flight recorder)."""

    plan: Plan
    total_s: float
    terms: Dict[str, float]
    # the per-term ratios that were APPLIED (tune/calibrate.py), or
    # None for a nominal-constants prediction — every downstream
    # artifact can tell a calibrated score from a nominal one
    calibration: Optional[Dict[str, float]] = None
    # pp>1 plans only: the schedule record that explains the score —
    # bubble fraction, rounded microbatch count, and the balanced
    # stage cut (so ``tune_decision`` shows WHERE the layers were
    # split and what the bubble cost)
    pipeline: Optional[Dict[str, Any]] = None

    def as_dict(self) -> Dict[str, Any]:
        out = {
            "plan": self.plan.describe(),
            "dp": self.plan.dp, "tp": self.plan.tp,
            "pp": self.plan.pp,
            "run_option": self.plan.run_option,
            "predicted_ms": round(self.total_s * 1e3, 6),
            "terms_ms": {k: round(v * 1e3, 6)
                         for k, v in self.terms.items()},
            "calibration": self.calibration,
        }
        if self.pipeline is not None:
            out["pipeline"] = self.pipeline
        return out


def ring_allreduce_bytes(payload_bytes: float, k: int) -> float:
    """Bytes moved on the wire by a k-way ring all-reduce of
    ``payload_bytes`` (reduce-scatter + all-gather: ~2x(k-1)/k)."""
    if k <= 1:
        return 0.0
    return 2.0 * payload_bytes * (k - 1) / k


def gather_bytes(payload_bytes: float, k: int) -> float:
    """One-way k-way all-gather / reduce-scatter wire bytes."""
    if k <= 1:
        return 0.0
    return float(payload_bytes) * (k - 1) / k


def _shard_fraction(k: int) -> float:
    """(k-1)/k — the fraction of a gathered payload that actually
    crosses the wire (each device already holds its own shard)."""
    return 0.0 if k <= 1 else (k - 1) / k


def lookup_wire_bytes(table_shape: Sequence[int], n_ids: int,
                      n_cnt: int, repl_bytes: int,
                      elem_bytes: int) -> int:
    """Per-step wire bytes of ONE sharded lookup event — the single
    source of truth shared by ``Engine.sparse_wire_bytes_per_step``
    and ``tools/wire_bytes_report.py`` (ISSUE 10 satellite): forward
    all_gather(ids, int32) + psum_scatter(rows) + backward
    all_gather(row grads) in the TABLE's dtype, the optional
    occurrence-count plane (int32), plus the recorded cross-replica
    combine bytes."""
    dim = int(np.prod(table_shape[1:])) if len(table_shape) > 1 else 1
    return int(n_ids * 4 + 2 * n_ids * dim * elem_bytes + n_cnt * 4
               + repl_bytes)


def dense_alternative_bytes(table_shape: Sequence[int],
                            elem_bytes: int) -> int:
    """Wire bytes of ring-all-reducing one table's full dense [V, D]
    gradient (~2 bytes moved per gradient byte) — the reference's
    AllReduce-everything baseline for that variable."""
    return int(2 * int(np.prod(table_shape)) * elem_bytes)


def wire_summary(wire: Dict[str, Any],
                 table_elem_bytes: int = 4) -> Dict[str, Any]:
    """Derived ratios of an ``Engine.sparse_wire_bytes_per_step()``
    accounting — the math ``tools/wire_bytes_report.py`` used to
    duplicate inline. The fp32 reference rescales the dense
    alternative to 4-byte elements (the reference ships fp32 dense
    gradients whatever the table dtype)."""
    sparse = int(wire.get("sparse_path_bytes") or 0)
    dense = int(wire.get("dense_allreduce_bytes") or 0)
    dense_fp32_ref = dense * 4 // int(table_elem_bytes)
    return {
        "sparse_over_dense": (sparse / dense) if dense else None,
        "dense_fp32_reference_bytes": dense_fp32_ref,
        "sparse_over_dense_fp32_ref": ((sparse / dense_fp32_ref)
                                       if dense_fp32_ref else None),
    }


def pipeline_bubble(microbatches: int, stages: int,
                    virtual_stages: int = 1) -> Dict[str, float]:
    """Bubble accounting of the SPMD pipeline schedules in
    ``ops/pipeline.py`` — the ONE owner of the tick math.

    The interleaved schedule rounds M up to whole rounds of S
    (``ops/pipeline._rounded_microbatches``); the ragged padding runs
    masked bubble entries, so the model prices the ROUNDED M — the
    predicted bubble matches what actually executes (ISSUE 18
    satellite). Ticks = V*M_sched + S - 1, ideal = V*M, so

        bubble_fraction = (S - 1) / (V*M_sched + S - 1)
        on_chip_scale   = (V*M_sched + S - 1) / (V*M)

    ``on_chip_scale`` multiplies the plan's on-chip roofline term: at
    M % S == 0 it equals 1/(1 - bubble_fraction)."""
    M, S, V = int(microbatches), int(stages), int(virtual_stages)
    if M < 1 or S < 1 or V < 1:
        raise ValueError(
            f"pipeline_bubble needs M, S, V >= 1; got M={M} S={S} "
            f"V={V}")
    m_sched = M if V == 1 else -(-M // S) * S
    ticks = V * m_sched + S - 1
    return {
        "bubble_fraction": (S - 1) / ticks,
        "on_chip_scale": ticks / (V * M),
        "microbatches_scheduled": m_sched,
        "ticks": ticks,
    }


def pipeline_wire_bytes(act_bytes: float, microbatches: int,
                        stages: int, virtual_stages: int = 1,
                        schedule: str = "gpipe", dp: int = 1,
                        tp: int = 1) -> Dict[str, float]:
    """Inter-stage transfer accounting — the ONE owner of the
    pipeline wire math (``predict`` and ``tools/wire_bytes_report.py``
    both call it).

    ``act_bytes`` is the GLOBAL-batch activation at one stage
    boundary; one ppermute hop carries one microbatch of one replica
    row, ``per_hop_bytes = act_bytes / (M * dp)``. The SPMD schedule
    ppermutes EVERY tick on every device (masked entries move zeros —
    physically real traffic), so the mesh-global activation bytes are
    ``per_hop * dp * tp * S * ticks`` (``tp`` columns each run an
    identical ring). Under 1F1B the cotangent stream mirrors the
    forward hops and doubles the total."""
    M = int(microbatches)
    bub = pipeline_bubble(M, stages, virtual_stages)
    per_hop = float(act_bytes) / (M * max(int(dp), 1))
    sends_per_tick = max(int(dp), 1) * max(int(tp), 1) * int(stages)
    activation = per_hop * sends_per_tick * bub["ticks"]
    cotangent = activation if str(schedule) == "1f1b" else 0.0
    return {
        "per_hop_bytes": per_hop,
        "ticks": bub["ticks"],
        "bubble_fraction": bub["bubble_fraction"],
        "microbatches_scheduled": bub["microbatches_scheduled"],
        "activation_bytes": activation,
        "cotangent_bytes": cotangent,
        "total_bytes": activation + cotangent,
    }


def balanced_stage_cut(layer_costs: Sequence[float],
                       stages: int) -> Tuple[list, list]:
    """Contiguous partition of per-layer costs into ``stages`` groups
    minimizing the maximum group sum (classic linear-partition DP).
    Returns ``(boundaries, stage_sums)``: ``boundaries`` has
    ``stages + 1`` entries with ``boundaries[s]:boundaries[s+1]`` the
    layers of stage s. The tuner records the cut in the scored
    artifact so ``tune_decision`` explains where the layers were
    split; the imbalance factor ``stages * max(sums) / sum(sums)``
    scales the on-chip term (a perfectly balanced cut scores 1)."""
    costs = [float(c) for c in layer_costs]
    L, S = len(costs), int(stages)
    if S < 1 or L < S:
        raise ValueError(
            f"balanced_stage_cut needs 1 <= stages <= num_layers; "
            f"got stages={S} over {L} layer(s)")
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def span(i, j):
        return prefix[j] - prefix[i]

    # dp[s][j] = minimal max-group-sum splitting costs[:j] into s groups
    INF = float("inf")
    dp_tab = [[INF] * (L + 1) for _ in range(S + 1)]
    cut = [[0] * (L + 1) for _ in range(S + 1)]
    dp_tab[0][0] = 0.0
    for s in range(1, S + 1):
        for j in range(s, L + 1):
            for i in range(s - 1, j):
                cand = max(dp_tab[s - 1][i], span(i, j))
                if cand < dp_tab[s][j]:
                    dp_tab[s][j] = cand
                    cut[s][j] = i
    bounds = [L]
    j = L
    for s in range(S, 0, -1):
        j = cut[s][j]
        bounds.append(j)
    bounds.reverse()
    sums = [span(bounds[s], bounds[s + 1]) for s in range(S)]
    return bounds, sums


def predict(plan: Plan, inputs: CostInputs) -> PlanCost:
    """Score one plan. Pure; see the module docstring for the model."""
    inp = inputs.resolved()
    n = plan.num_devices

    # ---- pipeline terms (ISSUE 18): a pp>1 plan scales its on-chip
    # roofline by the bubble (rounded-M ticks over ideal work) times
    # the stage-cut imbalance, and adds an inter-stage ppermute wire
    # term. pp=1 plans take none of this path — their breakdown stays
    # byte-identical to the 2-D model.
    on_scale = 1.0
    wire_pp = 0.0
    pp_record = None
    if plan.pp > 1:
        pl = inp.pipeline
        if not pl:
            raise ValueError(
                f"plan {plan.describe()} has pp>1 but "
                "CostInputs.pipeline is missing — only models that "
                "declare pipeline_info can be priced for pipeline "
                "plans")
        S = plan.pp
        V = max(int(plan.virtual_stages), 1)
        M = int(plan.microbatches
                or pl.get("microbatches") or 1)
        schedule = str(pl.get("schedule") or "gpipe")
        layer_costs = pl.get("layer_costs")
        if not layer_costs and pl.get("num_layers"):
            layer_costs = [1.0] * int(pl["num_layers"])
        cut, sums, imbalance = None, None, 1.0
        if layer_costs:
            cut, sums = balanced_stage_cut(layer_costs, S)
            total_c = sum(sums)
            imbalance = (S * max(sums) / total_c) if total_c else 1.0
        bub = pipeline_bubble(M, S, V)
        on_scale = bub["on_chip_scale"] * imbalance
        act_bytes = float(pl.get("act_bytes") or 0.0)
        if not act_bytes:
            # derivable fallback: one stage boundary carries the whole
            # global batch's [tokens, model_dim] activation
            act_bytes = (float(pl.get("global_batch") or 0)
                         * float(pl.get("model_dim") or 0)
                         * float(pl.get("act_itemsize") or 4))
        wires = pipeline_wire_bytes(
            act_bytes, M, S, V,
            schedule=schedule, dp=plan.dp, tp=plan.tp)
        wire_pp = wires["total_bytes"]
        pp_record = {
            "pp": S, "virtual_stages": V, "microbatches": M,
            "microbatches_scheduled": bub["microbatches_scheduled"],
            "schedule": schedule,
            "bubble_fraction": round(bub["bubble_fraction"], 6),
            "imbalance": round(imbalance, 6),
            "stage_cut": cut,
            "stage_costs": ([round(v, 6) for v in sums]
                            if sums else None),
        }

    compute_s = float(inp.flops) / (n * inp.peak_flops) * on_scale
    # kernel-aware HBM term: stream bytes split across devices like
    # cost_analysis bytes; resident (weight-fetch) bytes are paid per
    # device, so the mesh-global total is resident * n
    lstm_bytes = (float(inp.lstm_stream_bytes)
                  + float(inp.lstm_resident_bytes) * n)
    attn_bytes = float(inp.attn_stream_bytes)
    hbm_s = (float(inp.hbm_bytes) + lstm_bytes + attn_bytes) \
        / (n * inp.hbm_bps) * on_scale

    # dense (non-table) grads: full-mesh ring in every run option (the
    # batch axis spans the whole mesh, so every device holds a full
    # gradient to combine)
    wire_dense = ring_allreduce_bytes(inp.dense_grad_bytes, n)
    # ZeRO storage tax (SHARD): sharded dense params all-gathered for
    # forward AND backward consumption
    wire_zero = 0.0
    if plan.run_option == consts.RUN_SHARD:
        wire_zero = 2.0 * gather_bytes(inp.dense_grad_bytes, plan.tp)

    # tables: dense ring under AR; sparse exchange otherwise
    if plan.run_option == consts.RUN_AR:
        wire_table = ring_allreduce_bytes(inp.table_grad_bytes, n)
    else:
        # shard exchange rescaled from the probe's shard width; zero
        # when tp == 1 (rows are device-local, the engine takes the
        # plain-gather path)
        f_probe = _shard_fraction(inp.probe_tp)
        fwd = (inp.sparse_fwd_bytes * _shard_fraction(plan.tp) / f_probe
               if f_probe > 0 else
               # probe never sharded (tp==1 probe): approximate the
               # exchange with the dense shard-grad ring over tp — an
               # upper-bound stand-in, logged via the term name
               ring_allreduce_bytes(inp.table_grad_bytes / max(plan.tp, 1),
                                    plan.tp))
        f_repl_probe = _shard_fraction(inp.probe_dp)
        if inp.sparse_repl_bytes and f_repl_probe > 0:
            repl = (inp.sparse_repl_bytes
                    * _shard_fraction(plan.dp) / f_repl_probe)
        else:
            # probe mesh had one replica row, so nothing was recorded:
            # estimate the combine as each shard's dense [rows/tp, D]
            # grad psum'd over the dp rows
            repl = ring_allreduce_bytes(
                inp.table_grad_bytes / max(plan.tp, 1), plan.dp)
        wire_table = fwd + repl

    wire_bytes = wire_dense + wire_zero + wire_table + wire_pp
    # measured calibration (tune/calibrate.py): each term divides by
    # its persisted predicted/measured ratio, replacing the nominal
    # exchange rates with the rig's measured ones. Applied to the
    # underlying terms (compute AND hbm share the on_chip ratio — the
    # trace can't split what the chip overlaps) so the breakdown stays
    # consistent with the total.
    cal = inp.calibration or {}
    r_on = float(cal.get("on_chip", 1.0)) or 1.0
    r_wire = float(cal.get("wire", 1.0)) or 1.0
    compute_s /= r_on
    hbm_s /= r_on
    wire_s = wire_bytes / (n * inp.ici_bps) / r_wire
    # sync=False bounded staleness: the delayed-gradient exchange
    # overlaps the next step's compute; only the excess serializes
    hidden_s = min(wire_s, compute_s) if not plan.sync else 0.0
    total = max(compute_s, hbm_s) + (wire_s - hidden_s)
    terms = {
        "compute_s": compute_s,
        "hbm_s": hbm_s,
        # informational sub-term (INCLUDED in hbm_s, not additive):
        # the pallas-LSTM kernel's share of the HBM ceiling, so the
        # tune_decision artifact shows the kernel was priced
        "hbm_lstm_kernel_s": lstm_bytes / (n * inp.hbm_bps) / r_on,
        # same pattern for the paged-attention decode kernel
        "hbm_attn_kernel_s": attn_bytes / (n * inp.hbm_bps) / r_on,
        "wire_dense_s": wire_dense / (n * inp.ici_bps) / r_wire,
        "wire_zero_shard_s": wire_zero / (n * inp.ici_bps) / r_wire,
        "wire_table_s": wire_table / (n * inp.ici_bps) / r_wire,
        "wire_hidden_s": hidden_s,
    }
    if plan.pp > 1:
        # the inter-stage ppermute stream (ADDITIVE, part of wire_s);
        # calibrate.py folds it into the 'wire' term like any other
        terms["wire_pp_s"] = wire_pp / (n * inp.ici_bps) / r_wire
        # informational: the on-chip seconds the bubble + stage-cut
        # imbalance added (INCLUDED in compute_s/hbm_s, not additive)
        terms["pp_bubble_s"] = (max(compute_s, hbm_s)
                                * (1.0 - 1.0 / on_scale))
    return PlanCost(plan=plan, total_s=total, terms=terms,
                    calibration=(dict(cal) if cal else None),
                    pipeline=pp_record)


def inputs_from_engine(engine, tune_config=None,
                       calibration: Optional[Dict[str, float]] = None
                       ) -> CostInputs:
    """Extract :class:`CostInputs` from one built (not necessarily
    compiled) engine — host-side only: a re-trace + lower at worst,
    never a device execution. Lives here (duck-typed) so the model
    stays importable without the engine and the engine can import the
    shared wire formulas without a cycle."""
    import jax

    from parallax_tpu.common import flops as flops_lib
    from parallax_tpu.core import mesh as mesh_lib

    costs = engine.step_cost_analysis(cheap_only=False) or {}
    flops = float(costs.get("flops") or 0.0)
    hbm = float(costs.get("bytes accessed")
                or costs.get("bytes_accessed") or 0.0)

    dense_b = 0
    table_b = 0
    for vs in engine.plan.var_specs.values():
        try:
            elem = (np.dtype(vs.dtype).itemsize
                    if vs.dtype is not None else 4)
        except TypeError:
            elem = 4
        nbytes = int(np.prod(vs.shape)) * elem if vs.shape else elem
        if vs.is_sparse:
            table_b += nbytes
        else:
            dense_b += nbytes

    sparse_fwd = 0
    sparse_repl = 0
    for tshape, n_ids, n_cnt, repl_bytes, _sparse_repl, elem in \
            getattr(engine, "_lookup_records", ()):
        sparse_fwd += lookup_wire_bytes(tshape, n_ids, n_cnt, 0, elem)
        sparse_repl += int(repl_bytes)

    mesh = engine.mesh
    # pallas-LSTM kernel traffic (ops/pallas_lstm trace records for
    # THIS engine's mesh — recorded when the step traced; the
    # cost_analysis lower above is such a trace). A record whose
    # backward runs as the XLA residual scan or the recompute VJP
    # counts only the forward custom call ( + residual streams for
    # 'scan'): the XLA backward itself is priced by cost_analysis.
    lstm_stream = 0.0
    lstm_resident = 0.0
    try:
        from parallax_tpu.ops import pallas_lstm
        # records are per distinct trace signature, so one layer
        # traced at several batch shapes (compile-ahead buckets, an
        # eval step) leaves one record per B — collapse each
        # (layer-shape, sharding, bwd) group to its LARGEST batch,
        # the step the roofline prices, instead of summing buckets
        # into phantom traffic
        by_layer: Dict[Tuple, dict] = {}
        for rec in pallas_lstm.trace_records(mesh):
            key = (rec["T"], rec["E"], rec["H"], rec["P"],
                   rec["x_itemsize"], rec["w_itemsize"],
                   rec["n_shards"], rec["bwd"])
            if key not in by_layer or rec["B"] > by_layer[key]["B"]:
                by_layer[key] = rec
        for rec in by_layer.values():
            acct = pallas_lstm.kernel_hbm_bytes(
                rec["T"], rec["B"], rec["E"], rec["H"], rec["P"],
                rec["x_itemsize"], rec["w_itemsize"], bwd=rec["bwd"])
            lstm_stream += acct["stream_bytes"]
            lstm_resident += acct["resident_bytes_per_device"]
    except Exception:   # never fail plan pricing for the hint term
        pass
    # paged-attention kernel traffic (ops/pallas_paged_attention trace
    # records, impl='kernel' only — the einsum executor is ordinary
    # XLA that cost_analysis prices itself). Records dedup by static
    # signature, so identical decoder layers collapse to one record
    # (the lstm precedent); live pages are runtime-dynamic, so each
    # record prices at the table-width upper bound.
    attn_stream = 0.0
    try:
        from parallax_tpu.ops import pallas_paged_attention
        for rec in pallas_paged_attention.trace_records(mesh):
            if rec["impl"] != "kernel":
                continue
            acct = pallas_paged_attention.kernel_hbm_bytes(
                rec["S"], rec["G"], rec["D"], rec["page_size"],
                rec["S"] * rec["P"], rec["itemsize"])
            attn_stream += acct["total_bytes"]
    except Exception:   # never fail plan pricing for the hint term
        pass
    # pipeline capability (ISSUE 18): a model that declares
    # pipeline_info makes pp>1 plans enumerable and priceable. The
    # boundary activation bytes come from the probe's batch shapes —
    # [B, T] leading feed x model_dim x activation element size.
    pipeline = None
    pinfo = getattr(getattr(engine, "model", None),
                    "pipeline_info", None)
    if pinfo:
        pipeline = dict(pinfo)
        shapes = getattr(engine, "_batch_shapes", None)
        lead = None
        if isinstance(shapes, dict):
            for leaf in jax.tree.leaves(shapes):
                shp = getattr(leaf, "shape", None)
                if shp and len(shp) >= 1:
                    if lead is None or len(shp) > len(lead):
                        lead = tuple(shp)
        if lead:
            b = int(lead[0])
            tokens = b * int(lead[1]) if len(lead) > 1 else b
            pipeline.setdefault("global_batch", b)
            dim = int(pipeline.get("model_dim") or 0)
            elem = int(pipeline.get("act_itemsize") or 4)
            pipeline.setdefault("act_bytes", tokens * dim * elem)
    dev = jax.devices()[0]
    peak = flops_lib.device_peak_flops(dev.platform, dev.device_kind)
    tc = tune_config
    return CostInputs(
        flops=flops, hbm_bytes=hbm,
        dense_grad_bytes=dense_b, table_grad_bytes=table_b,
        sparse_fwd_bytes=sparse_fwd, sparse_repl_bytes=sparse_repl,
        lstm_stream_bytes=lstm_stream,
        lstm_resident_bytes=lstm_resident,
        attn_stream_bytes=attn_stream,
        probe_dp=int(mesh.shape[mesh_lib.AXIS_REPL]),
        probe_tp=int(mesh.shape[mesh_lib.AXIS_SHARD]),
        num_devices=mesh_lib.num_devices(mesh),
        peak_flops=(tc.peak_flops if tc and tc.peak_flops else peak),
        hbm_bps=(tc.hbm_gbps * 1e9 if tc and tc.hbm_gbps else None),
        ici_bps=(tc.ici_gbps * 1e9 if tc and tc.ici_gbps else None),
        peak_is_nominal=not bool(
            (tc and tc.peak_flops) or peak),
        calibration=calibration,
        pipeline=pipeline)
