"""Measured per-op / per-collective attribution from jax.profiler traces.

PR 5's timeline says where a step's HOST wall time went; the cost
model (tune/costmodel.py) predicts where the DEVICE time should go.
Nothing in the repo measured where it actually goes — this module
closes that gap. It has two halves:

* a **pure, unit-testable parser** over trace-event JSON (the
  ``*.trace.json.gz`` a ``jax.profiler`` capture writes): pick the
  device tracks, compute per-op *self* durations (nested events —
  a while-loop op containing its body's ops — are resolved by interval
  containment so nothing double-counts), merge overlapping intervals
  for the busy-time union, and bucket every op into the taxonomy
  compute / collective (all-reduce, all-gather, reduce-scatter,
  all-to-all, collective-permute) / copy / infeed / outfeed. The
  unattributed **residual** — wall time inside the capture window
  where no tracked device op ran — is always reported, never hidden:
  ``coverage`` is the fraction the per-op account explains.
* **HLO metadata joins**: ``build_hlo_index`` parses a compiled
  module's HLO text (``metadata={op_name="jit(train_step)/
  transpose(jvp(sampled_softmax))/dot_general" ...}``) so trace op
  names (``fusion.3``, ``dot.1``) map back to the layer whose
  ``jax.named_scope`` emitted them (``LAYER_SCOPES``; the device trace
  itself carries no ``op_name``, the compiled text does), and the
  dense-vs-sparse variable split — the paper's core axis — falls out
  of the layer (``SPARSE_LAYERS``). The index of the step that runs is
  the engine's to hand over (``Engine.layer_index()``).

The capture side is owned by ``profiler.ProfileHook`` (windowed
on-demand capture, ``session.profile_steps(n)``); the session exports
the parsed result as lazy ``profile.*`` registry gauges and a
chrome-lane summary. Everything here is host-side JSON work — no jax
import on the parse path, so the golden-fixture tests run without a
backend.

Backend honesty: on the XLA:CPU thunk runtime (the tier-1 rig) and on
TPU, op events carry ``args.hlo_op`` / ``args.hlo_module`` — that is
the tested device-track filter. A backend emitting no ``hlo_op``
events falls back to complete events on device-named process tracks
(best-effort, flagged via ``track_basis``).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# the attribution taxonomy, in presentation order
CATEGORIES = ("compute", "collective", "copy", "infeed", "outfeed")

# canonical collective kinds (the per-collective attribution axis);
# -start/-done async halves fold onto their base kind
_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute",
                     "collective-broadcast")

# The layers of the train steps, declared once: the names of the
# ``jax.named_scope``s around ops/embedding.embedding_lookup,
# ops/pallas_lstm.lstm_scan (and models/lm1b's lax.scan branch),
# ops/sampled_softmax.sampled_softmax_loss, and core/engine.train_step's
# dense (clip + optimizer + apply) and table (ops/sparse_optim row
# scatter) updates; and of models/keye_vl2's block: ``attention``
# (``_layer``: projections, norms, RoPE, the attention over the selected
# keys, the output product), ``indexer`` (``_layer``'s three indexer
# projections and ops/sparse_attention._chunk's scores, selection and
# loss; the inner scope, so it wins inside ``attention``), ``moe``
# (``_layer``: norm, router, grouping, the experts' products, the
# combine, the auxiliary loss) and ``lm_head`` (``loss_fn``: final
# norm, head, cross-entropy), with ``layer_scan`` around ``loss_fn``'s
# ``lax.scan`` over the blocks for what the scan itself costs (the
# matrices' cast to the compute dtype before the loop, a layer's
# weights cut out of the stack, its kept arrays and gradients written
# into theirs, the loop; the outermost, so a block's own names win);
# ``window_attention`` is ops/pallas_attention's, around the windowed
# flash calls of a layer that attends under a window, forward and
# backward (inside a block's ``attention``, where it wins: models/mellum2
# has both kinds of layer under one loop body, and the account tells a
# window layer's kernels from a full layer's by it); models/olmo_hybrid's
# ``linear_attention`` (a linear layer's mixer), ``delta_rule`` inside it
# (ops/delta_rule's kernels and their call site; the inner scope wins)
# and ``mlp`` (the dense SwiGLU MLP of both kinds); models/trinity's
# ``attn_gate`` (the gate's product and ``o * sigmoid(g)`` between the
# flash kernels' output and ``Wo``, inside ``attention``) and
# ops/moe.shared_expert's ``shared_expert`` (the SwiGLU every token takes,
# inside ``moe``; the inner scopes win, so ``attention`` and ``moe`` keep
# the rest of their blocks); models/glm4_moe_lite's ``mla_latent`` (both
# low-rank paths of the latent attention, their norms, the rotary key's
# broadcast and RoPE, inside ``attention``) and ``mtp`` (a
# multi-token-prediction block's two norms and its input product; the
# block's own ``attention``, ``moe`` and ``lm_head`` keep their names).
# A step holds the scopes of its own model only. ``layer_of`` reads them
# back off a compiled instruction's ``op_name``.
LAYER_SCOPES = ("embedding", "lstm", "sampled_softmax", "layer_scan",
                "attention", "window_attention", "indexer", "cca_mix",
                "attn_gate", "mla_latent", "linear_attention", "delta_rule",
                "mlp", "moe", "router", "shared_expert", "mtp", "lm_head",
                "dense_update", "table_update")
# the row-sharded table path — the paper's sparse side of the
# dense-vs-sparse variable split
SPARSE_LAYERS = ("embedding", "sampled_softmax", "table_update")


def categorize(name: str) -> Tuple[str, Optional[str]]:
    """``(category, collective_kind)`` of one HLO op name.

    Names arrive as instruction names (``all-reduce.1``, ``copy.2``,
    ``broadcast_multiply_fusion``): the ``.N`` uniquifier is stripped,
    fusions are compute whatever their root op contributed to the
    fused name (``copy_subtract_fusion`` is compiled arithmetic, not a
    transfer), and async collective halves (``all-gather-start``)
    fold onto their base kind."""
    base = name.split(".", 1)[0].lower()
    if "fusion" in base:
        return "compute", None
    for kind in _COLLECTIVE_KINDS:
        if base.startswith(kind):
            return "collective", kind
    if base.startswith(("collective", "partition-id", "replica-id")):
        return "collective", "other-collective"
    if base.startswith(("copy", "transpose")):
        return "copy", None
    if base.startswith(("infeed", "recv", "host-to-device")):
        return "infeed", None
    if base.startswith(("outfeed", "send", "device-to-host")):
        return "outfeed", None
    return "compute", None


def merge_intervals(intervals: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of half-open ``(start, end)`` intervals, sorted and
    overlap-merged — the busy-time primitive (a track running two
    overlapping ops is busy once, not twice)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e < s:
            s, e = e, s
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_durations(events: List[dict]) -> List[float]:
    """Per-event self duration on ONE track: ``dur`` minus the direct
    children's ``dur`` (children = events fully contained by interval
    on the same track — a ``while`` op event enclosing its body's op
    events must not double-count the body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["ts"], -events[i]["dur"]))
    child_sum = [0.0] * len(events)
    stack: List[int] = []
    for i in order:
        s = events[i]["ts"]
        e = s + events[i]["dur"]
        while stack and (events[stack[-1]]["ts"]
                         + events[stack[-1]]["dur"]) <= s:
            stack.pop()
        if stack:
            child_sum[stack[-1]] += events[i]["dur"]
        stack.append(i)
    return [max(0.0, ev["dur"] - c)
            for ev, c in zip(events, child_sum)]


def _envelope_wall(merged: List[Tuple[float, float]],
                   steps: Optional[int]) -> float:
    """The measured device step wall (µs) from the globally merged
    busy intervals: split at the ``steps - 1`` largest gaps (the
    inter-step host time — intra-step device gaps are scheduler-hop
    sized because collective events span their own waits) and sum the
    resulting per-step envelopes. Unknown ``steps`` (or a single
    island) keeps the raw span — conservative: coverage can only be
    under-reported, never inflated."""
    if not merged:
        return 0.0
    span = merged[-1][1] - merged[0][0]
    if not steps or steps < 2 or len(merged) < 2:
        return span
    gaps = sorted(
        ((merged[i + 1][0] - merged[i][1], i)
         for i in range(len(merged) - 1)), reverse=True)
    cut_after = {i for _, i in gaps[:steps - 1]}
    wall = 0.0
    start = merged[0][0]
    for i, (_s, e) in enumerate(merged):
        if i in cut_after or i == len(merged) - 1:
            wall += e - start
            if i + 1 < len(merged):
                start = merged[i + 1][0]
    return wall


def _track_meta(events: Sequence[dict]) -> Tuple[Dict, Dict]:
    """(pid -> process name, (pid, tid) -> thread name) metadata."""
    pids: Dict[Any, str] = {}
    tids: Dict[Tuple, str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        args = e.get("args") or {}
        if e.get("name") == "process_name":
            pids[e.get("pid")] = str(args.get("name", ""))
        elif e.get("name") == "thread_name":
            tids[(e.get("pid"), e.get("tid"))] = str(args.get("name",
                                                              ""))
    return pids, tids


def device_op_events(trace: Dict) -> Tuple[List[dict], str]:
    """The device-track complete events to attribute, plus the filter
    basis used (``"hlo_op"`` — the tested path — or
    ``"device_pid"`` best-effort fallback)."""
    events = trace.get("traceEvents", [])
    ops = [e for e in events
           if e.get("ph") == "X"
           and isinstance(e.get("args"), dict)
           and "hlo_op" in e["args"]
           and e.get("dur", 0) > 0]
    if ops:
        return ops, "hlo_op"
    pids, _tids = _track_meta(events)
    device_pids = {p for p, n in pids.items()
                   if "TPU" in n or "/device" in n.lower()}
    ops = [e for e in events
           if e.get("ph") == "X" and e.get("pid") in device_pids
           and e.get("dur", 0) > 0
           and "::" not in e.get("name", "")
           and not e.get("name", "").startswith("$")]
    return ops, "device_pid"


@dataclasses.dataclass
class Attribution:
    """One capture window's parsed account. All times are
    milliseconds. ``wall_ms`` is the measured DEVICE step wall: the
    sum of per-step envelopes (op intervals clustered at the
    ``steps - 1`` largest inter-execution gaps when ``steps`` is
    known — collectives are events that span their own sync waits, so
    intra-step device gaps are scheduler-hop sized while inter-step
    gaps are host time PR 5's timeline already attributes).
    ``attributed_ms`` is the overlap-merged union of op intervals,
    ``residual_ms = wall - attributed`` the device-wall time no
    tracked op explains — reported, never hidden; ``coverage`` their
    ratio. ``window_span_ms`` keeps the raw first-to-last span and
    ``inter_step_ms`` the excluded between-envelope host time, so
    nothing is silently dropped. Category/op/layer totals are
    *self*-duration sums (device-seconds, so concurrent devices add),
    with ``share`` normalized over the self-time total."""

    steps: Optional[int]
    events: int
    tracks: int
    track_basis: str
    wall_ms: float
    window_span_ms: float
    inter_step_ms: float
    attributed_ms: float
    residual_ms: float
    coverage: Optional[float]
    by_category: Dict[str, Dict[str, Any]]
    collectives: Dict[str, Dict[str, Any]]
    top_ops: List[Dict[str, Any]]
    layers: Dict[str, float]
    dense_sparse: Dict[str, float]
    by_module: Dict[str, float]
    source: Optional[str] = None
    # forward-vs-backward self-time split (ISSUE 14): joined from the
    # HLO op_name scope — XLA stamps backward ops with transpose(...)
    # scopes — so a training profile says how much of the step is the
    # backward. Needs hlo_index; all-unmapped without it (visible,
    # never wrong).
    fwd_bwd: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def step_wall_ms(self) -> Optional[float]:
        if not self.steps or self.wall_ms <= 0:
            return None
        return self.wall_ms / self.steps

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["step_wall_ms"] = (round(self.step_wall_ms, 4)
                             if self.step_wall_ms else None)
        return d


def attribute(trace: Dict, steps: Optional[int] = None,
              hlo_index: Optional[Dict[str, Dict]] = None,
              top: int = 20,
              source: Optional[str] = None) -> Attribution:
    """Parse one trace-event document into an :class:`Attribution`.

    Pure: ``trace`` is the loaded JSON, ``hlo_index`` (optional) the
    :func:`build_hlo_index` of the compiled module for layer /
    dense-sparse mapping, ``steps`` the number of training steps the
    window covered (per-step numbers divide by it)."""
    ops, basis = device_op_events(trace)
    if not ops:
        return Attribution(
            steps=steps, events=0, tracks=0, track_basis=basis,
            wall_ms=0.0, window_span_ms=0.0, inter_step_ms=0.0,
            attributed_ms=0.0, residual_ms=0.0,
            coverage=None, by_category={}, collectives={}, top_ops=[],
            layers={}, dense_sparse={}, by_module={}, source=source)

    # per-track self durations (nesting resolved per thread)
    by_track: Dict[Tuple, List[dict]] = {}
    for e in ops:
        by_track.setdefault((e.get("pid"), e.get("tid")),
                            []).append(e)
    self_us: Dict[int, float] = {}
    for tes in by_track.values():
        for e, s in zip(tes, _self_durations(tes)):
            self_us[id(e)] = s

    # busy union + per-step envelope wall across every device track
    intervals = [(e["ts"], e["ts"] + e["dur"]) for e in ops]
    merged = merge_intervals(intervals)
    busy_us = sum(e - s for s, e in merged)
    span_us = merged[-1][1] - merged[0][0]
    wall_us = _envelope_wall(merged, steps)

    cat_tot: Dict[str, float] = {}
    cat_n: Dict[str, int] = {}
    coll_tot: Dict[str, float] = {}
    coll_n: Dict[str, int] = {}
    op_tot: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    op_cat: Dict[str, str] = {}
    layer_tot: Dict[str, float] = {}
    split_tot = {"sparse_self_ms": 0.0, "dense_self_ms": 0.0,
                 "unmapped_self_ms": 0.0}
    dir_tot = {"forward_self_ms": 0.0, "backward_self_ms": 0.0,
               "unmapped_self_ms": 0.0}
    mod_tot: Dict[str, float] = {}
    for e in ops:
        s_ms = self_us[id(e)] / 1e3
        name = e.get("name", "?")
        cat, kind = categorize(name)
        cat_tot[cat] = cat_tot.get(cat, 0.0) + s_ms
        cat_n[cat] = cat_n.get(cat, 0) + 1
        if kind is not None:
            coll_tot[kind] = coll_tot.get(kind, 0.0) + s_ms
            coll_n[kind] = coll_n.get(kind, 0) + 1
        op_tot[name] = op_tot.get(name, 0.0) + s_ms
        op_n[name] = op_n.get(name, 0) + 1
        op_cat[name] = cat
        mod = (e.get("args") or {}).get("hlo_module")
        if mod:
            mod_tot[mod] = mod_tot.get(mod, 0.0) + s_ms
        meta = (hlo_index or {}).get(name)
        layer = layer_of(meta) if meta else None
        layer_tot[layer or "(unmapped)"] = \
            layer_tot.get(layer or "(unmapped)", 0.0) + s_ms
        split = sparse_split(meta) if meta else None
        key = {"sparse": "sparse_self_ms",
               "dense": "dense_self_ms"}.get(split,
                                             "unmapped_self_ms")
        split_tot[key] += s_ms
        direction = direction_of(meta) if meta else None
        dkey = {"forward": "forward_self_ms",
                "backward": "backward_self_ms"}.get(
                    direction, "unmapped_self_ms")
        dir_tot[dkey] += s_ms

    total_self = sum(cat_tot.values()) or 1.0
    by_category = {
        cat: {"self_ms": round(cat_tot.get(cat, 0.0), 4),
              "share": round(cat_tot.get(cat, 0.0) / total_self, 4),
              "events": cat_n.get(cat, 0)}
        for cat in CATEGORIES if cat in cat_tot}
    collectives = {
        kind: {"self_ms": round(v, 4), "events": coll_n[kind]}
        for kind, v in sorted(coll_tot.items(),
                              key=lambda kv: -kv[1])}
    top_ops = []
    for name, v in sorted(op_tot.items(),
                          key=lambda kv: -kv[1])[:int(top)]:
        meta = (hlo_index or {}).get(name)
        top_ops.append({
            "op": name, "category": op_cat[name],
            "self_ms": round(v, 4), "count": op_n[name],
            "layer": layer_of(meta) if meta else None,
            "split": sparse_split(meta) if meta else None,
        })
    return Attribution(
        steps=steps, events=len(ops), tracks=len(by_track),
        track_basis=basis,
        wall_ms=round(wall_us / 1e3, 4),
        window_span_ms=round(span_us / 1e3, 4),
        inter_step_ms=round(max(0.0, span_us - wall_us) / 1e3, 4),
        attributed_ms=round(busy_us / 1e3, 4),
        residual_ms=round(max(0.0, wall_us - busy_us) / 1e3, 4),
        coverage=(round(busy_us / wall_us, 4) if wall_us > 0
                  else None),
        by_category=by_category, collectives=collectives,
        top_ops=top_ops,
        layers={k: round(v, 4)
                for k, v in sorted(layer_tot.items(),
                                   key=lambda kv: -kv[1])[:top]},
        dense_sparse={k: round(v, 4) for k, v in split_tot.items()},
        by_module={k: round(v, 4) for k, v in mod_tot.items()},
        source=source,
        fwd_bwd={k: round(v, 4) for k, v in dir_tot.items()})


# -- HLO metadata joins ------------------------------------------------------

# "[ROOT] %name = type opcode(...) ..., metadata={...}". The type may
# be a tuple with spaces in it (a Pallas kernel's custom call returns
# one), so the opcode is the first lower-case word that opens a
# parenthesis after the " = " (shapes and layouts open theirs after
# ``T``, ``S`` or a bracket). The computation header lines
# ("%fused_computation (param: ...)") don't match — they have no
# " = ".
_HLO_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s+(.*)$")
_HLO_OPCODE_RE = re.compile(r"(?:^|[\s)}\]])([a-z][a-z0-9\-]*)\(")
# not the ``kernel_metadata={}`` inside a custom call's
# ``frontend_attributes``
_HLO_META_RE = re.compile(r"(?<![\w])metadata=\{([^}]*)\}")
_META_FIELD_RE = re.compile(r'(\w+)=(?:"([^"]*)"|(\S+))')


def build_hlo_index(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """{instruction name: {opcode, op_name, source_file,
    source_line}} from optimized-HLO text (``compiled.as_text()``).
    Trace op events are named by these instructions, so this is the
    join key back to the layers' scopes. Pure string parsing;
    instructions without metadata still index (opcode only)."""
    out: Dict[str, Dict[str, Any]] = {}
    for line in hlo_text.splitlines():
        m = _HLO_INSTR_RE.match(line)
        if not m:
            continue
        op = _HLO_OPCODE_RE.search(m.group(2))
        if not op:
            continue
        entry: Dict[str, Any] = {"opcode": op.group(1)}
        meta = _HLO_META_RE.search(line)
        if meta:
            for fm in _META_FIELD_RE.finditer(meta.group(1)):
                key = fm.group(1)
                if key in ("op_name", "source_file", "source_line"):
                    entry[key] = fm.group(2) or fm.group(3)
        out[m.group(1)] = entry
    return out


# one part of an op_name's scope path, AD's wrappers around it
_SCOPE_PART_RE = re.compile(r"^(?:(?:jvp|transpose)\()*([^()]*)\)*$")


def layer_of(meta: Optional[Dict[str, Any]]) -> Optional[str]:
    """The layer one index entry belongs to: the INNERMOST part of its
    ``op_name`` scope path that is one of ``LAYER_SCOPES``, with AD's
    ``jvp(...)`` / ``transpose(...)`` wrappers taken off each part
    (``jit(train_step)/transpose(jvp(sampled_softmax))/jvp(embedding)/
    gather`` -> ``embedding``: the sampled softmax fetches its
    candidate rows through the embedding lookup). None when no part is
    a declared layer."""
    if not meta:
        return None
    for part in reversed((meta.get("op_name") or "").split("/")):
        m = _SCOPE_PART_RE.match(part)
        if m and m.group(1) in LAYER_SCOPES:
            return m.group(1)
    return None


def direction_of(meta: Optional[Dict[str, Any]]) -> Optional[str]:
    """``"backward"`` when the op's ``op_name`` scope path carries a
    ``transpose(...)`` component (XLA's AD-transpose marker — the
    whole backward pass lives under it), ``"forward"`` for any other
    op_name'd op, None when the metadata carries no op_name at all.
    The join key for the training-step fwd/bwd attribution row
    (``attribute()``'s ``by_direction``, ISSUE 14)."""
    if not meta:
        return None
    op_name = meta.get("op_name") or ""
    if not op_name:
        return None
    if any(p.startswith("transpose(") for p in op_name.split("/")):
        return "backward"
    return "forward"


def sparse_split(meta: Optional[Dict[str, Any]]) -> Optional[str]:
    """``"sparse"`` when the op's layer is on the row-sharded table
    path (``SPARSE_LAYERS``), ``"dense"`` for any other declared
    layer, None for an op under no layer's scope."""
    layer = layer_of(meta)
    if layer is None:
        return None
    return "sparse" if layer in SPARSE_LAYERS else "dense"


_HLO_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)


def module_name(hlo_text: str) -> Optional[str]:
    """The compiled module's name (``jit_train_step``), as the device
    trace's line of program runs prints it."""
    m = _HLO_MODULE_RE.search(hlo_text)
    return m.group(1) if m else None


# -- trace loading -----------------------------------------------------------

def find_trace_file(outdir: str) -> Optional[str]:
    """Newest ``*.trace.json(.gz)`` under ``outdir`` (the layout
    ``jax.profiler`` writes: ``plugins/profile/<ts>/<host>...``)."""
    paths = (glob.glob(os.path.join(outdir, "**", "*.trace.json.gz"),
                       recursive=True)
             + glob.glob(os.path.join(outdir, "**", "*.trace.json"),
                         recursive=True))
    if not paths:
        return None
    return max(paths, key=os.path.getmtime)


def load_trace(path_or_dir: str) -> Tuple[Dict, str]:
    """(trace JSON, file path) from a trace file or a capture dir.
    Raises FileNotFoundError when no trace exists there."""
    path = path_or_dir
    if os.path.isdir(path_or_dir):
        found = find_trace_file(path_or_dir)
        if found is None:
            raise FileNotFoundError(
                f"no *.trace.json(.gz) under {path_or_dir!r}")
        path = found
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f), path


__all__ = [
    "Attribution", "CATEGORIES", "LAYER_SCOPES", "SPARSE_LAYERS",
    "attribute", "build_hlo_index", "categorize", "device_op_events",
    "direction_of", "find_trace_file", "layer_of", "load_trace",
    "merge_intervals", "module_name", "sparse_split",
]
