"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

The reduction lives with the benchmark so that every PR computes the
same number in the same way. It reads the file with nothing but JAX
(``jax.profiler.ProfileData``): the device planes, on each the line of
XLA operations, and on the host plane the marks the benchmark wrote.

Times are seconds on the trace's clock. An operation is
``Op(name, start, end, category)``. Operations on one device nest (a
``while`` holds its body), so busy time is the union of their intervals
and an operation's own time is its duration less what its children
cover.

Shape of a TPU v5e trace, as read from the traces recorded in PR 23
(``tests/data``): a plane ``/device:TPU:<n>`` holds the lines ``XLA
Modules`` (one event a program run, ``jit_train_step(<fingerprint>)``),
``XLA Ops`` (the core's serial stream; an event is named by the whole
HLO instruction text and carries no category) and ``Async XLA Ops`` (an
asynchronous operation from its ``-start`` to its ``-done``: copies and,
across chips, collectives in flight). The marks and the host's threads
are on ``/host:CPU``.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from lib import stats

Op = collections.namedtuple("Op", "name start end category")

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench."

# An event of the operations' line is named by the whole HLO
# instruction: ``%fusion.22 = f32[793470,512]{1,0:T(8,128)} fusion(...),
# kind=kCustom, calls=...``. The opcode is the first lower-case word
# that opens a parenthesis after the `` = `` (shapes and layouts open
# theirs after ``T``, ``S`` or a bracket).
_INSTRUCTION = re.compile(r"^%(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"(?:^|[\s)}\]])(?P<opcode>[a-z][a-z0-9\-]*)\(")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast",
                "ragged-all-to-all")
_CONTAINERS = ("while", "conditional", "call")


def parse_instruction(event_name: str):
    """``(name, opcode, result)`` of an event of the operations' line:
    the instruction's name without ``%``, its opcode, and its result
    shape without the layout. An event that is not an instruction is
    its own name and opcode."""
    m = _INSTRUCTION.match(event_name)
    if m is None:
        base = re.sub(r"\.\d+$", "", event_name)
        return event_name, base, ""
    rest = m.group("rest")
    op = _OPCODE.search(rest)
    if op is None:
        return m.group("name"), re.sub(r"\.\d+$", "", m.group("name")), ""
    result = re.sub(r"\{[^{}]*\}", "", rest[:op.start("opcode")]).strip()
    return m.group("name"), op.group("opcode"), result


def categorize(event_name: str) -> str:
    """``collective`` (with the async halves), ``mosaic`` (a custom call
    whose target is ``tpu_custom_call``: a Pallas kernel), ``container``
    (``while`` and the like, which hold other operations and do no work
    of their own), or the opcode."""
    _name, opcode, _ = parse_instruction(event_name)
    base = re.sub(r"-(start|done)$", "", opcode)
    if base in _COLLECTIVES:
        return "collective"
    if opcode == "custom-call":
        return "mosaic" if 'custom_call_target="tpu_custom_call"' \
            in event_name else "custom-call"
    if opcode in _CONTAINERS:
        return "container"
    return opcode


def short_name(event_name: str, limit: int = 120) -> str:
    """``fusion.22 fusion f32[793470,512]``, and for a custom call its
    target: what a reader needs of the instruction the trace prints."""
    name, opcode, result = parse_instruction(event_name)
    if not result and opcode == re.sub(r"\.\d+$", "", name):
        return event_name[:limit]
    text = f"{name} {opcode} {result}"
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    if target:
        text += f" target={target.group(1)}"
    kind = re.search(r"kind=(k\w+)", event_name)
    if kind:
        text += f" {kind.group(1)}"
    return text[:limit]


class Trace(NamedTuple):
    devices: Dict[int, List[Op]]        # device ordinal -> operations
    asyncs: Dict[int, List[Op]]         # device ordinal -> async spans
    modules: Dict[int, List[Op]]        # device ordinal -> program runs
    marks: Dict[str, List[float]]       # mark name -> start times
    lines: Dict[str, Dict[str, int]]    # plane -> line -> event count


def read(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    asyncs: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    marks: Dict[str, List[float]] = collections.defaultdict(list)
    lines: Dict[str, Dict[str, int]] = {}
    categories: Dict[str, str] = {}     # the same instruction recurs

    def ops_of(line):
        out = []
        for ev in line.events:
            cat = categories.get(ev.name)
            if cat is None:
                cat = categories[ev.name] = categorize(ev.name)
            start = ev.start_ns * 1e-9
            out.append(Op(ev.name, start, start + ev.duration_ns * 1e-9,
                          cat))
        return out

    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        counts = lines.setdefault(plane.name, {})
        for line in plane.lines:
            if m is not None and line.name in (OPS_LINE, ASYNC_LINE):
                ops = ops_of(line)
                counts[line.name] = len(ops)
                into = devices if line.name == OPS_LINE else asyncs
                into.setdefault(int(m.group(2)), []).extend(ops)
            elif m is not None and line.name == MODULES_LINE:
                runs = [Op(module_name(ev.name), ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9, "module")
                        for ev in line.events]
                counts[line.name] = len(runs)
                modules.setdefault(int(m.group(2)), []).extend(runs)
            elif m is None:
                n = 0
                for ev in line.events:
                    n += 1
                    if ev.name.startswith(MARK_PREFIX):
                        marks[ev.name].append(ev.start_ns * 1e-9)
                counts[line.name] = n
            else:
                counts[line.name] = sum(1 for _ in line.events)
    for ops in list(devices.values()) + list(asyncs.values()) \
            + list(modules.values()):
        ops.sort(key=lambda o: (o.start, -o.end))
    return Trace(devices, asyncs, modules, dict(marks), lines)


def module_name(event_name: str) -> str:
    """``jit_train_step(123456789)`` -> ``jit_train_step``: the line of
    program runs prints the program's name and its fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def runs_of(modules: List[Op], name_part: str, lo: float, hi: float):
    """The runs of the programs whose name holds ``name_part`` that
    start inside ``[lo, hi)``."""
    return [r for r in modules
            if name_part in r.name and lo <= r.start < hi]


def window(trace: Trace) -> Optional[Tuple[float, float]]:
    """The traced window on the trace's clock: from the benchmark's
    ``bench.sync`` mark to its ``bench.end`` mark."""
    if "bench.sync" not in trace.marks or "bench.end" not in trace.marks:
        return None
    return min(trace.marks["bench.sync"]), max(trace.marks["bench.end"])


def busy(ops: List[Op], lo: float, hi: float):
    """The disjoint intervals of ``[lo, hi]`` in which an operation ran."""
    return stats.merge_intervals(
        stats.clip_intervals([(o.start, o.end) for o in ops], lo, hi))


def idle_gaps(busy_intervals, lo: float, hi: float):
    """The gaps of ``[lo, hi]`` that no operation covers, longest first."""
    gaps = stats.subtract_intervals([(lo, hi)], busy_intervals)
    return sorted(gaps, key=lambda g: g[0] - g[1])


def self_times(ops: List[Op], lo: float, hi: float) -> Dict[str, float]:
    """Each operation name's own time inside ``[lo, hi]``: duration
    less the part its children cover. ``ops`` sorted by start, parents
    first."""
    out: Dict[str, float] = collections.defaultdict(float)
    stack: List[list] = []      # [op, clipped start, clipped end, child time]

    def close(entry):
        op, s, e, child = entry
        out[op.name] += max(0.0, (e - s) - child)

    for op in ops:
        s, e = max(op.start, lo), min(op.end, hi)
        if e <= s:
            continue
        while stack and stack[-1][0].end <= op.start:
            close(stack.pop())
        if stack:
            # the child's time inside its parent, once, at the parent
            ps, pe = stack[-1][1], stack[-1][2]
            stack[-1][3] += max(0.0, min(e, pe) - max(s, ps))
        stack.append([op, s, e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def category_intervals(ops: List[Op], category: str, lo: float, hi: float):
    return stats.merge_intervals(stats.clip_intervals(
        [(o.start, o.end) for o in ops if o.category == category], lo, hi))


def category_seconds(ops: List[Op], category: str, lo: float,
                     hi: float) -> float:
    return stats.total(category_intervals(ops, category, lo, hi))


def count(ops: List[Op], category: str, lo: float, hi: float) -> int:
    return sum(1 for o in ops
               if o.category == category and lo <= o.start < hi)


def exposed_seconds(ops: List[Op], category: str, lo: float,
                    hi: float) -> float:
    """The part of the ``category`` operations during which no other
    operation ran on the device (containers such as ``while`` do no
    work of their own and do not count as cover)."""
    mine = category_intervals(ops, category, lo, hi)
    others = stats.merge_intervals(stats.clip_intervals(
        [(o.start, o.end) for o in ops
         if o.category not in (category, "container")], lo, hi))
    return stats.total(stats.subtract_intervals(mine, others))
