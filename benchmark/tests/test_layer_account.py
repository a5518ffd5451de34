"""The per-layer account (``lib/layer_account``) on hand-made
operations and on two recorded one-chip traces: nesting under a
``while``, an instruction the index holds under no layer, one it does
not hold, another program's runs, a small LM1B's train step with the
program's own layer index, and the readers' ``None`` where there is
nothing to read."""

import gzip
import json
import os
import shutil
import types

import pytest

from lib import layer_account, layers
from reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _op(text, start, end):
    return xplane.Op(text, start, end, xplane.categorize(text))


INDEX = {"module": "jit_train_step",
         "layers": {"while.1": "lstm", "fusion.2": "lstm",
                    "fusion.3": "table_update", "copy.4": None,
                    "fusion.9": "embedding"},
         "scopes_found": ["embedding", "lstm", "table_update"]}

# one step: a while of 4 s whose body's fusion covers 3 s of it, a
# scatter fusion, a copy under no scope, and an instruction the index
# does not hold
OPS = [
    _op("%while.1 = (s32[], f32[8]{0}) while(%tuple), body=%b", 0.0, 4.0),
    _op("%fusion.2 = f32[8]{0} fusion(%p), kind=kLoop", 0.5, 3.5),
    _op("%fusion.3 = f32[100,8]{1,0} fusion(%p), kind=kCustom", 4.0, 6.0),
    _op("%copy.4 = f32[8]{0} copy(%p)", 6.0, 6.5),
    _op("%fusion.77 = f32[8]{0} fusion(%p), kind=kLoop", 7.0, 7.25),
]


def test_account_nests_and_names_every_row():
    acc = layer_account.own_seconds_by_layer(OPS, 0.0, 10.0, INDEX)
    assert acc == {"embedding": 0.0,        # declared, took no time
                   "lstm": pytest.approx(4.0),   # 1 s own + 3 s body
                   "table_update": pytest.approx(2.0),
                   layer_account.UNSCOPED: pytest.approx(0.5),
                   layer_account.UNKNOWN: pytest.approx(0.25)}
    # own times of one serial stream are its busy time
    busy = sum(e - s for s, e in xplane.busy(OPS, 0.0, 10.0))
    assert sum(acc.values()) == pytest.approx(busy)


def test_account_clips_to_the_window():
    acc = layer_account.own_seconds_by_layer(OPS, 3.0, 5.0, INDEX)
    assert acc["lstm"] == pytest.approx(1.0)
    assert acc["table_update"] == pytest.approx(1.0)
    assert acc[layer_account.UNSCOPED] == 0.0
    assert acc[layer_account.UNKNOWN] == 0.0


def test_another_programs_operations_are_unknown():
    """``fusion.3`` inside a run of another program is not the indexed
    program's ``fusion.3``."""
    runs = [xplane.Op("jit_train_step", 0.0, 3.9, "module"),
            xplane.Op("jit_other", 3.9, 6.2, "module"),
            xplane.Op("jit_train_step", 6.9, 7.3, "module")]
    acc = layer_account.own_seconds_by_layer(OPS, 0.0, 10.0, INDEX, runs)
    assert acc["lstm"] == pytest.approx(4.0)
    assert acc["table_update"] == 0.0
    assert acc[layer_account.UNSCOPED] == 0.0
    # fusion.3 and copy.4 ran in jit_other; fusion.77 is not indexed
    assert acc[layer_account.UNKNOWN] == pytest.approx(2.0 + 0.5 + 0.25)


def _context(trace, index, spans=()):
    """A reader's context over a trace, with a session that answers
    ``layer_index()`` (or has none, as a commit before it)."""
    session = types.SimpleNamespace()
    if index is not False:
        session.layer_index = lambda: index
    window = types.SimpleNamespace(t_sync=0.0, t_end=1.0)
    run = {"system": types.SimpleNamespace(session=session)}
    return layers.Context(None, run, {}, None, window, trace, list(spans))


def test_recorded_trace_joins_by_program():
    """The recorded one-chip trace runs two programs in turn; an index
    of one of them accounts for that one's operations and leaves the
    other's under ``unknown``."""
    trace = xplane.read(os.path.join(DATA, "v5e-1chip.xplane.pb"))
    lo, hi = xplane.window(trace)
    ops, runs = trace.devices[0], trace.modules[0]
    dense = [r for r in runs if r.name == "jit_dense_step"]
    names = {xplane.parse_instruction(o.name)[0] for o in ops
             if any(r.start <= o.start < r.end for r in dense)}
    some = sorted(names)[0]
    index = {"module": "jit_dense_step",
             "layers": {n: ("lstm" if n == some else None) for n in names},
             "scopes_found": ["lstm"]}
    acc = layer_account.own_seconds_by_layer(ops, lo, hi, index, runs)
    busy = sum(e - s for s, e in xplane.busy(ops, lo, hi))
    assert sum(acc.values()) == pytest.approx(busy, rel=1e-9)
    assert acc["lstm"] > 0 and acc[layer_account.UNSCOPED] > 0
    other = [o for o in ops
             if not any(r.start <= o.start < r.end for r in dense)]
    assert acc[layer_account.UNKNOWN] == pytest.approx(
        sum(xplane.self_times(other, lo, hi).values()))

    ctx = _context(trace, index)
    steps = len(xplane.runs_of(runs, "dense_step", lo, hi))
    assert steps > 0
    # the readers' arithmetic, per run of the named program
    ms = ctx.per_step_ms(acc["lstm"], "dense_step", "engine.step")
    assert ms == pytest.approx(1e3 * acc["lstm"] / steps)
    assert layer_account.account(ctx) == acc
    assert layer_account.account(ctx) is ctx.layer_account   # once
    cover = layer_account.coverage_percent(ctx)
    assert cover == pytest.approx(100 * acc["lstm"] / busy)


def test_recorded_lm1b_step_accounts_by_layer(tmp_path):
    """A small LM1B's train step on a TPU v5e (three steps, recorded
    in PR 25 by ``tools/record_layer_trace.py``) with the index its
    session gave: every traced instruction is in the index, every
    declared layer took time, the rows add to the busy time, the
    kernels are ``lstm``'s and the scatters into a table
    ``table_update``'s."""
    pb = tmp_path / "lm1b-small-layers.xplane.pb"
    with gzip.open(os.path.join(DATA, pb.name + ".gz"), "rb") as src, \
            open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(
            DATA, "lm1b-small-layers.layer_index.json.gz"), "rt") as f:
        index = json.load(f)
    trace = xplane.read(str(pb))
    lo, hi = xplane.window(trace)
    ops, runs = trace.devices[0], trace.modules[0]
    assert len(xplane.runs_of(runs, index["module"], lo, hi)) == 3
    acc = layer_account.own_seconds_by_layer(ops, lo, hi, index, runs)
    assert acc[layer_account.UNKNOWN] == 0.0
    assert index["scopes_found"] == ["embedding", "lstm", "sampled_softmax",
                                     "dense_update", "table_update"]
    assert all(acc[layer] > 0 for layer in index["scopes_found"])
    busy = sum(e - s for s, e in xplane.busy(ops, lo, hi))
    assert sum(acc.values()) == pytest.approx(busy, rel=1e-6)
    # what other readers take from the same trace sits inside its layer
    own = xplane.self_times(ops, lo, hi)
    kernels = sum(t for ev, t in own.items()
                  if xplane.categorize(ev) == "mosaic")
    assert 0 < kernels < acc["lstm"]
    names = {xplane.parse_instruction(ev)[0] for ev in own
             if xplane.categorize(ev) == "mosaic"}
    assert names == {"lstm_fwd_res.1", "lstm_bwd.1"}
    # the row scatters print as custom fusions with a table's shape
    tables = {ev: t for ev, t in own.items()
              if xplane.parse_instruction(ev)[1:] == ("fusion",
                                                      "f32[4096,128]")}
    assert tables
    assert {index["layers"][xplane.parse_instruction(ev)[0]]
            for ev in tables} == {"table_update"}
    assert sum(tables.values()) < acc["table_update"]


READERS = ["table_update_ms_per_step", "embedding_ms_per_step",
           "sampled_softmax_ms_per_step", "lstm_ms_per_step",
           "dense_update_ms_per_step", "unscoped_ms_per_step",
           "layer_account_coverage"]


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("case", ["no_layer_index", "no_executable",
                                  "no_device_plane"])
def test_readers_return_none_with_nothing_to_read(reader, case):
    """A program before ``layer_index()`` (the parent commit), a
    session without an AOT executable, a trace without a device plane
    (the CPU rehearsal): no number and no exception."""
    from lib import cell as cell_lib

    trace = xplane.read(os.path.join(DATA, "v5e-1chip.xplane.pb"))
    if case == "no_layer_index":
        ctx = _context(trace, False)
    elif case == "no_executable":
        ctx = _context(trace, None)
    else:
        ctx = _context(trace._replace(devices={}, modules={}), INDEX)
    assert cell_lib.load_plugin("layer_metrics", reader).read(ctx) is None


def test_a_layer_the_program_lacks_reads_nothing():
    ctx = _context(None, INDEX)
    ctx.layer_account = {"lstm": 1.0, layer_account.UNSCOPED: 0.0,
                         layer_account.UNKNOWN: 0.0}
    assert layer_account.layer_ms_per_step(ctx, "dense_update") is None
