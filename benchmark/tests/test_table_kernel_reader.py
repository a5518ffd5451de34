"""``table_kernel_ms_per_step`` on hand-made operations and on the
recorded LM1B step of PR 25: only the Mosaic calls under
``table_update`` count, and a program without one reads nothing."""

import gzip
import json
import os
import shutil
import types

import pytest

from lib import cell as cell_lib, layers
from reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
KERNEL = ('%adagrad_rows.3 = (f32[64,128]{1,0}, f32[64,128]{1,0}) '
          'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
LSTM = ('%lstm_fwd_res.1 = (bf16[4,8,16]{2,1,0}) custom-call(%x), '
        'custom_call_target="tpu_custom_call"')
SCATTER = "%fusion.3 = f32[64,128]{1,0} fusion(%p), kind=kCustom"


def _context(ops, index):
    trace = xplane.Trace({0: ops}, {}, {}, [], {})
    system = types.SimpleNamespace(session=types.SimpleNamespace(
        layer_index=lambda: index))
    ctx = layers.Context(None, {"system": system}, {}, None, None, None,
                         [{"name": "engine.step"}] * 2)
    ctx.trace, ctx.window = trace, (0.0, 10.0)
    return ctx


def _op(text, start, end):
    return xplane.Op(text, start, end, xplane.categorize(text))


def _read(ctx):
    return cell_lib.load_plugin(
        "layer_metrics", "table_kernel_ms_per_step").read(ctx)


def test_only_the_mosaic_calls_under_table_update_count():
    ops = [_op(LSTM, 0.0, 1.0), _op(KERNEL, 1.0, 1.5),
           _op(SCATTER, 2.0, 4.0), _op(KERNEL, 5.0, 5.5)]
    index = {"module": "jit_train_step", "scopes_found": [],
             "layers": {"lstm_fwd_res.1": "lstm", "fusion.3": "table_update",
                        "adagrad_rows.3": "table_update"}}
    # two steps (the host's spans), 1.0 s of the kernel: 500 ms a step
    assert _read(_context(ops, index)) == pytest.approx(500.0)


def test_the_recorded_step_of_pr_25_has_no_such_kernel(tmp_path):
    """Its Mosaic calls are the LSTM's two, under ``lstm``."""
    pb = tmp_path / "lm1b-small-layers.xplane.pb"
    with gzip.open(os.path.join(DATA, pb.name + ".gz"), "rb") as src, \
            open(pb, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with gzip.open(os.path.join(
            DATA, "lm1b-small-layers.layer_index.json.gz"), "rt") as f:
        index = json.load(f)
    ctx = _context(xplane.read(str(pb)).devices[0], index)
    assert any(op.category == "mosaic" for op in ctx.device_ops()[0][1])
    assert _read(ctx) is None


def test_a_program_without_the_kernel_reads_nothing():
    index = {"module": "jit_train_step", "scopes_found": [],
             "layers": {"lstm_fwd_res.1": "lstm",
                        "fusion.3": "table_update"}}
    ops = [_op(LSTM, 0.0, 1.0), _op(SCATTER, 2.0, 4.0)]
    assert _read(_context(ops, index)) is None
    assert _read(_context(ops, None)) is None
    assert _read(_context([], index)) is None
