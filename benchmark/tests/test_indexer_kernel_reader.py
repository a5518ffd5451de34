"""``indexer_kernel_ms_per_step`` and ``indexer_bwd_roofline`` on
hand-made operations, and ``kernels/indexer.cost``: only the calls
named ``indexer_bwd.N`` under ``indexer`` count (the Mosaic call, or the
custom fusion the compiler wraps around it), a program without the
kernel reads nothing, and the cost is the model's."""

import types

import pytest

from kernels import indexer, sparse_attn
from lib import cell as cell_lib, layers
from reduce import xplane

CALL = '%{name} = (bf16[1,16,512,64]{{3,2,1,0}}) custom-call(%a), ' \
       'custom_call_target="tpu_custom_call"'
# as the Keye step's compiler leaves it: a custom fusion around the call
# that writes into the chunk loop's stacked output (PR 28's trace)
WRAPPED = '%{name} = (bf16[4,1,512,16]{{3,2,1,0}}, bf16[1,8192,64]' \
          '{{2,1,0}}) fusion(%a, %b), kind=kCustom, calls=%fused.{name}'
SCORES = "%fusion.7 = f32[512,2048]{1,0} fusion(%p), kind=kOutput"
MODEL = {"seq_len": 8192, "q_chunk_size": 512, "indexer_heads": 16,
         "indexer_head_dim": 64, "indexer_topk": 2048}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _context(ops, index):
    trace = xplane.Trace({0: ops}, {}, {}, [], {})
    system = types.SimpleNamespace(session=types.SimpleNamespace(
        layer_index=lambda: index))
    ctx = layers.Context(types.SimpleNamespace(
        model=MODEL, plugin=cell_lib.load_plugin),
                         {"system": system}, {}, PEAKS, None, None,
                         [{"name": "engine.step"}] * 2)
    ctx.trace, ctx.window = trace, (0.0, 10.0)
    return ctx


def _op(text, start, end):
    return xplane.Op(text, start, end, xplane.categorize(text))


def _read(name, ctx):
    return cell_lib.load_plugin("layer_metrics", name).read(ctx)


def _index(**more):
    return {"module": "jit_train_step", "scopes_found": [],
            "layers": dict({"sparse_attn_bwd.2": "attention",
                            "fusion.7": "indexer"}, **more)}


def test_only_the_calls_of_its_own_name_under_indexer_count():
    ops = [_op(CALL.format(name="sparse_attn_bwd.2"), 0.0, 1.0),
           _op(CALL.format(name="indexer_bwd.4"), 1.0, 1.5),
           _op(SCORES, 2.0, 4.0),
           _op(CALL.format(name="indexer_bwd.4"), 5.0, 5.5)]
    ctx = _context(ops, _index(**{"indexer_bwd.4": "indexer"}))
    # two steps (the host's spans), 1.0 s of the kernel: 500 ms a step
    assert _read("indexer_kernel_ms_per_step", ctx) == pytest.approx(500.0)
    # one call a step of the sixteen a pass takes
    cost = indexer.cost(8192, 16, 64, 2048, 512, passes=1 / 16)
    assert _read("indexer_bwd_roofline", ctx) == pytest.approx(
        100 * cost["flops"] / 197e12 / 0.5)
    # the same call under another layer is not the indexer's
    ctx = _context(ops, _index(**{"indexer_bwd.4": "attention"}))
    assert _read("indexer_kernel_ms_per_step", ctx) is None


def test_the_call_inside_the_compilers_custom_fusion_counts_too():
    ops = [_op(WRAPPED.format(name="indexer_bwd.37"), 1.0, 1.5),
           _op(SCORES, 2.0, 4.0),
           _op(CALL.format(name="indexer_bwd.4"), 5.0, 5.5),
           # a loop fusion that only has the kernel's name in its operands
           _op("%fusion.9 = bf16[512,16]{1,0} fusion(%indexer_bwd.37), "
               "kind=kLoop", 6.0, 9.0)]
    ctx = _context(ops, _index(**{"indexer_bwd.4": "indexer",
                                  "indexer_bwd.37": "indexer",
                                  "fusion.9": "indexer"}))
    assert _read("indexer_kernel_ms_per_step", ctx) == pytest.approx(500.0)
    calls = cell_lib.load_plugin(
        "layer_metrics", "indexer_kernel_ms_per_step").calls(ctx)
    assert calls == pytest.approx((0.5, 1.0))


@pytest.mark.parametrize("metric", ["indexer_kernel_ms_per_step",
                                    "indexer_bwd_roofline"])
def test_a_program_without_the_kernel_reads_nothing(metric):
    ops = [_op(CALL.format(name="sparse_attn_bwd.2"), 0.0, 1.0),
           _op(SCORES, 2.0, 4.0)]
    assert _read(metric, _context(ops, _index())) is None
    assert _read(metric, _context(ops, None)) is None
    assert _read(metric, _context([], _index())) is None


def test_the_cost_is_the_models_work():
    # a sequence no longer than topk: every causal pair is selected
    short = indexer.cost(T=1024, Hi=16, Di=64, topk=2048, chunk=512)
    assert sparse_attn.selected_pairs(1024, 2048) == 1024 * 1025 // 2
    assert short["flops"] == 3 * 2 * 16 * 64 * (1024 * 1025 // 2)
    # the cell's: 2,048 keys a query past the first 2,048 queries
    one = indexer.cost(T=8192, Hi=16, Di=64, topk=2048, chunk=512)
    assert one["flops"] == 3 * 2 * 16 * 64 * (2048 * 2049 // 2
                                              + 6144 * 2048)
    assert one["bytes"] == 2 * 8192 * 16 * 64 * 2 \
        + 2 * 512 * 136 * 64 * 2 + 4 * (8192 * 8193 // 2)
    # flops scale with the products and with the passes, bytes with
    # the passes only
    two = indexer.cost(T=8192, Hi=16, Di=64, topk=2048, chunk=512,
                       products=6, passes=4)
    assert two["flops"] == 8 * one["flops"]
    assert two["bytes"] == 4 * one["bytes"]
