"""The trace reduction against small traces recorded on a TPU v5e in
PR 23 (``tools/record_test_trace.py``): ``data/v5e-1chip.xplane.pb``,
four rounds of a toy LSTM kernel and a matrix product with a 50 ms
sleep after the second, and ``data/v5e-4chip.xplane.pb``, the same on a
four-chip host, where the matrix product's sum crosses the chips."""

import os

import pytest

from lib import stats
from reduce import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

FUSION = ("%fusion.22 = f32[793470,512]{1,0:T(8,128)} fusion(f32[793470,512]"
          "{1,0:T(8,128)} %state_slice_state__softmax_w__.1, s32[49152]"
          "{0:T(1024)S(1)} %bitcast.114), kind=kCustom, "
          "calls=%fused_computation.22")
MOSAIC = ("%jvp__.1 = (bf16[20,2048,512]{2,1,0:T(8,128)(2,1)}, bf16[20,2048,"
          "8192]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[20,2048,8192]{2,1,0:"
          "T(8,128)(2,1)} %fusion.28), custom_call_target=\"tpu_custom_call\","
          " operand_layout_constraints={bf16[20,2048,8192]{2,1,0}}")
OTHER_CALL = ("%custom-call.30 = f32[49152,512]{1,0:T(8,128)S(1)} custom-call("
              "f32[49152,512]{1,0} %x), custom_call_target=\"Sharding\"")
ALL_REDUCE = ("%all-reduce-start.1 = f32[1024,8192]{1,0:T(8,128)} "
              "all-reduce-start(f32[1024,8192]{1,0:T(8,128)} %add.5), "
              "channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%sum")
COPY_DONE = ("%copy-done = bf16[4,128,128]{2,1,0:T(8,128)(2,1)S(1)} copy-done("
             "(bf16[4,128,128]{2,1,0:T(8,128)(2,1)S(1)}, bf16[4,128,128]"
             "{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) %copy-start)")
WHILE = ("%while.3 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) while((s32[]"
         "{:T(128)}, f32[8,128]{1,0:T(8,128)}) %tuple.1), condition=%cond, "
         "body=%body")


def test_instruction_names_as_a_tpu_trace_prints_them():
    assert xplane.parse_instruction(FUSION) == (
        "fusion.22", "fusion", "f32[793470,512]")
    assert xplane.parse_instruction(COPY_DONE)[:2] == ("copy-done",
                                                       "copy-done")
    assert xplane.parse_instruction(MOSAIC)[1] == "custom-call"
    assert xplane.parse_instruction("bench.sync") == (
        "bench.sync", "bench.sync", "")
    assert xplane.categorize(FUSION) == "fusion"
    assert xplane.categorize(MOSAIC) == "mosaic"
    assert xplane.categorize(OTHER_CALL) == "custom-call"
    assert xplane.categorize(ALL_REDUCE) == "collective"
    assert xplane.categorize(ALL_REDUCE.replace("-start", "-done")) \
        == "collective"
    assert xplane.categorize(WHILE) == "container"
    assert xplane.short_name(FUSION) == "fusion.22 fusion f32[793470,512] kCustom"
    assert xplane.short_name(MOSAIC).endswith("target=tpu_custom_call")
    assert len(xplane.short_name(MOSAIC * 3)) <= 120
    assert xplane.module_name("jit_train_step(123456789)") == "jit_train_step"


def test_self_time_is_duration_less_children():
    ops = [xplane.Op(WHILE, 0.0, 10.0, "container"),
           xplane.Op(FUSION, 1.0, 4.0, "fusion"),
           xplane.Op(ALL_REDUCE, 4.0, 6.0, "collective"),
           xplane.Op(MOSAIC, 12.0, 13.0, "mosaic")]
    own = xplane.self_times(ops, 0.0, 20.0)
    assert own[WHILE] == pytest.approx(5.0)
    assert own[FUSION] == pytest.approx(3.0)
    assert stats.total(xplane.busy(ops, 0.0, 20.0)) == pytest.approx(11.0)
    assert sum(own.values()) == pytest.approx(11.0)
    # clipped to the window
    assert xplane.self_times(ops, 2.0, 5.0)[FUSION] == pytest.approx(2.0)
    assert xplane.exposed_seconds(ops, "collective", 0, 20) \
        == pytest.approx(2.0)
    cover = ops + [xplane.Op(FUSION, 5.0, 7.0, "fusion")]
    cover.sort(key=lambda o: (o.start, -o.end))
    assert xplane.exposed_seconds(cover, "collective", 0, 20) \
        == pytest.approx(1.0)
    assert xplane.idle_gaps(xplane.busy(ops, 0, 20), 0, 20) \
        == [(13.0, 20.0), (10.0, 12.0)]


@pytest.fixture(scope="module")
def one_chip():
    return xplane.read(os.path.join(DATA, "v5e-1chip.xplane.pb"))


def test_one_chip_trace_shape(one_chip):
    assert sorted(one_chip.devices) == [0]
    assert one_chip.lines["/device:TPU:0"]["XLA Ops"] == 52
    assert one_chip.lines["/device:TPU:0"]["XLA Modules"] == 8
    assert len(one_chip.asyncs[0]) == 16
    runs = one_chip.modules[0]
    assert [r.name for r in runs] == ["jit_kernel_step",
                                      "jit_dense_step"] * 4
    # four kernel calls in the file, each ~2.3 us on the device
    kernels = [o for o in one_chip.devices[0] if o.category == "mosaic"]
    assert len(kernels) == 4
    assert all(2.0e-6 < o.end - o.start < 3.0e-6 for o in kernels)
    assert not any(o.category == "collective"
                   for o in one_chip.devices[0])


def test_one_chip_busy_union_and_idle_share(one_chip):
    lo, hi = xplane.window(one_chip)
    assert hi - lo == pytest.approx(0.0559, abs=5e-4)
    ops = one_chip.devices[0]
    merged = xplane.busy(ops, lo, hi)
    busy = stats.total(merged)
    # a few tens of microseconds of work in a 56 ms window
    assert busy == pytest.approx(37.6e-6, rel=0.02)
    assert 1.0 - busy / (hi - lo) > 0.999
    # no operation nests here, so own times add up to the union
    assert sum(xplane.self_times(ops, lo, hi).values()) \
        == pytest.approx(busy, rel=1e-9)
    assert busy <= sum(o.end - o.start for o in ops)
    # the recorder slept 50 ms after the second round
    longest = xplane.idle_gaps(merged, lo, hi)[0]
    assert 0.050 <= longest[1] - longest[0] <= 0.053
    # the host's mark and the device's events disagree by about a
    # millisecond: the first round ran "before" the mark that preceded it
    assert xplane.count(ops, "mosaic", lo, hi) == 3
    assert xplane.category_seconds(ops, "mosaic", lo, hi) \
        == pytest.approx(7.02e-6, rel=0.02)
    assert len(xplane.runs_of(one_chip.modules[0], "kernel_step", lo, hi)) == 3


@pytest.fixture(scope="module")
def four_chips():
    path = os.path.join(DATA, "v5e-4chip.xplane.pb")
    if not os.path.isfile(path):
        pytest.skip("no four-chip trace was recorded")
    return xplane.read(path)


def test_four_chip_trace_has_one_collective_a_round(four_chips):
    assert sorted(four_chips.devices) == [0, 1, 2, 3]
    lo, hi = xplane.window(four_chips)
    for d, ops in four_chips.devices.items():
        on_core = [o for o in ops if o.category == "collective"]
        in_flight = [o for o in four_chips.asyncs.get(d, [])
                     if o.category == "collective"]
        assert on_core or in_flight, d
        total = stats.total(stats.merge_intervals(
            xplane.category_intervals(ops, "collective", lo, hi)
            + xplane.category_intervals(four_chips.asyncs.get(d, []),
                                        "collective", lo, hi)))
        exposed = xplane.exposed_seconds(ops, "collective", lo, hi)
        assert 0 < exposed <= total + 1e-12
        assert total < 1e-3         # microseconds, not the 50 ms gap
