"""The seven readers of the ``compile`` layer on hand-made registries:
each reads its names off the registry as set-up left it
(``registry_before``), a registry without them (a parent tree's) reads
None and the metric is left out, and ``compile_s_in_window`` is the
window's rise of the three compile sums."""

import types

import pytest

from lib import cell as cell_lib

BEFORE = {"startup.api_s": 13.5, "compile.trace_s": 4.0,
          "compile.lower_s": 2.5, "compile.backend_s": 5.25,
          "compile.cache_retrieval_s": 5.0, "compile.cache_misses": 0,
          "compile.cache_hits": 31, "engine.model_traces": 4,
          "engine.recompiles": 0}
# a compile inside the window: every name may move after set-up, and
# the six set-up readers must not see it
AFTER = dict(BEFORE, **{"startup.api_s": 14.0, "compile.trace_s": 4.5,
                        "compile.lower_s": 2.75, "compile.backend_s": 6.25,
                        "compile.cache_retrieval_s": 5.5,
                        "compile.cache_misses": 1,
                        "engine.model_traces": 5})
READS = {"setup_program_s": 13.5, "setup_trace_lower_s": 6.5,
         "setup_backend_compile_s": 5.25, "setup_cache_load_s": 5.0,
         "setup_cache_misses": 0, "setup_model_traces": 4,
         "compile_s_in_window": 1.75}


def _read(metric, before, after):
    ctx = types.SimpleNamespace(
        run={"registry_before": before, "registry_after": after})
    return cell_lib.load_plugin("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric", sorted(READS))
def test_reads_the_registry_as_set_up_left_it(metric):
    assert _read(metric, BEFORE, AFTER) == pytest.approx(READS[metric])


@pytest.mark.parametrize("metric", sorted(READS))
def test_a_program_without_the_names_reads_nothing(metric):
    parent = {"engine.recompiles": 0, "pipeline.dispatch_ms": None}
    assert _read(metric, parent, dict(parent)) is None


def test_a_quiet_window_reads_zero():
    assert _read("compile_s_in_window", BEFORE, dict(BEFORE)) == 0
