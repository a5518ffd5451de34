"""Every cell of ``BENCHMARK.json`` end to end off the chip, as a tier-1
test: ``benchmark/run.py --rehearse-cpu`` runs the cell's own control
flow (build, warm up, the measured loop, the traced window, every
per-layer reader, the comparison with the reference) at tiny sizes with
the kernels interpreted. A PR that breaks the benchmark's path through
the program fails here, not on the chip. Counts only: a CPU's times are
nobody's numbers."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_cell_rehearses_with_every_per_layer_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={cell['chips']}"
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         cell["name"], "--seed", "1", "--seconds", "2", "--trace", "1",
         "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    listed = sorted(m["name"] for m in BENCH["per_layer"]
                    if cell["name"] in m.get("workloads",
                                             [cell["name"]]))
    assert listed
    assert line["metrics_a_chip_run_would_print"] == listed
    # every listed metric has its reader, and a reader that found
    # nothing to read on the CPU left its metric out without raising
    assert set(line["metrics_read"]) <= set(listed)
    assert "metrics" not in line
