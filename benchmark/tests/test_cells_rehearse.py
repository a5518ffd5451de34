"""Every cell end to end off the chip: with ``--rehearse-cpu`` the same
control flow at tiny sizes, counts only, stamped as a rehearsal; without
it a non-zero exit before compiling and no result."""

import json
import os
import subprocess
import sys

import pytest

from lib import cell as cell_lib

CELLS = [(w["name"], w["chips"])
         for w in cell_lib.load_json("BENCHMARK.json")["workloads"]]


def _run(workload, chips, trace, rehearse=True, seconds=3,
         root=cell_lib.ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    argv = [sys.executable, os.path.join(root, "benchmark", "run.py"),
            "--workload", workload, "--seed", "3", "--seconds",
            str(seconds), "--trace", str(trace)]
    if rehearse:
        argv.append("--rehearse-cpu")
    return subprocess.run(argv, cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


def _detail(proc):
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("benchmark-detail "))
    return json.loads(line[len("benchmark-detail "):])


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload,chips", CELLS)
def test_cell_rehearses(workload, chips, trace):
    proc = _run(workload, chips, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == {"rehearsal", "correct", "attempted", "failed",
                         "metrics_a_chip_run_would_print", "metrics_read",
                         "device"}
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    key = "per_layer" if trace else "end_to_end"
    cell = cell_lib.resolve(workload)
    want = sorted(m["name"] for m in getattr(cell, key))
    assert line["metrics_a_chip_run_would_print"] == want
    # counts only: no number stands under a metric's name
    assert "metrics" not in line
    detail = _detail(proc)
    assert "setup_s" not in detail
    # the comparison passed, and the negative control moved it: the
    # system fed 8-bit weights is further from the reference
    reference = detail["checks"]["reference"]
    assert reference["ok"]
    assert reference["nll_rms_err"] <= reference["nll_rms_tol"]
    worst = max(reference["grad_fro_err"].values())
    assert all(reference["grad_fro_err"][k] <= tol
               for k, tol in reference["grad_fro_tol"].items())
    control = reference["control_lstm_weights_8bit"]
    assert max(control["grad_fro_err"].values()) > 1.5 * worst
    assert control["nll_rms_err"] > reference["nll_rms_err"]


def test_a_four_chip_cell_is_one_more_entry(tmp_path):
    """What a later PR's four-chip cell needs: the files that are here
    under a ``BENCHMARK.json`` whose entry says ``"chips": 4``. The
    tables are then row-sharded over four devices and the comparison
    with the reference runs on the sharded parameters."""
    for name in ("benchmark", "parallax_tpu"):
        os.symlink(os.path.join(cell_lib.ROOT, name), tmp_path / name)
    bench = cell_lib.load_json("BENCHMARK.json")
    bench["workloads"][-1]["chips"] = 4
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    proc = _run(bench["workloads"][-1]["name"], 4, 1, root=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert not _detail(proc)["checks"]["static_failures"]


def test_off_the_chip_no_result_is_printed():
    proc = _run(CELLS[0][0], CELLS[0][1], 0, rehearse=False)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_last_line_keys_of_a_chip_run():
    """The key set of the real last line, from the code that prints it
    (a chip run cannot be made here)."""
    src = open(os.path.join(cell_lib.BENCH_DIR, "run.py")).read()
    assert '''line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": device}''' in src
    assert 'line["breakdown"] = breakdown' in src
