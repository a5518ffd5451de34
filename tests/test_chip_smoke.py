"""chip_smoke.py's contract, as far as a machine without a chip can
check it: the default invocation refuses the CPU before compiling
anything, a failed phase can never end in exit code 0, and the sizes
the chip run uses are the full widths. (What the script proves ON the
chip is recorded in CHANGES.md / PERF.md by the PR that ran it.)"""

import json

import jax
import pytest

import chip_smoke

_compiles = {"n": 0, "active": False}


def _count_compiles(event, duration, **kw):
    if _compiles["active"] and "backend_compile" in event:
        _compiles["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


@pytest.fixture
def compile_witness():
    _compiles.update(n=0, active=True)
    yield _compiles
    _compiles["active"] = False


def test_default_invocation_refuses_cpu_before_any_compile(
        capsys, compile_witness):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    # names the platform it found, on both streams
    assert "platform=cpu" in out
    assert "platform is 'cpu', not 'tpu'" in err
    # prints no result: no line of stdout is a JSON object
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
    assert compile_witness["n"] == 0


def _run_with_phases(monkeypatch, capsys, phases):
    monkeypatch.setattr(chip_smoke, "PHASES", phases)
    rc = chip_smoke.main(["--cpu-rehearsal"])
    line, verdict = capsys.readouterr().out.strip().splitlines()[-2:]
    # the last line is the verdict the driver reads: these keys, no other
    verdict = json.loads(verdict)
    assert list(verdict) == ["ok", "device"]
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    summary = json.loads(line)
    assert (verdict["ok"], verdict["device"]) == (
        summary["ok"], summary["device"])
    return rc, summary, line


def test_a_failed_phase_cannot_end_in_exit_code_zero(monkeypatch, capsys):
    def passes(sz, rehearsal):
        return {"lstm_bwd": ["scan"]}

    def fails(sz, rehearsal):
        raise RuntimeError("Mosaic said no")

    rc, summary, _ = _run_with_phases(
        monkeypatch, capsys,
        (("train", passes), ("kernels", fails), ("serve", passes)))
    assert rc == 1 and summary["ok"] is False
    # the later phase still ran: a chip run is too dear to stop early
    assert summary["phases"]["serve"]["ok"] is True
    assert "Mosaic said no" in summary["phases"]["kernels"]["error"]


def test_summary_is_stamped_and_claims_nothing(monkeypatch, capsys):
    def passes(sz, rehearsal):
        return {}

    rc, summary, line = _run_with_phases(
        monkeypatch, capsys,
        (("train", passes), ("kernels", passes), ("serve", passes)))
    assert rc == 0 and summary["ok"] is True
    assert summary["rehearsal"] is True        # never mistaken for a chip
    assert summary["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": jax.device_count()}
    assert line.endswith('"claim": null}')
    # no rate, utilization or speed-up under any name
    assert not [k for k in json.dumps(summary).lower().split('"')
                if any(w in k for w in ("per_sec", "/s", "mfu", "speedup",
                                        "utilization", "throughput"))]


def test_default_sizes_are_the_full_widths():
    """The chip run is cut in depth and steps, never in width."""
    sz = chip_smoke._sizes(rehearsal=False, n=4)
    assert sz["lm1b"] == {} and sz["nmt"] == {}    # the configs' defaults
    assert (sz["B"], sz["T"]) == (128 * 4, 20)
    assert sz["page_size"] >= 16                   # a whole bf16 tile
    assert (20, 128, 512, 2048, 512) in sz["lstm"]  # the flagship cell
    assert sz["requests"] >= 24                    # "a few dozen"


def test_rehearsal_covers_the_row_update_kernel():
    """The kernels phase's newest rows at the rehearsal's sizes: the
    in-place row update (interpreted here) against the scatter path,
    on a table whose last group of 8 rows is partial."""
    geo = chip_smoke._sizes(rehearsal=True, n=1)["table"]
    assert geo["V"] % 8 and geo["D"] % 128 == 0
    rows = []
    chip_smoke._table_checks(rows, geo, "interpret")
    assert [r["kernel"] for r in rows] == ["adagrad_rows_emb_ulp",
                                           "adagrad_rows_softmax_w_ulp"]
    assert all(r["ok"] and r["tol"] == 2 for r in rows)
    full = chip_smoke._sizes(rehearsal=False, n=1)["table"]
    assert (full["V"], full["D"]) == (793470, 512)     # the cells' tables
    assert full["B"] * full["T"] == 2560               # emb's slots
    assert full["B"] * full["T"] + full["samples"] == 10752
