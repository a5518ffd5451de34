"""BENCHMARK.json against the contract it is written to, and against
the files it names: every name resolves by data alone."""

import json
import os
import re

import pytest

from lib import cell as cell_lib

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return cell_lib.load_json("BENCHMARK.json")


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    size = os.path.getsize(os.path.join(cell_lib.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_names_are_plain_and_unique(bench):
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in bench[key]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for key in ("configs", "workloads"):
        assert all(len(x["why"]) <= 200 for x in bench[key])


def test_cells(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in cells)
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in bench["configs"]}


def test_configs_are_files_with_their_plain_reference(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        cfg = cell_lib.load_json(c["file"])
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(cell_lib.BENCH_DIR, "kinds",
                                           cfg["kind"] + ".py"))
        for folder, name in (("builders", cfg["builder"]),
                             ("reference", c["name"])):
            assert os.path.isfile(os.path.join(
                cell_lib.BENCH_DIR, folder, name + ".py")), (folder, name)
        # widths are never cut
        banned = re.compile(r"(_dim|_rank|hidden|intermediate|head)")
        assert not any(banned.search(k) for k in c["reduced"])


def test_traffic_files_name_their_generator(bench):
    for w in bench["workloads"]:
        t = cell_lib.load_json("benchmark", "traffic",
                               w["traffic"] + ".json")
        assert t["name"] == w["traffic"]
        assert os.path.isfile(os.path.join(
            cell_lib.BENCH_DIR, "generators", t["generator"] + ".py"))
        assert t["why"] and t["who"] and "parameters" in t


def test_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(bench["per_layer"]) <= 128
    assert e2e["setup_s"]["bound"] == 0.1
    assert "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
    cells = [w["name"] for w in bench["workloads"]]

    def reported_in(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in bench["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in e2e
        assert os.path.isfile(os.path.join(
            cell_lib.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        for c in cells:
            # a per-layer metric is reported only where what it moves is
            if reported_in(m, c):
                assert reported_in(e2e[m["moves"]], c), (m["name"], c)
    for c in cells:
        mine = [m for m in bench["end_to_end"] if reported_in(m, c)]
        assert any(m["name"] == "setup_s" for m in mine)
        assert len(mine) >= 2
        assert any(reported_in(m, c) for m in bench["per_layer"])


def test_every_cell_resolves_by_data(bench):
    for w in bench["workloads"]:
        cell = cell_lib.resolve(w["name"])
        assert cell.chips == w["chips"]
        assert cell.model and cell.mix
        rehearsal = cell_lib.resolve(w["name"], rehearse=True)
        assert rehearsal.model != cell.model


def test_peaks_table():
    with open(os.path.join(cell_lib.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    v5e = peaks["chips"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    from lib import device
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_plugins_are_keyed_by_folder_and_name(tmp_path, monkeypatch):
    """A plugin's file may be named like a module the process has
    imported, or like another folder's plugin: each loads its own."""
    import random
    import sys
    for folder, value in (("generators", 1), ("builders", 2)):
        (tmp_path / folder).mkdir()
        (tmp_path / folder / "random.py").write_text(f"WHICH = {value}\n")
    monkeypatch.setattr(cell_lib, "BENCH_DIR", str(tmp_path))
    a = cell_lib.load_plugin("generators", "random")
    b = cell_lib.load_plugin("builders", "random")
    try:
        assert (a.WHICH, b.WHICH) == (1, 2)
        assert sys.modules["random"] is random
        assert cell_lib.load_plugin("generators", "random") is a
    finally:
        del sys.modules["benchmark.generators.random"]
        del sys.modules["benchmark.builders.random"]
    with pytest.raises(FileNotFoundError):
        cell_lib.load_plugin("generators", "no-such-generator")


def test_the_comparison_fails_beyond_its_tolerances():
    """The comparison that decides ``correct``, on made-up numbers: it
    passes inside both tolerances and fails outside either. (On the
    chip every run proves the tolerances themselves with its negative
    control: the system fed 8-bit weights must fail them.)"""
    import numpy as np
    builder = cell_lib.load_plugin("builders", "lm1b_train")
    cell = cell_lib.resolve(cell_lib.load_json("BENCHMARK.json")
                            ["workloads"][0]["name"])
    tol = builder.tolerances(cell)
    assert set(tol) == {"nll_rms_tol", "grad_fro_tol"}
    assert builder.tolerances(cell_lib.resolve(cell.name, rehearse=True)) \
        != tol
    rng = np.random.default_rng(0)
    nll = rng.uniform(1.0, 15.0, (8, 20)).astype(np.float32)
    grads = {k: rng.normal(size=(64, 32)).astype(np.float32)
             for k in ("w", "b", "w_proj")}

    def off(x, scale):
        return x + scale * rng.normal(size=x.shape).astype(np.float32)

    def shifted(nll_by, grad_by):
        return [(off(nll, nll_by),
                 {k: off(v, grad_by) for k, v in grads.items()})] * 2

    want = [(nll, grads)] * 2       # two batches
    tightest = min(tol["grad_fro_tol"].values())
    inside = builder.compare(
        shifted(0.5 * tol["nll_rms_tol"], 0.5 * tightest), want, tol)
    assert inside["ok"]
    assert not builder.compare(
        shifted(2 * tol["nll_rms_tol"], 0.0), want, tol)["ok"]
    # past the tightest array's tolerance and inside the others'
    assert not builder.compare(shifted(0.0, 2 * tightest), want, tol)["ok"]
    # one position wrong by a nat among 320 fails too
    one = nll.copy()
    one[3, 7] += 1.0
    assert not builder.compare([(nll, grads), (one, grads)], want,
                               tol)["ok"]
    # errors of both signs do not cancel, as they did in the mean NLL
    signs = np.where(np.arange(160).reshape(8, 20) % 2, 1.0, -1.0)
    assert not builder.compare(
        [(nll + 2 * tol["nll_rms_tol"] * signs.astype(np.float32),
          grads)] * 2, want, tol)["ok"]
