"""Cells, configurations, entries and per-layer metrics are found by name."""
import json
import re
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return harness.load_benchmark(ROOT)


def test_benchmark_keys_and_names(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and bm["command"][1] == "bench/run.py"
    names = [c["name"] for c in bm["configs"]] + [w["name"] for w in bm["workloads"]]
    names += [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for item in bm["configs"] + bm["workloads"]:
        assert 1 <= len(item["why"]) <= 200 and "\n" not in item["why"]
    for c in bm["configs"]:
        assert len(c["source"]) <= 200


def test_end_to_end_bounds_and_sources(bm):
    names = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in names
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = next(m for m in bm["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25


def test_every_cell_reports_setup_another_metric_and_a_layer(bm):
    for w in bm["workloads"]:
        e2e = {m["name"] for m in harness.end_to_end_for(bm, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layers = harness.per_layer_for(bm, w["name"])
        assert layers, w["name"]
        for m in layers:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_four_chip_cells_are_at_most_half(bm):
    four = sum(1 for w in bm["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bm["workloads"]) // 2)
    assert all(w["chips"] in (1, 4) for w in bm["workloads"])


def test_run_seconds_fits_a_full_check_of_24_cells(bm):
    rs = bm["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_name_finds_its_files(bm):
    used = set()
    for w in bm["workloads"]:
        cfg = harness.load_config(bm, w["config"], root=ROOT)
        used.add(w["config"])
        wl = harness.load_workload_file(w["name"])
        assert harness.load_entry(wl["entry"]).Entry
        assert "limits" in wl and "trace_seconds" in wl
        assert cfg["name"] == w["config"]
    assert used == {c["name"] for c in bm["configs"]}
    files = [c["file"] for c in bm["configs"]]
    assert len(files) == len(set(files)) and all(f.startswith("bench/") for f in files)
    for m in bm["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def test_config_files_state_reduced_and_assumed(bm):
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert cfg["assumed"]


FIXTURE_CONFIG = {"name": "fixture-2d", "spec": "star-2d1r", "weights": None,
                  "reduced": [], "assumed": ["fixture"]}


@pytest.fixture
def fixture_tree(tmp_path):
    """A checkout-shaped tree with one configuration, one cell and one
    per-layer metric that the shipped benchmark does not know."""
    import numpy as np
    from repro.core.stencil import paper_suite
    w = next(s for s in paper_suite() if s.name == "star-2d1r").weights
    bench = tmp_path / "bench"
    for d in ("configs", "workloads", "metrics"):
        (bench / d).mkdir(parents=True)
    cfg = dict(FIXTURE_CONFIG, weights=np.asarray(w).tolist())
    (bench / "configs" / "fixture-2d.json").write_text(json.dumps(cfg))
    (bench / "workloads" / "fixture-2d.iterate.json").write_text(json.dumps({
        "entry": "iterate", "backend": "direct", "grid": [24, 24], "steps_per_call": 2,
        "init": {"modes": 2, "noise": 0.1}, "trace_seconds": 1,
        "limits": {"max_rel_err": 1e-4}}))
    (bench / "metrics" / "fixture_layer.py").write_text(
        "def read(facts):\n    return facts.get('steps')\n")
    bm = {"configs": [{"name": "fixture-2d", "file": "bench/configs/fixture-2d.json"}],
          "workloads": [{"name": "fixture-2d.iterate", "config": "fixture-2d", "chips": 1}],
          "end_to_end": [{"name": "gstencil_per_s", "unit": "GStencil/s"},
                         {"name": "setup_s", "unit": "s"}],
          "per_layer": [{"name": "fixture_layer", "unit": "steps", "moves": "gstencil_per_s"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path


def test_a_new_cell_needs_only_new_files(fixture_tree, cpu_run):
    from bench import run as bench_run  # noqa: F401  (the harness module)
    bm = harness.load_benchmark(fixture_tree)
    cell = harness.find_workload(bm, "fixture-2d.iterate")
    cfg = harness.load_config(bm, cell["config"], root=fixture_tree)
    wl = harness.load_workload_file("fixture-2d.iterate", bench=fixture_tree / "bench")
    reader = harness.load_reader("fixture_layer", bench=fixture_tree / "bench")
    assert reader.read({"steps": 7}) == 7
    assert [m["name"] for m in harness.per_layer_for(bm, "fixture-2d.iterate")] == \
        ["fixture_layer"]
    line, closing = bench_run.run_cell(bm, "fixture-2d.iterate", wl, cfg, seed=2**31 + 3,
                                       seconds=0.2, trace=False)
    out = json.loads(line)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"gstencil_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert closing[-1].startswith("bench check max_rel_err")
    with pytest.raises(KeyError):
        harness.find_workload(bm, "no-such.cell")
