"""The harness finds a cell's configuration, traffic, limits and metrics
by name alone, so a later change adds one by adding files."""
import json
import os
import shutil

import pytest

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


@pytest.mark.parametrize("workload", [w["name"]
                                      for w in MANIFEST["workloads"]])
def test_every_cell_loads(workload):
    cell = harness.load_cell(workload)
    assert cell.cfg["name"] in {c["name"] for c in MANIFEST["configs"]}
    assert set(harness.load_limits(workload)) == set(harness.CHECKS)
    assert callable(cell.family.program)
    for name in cell.per_layer:
        assert callable(harness.load_metric(name).read)


def test_a_new_cell_is_found_from_new_files_only(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(bench / "configs" / "case7.json") as f:
        cfg = json.load(f)
    cfg["name"] = "case7x"
    (bench / "configs" / "case7x.json").write_text(json.dumps(cfg))
    traffic = json.loads((bench / "traffic" / "sgwu.json").read_text())
    traffic["batch"] = 32
    (bench / "traffic" / "sgwu.b32.json").write_text(json.dumps(traffic))
    (bench / "metrics" / "new_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    manifest = dict(MANIFEST)
    manifest["configs"] = MANIFEST["configs"] + [
        {"name": "case7x", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/case7x.json"}]
    manifest["workloads"] = MANIFEST["workloads"] + [
        {"name": "case7x.sgwu.b32", "config": "case7x",
         "traffic": "sgwu.b32", "chips": 1, "why": "test"}]
    manifest["per_layer"] = MANIFEST["per_layer"] + [
        {"name": "new_share", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "test", "moves": "images_per_s",
         "workloads": ["case7x.sgwu.b32"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    monkeypatch.setattr(harness, "BENCH", str(bench))
    cell = harness.load_cell("case7x.sgwu.b32", root=str(tmp_path))
    assert cell.cfg["name"] == "case7x" and cell.traffic["batch"] == 32
    assert "new_share" in cell.per_layer
    assert set(cell.per_layer) == {m["name"] for m in manifest["per_layer"]}
    assert harness.load_metric("new_share").read({}) == 42.0


def test_an_unknown_cell_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no.such.cell")


def test_a_traffic_file_with_a_missing_key_is_refused():
    from generator import check_traffic
    with pytest.raises(ValueError, match="missing"):
        check_traffic({"engine": "vmap"})
