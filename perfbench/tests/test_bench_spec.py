"""BENCHMARK.json resolves, by name, to the files of every cell, and a cell
added as files and entries alone is found."""

import json
import re
import shutil

import pytest

from perfbench import harness, spec

ROOT = harness.ROOT
SPEC = spec.load(ROOT)
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_to_its_files(name):
    cell = spec.cell(SPEC, ROOT, name)
    assert cell.chips in (1, 4)
    for kind, key in (("systems", "system"), ("references", "reference")):
        assert (spec.BENCH_DIR / kind / f"{cell.config[key]}.py").is_file()
    assert (spec.BENCH_DIR / "inputs" / f"{cell.traffic['input']}.py").is_file()
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    moved = {m["moves"] for m in cell.per_layer}
    assert moved <= e2e


@pytest.mark.parametrize("name", METRICS)
def test_every_metric_has_a_reader(name):
    assert callable(spec.reader(name).read)


def test_benchmark_json_keeps_to_its_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["perfbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/")
        assert (ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e
    for item in (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
                 + SPEC["per_layer"]):
        assert NAME.match(item["name"]), item["name"]
        assert item["name"] not in names
        names.add(item["name"])
    assert "p99_latency_ms" not in e2e


def test_a_cell_added_as_files_alone_is_found(tmp_path):
    """A later PR adds a mix, a metric and a cell without editing a file:
    the copy's harness finds them by name."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = json.loads(
        (spec.BENCH_DIR / "traffic" / "frames-max-64px.json").read_text())
    mix.update(name="frames-poisson-64px", arrival="poisson",
               rate_per_s=37.2)
    (tmp_path / "perfbench" / "traffic" / "frames-poisson-64px.json"
     ).write_text(json.dumps(mix))
    (tmp_path / "perfbench" / "metrics" / "loadgen.lag_p50_ms.py").write_text(
        "from perfbench.harness import percentile\n\n\n"
        "def read(rec):\n"
        "    return percentile([r.submitted - r.due for r in rec.requests],"
        " 50) * 1e3\n")
    doc["workloads"].append({
        "name": "c64-frames-poisson", "config": "collision-64px",
        "traffic": "frames-poisson-64px", "chips": 1, "why": "arrivals"})
    doc["per_layer"].append({
        "name": "loadgen.lag_p50_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "load generator",
        "moves": "p95_latency_ms", "workloads": ["c64-frames-poisson"]})
    doc["end_to_end"].append({
        "name": "p95_latency_ms", "unit": "ms", "better": "lower",
        "bound": 0.25, "source": "host_clock",
        "workloads": ["c64-frames-poisson"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = tmp_path / "perfbench"
    cell = spec.cell(spec.load(tmp_path), tmp_path, "c64-frames-poisson",
                     bench_dir=bench)
    assert cell.traffic["rate_per_s"] == 37.2
    assert cell.config["layer_sizes"] == [4096, 512, 2]
    assert {m["name"] for m in cell.end_to_end} == {
        "p95_latency_ms", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["loadgen.lag_p50_ms"]
    reader = spec.reader("loadgen.lag_p50_ms", bench_dir=bench)
    assert reader.read.__module__.endswith("loadgen.lag_p50_ms")


def test_an_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        spec.cell(SPEC, ROOT, "no-such-cell")
