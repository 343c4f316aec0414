"""The manifest: every cell's files are found by name, names and units
keep their character rules, and a cell is added by data files alone."""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from chipbench import manifest

CHECKOUT = manifest.CHECKOUT
BENCH = manifest.load()

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(name):
    cell = manifest.cell(name)
    assert cell.config["name"] == cell.config_name
    assert manifest.kind_module(cell.kind).build
    for m in cell.per_layer:
        assert callable(manifest.reader(cell.reader_path(m["name"])))
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for key in cell.traffic["limits"]:
        assert manifest.NAME_RE.fullmatch(key)


def test_names_units_and_keys():
    assert set(BENCH) == TOP_KEYS
    assert manifest.problems(BENCH) == []
    for key, keys in ENTRY_KEYS.items():
        for e in BENCH[key]:
            extra = {"workloads"} if key in ("end_to_end", "per_layer") \
                else set()
            assert keys <= set(e) <= keys | extra, e["name"]
            for field in ("why", "layer", "source"):
                if field in e:
                    assert 1 <= len(e[field]) <= 200 and "\n" not in \
                        e[field] and "\t" not in e[field], (e["name"], field)
    assert len(json.dumps(BENCH).encode()) <= 64 * 1024


def test_metrics_cells_and_bounds_fit_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", ()):
            assert w in cells
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells), (m["name"], w)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    n = 24
    runs = 2 + 14 * n
    assert runs * (BENCH["run_seconds"] + 60) + n * 2 * 90 + 1200 <= 43200
    for path in BENCH["paths"]:
        assert (CHECKOUT / path).is_dir()
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_cell_added_as_data_files_alone(tmp_path):
    shutil.copytree(CHECKOUT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(tmp_path / "chipbench")
    bench = json.loads(json.dumps(BENCH))
    # a new traffic mix, a new cell on it and a new per-layer metric
    traffic = json.loads((tmp_path / "chipbench/traffic/b8.json")
                         .read_text())
    traffic["batch"] = 1
    (tmp_path / "chipbench/traffic/b1.json").write_text(json.dumps(traffic))
    (tmp_path / "chipbench/metrics/launches_read.py").write_text(
        "def read(ctx):\n    return ctx.counters['launches']\n")
    bench["workloads"].append({"name": "encode.mc4-84-c9.b1",
                               "config": "mc4-84-c9", "traffic": "b1",
                               "chips": 1, "why": "batch 1"})
    bench["end_to_end"][0]["workloads"].append("encode.mc4-84-c9.b1")
    bench["per_layer"].append({"name": "launches_read", "unit": "launches",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": "frames_per_s",
                               "workloads": ["encode.mc4-84-c9.b1"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = manifest.cell("encode.mc4-84-c9.b1", tmp_path)
    assert cell.traffic["batch"] == 1 and cell.kind == "encode"
    assert [m["name"] for m in cell.per_layer] == ["launches_read"]
    assert {m["name"] for m in cell.end_to_end} == {"frames_per_s",
                                                    "setup_s"}
    after = _digest(tmp_path / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_missing_files_are_named():
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(manifest.ManifestError, match="no-such-mix"):
        _cell_from(bench, bench["workloads"][0]["name"])


def _cell_from(bench, name):
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        d = Path(d)
        shutil.copytree(CHECKOUT / "chipbench", d / "chipbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        (d / "BENCHMARK.json").write_text(json.dumps(bench))
        return manifest.cell(name, d)
