"""``BENCHMARK.json`` and the files it names, found by name.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  The configuration's file is the entry's ``file``; the traffic mix is
``<bench>/traffic/<traffic>.json``, whose ``kind`` names the generator
``chipbench/kinds/<kind>.py``; each per-layer metric is read by
``<bench>/metrics/<metric>.py``.  Adding a cell, a configuration, a
traffic mix or a metric is adding files and entries: nothing here lists
them.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


class ManifestError(ValueError):
    """A cell, or a file that a cell needs, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with everything it names, read from disk."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple       # metric entries this cell reports, trace 0
    per_layer: tuple        # metric entries this cell reports, trace 1
    bench_dir: Path

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def reader_path(self, metric: str) -> Path:
        return self.bench_dir / "metrics" / f"{metric}.py"


def load(checkout: Path = CHECKOUT) -> dict:
    path = Path(checkout) / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise ManifestError(f"no {path}") from e


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} named {name!r}; have "
                        f"{', '.join(e['name'] for e in entries)}")


def _read_json(path: Path, what: str) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise ManifestError(f"{what}: no file {path}") from e


def reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: the cells it lists, or, for a
    per-layer metric without a list, every cell that reports the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def cell(name: str, checkout: Path = CHECKOUT) -> Cell:
    """The cell ``name`` of ``<checkout>/BENCHMARK.json``, with its
    configuration, traffic and metric entries read and checked."""
    checkout = Path(checkout)
    bench = load(checkout)
    bench_dir = checkout / bench["paths"][0]
    w = _by_name(bench["workloads"], name, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    config = _read_json(checkout / c["file"], f"config {c['name']}")
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']}")
    kind = traffic.get("kind", "")
    if not NAME_RE.fullmatch(kind) or not (
            BENCH_DIR / "kinds" / f"{kind}.py").is_file():
        raise ManifestError(f"traffic {w['traffic']}: no generator for "
                            f"kind {kind!r}")
    e2e = tuple(m for m in bench["end_to_end"] if reports(m, name, ()))
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if reports(m, name, e2e_names))
    found = Cell(name=name, chips=int(w["chips"]), config_name=c["name"],
                 config=config, traffic_name=w["traffic"], traffic=traffic,
                 end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)
    for m in per_layer:
        if not found.reader_path(m["name"]).is_file():
            raise ManifestError(f"metric {m['name']}: no reader "
                                f"{found.reader_path(m['name'])}")
    return found


def kind_module(kind: str):
    """The generator and driver of a traffic kind (``kinds/<kind>.py``)."""
    return importlib.import_module(f"chipbench.kinds.{kind}")


def reader(path: Path):
    """The ``read(ctx)`` function of one per-layer metric's file."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def problems(bench: dict) -> list[str]:
    """Names, units and keys of ``bench`` that break the manifest's
    character rules."""
    out = []

    def name(v, where):
        if not isinstance(v, str) or not NAME_RE.fullmatch(v):
            out.append(f"{where}: bad name {v!r}")

    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in bench[key]:
            name(e["name"], key)
            if e["name"] in seen:
                out.append(f"{key}: {e['name']} twice")
            seen.add(e["name"])
    for c in bench["configs"]:
        for k in c["reduced"]:
            name(k, f"config {c['name']} reduced")
    for w in bench["workloads"]:
        name(w["config"], f"workload {w['name']} config")
        name(w["traffic"], f"workload {w['name']} traffic")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.fullmatch(m["unit"]):
            out.append(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            out.append(f"metric {m['name']}: better {m['better']!r}")
    return out
