"""Discovery by name: the benchmark's cells, configurations, traffic
mixes, per-cell rates and per-layer metric readers are files, found from
the names in ``BENCHMARK.json``.  Adding a cell, a mix or a metric adds a
file and an entry; no code here changes."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = "chipbench"


def load(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    return _named(bench["workloads"], name, "workload")


def config(root: Path, bench: dict, name: str) -> dict:
    entry = _named(bench["configs"], name, "config")
    return json.loads((root / entry["file"]).read_text())


def traffic(root: Path, name: str) -> dict:
    return json.loads((root / BENCH / "traffic" / f"{name}.json").read_text())


def rate(root: Path, cell_name: str) -> dict:
    """An open-loop cell's fixed offered rate and the sweep it came from."""
    path = root / BENCH / "traffic" / "rates" / f"{cell_name}.json"
    return json.loads(path.read_text())


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def metrics(bench: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    key = "per_layer" if traced else "end_to_end"
    return [m for m in bench[key] if _reports(m, cell_name)]


def reader(root: Path, name: str):
    """``read(ctx) -> float | None`` of the per-layer metric ``name``,
    from ``chipbench/metrics/<name>.py``."""
    path = root / BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
