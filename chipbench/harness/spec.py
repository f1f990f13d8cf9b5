"""Discovery by name: the benchmark's cells, configurations, model
families, traffic mixes, per-cell rates and per-layer metric readers are
files, found from the names in ``BENCHMARK.json`` and in the
configuration files.  Adding a cell, a family, a mix or a metric adds a
file and an entry; no code here changes."""
from __future__ import annotations

import functools
import importlib.util
import json
import math
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


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, name: str):
    """``read(ctx) -> float | None`` of the per-layer metric ``name``,
    from ``chipbench/metrics/<name>.py``."""
    path = root / BENCH / "metrics" / f"{name}.py"
    return _module(path, f"chipbench_metric_{name.replace('.', '_')}").read


def family(root: Path, name: str):
    """The model family ``name``, from ``chipbench/models/<name>.py``: its
    weights (``shapes``), the program's tree over them
    (``program_tree``), the program fields it fixes (``program_keys``),
    the reference's layer body (``layer``), what a control rounds
    (``MATRICES``) and the weights a token uses
    (``matmul_params_per_layer``)."""
    path = (root / BENCH / "models" / f"{name}.py").resolve()
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for the model family "
                                f"{name!r}")
    return _family(path)


@functools.cache
def _family(path: Path):
    # one module a file, so the reference's compiled layer, keyed by the
    # family, is traced once a process
    return _module(path, f"chipbench_family_{path.stem.replace('.', '_')}")


def check(root: Path, bench: dict, cell_: dict) -> tuple[dict, object]:
    """The cell's configuration and family, checked before any set-up: the
    family's file exists, and the configuration's mesh (none: one chip)
    spans exactly the chips the cell asks for."""
    conf = config(root, bench, cell_["config"])
    fam = family(root, conf["family"])
    chips = math.prod(conf.get("mesh", {}).values())
    if chips != cell_["chips"]:
        raise ValueError(f"{cell_['name']}: the mesh of {conf['name']} "
                         f"{conf.get('mesh', {})} spans {chips} chip(s); "
                         f"the cell asks for {cell_['chips']}")
    return conf, fam
