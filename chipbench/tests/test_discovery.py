import json
import re

import pytest

from harness import spec
from conftest import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load(REPO)


def test_cells_find_their_files_by_name(bench):
    for cell in bench["workloads"]:
        conf, fam = spec.check(REPO, bench, cell)  # family file, mesh size
        mix = spec.traffic(REPO, cell["traffic"])
        assert conf["name"] == cell["config"]
        assert callable(fam.layer) and fam.MATRICES
        if mix["loop"] == "open":
            assert spec.rate(REPO, cell["name"])["rate_rps"] > 0


def test_every_per_layer_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        assert callable(spec.reader(REPO, m["name"]))


def test_metrics_follow_the_cells(bench):
    cells = {c["name"] for c in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
    for c in cells:
        names = {m["name"] for m in spec.metrics(bench, c, False)}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics(bench, c, True)


def test_names_and_keys_keep_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in bench["per_layer"]}
    assert all("\n" not in layer for layer in layers)
    assert len(json.dumps(bench)) < 64 * 1024


def test_a_new_mix_config_and_metric_are_files_and_entries(tiny_root):
    """Adding files and entries is all a new cell or metric needs."""
    b = spec.load(tiny_root)
    (tiny_root / "chipbench" / "metrics" / "answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["per_layer"].append({"name": "answer", "unit": "1", "better": "higher",
                           "source": "program_counter", "layer": "test",
                           "moves": "setup_s"})
    assert spec.reader(tiny_root, "answer")(None) == 42.0
    names = {m["name"] for m in spec.metrics(b, "tiny.tiny-mix", True)}
    assert "answer" in names
    assert spec.traffic(tiny_root, "tiny-closed")["loop"] == "closed"
    assert spec.config(tiny_root, b, "tiny")["model"]["hidden_size"] == 64
    with pytest.raises(KeyError):
        spec.cell(b, "no-such-cell")
