"""The benchmark's own tests: ``python -m pytest chipbench/tests``.

They run on the CPU at a tiny size.  A tiny checkout is laid out in a
temporary directory: the real harness, metric readers and model families,
and a tiny configuration, its test-only family and traffic mixes, found by
name as a cell's would be."""
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(REPO / "chipbench"))
sys.path.insert(0, str(REPO / "src"))


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    (root / "src").symlink_to(REPO / "src")
    cb = root / "chipbench"
    cb.mkdir()
    shutil.copytree(REPO / "chipbench" / "metrics", cb / "metrics")
    shutil.copytree(REPO / "chipbench" / "models", cb / "models")
    shutil.copy(DATA / "tiny-dense.py", cb / "models" / "tiny-dense.py")
    (cb / "configs").mkdir()
    (cb / "traffic" / "rates").mkdir(parents=True)
    shutil.copy(DATA / "tiny.json", cb / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-mix.json", cb / "traffic" / "tiny-mix.json")
    shutil.copy(DATA / "tiny-closed.json", cb / "traffic" / "tiny-closed.json")
    (cb / "traffic" / "rates" / "tiny.tiny-mix.json").write_text(
        json.dumps({"rate_rps": 6.0}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.tiny-mix", "config": "tiny", "traffic": "tiny-mix",
         "chips": 1, "why": "test"},
        {"name": "tiny.tiny-closed", "config": "tiny",
         "traffic": "tiny-closed", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
