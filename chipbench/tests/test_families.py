"""Model families as files: the dense family reproduces, bit for bit, the
weights and reference readings the harness gave before it moved into
``chipbench/models/dense.py``; a configuration's ``family`` names the file
that runs, and a family with no file fails before set-up."""
import hashlib
import json

import numpy as np
import pytest

from conftest import DATA, REPO
from harness import cell, reference, roofline, spec, weights
from test_run_cpu import _run

RECORDED = json.loads((DATA / "dense-readings.json").read_text())
TINY = json.loads((DATA / "tiny.json").read_text())["model"]


def _digest(w):
    h = hashlib.sha256()
    for name in sorted(w):
        a = np.asarray(w[name])
        h.update(f"{name} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def dense_weights():
    dense = spec.family(REPO, "dense")
    return dense, weights.make(dense, TINY, RECORDED["seed"])


def test_dense_weights_are_the_recorded_ones(dense_weights):
    _, w = dense_weights
    assert sorted(w) == RECORDED["names"]       # the order keys are drawn in
    assert _digest(w) == RECORDED["weights_sha256"]


@pytest.mark.parametrize("quant", [None, "int8", "fp8"])
def test_dense_reference_is_the_recorded_one(dense_weights, quant):
    dense, w = dense_weights
    prompt, served = RECORDED["prompt"], RECORDED["served"]
    seq, rows = reference._served(prompt, served)
    h = np.asarray(reference.hidden(dense, w, TINY, seq, rows, quant=quant))
    assert (hashlib.sha256(h.tobytes()).hexdigest()
            == RECORDED[f"hidden_sha256_{quant or 'f32'}"])
    if quant is None:
        got = reference.gaps(dense, w, TINY, prompt, served)
    else:
        got = reference.control_gaps(dense, w, TINY, prompt, served, quant)
    want = RECORDED["gaps" if quant is None else f"control_gaps_{quant}"]
    assert np.array_equal(np.asarray(got, np.float64), np.asarray(want))


def test_the_family_the_configuration_names_is_the_one_that_runs(tiny_root):
    fam = spec.family(tiny_root, "tiny-dense")
    fam.CALLED.clear()
    res = _run(tiny_root, "tiny.tiny-closed")
    assert res["correct"], res["check"]
    assert fam.CALLED >= {"shapes", "program_tree", "program_keys", "layer"}
    roofline.model_flops(fam, TINY, [(0, 4)], 1)
    assert "matmul_params_per_layer" in fam.CALLED


def _with_config(root, tmp_path, name, chips, **conf_keys):
    """A copy of the tiny checkout with one more configuration, ``name``
    (the tiny one with ``conf_keys``), and its cell on the closed mix."""
    import shutil

    new = tmp_path / "checkout"
    shutil.copytree(root, new, symlinks=True)
    conf = json.loads((DATA / "tiny.json").read_text())
    conf.update(name=name, **conf_keys)
    cb = new / "chipbench"
    (cb / "configs" / f"{name}.json").write_text(json.dumps(conf))
    bench = spec.load(new)
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"chipbench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.tiny-closed", "config": name,
                               "traffic": "tiny-closed", "chips": chips,
                               "why": "test"})
    (new / "BENCHMARK.json").write_text(json.dumps(bench))
    return new


def _no_setup(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("set-up began")

    monkeypatch.setattr(weights, "make", refuse)
    monkeypatch.setattr(cell, "program_config", refuse)


def test_a_family_with_no_file_fails_before_set_up(tiny_root, tmp_path,
                                                   monkeypatch):
    root = _with_config(tiny_root, tmp_path, "lost", 1, family="no-such")
    _no_setup(monkeypatch)
    with pytest.raises(FileNotFoundError,
                       match=r"chipbench/models/no-such\.py"):
        _run(root, "lost.tiny-closed")


def test_a_mesh_that_is_not_the_cells_chips_fails_before_set_up(
        tiny_root, tmp_path, monkeypatch):
    root = _with_config(tiny_root, tmp_path, "wide", 1,
                        mesh={"data": 2, "model": 2})
    _no_setup(monkeypatch)
    with pytest.raises(ValueError, match="spans 4 chip"):
        _run(root, "wide.tiny-closed")
