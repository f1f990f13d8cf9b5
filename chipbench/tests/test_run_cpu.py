"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: the window, the readings and the check.  With the timed path broken
underneath, ``correct`` has to come out false: once for each fault a
served cell can have on one chip."""
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO
from harness import cell, spec


def _run(root, name="tiny.tiny-mix", patch=None, seed=2 ** 33 + 3, **kw):
    bench = spec.load(root)
    return cell.run(root, bench, spec.cell(bench, name), seed=seed,
                    seconds=2, traced=False, t_start=time.monotonic(),
                    devices=jax.devices(), patch=patch, drain_s=5.0, **kw)


@pytest.mark.parametrize("name", ["tiny.tiny-mix", "tiny.tiny-closed"])
def test_sound_run_is_correct(tiny_root, name):
    res = _run(tiny_root, name)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "check"
    assert res["device"]["platform"] == "cpu"


def _alter_tokens(engine):
    fused = engine.executor.fused_step

    def step(cache, state):
        cache, state, packed = fused(cache, state)
        tok = packed[:, 0]
        tok = jnp.where(tok >= 0, (tok + 1) % engine.cfg.vocab_size, tok)
        return cache, state, packed.at[:, 0].set(tok)

    engine.executor.fused_step = step


def _state_unchanged(engine):
    """The decode step hands back the KV pool it was given: the tokens it
    decodes are never written."""
    fused = engine.executor.fused_step

    def step(cache, state):
        kept = jax.tree.map(jnp.copy, cache)
        _, state, packed = fused(cache, state)
        return kept, state, packed

    engine.executor.fused_step = step


def _half_batch(engine):
    """Slots in the upper half of the pool never decode."""
    fused = engine.executor.fused_step
    B = engine.ecfg.max_batch

    def step(cache, state):
        live = state["live"] & (jnp.arange(B) < B // 2)
        return fused(cache, dict(state, live=live))

    engine.executor.fused_step = step


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(tiny_root, fault):
    # the closed loop keeps every slot of the pool busy
    res = _run(tiny_root, "tiny.tiny-closed", patch=fault)
    assert not res["correct"], res["check"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_lower_precision_control_fails_the_limit(tiny_root, seed):
    """The control: the harness's own check, with the reference with fp8
    weights in the program's place (the token it puts first, at each
    position of the program's sampled prompts and served tokens), comes
    out not correct where the program's run is correct (tiny readings, 8
    requests compared: program 0 to 0.0102 over 12 seeds, fp8 control
    0.022-0.075, limit 0.015; with 3 compared the control read 0.0126 on
    some draws of seed 3)."""
    import control

    bench = spec.load(tiny_root)
    cell_ = spec.cell(bench, "tiny.tiny-closed")
    r = control.readings(tiny_root, bench, cell_, seed, 2, jax.devices(),
                         controls=("fp8",))
    assert r["correct"] and r["max_logit_gap"] <= r["limit"]
    assert not r["control_fp8"]["correct"]
    assert r["control_fp8"]["max_logit_gap"] > r["limit"]


def test_a_declared_metric_that_reads_nothing_fails_the_run(
        tiny_root, monkeypatch):
    real = cell.end_to_end
    monkeypatch.setattr(cell, "end_to_end",
                        lambda run: dict(real(run), itl_p95_ms=None))
    with pytest.raises(RuntimeError, match="itl_p95_ms read nothing"):
        _run(tiny_root, "tiny.tiny-closed")


def test_off_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "qwen2.5-3b.decode-heavy", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
