"""Decode and chunk steps commit K/V rows in place into the layer-stacked
pool, in a layer scan or, for small groups, unrolled: either way logits and
every cache leaf match, bit for bit, a reference layer loop that slices
layer ``r`` out of the pool, runs ``_apply_block`` on it and writes the
whole layer back.  The reference loops the way the code under test does
(``lax.scan`` or unrolled), since XLA's CPU backend rounds bf16
differently in a loop body than in straight-line code."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduce_config
from repro.models import transformer as T

B, KV_LEN, CHUNK = 4, 32, 8


def _reference_run_stack(stack_params, x, *, cfg, groups, mode, pos,
                         caches, impl="auto", causal=True, unrolled, **_):
    def layer(x, pool, p_blk, r, spec):
        c_blk = jax.tree.map(lambda a: a[r], pool)
        x, c_out, _ = T._apply_block(
            p_blk, x, c_blk, cfg=cfg, spec=spec, mode=mode, pos=pos,
            cross_src=None, impl=impl, causal=causal)
        pool = jax.tree.map(lambda a, o: a.at[r].set(o.astype(a.dtype)),
                            pool, c_out)
        return x, pool

    new_caches = []
    for gp, gc, spec in zip(stack_params, caches, groups):
        if unrolled:
            for r in range(spec.repeats):
                x, gc = layer(x, gc, jax.tree.map(lambda a: a[r], gp), r, spec)
        else:
            (x, gc), _ = jax.lax.scan(
                lambda c, xs, spec=spec: (layer(*c, *xs, spec), None),
                (x, gc), (gp, jnp.arange(spec.repeats)))
        new_caches.append(gc)
    return x, new_caches, jnp.zeros((), jnp.float32)


def _steps(cfg, params, cache, *, chunk):
    """Five decode steps (one row dead, one joining late), then one chunk
    step with padded rows; yields (logits, cache) after each."""
    decode = jax.jit(lambda p, c, t, q: T.decode_step(p, cfg, c, t, q))
    key = jax.random.PRNGKey(1)
    for s in range(5):
        key, sub = jax.random.split(key)
        toks = jax.random.randint(sub, (B,), 0, cfg.vocab_size)
        pos = jnp.asarray([s, s + 3, -1, s - 2], jnp.int32)
        pos = jnp.where(pos >= 0, pos, -1)
        logits, cache = decode(params, cache, toks, pos)
        yield logits, cache
    if not chunk:
        return
    step = jax.jit(lambda p, c, t, q, i: T.chunk_prefill_step(p, cfg, c, t,
                                                              q, i))
    toks = jax.random.randint(key, (B, CHUNK), 0, cfg.vocab_size)
    start = jnp.asarray([5, 8, 0, -1], jnp.int32)[:, None]
    n_real = jnp.asarray([CHUNK, 3, 5, 0], jnp.int32)[:, None]
    idx = jnp.arange(CHUNK, dtype=jnp.int32)[None, :]
    pos = jnp.where((idx < n_real) & (start >= 0), start + idx, -1)
    take = jnp.maximum(n_real[:, 0] - 1, 0)
    yield step(params, cache, toks, pos, take)


CASES = {
    # qwen-shaped GQA stack at 12 layers, fp and quantised pools
    "qwen_fp": ("qwen2.5-3b", 12, 0, True),
    "qwen_kv8": ("qwen2.5-3b", 12, 8, True),
    "qwen_kv4": ("qwen2.5-3b", 12, 4, True),
    # parallel attention + MLP block (GPT-J)
    "gptj_fp": ("gpt-j", 4, 0, True),
    # local ring caches between global layers
    "gemma2_fp": ("gemma2-9b", 4, 0, True),
    # layers that write back their whole state: MLA, RG-LRU, SSM, cross
    "deepseek_mla": ("deepseek-v2-236b", 0, 0, True),
    "recurrentgemma": ("recurrentgemma-9b", 0, 0, False),
    "mamba2": ("mamba2-130m", 0, 0, False),
    "vision_cross": ("llama-3.2-vision-90b", 0, 0, False),
}


@pytest.mark.parametrize("loop", ["scan", "unrolled"])
@pytest.mark.parametrize("case", list(CASES))
def test_inplace_commit_matches_layer_writeback_loop(case, loop, monkeypatch):
    arch, n_layers, kv_bits, chunk = CASES[case]
    unrolled = loop == "unrolled"
    cfg = reduce_config(get_config(arch))
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, B, KV_LEN, kv_bits=kv_bits)
    if cfg.family == "vlm":     # a non-zero cross cache to read
        cache = jax.tree.map(
            lambda a: a + jnp.asarray(0.01, a.dtype) if a.dtype == jnp.bfloat16
            else a, cache)

    monkeypatch.setattr(T, "DECODE_UNROLL", cfg.n_layers if unrolled else 0)
    got = list(_steps(cfg, params, cache, chunk=chunk))
    monkeypatch.setattr(T, "run_stack", functools.partial(
        _reference_run_stack, unrolled=unrolled))
    want = list(_steps(cfg, params, cache, chunk=chunk))

    assert len(got) == len(want) == 5 + chunk
    for (g_logits, g_cache), (w_logits, w_cache) in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_logits, np.float32),
                                      np.asarray(w_logits, np.float32))
        g_leaves, g_tree = jax.tree.flatten(g_cache)
        w_leaves, w_tree = jax.tree.flatten(w_cache)
        assert g_tree == w_tree
        for g, w in zip(g_leaves, w_leaves):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                          np.asarray(w.astype(jnp.float32)))


def test_kv_commit_layer_counts():
    """qwen2.5-3b commits all 36 layers in place and writes back none, and
    so does a group small enough to run unrolled; stacks with
    non-attention kinds write those layers back."""
    qwen = get_config("qwen2.5-3b")
    assert T.kv_commit_layers(qwen, T.build_groups(qwen)) == (36, 0)
    small = reduce_config(qwen)
    assert small.n_layers <= T.DECODE_UNROLL
    assert T.kv_commit_layers(small, T.build_groups(small)) == (
        small.n_layers, 0)
    gemma = get_config("gemma2-9b")
    assert T.kv_commit_layers(gemma, T.build_groups(gemma)) == (
        gemma.n_layers, 0)
    for arch in ("recurrentgemma-9b", "mamba2-130m", "deepseek-v2-236b",
                 "llama-3.2-vision-90b"):
        cfg = get_config(arch)
        inplace, writeback = T.kv_commit_layers(cfg, T.build_groups(cfg))
        assert writeback > 0, arch
        assert inplace + writeback >= cfg.n_layers, arch
    mamba = get_config("mamba2-130m")
    assert T.kv_commit_layers(mamba, T.build_groups(mamba)) == (
        0, mamba.n_layers)


def test_engine_stats_report_commit_counts():
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = dataclasses.replace(reduce_config(get_config("qwen2.5-3b")),
                              n_layers=12)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=2, kv_len=KV_LEN, max_new_tokens=3, impl="ref"))
    want = {"kv_rows_inplace_layers": cfg.n_layers, "kv_writeback_layers": 0}
    assert want.items() <= eng.stats().items()
    eng.submit(np.asarray([1, 2, 3]))
    eng.run_until_drained()
    s = eng.stats()
    assert s["finished"] == 1 and want.items() <= s.items()
