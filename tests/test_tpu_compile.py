"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

Interpret mode runs the kernel bodies on the CPU but never meets the TPU's
tiling and memory rules; these tests hand each kernel of the serving path
to the TPU compiler at qwen2.5-3b's widths (Hq=16, Hkv=2, hd=128,
d_model=2048, d_ff=11008) for a chip that is described, not attached.
Nothing runs, so they check only that the compiler accepts the kernel and
that a Mosaic custom call is in the program, named by its
``kernel_metadata`` (the text a chip trace carries for the op).

The topology is described inside a fixture (never at import), and every
case lives in this one file, so that only the test worker given this file
loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.decode import (flash_decode_fwd,
                                                  flash_decode_quant_fwd)
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.quant.kernel import quant_matmul_pallas

B, SKV, HQ, HKV, HD = 8, 1024, 16, 2, 128     # serving pool at qwen widths
C = 128                                       # packed-prefill stream
D_MODEL, D_FF = 2048, 11008


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single described chip, with the persistent compilation cache off:
    a compile for a described device is written but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _decode_fp(shape):
    return (lambda q, k, v, qp, kp: flash_decode_fwd(q, k, v, q_pos=qp,
                                                     kv_pos=kp),
            [shape((B, 1, HQ, HD), jnp.bfloat16),
             shape((B, SKV, HKV, HD), jnp.bfloat16),
             shape((B, SKV, HKV, HD), jnp.bfloat16),
             shape((B, 1), jnp.int32), shape((B, SKV), jnp.int32)])


def _decode_quant(bits):
    def case(shape):
        hdq = HD // (2 if bits == 4 else 1)
        return (lambda q, kq, ks, vq, vs, qp, kp: flash_decode_quant_fwd(
                    q, kq, ks, vq, vs, kv_bits=bits, q_pos=qp, kv_pos=kp),
                [shape((B, 1, HQ, HD), jnp.bfloat16),
                 shape((B, SKV, HKV, hdq), jnp.int8),
                 shape((B, SKV, HKV), jnp.float32),
                 shape((B, SKV, HKV, hdq), jnp.int8),
                 shape((B, SKV, HKV), jnp.float32),
                 shape((B, 1), jnp.int32), shape((B, SKV), jnp.int32)])
    return case


def _packed_prefill(shape):
    return (lambda q, k, v, seg: flash_attention_fwd(q, k, v, segments=seg,
                                                     causal=True),
            [shape((1, HQ, C, HD), jnp.bfloat16),
             shape((1, HKV, C, HD), jnp.bfloat16),
             shape((1, HKV, C, HD), jnp.bfloat16),
             shape((1, C), jnp.int32)])


def _dequant_matmul(bits):
    def case(shape):
        return (lambda x, q, s: quant_matmul_pallas(x, q, s, bits=bits),
                [shape((B, D_MODEL), jnp.bfloat16),
                 shape((D_MODEL // (2 if bits == 4 else 1), D_FF), jnp.int8),
                 shape((1, D_FF), jnp.float32)])
    return case


CASES = {
    "decode_fp": _decode_fp,
    "decode_kv8": _decode_quant(8),
    "decode_kv4": _decode_quant(4),
    "packed_prefill": _packed_prefill,
    "dequant_matmul_int8": _dequant_matmul(8),
    "dequant_matmul_int4": _dequant_matmul(4),
}
KERNEL = {"decode_fp": "decode_attention",
          "decode_kv8": "decode_attention_quant",
          "decode_kv4": "decode_attention_quant",
          "packed_prefill": "flash_attention",
          "dequant_matmul_int8": "quant_matmul",
          "dequant_matmul_int4": "quant_matmul"}


def _kernels(text: str) -> set:
    """Names in the ``kernel_metadata`` of the program's custom calls."""
    return set(re.findall(r'kernel_metadata=\{\s*"kernel":"(\w+)"\s*\}',
                          text))


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    fn, args = CASES[case](shape)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert _kernels(text) == {KERNEL[case]}


@pytest.mark.parametrize("layers", [2, 12])
def test_sharded_decode_step_compiles_for_v5e_2x2(topo, one_chip,
                                                  monkeypatch, layers):
    """The engine's fused decode step with ``impl="flash"`` on a (data=2,
    model=2) mesh of described chips, at qwen2.5-3b widths cut to two
    layers (an unrolled layer loop) or twelve (a layer scan).  GSPMD
    refuses to partition a Mosaic kernel, so the kernel must reach the
    compiler inside a shard_map, with the KV pool local to each device (no
    all-gather of it); each layer's rows commit in place into the sharded
    stacked pool, so no device copies or update-slices its whole shard."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.config import get_config
    from repro.models import transformer as T
    from repro.parallel.sharding import cache_shardings
    from repro.serving.engine import EngineConfig
    from repro.serving.executor import Executor

    # ops picks compiled kernels for impl="flash" only on a TPU backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=layers)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    ex = Executor(cfg, None, EngineConfig(max_batch=B, kv_len=SKV,
                                          impl="flash"), mesh=mesh)
    rep = NamedSharding(mesh, P())

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.PRNGKey(0), param_dtype=jnp.bfloat16))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, SKV))
    state = jax.eval_shape(lambda: {
        "tokens": jnp.zeros((B,), jnp.int32), "pos": jnp.zeros((B,), jnp.int32),
        "budget": jnp.zeros((B,), jnp.int32), "live": jnp.zeros((B,), bool),
        "key": jax.random.PRNGKey(0)})
    text = ex.jit_step.lower(
        placed(params, jax.tree.map(lambda _: rep, params)),
        placed(cache, cache_shardings(cache, ex.shard_ctx)),
        placed(state, jax.tree.map(lambda _: rep, state))).compile().as_text()
    assert "tpu_custom_call" in text
    assert _kernels(text) == {"decode_attention"}
    # every all-gather result is far smaller than one layer's K pool
    k_elems = B * SKV * HKV * HD
    for line in text.splitlines():
        if "all-gather" in line and "=" in line:
            result = line.split("=", 1)[1].split("all-gather")[0]
            for dims in re.findall(r"\[([\d,]*)\]", result):
                n = np.prod([int(d) for d in dims.split(",") if d])
                assert n < k_elems // 8, line
    # batch over data, KV heads over model: each device holds a quarter
    assert cache_shardings(cache, ex.shard_ctx)["stack"][0]["u0"]["attn"][
        "k"].spec == P(None, "data", None, "model", None)
    assert not _whole_pool_moves(text, (layers, B // 2, SKV, HKV // 2, HD))


@pytest.mark.parametrize("program", ["fused_step", "chunk_step"])
def test_layer_scan_commits_kv_in_place_for_v5e(one_chip, monkeypatch,
                                                program):
    """The fused decode step and the chunk step at qwen2.5-3b widths cut to
    12 layers, compiled for one described chip: the layer scan carries the
    donated KV pool and commits each layer's rows into it, so no copy or
    update-slice of the whole stacked pool is in the program, and its
    temporaries stay below one layer's K pool."""
    import dataclasses

    from repro.config import get_config
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig
    from repro.serving.executor import Executor

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layers = 12
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=layers)
    ex = Executor(cfg, None, EngineConfig(max_batch=B, kv_len=SKV,
                                          impl="flash"))

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.PRNGKey(0), param_dtype=jnp.bfloat16)))
    cache = placed(jax.eval_shape(lambda: T.init_cache(cfg, B, SKV)))
    vec = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    state = {"tokens": vec, "pos": vec, "budget": vec,
             "live": jax.ShapeDtypeStruct((B,), bool, sharding=one_chip),
             "key": placed(jax.eval_shape(lambda: jax.random.PRNGKey(0)))}
    if program == "fused_step":
        lowered = ex.jit_step.lower(params, cache, state)
    else:
        blk = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one_chip)
        lowered = ex.jit_chunk_step.lower(
            params, cache, state, blk, blk, vec,
            jax.ShapeDtypeStruct((B,), bool, sharding=one_chip), vec)
    compiled = lowered.compile()
    text = compiled.as_text()

    assert not _whole_pool_moves(text, (layers, B, SKV, HKV, HD))
    k_layer_bytes = B * SKV * HKV * HD * 2
    assert compiled.memory_analysis().temp_size_in_bytes < k_layer_bytes


def _whole_pool_moves(text: str, pool: tuple) -> list:
    """Instructions of a compiled program that copy the stacked pool or
    update a slice of it into a whole new pool-shaped result: a ``copy``, a
    ``dynamic-update-slice``, or a fusion that holds one.  Shapes compare
    with their unit dims dropped (a bitcast may drop them)."""
    def dims(shape):
        return tuple(int(d) for d in shape.split(",") if d and d != "1")

    comps = dict(re.findall(r"^(%\S+) .*?\{\n(.*?)\n\}$", text, re.M | re.S))
    op = re.compile(r"=\s*\w+\[([\d,]*)\]\S*\s+([\w-]+)\((.*)")
    moves = []
    for line in text.splitlines():
        m = op.search(line)
        if not m or dims(m.group(1)) != dims(",".join(map(str, pool))):
            continue
        called = re.search(r"calls=(%[\w.-]+)", m.group(3))
        body = comps.get(called.group(1), "") if called else ""
        if m.group(2) in ("copy", "dynamic-update-slice") or (
                m.group(2) == "fusion" and "dynamic-update-slice" in
                line.split("=", 1)[0] + body):
            moves.append(line)
    return moves
