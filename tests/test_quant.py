"""Quantization plane: round-trip properties (scales, int4 pack/unpack,
error bounds), the fused dequant-matmul and quantised-KV decode kernels vs
their fp oracles, the quantised serving engine (token parity against the
fake-quant oracle), and the precision-aware Plane-B traffic model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduce_config
from repro.quant.core import (QMAX, QuantTensor, dequantize, dequantize_kv,
                              fake_quantize_params, pack_int4, quantize,
                              quantize_kv, quantize_kv_cache, quantize_params,
                              unpack_int4)
from repro.quant.ops import quant_matmul


# ---------------------------------------------------------------------------
# round-trip properties
# ---------------------------------------------------------------------------

def test_int4_pack_unpack_bijective():
    """Every int4 code value survives pack→unpack on any axis."""
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.integers(-8, 8, size=(6, 10, 8)), jnp.int8)
    for axis in (-1, 0, 1):
        p = pack_int4(c, axis=axis)
        assert p.shape[axis] * 2 == c.shape[axis]
        assert (unpack_int4(p, axis=axis) == c).all()
    # the full nibble range, incl. the -8 edge
    edge = jnp.asarray([[-8, 7], [-1, 0], [3, -5]], jnp.int8)
    assert (unpack_int4(pack_int4(edge, -1), -1) == edge).all()


def test_pack_int4_odd_axis_raises():
    with pytest.raises(ValueError, match="even"):
        pack_int4(jnp.zeros((3, 5), jnp.int8), axis=-1)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("group", [0, 16])
def test_weight_quant_scale_correctness_and_error_bound(bits, group):
    """Per-channel/group scales equal max|w|/qmax over their group, and the
    reconstruction error is bounded by scale/2 (round-to-nearest)."""
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
    qt = quantize(w, bits, group=group)
    qmax = QMAX[bits]
    wf = np.asarray(w, np.float64)
    if group:
        grp = wf.reshape(64 // group, group, 48)
        expect = np.abs(grp).max(axis=1) / qmax
    else:
        expect = np.abs(wf).max(axis=0, keepdims=True) / qmax
    np.testing.assert_allclose(np.asarray(qt.scale), expect, rtol=1e-6)
    err = np.abs(np.asarray(dequantize(qt)) - wf)
    scale_full = np.repeat(expect, group, axis=0) if group else expect
    assert (err <= scale_full / 2 + 1e-7).all()


def test_dequant_error_shrinks_with_bit_width():
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    err = {bits: float(jnp.abs(dequantize(quantize(w, bits)) - w).max())
           for bits in (8, 4)}
    assert err[8] < err[4]
    # int8 error ~ scale/2 = max|w|/254; int4 ~ max|w|/14
    mx = float(jnp.abs(w).max())
    assert err[8] <= mx / 254 * 1.01
    assert err[4] <= mx / 14 * 1.01


@pytest.mark.parametrize("bits", [8, 4])
def test_kv_quant_round_trip(bits):
    """Per-(token, head) scales: row-wise error bound; all-zero rows (empty
    slots) reconstruct exact zeros."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 4, 16))
    x = x.at[0, 3].set(0.0)                      # an empty row per head
    codes, scale = quantize_kv(x, bits)
    assert codes.dtype == jnp.int8
    assert scale.shape == (2, 7, 4)
    back = dequantize_kv(codes, scale, bits)
    bound = np.asarray(scale)[..., None] / 2 + 1e-7
    assert (np.abs(np.asarray(back - x)) <= bound).all()
    assert (np.asarray(back[0, 3]) == 0.0).all()


def test_quantize_invalid_bits_raises():
    w = jnp.zeros((8, 8))
    with pytest.raises(ValueError):
        quantize(w, 16)
    with pytest.raises(ValueError):
        quantize_kv(w, 2)


# ---------------------------------------------------------------------------
# fused dequant-matmul kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("group", [0, 32])
def test_quant_matmul_kernel_matches_ref(bits, group):
    """The Pallas kernel (interpret mode) reproduces the reference
    dequant+matmul bit-for-bit (both accumulate the same dequantised f32
    weights)."""
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 128))
    w = jax.random.normal(jax.random.PRNGKey(4), (128, 256))
    qt = quantize(w, bits, group=group)
    ref = quant_matmul(x, qt, impl="ref")
    out = quant_matmul(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_quant_matmul_untileable_falls_back():
    """Shapes the Pallas grid can't tile exactly fall back to ref."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 48))
    w = jax.random.normal(jax.random.PRNGKey(6), (48, 50))
    qt = quantize(w, 8)
    out = quant_matmul(x, qt, impl="pallas_interpret")
    ref = quant_matmul(x, qt, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_quant_matmul_explicit_pallas_untileable_raises():
    """impl='pallas' names the kernel: shapes it cannot tile raise instead
    of silently running the reference."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 48))
    qt = quantize(jax.random.normal(jax.random.PRNGKey(6), (48, 300)), 8)
    with pytest.raises(ValueError, match="no dequant-matmul kernel"):
        quant_matmul(x, qt, impl="pallas")


# ---------------------------------------------------------------------------
# quantised-KV decode kernel vs fp oracle
# ---------------------------------------------------------------------------

def _pool(key, B, Skv, Hq, Hkv, hd, lengths, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, hd), dtype)
    L = np.asarray(lengths, np.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32), (B, Skv))
    kv_pos = jnp.where(kv_pos < L[:, None], kv_pos, -1)
    q_pos = jnp.asarray(L[:, None] - 1, jnp.int32)
    return q, k, v, q_pos, kv_pos


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])  # MHA/GQA/MQA
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("bits", [8, 4])
def test_quant_decode_kernel_matches_dequant_oracle(Hq, Hkv, window, bits):
    """The quantised-KV decode kernel equals the reference attention over
    the *dequantised* cache (same codes, same scales) — quantisation error
    lives entirely in the representation, never in the kernel."""
    from repro.kernels.flash_attention.decode import flash_decode_quant_fwd
    from repro.kernels.flash_attention.ref import attention_ref

    B, Skv, hd = 3, 64, 32
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(0), B, Skv, Hq, Hkv,
                                   hd, lengths=[3, 31, 64])
    k_q, k_s = quantize_kv(k, bits)
    v_q, v_s = quantize_kv(v, bits)
    out = flash_decode_quant_fwd(q, k_q, k_s, v_q, v_s, kv_bits=bits,
                                 q_pos=q_pos, kv_pos=kv_pos, window=window,
                                 interpret=True)
    ref = attention_ref(q, dequantize_kv(k_q, k_s, bits).astype(q.dtype),
                        dequantize_kv(v_q, v_s, bits).astype(q.dtype),
                        q_pos=q_pos, kv_pos=kv_pos, kv_valid=kv_pos >= 0,
                        causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_quant_decode_kernel_empty_slot_zeros():
    from repro.kernels.flash_attention.decode import flash_decode_quant_fwd

    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(1), 2, 32, 4, 2, 16,
                                   lengths=[10, 20])
    kv_pos = kv_pos.at[1].set(-1)
    k_q, k_s = quantize_kv(k, 8)
    v_q, v_s = quantize_kv(v, 8)
    out = flash_decode_quant_fwd(q, k_q, k_s, v_q, v_s, kv_bits=8,
                                 q_pos=q_pos, kv_pos=kv_pos, interpret=True)
    assert bool(jnp.isfinite(out).all())
    assert bool((out[1] == 0.0).all())


def test_ops_quant_route_matches_ref_route():
    """ops.attention with k_scale/v_scale: the kernel route and the
    dequantise-up-front ref route agree."""
    from repro.kernels.flash_attention.ops import attention

    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(2), 2, 64, 4, 2, 16,
                                   lengths=[20, 64])
    k_q, k_s = quantize_kv(k, 4)
    v_q, v_s = quantize_kv(v, 4)
    kw = dict(k_scale=k_s, v_scale=v_s, kv_bits=4, q_pos=q_pos,
              kv_pos=kv_pos, kv_valid=kv_pos >= 0, causal=True)
    out = attention(q, k_q, v_q, impl="flash", **kw)
    ref = attention(q, k_q, v_q, impl="ref", **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# parameter-tree quantisation
# ---------------------------------------------------------------------------

def test_quantize_params_selects_dense_projections_only():
    from repro.models import transformer as T

    cfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, 8)
    leaves = jax.tree_util.tree_leaves_with_path(
        qp, is_leaf=lambda x: isinstance(x, QuantTensor))

    def kinds(pred):
        return {str(getattr(p[-1], "key", "")) for p, l in leaves if pred(l)}

    quantised = kinds(lambda l: isinstance(l, QuantTensor))
    kept_fp = kinds(lambda l: not isinstance(l, QuantTensor))
    assert {"wq", "wk", "wv", "wo"} <= quantised
    # router, biases, norms, embeddings and the 4-D MoE expert banks stay fp
    assert "router" in kept_fp
    assert "tok" in kept_fp
    for pth, leaf in leaves:
        keys = [str(getattr(p, "key", "")) for p in pth]
        if "experts" in keys:
            assert not isinstance(leaf, QuantTensor), keys


def test_fake_quantize_params_matches_quantised_values():
    from repro.models import transformer as T

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    qp = quantize_params(params, 8)
    fq = fake_quantize_params(params, 8)
    qt = qp["stack"][0]["u0"]["attn"]["wq"]
    assert isinstance(qt, QuantTensor)
    np.testing.assert_array_equal(
        np.asarray(dequantize(qt)),
        np.asarray(fq["stack"][0]["u0"]["attn"]["wq"]))


# ---------------------------------------------------------------------------
# serving engine: quantised paths
# ---------------------------------------------------------------------------

def _drain(cfg, params, *, weight_bits=0, kv_bits=0, impl="ref",
           prompts=(6, 10, 14), max_new=5, kv_len=64, max_batch=3):
    from repro.serving.engine import EngineConfig, ServingEngine

    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=max_batch, kv_len=kv_len, max_new_tokens=max_new,
        impl=impl, prefill_chunk=32, weight_bits=weight_bits,
        kv_bits=kv_bits))
    rng = np.random.default_rng(0)
    for plen in prompts:
        eng.submit(rng.integers(0, cfg.vocab_size, size=plen))
    done = eng.run_until_drained()
    return [tuple(r.output) for r in sorted(done, key=lambda r: r.uid)], eng


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma2-9b"])
def test_engine_w8_matches_fake_quant_oracle_exactly(arch):
    """Weight-only int8 serving must be token-identical to an fp engine
    running the dequantise(quantise(W)) weights: the quantised path changes
    the weight *values* once, offline — never the arithmetic."""
    from repro.models import transformer as T

    cfg = reduce_config(get_config(arch))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    got, _ = _drain(cfg, params, weight_bits=8)
    oracle, _ = _drain(cfg, fake_quantize_params(params, 8))
    assert got == oracle


@pytest.mark.parametrize("arch,wb,kb", [
    ("qwen2.5-3b", 8, 8),        # GQA, packed admission
    ("gemma2-9b", 8, 8),         # local sliding-window ring + softcaps
    ("recurrentgemma-9b", 8, 8),  # hybrid local+recurrent (padded admission)
    ("qwen2.5-3b", 4, 4),        # packed-int4 extreme
])
def test_engine_quantised_drains_and_tracks_fp(arch, wb, kb):
    """Quantised serving drains every request to completion with the same
    episode shape as fp; int8 stays close to the fp tokens (bounded drift —
    random-init reduced models have tiny logit margins, so exact parity is
    not required here; the fake-quant oracle test pins exactness where it
    is well-defined)."""
    from repro.models import transformer as T

    cfg = reduce_config(get_config(arch))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    fp, _ = _drain(cfg, params)
    out, eng = _drain(cfg, params, weight_bits=wb, kv_bits=kb)
    assert len(out) == len(fp)
    assert [len(o) for o in out] == [len(f) for f in fp]
    if wb == 8:
        prefix = np.mean([sum(x == y for x, y in zip(a, b)) / max(len(a), 1)
                          for a, b in zip(fp, out)])
        assert prefix >= 0.4, f"int8 drifted too far from fp: {prefix}"
    stats = eng.stats()
    assert stats["weight_bits"] == (wb or 16)
    assert stats["kv_bits"] == (kb or 16)


def test_engine_kv_cache_stored_quantised():
    """kv_bits=8 keeps the slot pool int8 end-to-end: no fp k/v leaves
    exist in the engine cache, and the code/scale planes are populated by
    prefill + decode commits."""
    from repro.models import transformer as T

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    _, eng = _drain(cfg, params, kv_bits=8)
    leaves = jax.tree_util.tree_flatten_with_path(eng.cache)[0]
    names = {str(getattr(p[-1], "key", "")) for p, _ in leaves}
    assert {"k_q", "k_s", "v_q", "v_s"} <= names
    assert "k" not in names and "v" not in names
    for pth, leaf in leaves:
        name = str(getattr(pth[-1], "key", ""))
        if name in ("k_q", "v_q"):
            assert leaf.dtype == jnp.int8
            assert int(jnp.abs(leaf).max()) > 0    # commits actually landed


def test_engine_quant_flash_impl_matches_ref_impl_shape():
    """The quantised pool also routes through the Pallas decode kernel
    (impl='flash'); both impls drain with identical episode shapes."""
    from repro.models import transformer as T

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    ref, _ = _drain(cfg, params, weight_bits=8, kv_bits=8, impl="ref")
    fl, _ = _drain(cfg, params, weight_bits=8, kv_bits=8, impl="flash")
    assert [len(o) for o in fl] == [len(o) for o in ref]


def test_engine_invalid_bits_raise():
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="weight_bits"):
        ServingEngine(cfg, params, EngineConfig(weight_bits=3))
    with pytest.raises(ValueError, match="kv_bits"):
        ServingEngine(cfg, params, EngineConfig(kv_bits=16))


# ---------------------------------------------------------------------------
# precision-aware Plane-B traffic + bridge
# ---------------------------------------------------------------------------

def test_traffic_precision_scaling_monotone():
    from repro.core.traffic import (Workload, decode_step_phases,
                                    decode_weight_stream_bytes,
                                    total_traffic_bytes)

    cfg = get_config("qwen2.5-3b")
    tot = {}
    for bits in (16, 8, 4):
        w = Workload.from_config(cfg, seq_len=128, weight_bits=bits,
                                 kv_bits=bits)
        tot[bits] = total_traffic_bytes(decode_step_phases(w, 200, 4))
    assert tot[4] < tot[8] < tot[16]
    # weight streams halve (plus the small f32 scale plane) at int8
    w16 = Workload.from_config(cfg, seq_len=128)
    w8 = Workload.from_config(cfg, seq_len=128, weight_bits=8)
    ratio = decode_weight_stream_bytes(w8) / decode_weight_stream_bytes(w16)
    assert 0.5 < ratio < 0.52


def test_traffic_fp16_default_unchanged():
    """weight_bits=kv_bits=16 is the pre-quantisation model, term by term
    (the Table-4 calibration surface cannot move)."""
    from repro.core import traffic

    w_def = traffic.Workload.from_config(get_config("gpt-j"), seq_len=64)
    w_exp = traffic.Workload.from_config(get_config("gpt-j"), seq_len=64,
                                         weight_bits=16, kv_bits=16)
    for fn in (traffic.transformer_phases, traffic.prefill_phases):
        for a, b in zip(fn(w_def), fn(w_exp)):
            assert dataclasses.asdict(a) == dataclasses.asdict(b)
    assert w_def.weight_dram_bytes(100, 200) == 100 * 200 * traffic.BYTES


def test_traffic_invalid_bits_raise():
    from repro.core.traffic import Workload

    with pytest.raises(ValueError, match="precision"):
        Workload.from_config(get_config("gpt-j"), seq_len=8, weight_bits=2)


def test_kv_cache_bytes_scale_with_kv_bits():
    from repro.core.traffic import Workload, kv_cache_bytes_per_layer

    cfg = get_config("qwen2.5-3b")
    w16 = Workload.from_config(cfg, seq_len=64)
    w8 = Workload.from_config(cfg, seq_len=64, kv_bits=8)
    w4 = Workload.from_config(cfg, seq_len=64, kv_bits=4)
    b16 = kv_cache_bytes_per_layer(w16, 1000)
    b8 = kv_cache_bytes_per_layer(w8, 1000)
    b4 = kv_cache_bytes_per_layer(w4, 1000)
    assert b4 < b8 < b16
    # int8 halves the element bytes; the f32 per-(token, head) scale plane
    # rides on top
    assert b8 == pytest.approx(b16 / 2 + 2.0 * 1000 * w8.n_kv_heads * 4)


def test_bridge_carries_measured_precision():
    """engine(weight_bits=8, kv_bits=8) → stats → mix_from_stats →
    cosim_from_engine: the replayed Plane-B traffic shrinks vs the fp
    replay of the same mix."""
    import dataclasses as dc

    from repro.core.cosim import cosim_mix, mix_from_stats
    from repro.models import transformer as T

    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    _, eng = _drain(cfg, params, weight_bits=8, kv_bits=8)
    mix = mix_from_stats(eng.stats())
    assert mix.weight_bits == 8 and mix.kv_bits == 8
    full = get_config("qwen2.5-3b")
    quant = cosim_mix(full, mix, 64)
    fp = cosim_mix(full, dc.replace(mix, weight_bits=16, kv_bits=16), 64)
    for arch in quant:
        assert quant[arch]["decode_bytes"] < fp[arch]["decode_bytes"]
        assert quant[arch]["prefill_bytes"] < fp[arch]["prefill_bytes"]


def test_generation_phases_scale_with_precision():
    from repro.core.cosim import Episode, EpisodeMix, generation_phases
    from repro.core.traffic import total_traffic_bytes

    def mix(bits):
        return EpisodeMix([Episode(64, 16, 2)], prefill_chunk=16,
                          max_batch=4, active_hist={4: 1},
                          max_stall_tokens=16,
                          weight_bits=bits, kv_bits=bits)

    t16 = total_traffic_bytes(generation_phases("qwen2.5-3b", mix(16)))
    t8 = total_traffic_bytes(generation_phases("qwen2.5-3b", mix(8)))
    assert t8 < 0.7 * t16


# ---------------------------------------------------------------------------
# report hardening (malformed BENCH_*.json must not kill the report)
# ---------------------------------------------------------------------------

def test_report_skips_malformed_records(tmp_path, monkeypatch, capsys):
    import benchmarks.report as report

    dryrun = tmp_path / "dryrun"
    dryrun.mkdir()
    (dryrun / "broken.json").write_text('{"arch": "x", "shape":')  # truncated
    (dryrun / "nokeys.json").write_text('{"unrelated": 1}')
    (dryrun / "ok.json").write_text(
        '{"arch": "a", "shape": "s", "mesh": "single", "status": "skipped",'
        ' "reason": "test"}')
    monkeypatch.setattr(report, "DRYRUN", str(dryrun))

    recs = report.load()
    assert list(recs) == [("a", "s", "single")]
    err = capsys.readouterr().err
    assert "broken.json" in err and "nokeys.json" in err

    # malformed benchmark records degrade to a notice, not a traceback
    (tmp_path / "BENCH_serving.json").write_text("{not json")
    (tmp_path / "BENCH_cosim.json").write_text('["wrong shape"')
    (tmp_path / "BENCH_quant.json").write_text("")
    assert "malformed" in report.serving_table()
    assert "malformed" in report.cosim_table()
    assert "malformed" in report.quant_table()

    # valid JSON with a stale schema (missing keys) degrades per-section
    (tmp_path / "BENCH_quant.json").write_text('{"arch": "x"}')
    assert "section unavailable" in report._render(report.quant_table)


def test_report_quant_table_renders(tmp_path, monkeypatch):
    """quant_table renders the real smoke record when present."""
    import json
    import os

    import benchmarks.report as report

    smoke = os.path.join(os.path.dirname(report.__file__), "..",
                         "experiments", "BENCH_quant_smoke.json")
    if not os.path.exists(smoke):
        pytest.skip("no quant smoke record")
    dryrun = tmp_path / "dryrun"
    dryrun.mkdir()
    rec = json.load(open(smoke))
    (tmp_path / "BENCH_quant.json").write_text(json.dumps(rec))
    monkeypatch.setattr(report, "DRYRUN", str(dryrun))
    table = report.quant_table()
    assert "fake-quant oracle parity" in table
    assert "Plane-B projection" in table
