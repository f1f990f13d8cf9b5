"""Pallas decode-attention kernel (interpret mode) vs the pure-jnp oracle:
GQA folding, sliding window, per-slot lengths, empty slots, bf16, and the
``impl="flash"`` routing through ops/attention/decode_step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduce_config
from repro.kernels.flash_attention.decode import flash_decode_fwd
from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.models import transformer as T


def _pool(key, B, Skv, Hq, Hkv, hd, lengths, dtype=jnp.float32):
    """Random (q, k, v, q_pos, kv_pos) for a slotted pool with per-slot
    lengths: slot i holds tokens 0..lengths[i]-1, the query sits at
    position lengths[i]-1, and entries beyond the length are empty (-1)."""
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
def test_decode_kernel_matches_ref(Hq, Hkv, window):
    B, Skv, hd = 3, 64, 32
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(0), B, Skv, Hq, Hkv,
                                   hd, lengths=[3, 31, 64])
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                           window=window, interpret=True)
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                        kv_valid=kv_pos >= 0, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_softcap():
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(1), 2, 32, 4, 2, 16,
                                   lengths=[7, 30])
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos, softcap=30.0,
                           interpret=True)
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                        kv_valid=kv_pos >= 0, causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_multiblock_sweep():
    """Skv spanning several K/V blocks exercises the online-softmax carry."""
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(2), 2, 512, 4, 2, 16,
                                   lengths=[200, 512])
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos, block_k=128,
                           interpret=True)
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                        kv_valid=kv_pos >= 0, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("Hq,Hkv,window", [(4, 4, 0), (4, 2, 0), (8, 1, 0),
                                           (4, 2, 16)])
def test_decode_kernel_bf16_matrix(Hq, Hkv, window):
    """Acceptance: ≤ 1e-2 max abs error in bf16 across GQA/window/empty."""
    B, Skv, hd = 3, 64, 32
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(3), B, Skv, Hq, Hkv,
                                   hd, lengths=[5, 33, 64],
                                   dtype=jnp.bfloat16)
    kv_pos = kv_pos.at[0].set(-1)          # slot 0 fully empty
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                           window=window, interpret=True)
    ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), q_pos=q_pos, kv_pos=kv_pos,
                        kv_valid=kv_pos >= 0, causal=True, window=window)
    assert out.dtype == jnp.bfloat16
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    assert err <= 1e-2, err


def test_decode_kernel_empty_slot_yields_zeros():
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(4), 2, 32, 4, 4, 16,
                                   lengths=[10, 20])
    kv_pos = kv_pos.at[1].set(-1)
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                           interpret=True)
    assert bool(jnp.isfinite(out).all())
    assert bool((out[1] == 0.0).all())
    # the non-empty slot is unaffected
    ref = attention_ref(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                        kv_valid=kv_pos >= 0, causal=True)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               atol=2e-5)


def test_decode_kernel_ring_buffer_order():
    """Ring caches store positions out of order — the kernel masks by the
    position *values*, so a rolled pool must give identical output."""
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(5), 1, 32, 4, 2, 16,
                                   lengths=[32])
    roll = 11
    k2 = jnp.roll(k, roll, axis=1)
    v2 = jnp.roll(v, roll, axis=1)
    kv_pos2 = jnp.roll(kv_pos, roll, axis=1)
    out = flash_decode_fwd(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                           window=8, interpret=True)
    out2 = flash_decode_fwd(q, k2, v2, q_pos=q_pos, kv_pos=kv_pos2,
                            window=8, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=2e-5)


def test_ops_decode_honours_arbitrary_kv_valid():
    """A caller-supplied kv_valid that is NOT kv_pos>=0 must be honoured by
    the kernel route (folded into kv_pos), matching ref exactly."""
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(8), 2, 32, 4, 2, 16,
                                   lengths=[20, 32])
    valid = (kv_pos % 3 != 0) & (kv_pos >= 0)      # arbitrary extra mask
    out = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_valid=valid,
                    causal=True, impl="flash")
    ref = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_valid=valid,
                    causal=True, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ops_routes_flash_decode():
    """impl='flash' with Sq==1 + explicit positions must route to the decode
    kernel (and agree with ref); cross-style causal=False must not."""
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(6), 2, 32, 4, 2, 16,
                                   lengths=[9, 25])
    out = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                    kv_valid=kv_pos >= 0, causal=True, impl="flash")
    ref = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                    kv_valid=kv_pos >= 0, causal=True, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # causal=False (cross decode) falls back to ref without error
    out_x = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False,
                      impl="flash")
    assert out_x.shape == out.shape


def test_ops_explicit_pallas_raises_without_a_kernel():
    """impl='pallas' names the kernel: a call no kernel fits (cross-style
    causal=False decode, a chunk of queries with explicit positions) raises
    instead of running the reference unseen; impl='flash' still falls back."""
    q, k, v, q_pos, kv_pos = _pool(jax.random.PRNGKey(9), 2, 32, 4, 2, 16,
                                   lengths=[9, 25])
    with pytest.raises(ValueError, match="no Pallas attention kernel"):
        attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=False,
                  impl="pallas")
    qc = jnp.concatenate([q, q], axis=1)               # (B, 2, Hq, hd)
    pos2 = jnp.concatenate([q_pos, q_pos + 1], axis=1)
    with pytest.raises(ValueError, match="no Pallas attention kernel"):
        attention(qc, k, v, q_pos=pos2, kv_pos=kv_pos, causal=True,
                  impl="pallas")
    out = attention(qc, k, v, q_pos=pos2, kv_pos=kv_pos, causal=True,
                    impl="flash")
    assert out.shape == qc.shape


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "gemma2-9b"])
def test_decode_step_flash_matches_ref(arch):
    """Full model decode_step: flash vs ref logits (gemma2 covers the
    local/ring + softcap path, qwen the GQA global path)."""
    cfg = reduce_config(get_config(arch))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    B, S = 2, 32
    cache_r = T.init_cache(cfg, B, S, dtype=jnp.bfloat16)
    prompt = jnp.asarray([[5, 9, 2, 7], [1, 2, 3, 4]], jnp.int32)
    logits, pcache = T.prefill(params, cfg, {"tokens": prompt}, kv_cap=S)
    cache = jax.tree_util.tree_map(
        lambda pool, one: one.astype(pool.dtype), cache_r, pcache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.asarray([4, 4], jnp.int32)
    for _ in range(3):
        lr, cache_ref = T.decode_step(params, cfg, cache, tok, pos, impl="ref")
        lf, cache_fl = T.decode_step(params, cfg, cache, tok, pos,
                                     impl="flash")
        err = float(jnp.abs(lr.astype(jnp.float32)
                            - lf.astype(jnp.float32)).max())
        assert err <= 1e-2, err
        cache, tok, pos = cache_ref, jnp.argmax(lr, -1).astype(jnp.int32), pos + 1
