"""Attention layers: MHA/GQA/MQA, local (sliding-window, ring-buffer cache),
cross-attention, and DeepSeek MLA (naive train path + absorbed decode path).

These are the paper's *dynamic* kernels — per-token-changing operands that
the paper routes to the SM/MC/DRAM plane (§3.1).  The sharding plan gives
their activations head-wise placement ("SM cluster"); the inner product
runs through :mod:`repro.kernels.flash_attention`.

Serving modes beyond train/decode:

- ``mode="prefill"`` with ``segments=`` — **packed ragged prefill**: several
  prompts in one token stream, per-token prompt ids, no cross-prompt
  attention.  Returns the *raw per-token* cache (no slot padding); the
  serving engine gathers each segment into its KV slot.
- ``mode="chunk"`` — **chunked prefill continuation**: a block of S tokens
  per batch row is written into the existing KV cache at explicit
  positions (``pos < 0`` = pad, dropped) and attends to the whole cache,
  so later chunks of a long prompt see the KV of earlier chunks.
- ``mode="prefill"`` with ``length=`` — right-padded single-prompt prefill
  whose cache state is *exact* at ``length`` (ring caches keep the last
  real tokens, not the pads).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.common import NEG_INF
from repro.kernels.flash_attention.ops import attention as flash_attention
from repro.models.modules import apply_rope, dense_init, rmsnorm
from repro.parallel import constrain
from repro.quant.core import (dequantize_kv, kv_cache_bits, quantize_kv,
                              quantize_kv_cache)
from repro.quant.ops import qdense


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg, *, cross: bool = False, dtype=jnp.float32):
    D = cfg.d_model
    Hq, Hkv, hd, hdv = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, Hq * hd), dtype),
        "wk": dense_init(ks[1], (D, Hkv * hd), dtype),
        "wv": dense_init(ks[2], (D, Hkv * hdv), dtype),
        "wo": dense_init(ks[3], (Hq * hdv, D), dtype, fan_in=Hq * hdv),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((Hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hdv,), jnp.float32)
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def init_mla(key, cfg, *, dtype=jnp.float32):
    D, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    p = {
        "wkv_a": dense_init(ks[0], (D, kvr + dr), dtype),
        "kv_norm": jnp.zeros((kvr,), jnp.float32),
        "wkv_b": dense_init(ks[1], (kvr, H, dn + dv), dtype, fan_in=kvr),
        "wo": dense_init(ks[2], (H * dv, D), dtype, fan_in=H * dv),
    }
    if qr:
        p["wq_a"] = dense_init(ks[3], (D, qr), dtype)
        p["q_norm"] = jnp.zeros((qr,), jnp.float32)
        p["wq_b"] = dense_init(ks[4], (qr, H, dn + dr), dtype, fan_in=qr)
    else:
        p["wq"] = dense_init(ks[3], (D, H, dn + dr), dtype)
    return p


# ---------------------------------------------------------------------------
# KV caches
# ---------------------------------------------------------------------------

def init_kv_cache(cfg, kind: str, batch: int, kv_len: int, dtype,
                  n_cross: int = 0, kv_bits: int = 0):
    """``kv_bits`` (0 | 8 | 4): 0 keeps the fp pool; 8/4 allocate the
    *quantised* slot pool — int8 code planes (packed two-per-byte along the
    head dim for int4) plus per-(entry, head) f32 scales, quantised on
    commit and dequantised on read.  Quantisation covers the self-attention
    k/v pools; MLA latent and cross caches stay fp (documented)."""
    Hkv, hd, hdv = cfg.n_kv_heads, cfg.head_dim, cfg.v_head_dim
    if cfg.is_mla and kind != "cross":
        return {
            "ckv": jnp.zeros((batch, kv_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, kv_len, cfg.rope_head_dim), dtype),
            "pos": jnp.full((batch, kv_len), -1, jnp.int32),
        }
    if kind == "cross":
        return {
            "k": jnp.zeros((batch, n_cross, Hkv, hd), dtype),
            "v": jnp.zeros((batch, n_cross, Hkv, hdv), dtype),
        }
    cap = kv_len if kind == "global" else min(cfg.window, kv_len)
    if kv_bits in (4, 8):
        pack = 2 if kv_bits == 4 else 1
        if hd % pack or hdv % pack:
            raise ValueError(f"int4 KV needs even head dims, got {hd}/{hdv}")
        return {
            "k_q": jnp.zeros((batch, cap, Hkv, hd // pack), jnp.int8),
            "k_s": jnp.zeros((batch, cap, Hkv), jnp.float32),
            "v_q": jnp.zeros((batch, cap, Hkv, hdv // pack), jnp.int8),
            "v_s": jnp.zeros((batch, cap, Hkv), jnp.float32),
            "pos": jnp.full((batch, cap), -1, jnp.int32),
        }
    if kv_bits:
        raise ValueError(f"kv_bits must be 0, 4 or 8, got {kv_bits}")
    return {
        "k": jnp.zeros((batch, cap, Hkv, hd), dtype),
        "v": jnp.zeros((batch, cap, Hkv, hdv), dtype),
        "pos": jnp.full((batch, cap), -1, jnp.int32),
    }


def ring_positions(length, cap: int):
    """Position held by each slot of a ``cap``-entry ring cache after
    prefilling ``length`` tokens: slot ``s`` holds ``p ≡ s (mod cap)``,
    ``p ∈ [length-cap, length)``; ``p < 0`` = empty.  ``length`` broadcasts
    (scalar → (cap,), (B, 1) → (B, cap)).  For global caches (cap >= length)
    this degenerates to the identity layout.  The single source of truth for
    the layout shared by prefill ring fill and the serving engine's packed
    multi-slot insert."""
    s_idx = jnp.arange(cap, dtype=jnp.int32)
    return length - 1 - ((length - 1 - s_idx) % cap)


def _ring_fill(k, v, positions, cap, length=None):
    """Build a ring cache holding the last ``cap`` prefilled tokens.

    Without ``length`` the stream is exact and the last ``cap`` of S tokens
    are kept.  With ``length`` (traced scalar) the stream is right-padded
    (positions are ``arange(S)``) and the ring keeps the last
    ``min(length, cap)`` *real* tokens — pads never enter the cache and
    never evict real entries.
    """
    B, S = k.shape[0], k.shape[1]
    if length is None:
        keep = min(S, cap)
        pos_tail = positions[:, S - keep:]               # (B, keep)
        slots = pos_tail % cap
        bidx = jnp.arange(B)[:, None]
        kc = jnp.zeros((B, cap) + k.shape[2:], k.dtype).at[bidx, slots].set(k[:, S - keep:])
        vc = jnp.zeros((B, cap) + v.shape[2:], v.dtype).at[bidx, slots].set(v[:, S - keep:])
        pc = jnp.full((B, cap), -1, jnp.int32).at[bidx, slots].set(pos_tail)
        return kc, vc, pc
    p = ring_positions(length, cap)
    valid = p >= 0
    src = jnp.clip(p, 0, S - 1)
    kc = jnp.where(valid[None, :, None, None], k[:, src], 0)
    vc = jnp.where(valid[None, :, None, None], v[:, src], 0)
    pc = jnp.broadcast_to(jnp.where(valid, p, -1), (B, cap))
    return kc, vc, pc


def _pad_cache(x, cap):
    B, S = x.shape[0], x.shape[1]
    if cap <= S:
        return x
    pad = jnp.zeros((B, cap - S) + x.shape[2:], x.dtype)
    return jnp.concatenate([x, pad], axis=1)


def _pad_pos(pos, cap):
    B, S = pos.shape
    if cap <= S:
        return pos
    return jnp.concatenate([pos, jnp.full((B, cap - S), -1, jnp.int32)], axis=1)


def _ring_write(cache, new_leaves: dict, pos, layer=None):
    """Write S tokens at per-(row, token) ``pos`` into the cache (ring for
    local, direct for global).  ``new_leaves`` maps cache leaf names to the
    (B, S, ...) values to commit — ``{"k", "v"}`` for fp pools, the
    code/scale planes for quantised ones — so one scatter covers both
    layouts.  ``pos < 0`` entries are dropped — dead pool slots and chunk
    pads never touch the cache.  Within one call only the last ``cap``
    positions of a row survive the ring, so those are the only ones written
    (keeps scatter indices unique per row).

    ``layer`` (layer index): the cache leaves are the stacked pool
    ``(L, B, cap, ...)`` and the rows land at ``[layer, row, slot]`` — one
    scatter into the stacked leaf, which a layer scan carrying the donated
    pool updates in place."""
    cap = cache["pos"].shape[-1]
    B, S = pos.shape
    row_max = jnp.max(jnp.where(pos >= 0, pos, -1), axis=1, keepdims=True)
    valid = (pos >= 0) & (pos > row_max - cap)
    slot = jnp.where(valid, pos % cap, cap)          # cap = out of bounds
    bidx = jnp.arange(B)[:, None]
    idx = (bidx, slot) if layer is None else (layer, bidx, slot)
    new = {name: cache[name].at[idx].set(
        leaf.astype(cache[name].dtype), mode="drop")
        for name, leaf in new_leaves.items()}
    new["pos"] = cache["pos"].at[idx].set(pos, mode="drop")
    return new


def _commit_kv(cache, new_k, new_v, pos, layer=None):
    """Commit fresh K/V rows into the slot pool: fp pools write the rows
    as-is; quantised pools quantise on commit (one symmetric scale per
    (token, head) row, int8 codes, packed for int4) so an fp copy of the
    cache never exists between steps.  ``layer``: see :func:`_ring_write`."""
    if "k_q" in cache:
        bits = kv_cache_bits(cache, new_k.shape[-1])
        k_q, k_s = quantize_kv(new_k, bits)
        v_q, v_s = quantize_kv(new_v, bits)
        return _ring_write(cache, {"k_q": k_q, "k_s": k_s,
                                   "v_q": v_q, "v_s": v_s}, pos, layer)
    return _ring_write(cache, {"k": new_k, "v": new_v}, pos, layer)


def layer_of(cache, layer):
    """Layer ``layer`` of a layer-stacked cache tree (the tree itself when
    ``layer`` is None)."""
    if layer is None:
        return cache
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        cache)


# ---------------------------------------------------------------------------
# apply — standard path
# ---------------------------------------------------------------------------

def apply_attention(
    p,
    x,                       # (B, S, D)
    *,
    cfg,
    kind: str,               # global | local | cross
    mode: str,               # train | prefill | chunk | decode
    pos,                     # (B, S) int32 (decode: (B, 1); chunk: -1 = pad)
    cache=None,
    cross_src=None,          # (B, S_src, D) for cross in train/prefill
    impl: str = "auto",
    causal: bool = True,     # encoder stacks pass False
    kv_cap: int = 0,         # prefill: cache capacity to allocate (>= S)
    length=None,             # prefill: true prompt length of a padded stream
    segments=None,           # prefill: (B, S) packed prompt ids, -1 = pad
    kv_bits: int = 0,        # prefill: 8/4 returns a quantised cache
    layer=None,              # decode/chunk: ``cache`` is the layer-stacked
                             # pool; rows commit in place at this layer
):
    B, S, D = x.shape
    Hq, Hkv, hd, hdv = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.v_head_dim
    dt = x.dtype
    causal = causal and kind != "cross"
    window = cfg.window if kind == "local" else 0
    theta = cfg.rope_theta_local if (kind == "local" and cfg.rope_theta_local) else cfg.rope_theta

    q = qdense(x, p["wq"], dt, "weight_full")
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    q = q.reshape(B, S, Hq, hd)

    if kind == "cross":
        if mode in ("decode", "chunk"):
            k, v = cache["k"], cache["v"]
            new_cache = cache
            # non-causal attention over a fully-valid cache expressed via
            # the masked explicit-position path: every q_pos >= every
            # kv_pos makes the causal predicate vacuous, so impl="flash"
            # runs the Pallas decode kernel instead of silently
            # downgrading to the reference path
            Skv = k.shape[1]
            kv_pos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32),
                                      (B, Skv))
            q = constrain(q, "act_heads")
            out = flash_attention(q, k, v, causal=True,
                                  softcap=cfg.attn_softcap, impl=impl,
                                  q_pos=jnp.full((B, S), Skv, jnp.int32),
                                  kv_pos=kv_pos, kv_valid=None)
        else:
            src = cross_src.astype(dt)
            k = qdense(src, p["wk"], dt)
            v = qdense(src, p["wv"], dt)
            if "bk" in p:
                k = k + p["bk"].astype(dt)
                v = v + p["bv"].astype(dt)
            k = k.reshape(B, -1, Hkv, hd)
            v = v.reshape(B, -1, Hkv, hdv)
            new_cache = {"k": k, "v": v} if mode == "prefill" else None
            q = constrain(q, "act_heads")
            out = flash_attention(q, k, v, causal=False,
                                  softcap=cfg.attn_softcap, impl=impl)
        out = qdense(out.reshape(B, S, Hq * hdv), p["wo"], dt)
        return out, new_cache

    k = qdense(x, p["wk"], dt, "weight_full")
    v = qdense(x, p["wv"], dt, "weight_full")
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hdv)

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.use_rope:
        q = apply_rope(q, pos, theta)
        k = apply_rope(k, pos, theta)
    q = constrain(q, "act_heads")
    k = constrain(k, "kv_heads")
    v = constrain(v, "kv_heads")

    if mode in ("train", "prefill"):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              softcap=cfg.attn_softcap, impl=impl,
                              segments=segments)
        new_cache = None
        if mode == "prefill":
            if segments is not None:
                # packed ragged prefill: raw per-token cache; the serving
                # engine gathers each segment into its KV slot
                new_cache = {"k": k, "v": v,
                             "pos": jnp.where(segments >= 0, pos, -1)}
            else:
                cap = max(kv_cap, S)
                if kind == "local":
                    kc, vc, pc = _ring_fill(k, v, pos, min(cfg.window, cap),
                                            length=length)
                    new_cache = {"k": kc, "v": vc, "pos": pc}
                else:
                    new_cache = {"k": _pad_cache(k, cap),
                                 "v": _pad_cache(v, cap),
                                 "pos": _pad_pos(pos, cap)}
            if kv_bits:
                # quantise the freshly-built cache so it matches the
                # engine's quantised slot pool (empty entries stay zeros)
                new_cache = quantize_kv_cache(new_cache, kv_bits)
    else:  # decode (S == 1 — Pallas decode kernel) / chunk (S-token write)
        quant = "k_q" in cache
        bits = kv_cache_bits(cache, hd) if quant else 0
        new_cache = _commit_kv(cache, k, v, pos, layer)
        # chunk attends to the PRE-write cache plus the in-stream chunk: the
        # chunk write may evict ring entries that early chunk queries still
        # need (their window reaches back before the chunk), and cache
        # positions are all < the chunk's, so no duplicates.  Decode reads
        # the cache with its new row committed.
        cur = layer_of(cache if mode == "chunk" else new_cache, layer)
        qkw = {}
        if mode == "chunk":
            # a quantised cache is dequantised for the read (the committed
            # pool stays int8; the in-stream chunk attends at fp)
            if quant:
                ck = dequantize_kv(cur["k_q"], cur["k_s"], bits)
                cv = dequantize_kv(cur["v_q"], cur["v_s"], bits)
            else:
                ck, cv = cur["k"], cur["v"]
            kc = jnp.concatenate([ck.astype(k.dtype), k], axis=1)
            vc = jnp.concatenate([cv.astype(v.dtype), v], axis=1)
            kv_pos = jnp.concatenate([cur["pos"], pos], axis=1)
        elif quant:
            # dequantise-on-read decode: codes + scales go straight to the
            # kernel route (in-VMEM dequant); the fp cache never exists
            kc, vc, kv_pos = cur["k_q"], cur["v_q"], cur["pos"]
            qkw = dict(k_scale=cur["k_s"], v_scale=cur["v_s"], kv_bits=bits)
        else:
            kc, vc, kv_pos = cur["k"], cur["v"], cur["pos"]
        out = flash_attention(
            q, kc, vc,
            q_pos=pos, kv_pos=kv_pos, kv_valid=kv_pos >= 0,
            causal=causal, window=window, softcap=cfg.attn_softcap,
            impl=impl, **qkw)

    out = qdense(out.reshape(B, S, Hq * hdv), p["wo"], dt, "weight_full")
    return out, new_cache


# ---------------------------------------------------------------------------
# apply — MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_q(p, x, pos, cfg):
    B, S, _ = x.shape
    dt = x.dtype
    H, dn, dr = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    if "wq_a" in p:
        cq = rmsnorm(x @ p["wq_a"].astype(dt), p["q_norm"])
        q = jnp.einsum("bsr,rhd->bshd", cq, p["wq_b"].astype(dt))
    else:
        q = jnp.einsum("bsD,Dhd->bshd", x, p["wq"].astype(dt))
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, pos, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, x, pos, cfg):
    dt = x.dtype
    kvr, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    ckv_full = x @ p["wkv_a"].astype(dt)
    ckv = rmsnorm(ckv_full[..., :kvr], p["kv_norm"])
    kr = ckv_full[..., kvr:]
    kr = apply_rope(kr[:, :, None, :], pos, cfg.rope_theta)[:, :, 0, :]
    return ckv, kr


def apply_mla(p, x, *, cfg, mode, pos, cache=None, impl="auto", kv_cap: int = 0,
              length=None, segments=None):
    """MLA self-attention.  train/prefill: naive expanded path (packed
    ragged prefill via ``segments=``); decode/chunk: absorbed latent-space
    path (the serving memory-traffic optimisation the paper's MQA
    discussion anticipates, §3.2)."""
    B, S, D = x.shape
    dt = x.dtype
    H, dn, dr, dv = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    scale = (dn + dr) ** -0.5

    q_nope, q_rope = _mla_q(p, x, pos, cfg)
    ckv, kr = _mla_kv_latent(p, x, pos, cfg)

    if mode in ("train", "prefill"):
        kv = jnp.einsum("bsr,rhd->bshd", ckv, p["wkv_b"].astype(dt))
        kv = constrain(kv, "kv_heads")
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], (B, S, H, dr))], -1)
        k = constrain(k, "kv_heads")
        v = constrain(v, "kv_heads")
        q = jnp.concatenate([q_nope, q_rope], -1)
        q = constrain(q, "act_heads")
        out = flash_attention(q, k, v, causal=True, scale=scale, impl=impl,
                              segments=segments)
        new_cache = None
        if mode == "prefill":
            if segments is not None:
                new_cache = {"ckv": ckv, "kr": kr,
                             "pos": jnp.where(segments >= 0, pos, -1)}
            else:
                cap = max(kv_cap, S)
                new_cache = {"ckv": _pad_cache(ckv, cap),
                             "kr": _pad_cache(kr, cap),
                             "pos": _pad_pos(pos, cap)}
    else:  # decode / chunk — absorbed; pos < 0 entries are dropped
        cap = cache["ckv"].shape[1]
        bidx = jnp.arange(B)[:, None]
        slot = jnp.where(pos >= 0, pos, cap)         # cap = out of bounds
        new_cache = {
            "ckv": cache["ckv"].at[bidx, slot].set(
                ckv.astype(cache["ckv"].dtype), mode="drop"),
            "kr": cache["kr"].at[bidx, slot].set(
                kr.astype(cache["kr"].dtype), mode="drop"),
            "pos": cache["pos"].at[bidx, slot].set(pos, mode="drop"),
        }
        ckv_all, kr_all, kv_pos = new_cache["ckv"], new_cache["kr"], new_cache["pos"]
        w_uk = p["wkv_b"][..., :dn].astype(dt)        # (kvr, H, dn)
        w_uv = p["wkv_b"][..., dn:].astype(dt)        # (kvr, H, dv)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
        logits = (jnp.einsum("bqhr,bkr->bhqk", q_lat.astype(jnp.float32),
                             ckv_all.astype(jnp.float32))
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope.astype(jnp.float32),
                               kr_all.astype(jnp.float32))) * scale
        mask = (kv_pos[:, None, None, :] <= pos[:, None, :, None]) & \
               (kv_pos >= 0)[:, None, None, :]
        logits = jnp.where(mask, logits, NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        # fully-masked rows (chunk pads) must produce zeros, not NaN
        w = jnp.where(mask.any(axis=-1)[..., None], w, 0.0).astype(dt)
        ctx = jnp.einsum("bhqk,bkr->bqhr", w, ckv_all)
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, w_uv)

    out = qdense(out.reshape(B, S, H * dv), p["wo"], dt)
    return out, new_cache
