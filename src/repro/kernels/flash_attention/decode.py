"""Pallas TPU decode-attention kernel: one query token per KV slot.

The serving hot loop is the paper's end-to-end inference term: every decoded
token re-reads the whole KV pool (X-Former §IV, HeTraX §3 both identify this
attention-to-memory traffic as the dominant cost).  The prefill flash kernel
(:mod:`.kernel`) tiles a *long* query block; decode has ``Sq == 1`` per slot,
so the operative constraint is streaming K/V through VMEM exactly once while
the (tiny) query block and the online-softmax state never leave VMEM.

Layout/grid:

- grid ``(B, Hkv, Skv/bk)`` — one program per (slot, KV head, K/V block);
  the trailing axis is sequential on TPU so VMEM scratch carries the
  online-softmax state ``(m, l, acc)`` across the K/V sweep of each slot.
- GQA head-folding: the ``rep = Hq // Hkv`` query heads that share one KV
  head are folded into the *rows* of a single ``(rep, hd)`` query block, so
  the score matmul is one MXU op per block instead of ``rep`` vector ops.
- positions are explicit: ``kv_pos`` is the per-entry token position in the
  slotted pool (``-1`` = empty / invalid entry) and ``q_pos`` the query
  position per slot.  Causality, sliding window, per-slot lengths and
  empty-slot masking all reduce to one mask on ``(q_pos, kv_pos)`` — the
  kernel never assumes entries are ordered, so ring-buffer (local-window)
  caches work unmodified.
- fully-masked slots (empty pool slots in a continuous-batching engine)
  produce exact zeros, not NaN.

``interpret=True`` runs the same kernel body through the Pallas interpreter
so CPU tests exercise the real kernel, not a shadow implementation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention.common import NEG_INF, block_size, vmem
from repro.quant.core import int4_planes


def _decode_mask(qpos_ref, kvpos_ref, window: int):
    """(1, bk) valid+causal(+window) mask from explicit positions."""
    qp = qpos_ref[0, 0, 0]                            # scalar int32
    kp = kvpos_ref[0]                                 # (1, bk)
    mask = (kp >= 0) & (kp <= qp)                     # valid + causal
    if window:
        mask &= qp - kp < window
    return mask


def _online_update(qs, ks, vs, mask, m_scr, l_scr, acc_scr, *,
                   scale: float, softcap: float):
    """One K/V block of the online-softmax sweep (shared by the fp and
    quantised-KV decode kernels; operands already dequantised f32).

    Operands come as matching planes of the head dim: the score sums the
    plane-wise ``q · k`` contractions, and value plane ``i`` accumulates
    into ``acc_scr[i]``.  fp and int8 pass one plane; int4 passes the even
    and odd halves (see :func:`repro.quant.core.int4_planes`)."""
    s = sum(jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        for q, k in zip(qs, ks)) * scale              # (rep, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask, s, NEG_INF)                   # (1,bk) -> (rep,bk)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    for i, v in enumerate(vs):
        acc_scr[i] = acc_scr[i] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _sweep(body, qpos_ref, kvpos_ref, o_ref, m_scr, l_scr, acc_scr, *,
           window: int):
    """The K/V-axis skeleton both decode kernels share: reset the softmax
    state on the first block, run ``body(mask)`` on blocks with at least
    one attendable entry, normalise into ``o_ref`` on the last."""
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    mask = _decode_mask(qpos_ref, kvpos_ref, window)

    # whole block masked (empty slot / outside the window) -> skip the MXU
    @pl.when(jnp.any(mask))
    def _body():
        body(mask)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)               # empty slot -> zeros
        for i in range(acc_scr.shape[0]):
            o_ref[0, 0, i] = (acc_scr[i] / l).astype(o_ref.dtype)


def _decode_kernel(
    q_ref,                        # (1, 1, rep, hd)
    k_ref,                        # (1, 1, bk, hd)
    v_ref,                        # (1, 1, bk, hdv)
    qpos_ref,                     # (1, 1, 1)
    kvpos_ref,                    # (1, 1, bk)
    o_ref,                        # (1, 1, 1, rep, hdv)
    m_scr, l_scr, acc_scr,        # VMEM scratch: (rep,1), (rep,1), (1,rep,hdv)
    *,
    scale: float,
    window: int,
    softcap: float,
):
    def body(mask):
        q = q_ref[0, 0].astype(jnp.float32)           # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hdv)
        _online_update((q,), (k,), (v,), mask, m_scr, l_scr, acc_scr,
                       scale=scale, softcap=softcap)

    _sweep(body, qpos_ref, kvpos_ref, o_ref, m_scr, l_scr, acc_scr,
           window=window)


def _positions(q_pos, kv_pos, B, Skv):
    """Per-slot positions as ``(B, 1, 1)`` / ``(B, 1, Skv)``: a block's last
    two dims must be multiples of (8, 128) or span the array, which a
    ``(1, ·)`` block of a ``(B, ·)`` array breaks on the TPU whenever B > 1."""
    return (q_pos.astype(jnp.int32).reshape(B, 1, 1),
            kv_pos.astype(jnp.int32).reshape(B, 1, Skv))


def _pos_specs(bk):
    return [pl.BlockSpec((1, 1, 1), lambda b, h, ik: (b, 0, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, h, ik: (b, 0, ik))]


def _planes_out(out, B, Hq, hdv):
    """``(B, Hkv, P, rep, hdv/P)`` kernel output -> ``(B, 1, Hq, hdv)``,
    re-interleaving the P head-dim planes (P = 2 for int4 values)."""
    return out.transpose(0, 1, 3, 4, 2).reshape(B, 1, Hq, hdv)


def flash_decode_fwd(
    q: jax.Array,        # (B, 1, Hq, hd)   one query token per slot
    k: jax.Array,        # (B, Skv, Hkv, hd)  slotted KV pool
    v: jax.Array,        # (B, Skv, Hkv, hdv)
    *,
    q_pos: jax.Array,    # (B, 1) int32  query position per slot
    kv_pos: jax.Array,   # (B, Skv) int32  entry position, -1 = empty
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, hdv = v.shape
    if Sq != 1:
        raise ValueError(f"decode kernel needs Sq == 1, got {Sq}")
    rep = Hq // Hkv
    if rep * Hkv != Hq:
        raise ValueError(f"Hq ({Hq}) must be a multiple of Hkv ({Hkv})")
    scale = scale if scale is not None else hd ** -0.5
    bk = block_size(block_k, Skv)
    if Skv % bk:
        raise ValueError(f"block size ({bk}) must divide Skv ({Skv})")

    # fold GQA groups into query-block rows: (B, Hkv, rep, hd)
    qf = q[:, 0].reshape(B, Hkv, rep, hd)
    kt = k.transpose(0, 2, 1, 3)                  # (B, Hkv, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)                  # (B, Hkv, Skv, hdv)
    qp, kp = _positions(q_pos, kv_pos, B, Skv)

    grid = (B, Hkv, Skv // bk)
    kern = functools.partial(
        _decode_kernel, scale=scale, window=window, softcap=softcap)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, hdv), lambda b, h, ik: (b, h, ik, 0)),
            *_pos_specs(bk),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, rep, hdv),
                               lambda b, h, ik: (b, h, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, 1, rep, hdv), q.dtype),
        name="decode_attention",
        metadata={"kernel": "decode_attention"},
        scratch_shapes=[
            vmem((rep, 1)),
            vmem((rep, 1)),
            vmem((1, rep, hdv)),
        ],
        interpret=interpret,
    )(qf, kt, vt, qp, kp)

    return _planes_out(out, B, Hq, hdv)


# ---------------------------------------------------------------------------
# quantised-KV variant
# ---------------------------------------------------------------------------

def _decode_quant_kernel(
    *refs,
    scale: float,
    window: int,
    softcap: float,
    kv_bits: int,
):
    # int8: q (1,1,rep,hd); int4: q split into even / odd head-dim halves,
    # each (1,1,rep,hd/2), matching the low / high nibble planes of the codes
    nq = 2 if kv_bits == 4 else 1
    q_refs = refs[:nq]
    (kq_ref,                      # (1, 1, bk, hd')  int8 codes (hd' = hd/pack)
     ks_ref,                      # (1, 1, bk, 1)    f32 per-(entry, head)
     vq_ref,                      # (1, 1, bk, hdv')
     vs_ref,                      # (1, 1, bk, 1)
     qpos_ref,                    # (1, 1, 1)
     kvpos_ref,                   # (1, 1, bk)
     o_ref,                       # (1, 1, P, rep, hdv/P)
     m_scr, l_scr, acc_scr) = refs[nq:]

    def body(mask):
        qs = tuple(r[0, 0].astype(jnp.float32) for r in q_refs)
        kq = kq_ref[0, 0]                             # (bk, hd') int8
        vq = vq_ref[0, 0]
        if kv_bits == 4:
            # adjacent-pair nibble planes along the head dim — the packing
            # contract of repro.quant.core (single source of truth)
            kq, vq = int4_planes(kq), int4_planes(vq)
        else:
            kq, vq = (kq,), (vq,)
        # in-VMEM dequant: the pool streams HBM→VMEM at 1 or 0.5 B/element
        ks = ks_ref[0, 0].astype(jnp.float32)
        vs = vs_ref[0, 0].astype(jnp.float32)
        k = tuple(c.astype(jnp.float32) * ks for c in kq)
        v = tuple(c.astype(jnp.float32) * vs for c in vq)
        _online_update(qs, k, v, mask, m_scr, l_scr, acc_scr,
                       scale=scale, softcap=softcap)

    _sweep(body, qpos_ref, kvpos_ref, o_ref, m_scr, l_scr, acc_scr,
           window=window)


def flash_decode_quant_fwd(
    q: jax.Array,        # (B, 1, Hq, hd)
    k_q: jax.Array,      # (B, Skv, Hkv, hd')  int8 codes (hd' = hd or hd/2)
    k_s: jax.Array,      # (B, Skv, Hkv) f32 per-(entry, head) scales
    v_q: jax.Array,      # (B, Skv, Hkv, hdv')
    v_s: jax.Array,      # (B, Skv, Hkv)
    *,
    kv_bits: int,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over a *quantised* slot pool: same grid, masking and
    online-softmax sweep as :func:`flash_decode_fwd`, but the K/V blocks
    arrive as int8 codes (packed two-per-byte for ``kv_bits=4``) with
    per-(entry, head) scales and are dequantised in VMEM — an fp copy of
    the cache never exists outside the per-block scratch."""
    if kv_bits not in (4, 8):
        raise ValueError(f"kv_bits must be 4 or 8, got {kv_bits}")
    pack = 2 if kv_bits == 4 else 1
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, hdq = k_q.shape
    hdvq = v_q.shape[-1]
    hdv = hdvq * pack
    if Sq != 1:
        raise ValueError(f"decode kernel needs Sq == 1, got {Sq}")
    if hdq * pack != hd:
        raise ValueError(f"codes head dim {hdq} != {hd} at {kv_bits} bits")
    rep = Hq // Hkv
    if rep * Hkv != Hq:
        raise ValueError(f"Hq ({Hq}) must be a multiple of Hkv ({Hkv})")
    scale = scale if scale is not None else hd ** -0.5
    bk = block_size(block_k, Skv)
    if Skv % bk:
        raise ValueError(f"block size ({bk}) must divide Skv ({Skv})")

    qf = q[:, 0].reshape(B, Hkv, rep, hd)
    # int4: even / odd head dims meet the low / high nibble planes
    qs = (qf[..., 0::2], qf[..., 1::2]) if pack == 2 else (qf,)
    kqt = k_q.transpose(0, 2, 1, 3)               # (B, Hkv, Skv, hd')
    vqt = v_q.transpose(0, 2, 1, 3)
    kst = k_s.transpose(0, 2, 1)[..., None].astype(jnp.float32)
    vst = v_s.transpose(0, 2, 1)[..., None].astype(jnp.float32)
    qp, kp = _positions(q_pos, kv_pos, B, Skv)

    grid = (B, Hkv, Skv // bk)
    kern = functools.partial(
        _decode_quant_kernel, scale=scale, window=window, softcap=softcap,
        kv_bits=kv_bits)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            *[pl.BlockSpec((1, 1, rep, hdq), lambda b, h, ik: (b, h, 0, 0))
              for _ in qs],
            pl.BlockSpec((1, 1, bk, hdq), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, 1), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, hdvq), lambda b, h, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, 1), lambda b, h, ik: (b, h, ik, 0)),
            *_pos_specs(bk),
        ],
        out_specs=pl.BlockSpec((1, 1, pack, rep, hdvq),
                               lambda b, h, ik: (b, h, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, pack, rep, hdvq), q.dtype),
        name="decode_attention_quant",
        metadata={"kernel": "decode_attention_quant"},
        scratch_shapes=[
            vmem((rep, 1)),
            vmem((rep, 1)),
            vmem((pack, rep, hdvq)),
        ],
        interpret=interpret,
    )(*qs, kqt, kst, vqt, vst, qp, kp)

    return _planes_out(out, B, Hq, hdv)
