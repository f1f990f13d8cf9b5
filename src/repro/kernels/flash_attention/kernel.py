"""Pallas TPU flash-attention forward kernel (contiguous and ragged/packed).

TPU-native adaptation of the paper's SM-chiplet attention dataflow: the
paper partitions Q/K/V across SM chiplets with the FlashAttention schedule
and fuses score+softmax so the O(N²) intermediate never crosses the NoI
(§3.2 steps 2-4).  On TPU the analogous fast/slow boundary is VMEM↔HBM:
this kernel tiles Q into MXU-aligned blocks held in VMEM, streams K/V
blocks through, and keeps the online-softmax running statistics (m, l) and
the output accumulator in VMEM scratch for the whole K/V sweep.

Grid: ``(B, Hq, Sq/bq, Skv/bk)`` — the trailing (minor) grid axis is
sequential on TPU, so scratch carries state across the K/V sweep of each
Q block.  GQA folds the head-group mapping into the K/V index_map.

**Ragged / packed-segment mode** (``segments=``): multiple prompts are
packed back-to-back into one token stream; ``segments`` gives each token
its prompt id (``-1`` = pad).  Masking adds a same-segment predicate, so a
query never attends across a prompt boundary.  Because segments are
contiguous, packed-index causality + segment equality is exactly
within-prompt causality, and the packed-index distance equals the
positional distance for the sliding window.  Tiles whose mask is entirely
false — causally-dead tiles at trace time, segment-crossing tiles at run
time — skip the MXU work entirely.

Forward only: the serving path (the paper's setting — inference) uses it
directly; training uses the reference path (XLA fuses adequately there and
the dry-run needs portable HLO).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.flash_attention.common import NEG_INF, block_size, vmem


def _flash_fwd_kernel(
    *refs,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    bq: int,
    bk: int,
    kv_len: int,
    segmented: bool,
):
    if segmented:
        (q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_idx = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_idx = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # the mask depends only on indices and segment ids — computed before the
    # MXU body so a fully-masked tile (segment-crossing, pad-only) skips the
    # matmuls entirely
    mask = k_idx < kv_len
    if causal:
        mask &= k_idx <= q_idx
    if window:
        mask &= q_idx - k_idx < window
    if segmented:
        qseg = qseg_ref[0][:, None]
        mask &= (qseg == kseg_ref[0][None, :]) & (qseg >= 0)  # pad q rows -> 0

    # grid-structural skip (trace-time shape, no data needed) ...
    block_needed = True
    if causal:
        block_needed = jnp.logical_and(block_needed, ik * bk <= iq * bq + bq - 1)
    if window:
        block_needed = jnp.logical_and(block_needed, (iq * bq) - (ik * bk + bk - 1) < window)
    # ... plus the data-dependent skip for segment-crossing tiles
    block_needed = jnp.logical_and(block_needed, jnp.any(mask))

    @pl.when(block_needed)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hdv)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero for masked entries: a row that is fully masked
        # within a computed block (pad row in a mixed tile) has
        # m_new == NEG_INF, where exp(s - m_new) would be exp(0) = 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)    # (bq, bk)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(
    q: jax.Array,   # (B, Hq, Sq, hd)
    k: jax.Array,   # (B, Hkv, Skv, hd)
    v: jax.Array,   # (B, Hkv, Skv, hdv)
    *,
    segments: jax.Array | None = None,   # (B, S) int32 prompt ids, -1 = pad
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Hq, Sq, hd = q.shape
    _, Hkv, Skv, hdv = v.shape
    rep = Hq // Hkv
    scale = scale if scale is not None else hd ** -0.5
    bq = block_size(block_q, Sq)
    bk = block_size(block_k, Skv)
    if Sq % bq or Skv % bk:
        raise ValueError(f"seq lens ({Sq},{Skv}) must divide blocks ({bq},{bk})")
    if segments is not None and Sq != Skv:
        raise ValueError("packed-segment attention is self-attention: Sq must equal Skv")

    grid = (B, Hq, Sq // bq, Skv // bk)
    kern = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, kv_len=Skv,
        segmented=segments is not None)

    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik, rep=rep: (b, h // rep, ik, 0)),
        pl.BlockSpec((1, 1, bk, hdv), lambda b, h, iq, ik, rep=rep: (b, h // rep, ik, 0)),
    ]
    operands = [q, k, v]
    if segments is not None:
        seg = segments.astype(jnp.int32)
        in_specs += [
            pl.BlockSpec((1, bq), lambda b, h, iq, ik: (b, iq)),   # q-side ids
            pl.BlockSpec((1, bk), lambda b, h, iq, ik: (b, ik)),   # k-side ids
        ]
        operands += [seg, seg]

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, hdv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hdv), q.dtype),
        name="flash_attention",
        metadata={"kernel": "flash_attention"},
        scratch_shapes=[
            vmem((bq, 1)),
            vmem((bq, 1)),
            vmem((bq, hdv)),
        ],
        interpret=interpret,
    )(*operands)
