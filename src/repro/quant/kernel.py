"""Pallas TPU kernel: fused dequantise-matmul over weight-only quantised
matrices (int8 and packed int4).

Generalises the PIM-MVM crossbar kernel (``kernels/pim_mvm``) from its
fixed 128×128-tile int8 layout to the serving quantisation layout of
:mod:`repro.quant.core`: per-output-channel (or per-K-group) scales and an
optional packed-int4 code plane.  The transferable property is the same —
**fp weights never exist in HBM**: codes stream HBM→VMEM at 1 or 0.5 bytes
per element, are dequantised in VMEM, and accumulate in fp32 on the MXU.

Grid ``(M/bm, N/bn, K/bk)``; the trailing K axis is sequential on TPU so
the fp32 accumulator lives in VMEM scratch across the K sweep.  For int4
the code block is ``(bk/2, bn)`` — adjacent-pair packing along K keeps a
contiguous packed block ↔ contiguous original rows.  The kernel never
interleaves the nibbles back into rows: the low / high planes contract
against the even / odd K columns of ``x``, split outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.core import int4_planes


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _qmm_kernel(*refs, n_k: int, bits: int, group: int):
    # int8: x (bm, bk) against codes (bk, bn).  int4: x split into its even /
    # odd K columns, each (bm, bk/2), against the low / high nibble planes
    # of the packed (bk/2, bn) codes
    nx = 2 if bits == 4 else 1
    x_refs = refs[:nx]
    q_ref, s_ref, o_ref, acc_scr = refs[nx:]
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                                   # int8 codes (packed?)
    # adjacent-pair nibble planes along K (repro.quant.core contract)
    planes = int4_planes(q) if bits == 4 else (q,)
    s = s_ref[...].astype(jnp.float32)               # (rows/g | 1, bn)
    if group:
        s = jnp.repeat(s, group // nx, axis=0)       # (bk/nx, bn)
    for x_ref, codes in zip(x_refs, planes):
        x = x_ref[...].astype(jnp.float32)
        w = codes.astype(jnp.float32) * s            # in-VMEM dequant
        acc_scr[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def quant_matmul_pallas(x, q, scale, *, bits: int, group: int = 0,
                        bm: int = 128, bn: int = 256, bk: int = 512,
                        interpret: bool = False):
    """x (M, K) · dequant(q, scale) -> (M, N); output dtype follows x.

    ``q`` is (K, N) int8 or (K/2, N) packed int4; ``scale`` (1, N) f32
    per-channel or (K/group, N) per-group.  Every block must tile exactly
    (the dispatch wrapper falls back to the reference path otherwise).
    """
    pack = 2 if bits == 4 else 1
    M, K = x.shape
    Kq, N = q.shape
    if Kq * pack != K:
        raise ValueError(f"codes {q.shape} do not match K={K} at {bits} bits")
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or K % bk or N % bn:
        raise ValueError(f"dims {(M, K, N)} must divide blocks {(bm, bk, bn)}")
    if group and (bk % group or group % pack):
        raise ValueError(f"group {group} must divide the K block {bk}"
                         + (" and be even at 4 bits" if pack == 2 else ""))
    n_k = K // bk
    sk = (bk // group) if group else 1               # scale rows per block
    # int4: the even / odd K columns of x meet the low / high code planes
    xs = (x[:, 0::2], x[:, 1::2]) if pack == 2 else (x,)

    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, bits=bits, group=group),
        grid=grid,
        in_specs=[
            *[pl.BlockSpec((bm, bk // pack), lambda i, j, k: (i, k))
              for _ in xs],
            pl.BlockSpec((bk // pack, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((sk, bn),
                         (lambda i, j, k: (k, j)) if group else
                         (lambda i, j, k: (0, j))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[_vmem((bm, bn))],
        name="quant_matmul",
        metadata={"kernel": "quant_matmul"},
        interpret=interpret,
    )(*xs, q, scale)
