"""Pallas TPU kernel: weight-stationary quantised MVM (ReRAM-crossbar analogue).

Paper mapping (DESIGN.md §3): the static FF layers run on ReRAM chiplets
built from 128×128 crossbars with 2-bit cells — a weight value lives
bit-sliced across 4 cells of a crossbar row, and activations stream
through the stationary array.  Analog MVM itself has no TPU analogue; the
*transferable* property is **weight-stationary low-precision execution
with per-crossbar-tile granularity**:

- weights are stored int8, quantised with one fp32 scale per 128×128 tile
  (= one crossbar): the same granularity the bit-sliced cells impose;
- the kernel streams activation tiles from HBM through VMEM, dequantises
  the weight tile *in VMEM* (fp weights never exist in HBM — the memory-
  roofline win: 2× fewer weight bytes than bf16, 4× vs fp32), and
  accumulates in fp32 on the MXU;
- block shapes are multiples of 128 on both matmul dims, matching the
  crossbar geometry AND the MXU systolic array.

Grid: (M/bm, N/bn, K/bk); the trailing K axis is sequential on TPU so the
fp32 accumulator lives in VMEM scratch across the K sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

XBAR = 128  # crossbar dimension == MXU tile


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _pim_mvm_kernel(x_ref, wq_ref, scale_ref, o_ref, acc_scr, *, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.float32)              # (bm, bk)
    wq = wq_ref[...].astype(jnp.float32)            # (bk, bn) int8 -> f32
    scales = scale_ref[...].astype(jnp.float32)     # (bk/128, bn/128)
    # expand crossbar-tile scales to element granularity (in-VMEM dequant)
    w = wq * jnp.repeat(jnp.repeat(scales, XBAR, axis=0), XBAR, axis=1)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _done():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def pim_mvm_pallas(x, wq, scales, *, bm: int = 128, bn: int = 256,
                   bk: int = 512, interpret: bool = False):
    """x (M, K) · dequant(wq (K, N) int8, scales (K/128, N/128)) -> (M, N).

    Output dtype follows x.  Block defaults keep the working set
    (bm·bk + bk·bn + bm·bn fp32) well under one v5e core's VMEM while the
    (bk, bn) weight tile spans whole crossbars.
    """
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    bm = min(bm, M)
    bk = min(bk, K)
    bn = min(bn, N)
    if M % bm or K % bk or N % bn:
        raise ValueError(f"dims {(M, K, N)} must divide blocks {(bm, bk, bn)}")
    if bk % XBAR or bn % XBAR:
        raise ValueError("weight blocks must tile 128x128 crossbars")
    n_k = K // bk

    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_pim_mvm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // XBAR, bn // XBAR), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[_vmem((bm, bn))],
        name="pim_mvm",
        metadata={"kernel": "pim_mvm"},
        interpret=interpret,
    )(x, wq, scales)
