"""Dispatch layer for quantised dense compute.

``qdense`` is the single matmul entry point the model library routes its
dense projections through: a plain fp array behaves exactly as the
pre-quantisation code (cast + optional sharding constraint + ``@``, so the
fp path is bit-identical), a :class:`repro.quant.core.QuantTensor` runs the
fused dequant-matmul — the Pallas kernel on TPU (codes dequantised in VMEM,
fp weights never in HBM), a reference dequant+matmul elsewhere.
"""
from __future__ import annotations

import jax

from repro.parallel import constrain
from repro.quant import kernel as _kernel
from repro.quant.core import QuantTensor, dequantize


def quant_matmul(x: jax.Array, qt: QuantTensor, *, impl: str = "auto"):
    """x (..., K) · dequant(qt (K, N)) -> (..., N), dtype follows x.

    impl: ref | pallas | pallas_interpret | auto (pallas on TPU, else ref).
    Under ``auto`` and ``pallas_interpret``, shapes the Pallas grid cannot
    tile exactly fall back to ref; an explicit ``pallas`` raises instead,
    so a compiled run never drops to the reference unseen.
    """
    strict = impl == "pallas"
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl in ("pallas", "pallas_interpret"):
        lead = x.shape[:-1]
        K = x.shape[-1]
        N = qt.scale.shape[-1]
        M = 1
        for d in lead:
            M *= d
        bm, bn, bk = min(128, M), min(256, N), min(512, K)
        group_ok = not qt.group or (bk % qt.group == 0
                                    and (qt.bits != 4 or qt.group % 2 == 0))
        tiles = M % bm == 0 and N % bn == 0 and K % bk == 0 and group_ok
        if tiles and qt.q.ndim == 2:
            out = _kernel.quant_matmul_pallas(
                x.reshape(M, K), qt.q, qt.scale, bits=qt.bits, group=qt.group,
                bm=bm, bn=bn, bk=bk, interpret=impl == "pallas_interpret")
            return out.reshape(lead + (N,))
        if strict:
            raise ValueError(
                f"no dequant-matmul kernel tiles x {x.shape} · codes "
                f"{qt.q.shape} (bits={qt.bits}, group={qt.group})")
    return x @ dequantize(qt).astype(x.dtype)


def qdense(x: jax.Array, w, dt=None, constraint: str | None = None, *,
           impl: str = "auto"):
    """Dense projection that accepts fp weights or a QuantTensor.

    fp: ``x @ constrain(w.astype(dt), constraint)`` — byte-for-byte the
    pre-quantisation path.  QuantTensor: fused dequant-matmul (sharding
    constraints don't apply to code planes; quantised serving runs
    replicated weights).
    """
    if isinstance(w, QuantTensor):
        return quant_matmul(x, w, impl=impl)
    dt = dt if dt is not None else x.dtype
    wf = w.astype(dt)
    if constraint is not None:
        wf = constrain(wf, constraint)
    return x @ wf
