"""jit'd dispatch wrapper for attention.

``impl``:
  - ``ref``               pure-jnp chunked oracle (CPU, dry-run HLO)
  - ``pallas``            TPU Pallas kernels (compiled); raises when no
                          kernel fits the call
  - ``pallas_interpret``  Pallas kernel bodies executed in Python on CPU
  - ``flash``             serving fast path: Pallas kernels, compiled on TPU
                          and interpreted elsewhere (CPU tests exercise the
                          real kernel bodies)
  - ``auto``              pallas on TPU backends, ref elsewhere

Under every impl but ``pallas``, a call no kernel fits (e.g. the chunked-
prefill step, whose queries carry explicit positions) runs the reference.

Two Pallas kernels sit behind this wrapper:

- :func:`..kernel.flash_attention_fwd` — train/prefill self-attention with
  implicit positions (long query blocks).  With ``segments=`` it runs the
  **ragged/packed** variant: several prompts in one token stream, per-token
  prompt ids (-1 = pad), no cross-prompt attention;
- :func:`..decode.flash_decode_fwd`    — the decode fast path: ``Sq == 1``
  with explicit ``q_pos``/``kv_pos`` vectors (slotted / ring-buffer caches,
  per-slot lengths, empty-slot masking).

The decode kernel treats ``kv_pos < 0`` as invalid; an explicit ``kv_valid``
mask is folded into ``kv_pos`` before the call (masked entries become -1),
so any caller-supplied mask is honoured exactly.  Non-causal decode with
explicit positions (cross-attention) is expressed by callers as causal
attention with ``q_pos >= max(kv_pos)`` — see ``models/attention.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention.common import blocks_aligned
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention import kernel as _kernel
from repro.kernels.flash_attention import decode as _decode
from repro.parallel.api import current_plan


def _pallas_ok(q, k, causal, q_pos, kv_pos, kv_valid, window, segments):
    if q_pos is not None or kv_pos is not None or kv_valid is not None:
        return False
    B, Sq, Hq, hd = q.shape
    Skv = k.shape[1]
    if Sq < 8 or Skv < 8:
        return False
    if segments is not None and Sq != Skv:
        return False
    return (blocks_aligned(Sq, 128) and blocks_aligned(Skv, 128)
            and Hq % k.shape[2] == 0)


def _decode_ok(q, k, causal, q_pos, kv_pos):
    if not causal or q_pos is None or kv_pos is None:
        return False
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if Sq != 1 or Hq % Hkv:
        return False
    return blocks_aligned(Skv, 128)


def _per_device(run, q, *rest):
    """``run(q, *rest)`` -> ``(B, Sq, Hq, hdv)``, once per device under the
    active sharding plan.

    GSPMD cannot partition a Mosaic kernel, so under a plan the call runs
    in a ``shard_map``: the batch splits over the plan's batch axes and the
    heads over its head axis when q and K/V heads share it.  Sequence dims
    stay whole on every device (the kernels index positions from 0).  Every
    operand leads with batch; 4-D and 3-D ones carry heads on axis 2."""
    plan = current_plan()
    if plan is None:
        return run(q, *rest)
    q_spec = plan.spec("act_heads") or P()
    kv_spec = plan.spec("kv_heads") or P()
    pad = lambda spec: tuple(spec) + (None,) * (4 - len(spec))  # noqa: E731
    b, _, h, _ = pad(q_spec)
    if pad(kv_spec)[2] != h:
        h = None

    def spec(x):
        return P(*(b, None, h, None)[:x.ndim]) if x.ndim >= 3 else P(b, None)

    args = (q,) + rest
    return jax.shard_map(run, mesh=plan.mesh,
                         in_specs=tuple(spec(x) for x in args),
                         out_specs=P(b, None, h, None),
                         check_vma=False)(*args)


def attention(
    q: jax.Array,            # (B, Sq, Hq, hd)
    k: jax.Array,            # (B, Skv, Hkv, hd)   (int8 codes when k_scale=)
    v: jax.Array,            # (B, Skv, Hkv, hdv)
    *,
    q_pos: Optional[jax.Array] = None,
    kv_pos: Optional[jax.Array] = None,
    kv_valid: Optional[jax.Array] = None,
    segments: Optional[jax.Array] = None,   # (B, S) packed prompt ids, -1 pad
    k_scale: Optional[jax.Array] = None,    # (B, Skv, Hkv) quantised-KV scales
    v_scale: Optional[jax.Array] = None,
    kv_bits: int = 0,                       # 8 | 4 when k_scale/v_scale given
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    scale: Optional[float] = None,
    impl: str = "auto",
) -> jax.Array:
    """``k_scale``/``v_scale`` switch K/V to the quantised-KV convention:
    ``k``/``v`` carry int8 codes (packed two-per-byte along the head dim for
    ``kv_bits=4``) with per-(entry, head) scales.  The decode-shaped Pallas
    route runs :func:`..decode.flash_decode_quant_fwd` (in-VMEM dequant);
    every other route dequantises up front and proceeds as fp."""
    if impl not in ("ref", "auto", "flash", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown attention impl {impl!r}")
    strict = impl == "pallas"
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "ref"
    if impl == "flash":
        impl = "pallas" if on_tpu else "pallas_interpret"

    if k_scale is not None:
        if kv_bits not in (4, 8):
            raise ValueError(f"quantised KV needs kv_bits 4 or 8, got {kv_bits}")
        if impl in ("pallas", "pallas_interpret") and \
                _decode_ok(q, k, causal, q_pos, kv_pos):
            kp = kv_pos if kv_valid is None else jnp.where(kv_valid, kv_pos, -1)
            run = functools.partial(
                _decode.flash_decode_quant_fwd, kv_bits=kv_bits,
                window=window, softcap=softcap, scale=scale,
                interpret=impl == "pallas_interpret")
            return _per_device(
                lambda q, k, ks, v, vs, qp, kp: run(q, k, ks, v, vs,
                                                    q_pos=qp, kv_pos=kp),
                q, k, k_scale, v, v_scale, q_pos, kp)
        from repro.quant.core import dequantize_kv
        k = dequantize_kv(k, k_scale, kv_bits).astype(q.dtype)
        v = dequantize_kv(v, v_scale, kv_bits).astype(q.dtype)

    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        if _decode_ok(q, k, causal, q_pos, kv_pos):
            kp = kv_pos if kv_valid is None else jnp.where(kv_valid, kv_pos, -1)
            run = functools.partial(
                _decode.flash_decode_fwd, window=window, softcap=softcap,
                scale=scale, interpret=interpret)
            return _per_device(
                lambda q, k, v, qp, kp: run(q, k, v, q_pos=qp, kv_pos=kp),
                q, k, v, q_pos, kp)
        if _pallas_ok(q, k, causal, q_pos, kv_pos, kv_valid, window, segments):
            def run(q, k, v, *seg):
                out = _kernel.flash_attention_fwd(
                    q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), segments=seg[0] if seg else None,
                    causal=causal, window=window, softcap=softcap,
                    scale=scale, interpret=interpret)
                return out.transpose(0, 2, 1, 3)
            return _per_device(run, q, k, v,
                               *(() if segments is None else (segments,)))
        if strict:
            raise ValueError(
                f"no Pallas attention kernel fits q {q.shape}, k {k.shape} "
                f"(q_pos={q_pos is not None}, kv_pos={kv_pos is not None}, "
                f"segments={segments is not None}, causal={causal})")

    return attention_ref(
        q, k, v, q_pos=q_pos, kv_pos=kv_pos, kv_valid=kv_valid,
        q_seg=segments, kv_seg=segments,
        causal=causal, window=window, softcap=softcap, scale=scale)
