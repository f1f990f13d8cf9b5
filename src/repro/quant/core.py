"""Quantization plane: int8 / packed-int4 weights and quantized KV rows.

The paper's ReRAM PIM chiplets are low-precision compute by construction
(2-bit cells, bit-sliced weights), and the serving workloads it targets are
memory-bound: weight re-streaming and KV-cache reads dominate decode fabric
bytes (97–99% in the Plane-B generation model).  Quantization is the lever
that shrinks exactly those bytes, so this module is the single source of
truth for every quantised representation in the repo:

- **weights** — weight-only symmetric quantisation to int8 or packed int4
  with per-output-channel scales (optionally per-``group`` rows of the
  contraction dim).  :class:`QuantTensor` is a pytree, so quantised params
  ride through ``jax.jit``/``lax.scan``/donation like any other leaf;
- **KV rows** — per-(token, head) symmetric scales, quantised when a row is
  committed to the slot pool and dequantised on read
  (:mod:`repro.models.attention` / the Pallas decode kernel);
- **crossbar tiles** — ``quantize_weights``, the 128×128 per-crossbar-tile
  int8 quantiser the PIM-MVM kernel programs its arrays with (moved here
  from ``kernels/pim_mvm/ops.py``; that module re-exports it).

Packed int4 stores two codes per int8 byte as *adjacent pairs* along the
packing axis (code ``2i`` in the low nibble, ``2i+1`` in the high nibble),
so any contiguous block of packed rows maps to a contiguous block of
original rows — the property the blocked Pallas kernels rely on to unpack
tiles in VMEM.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

XBAR = 128          # crossbar dimension == MXU tile (pim_mvm contract)
QMAX = {8: 127, 4: 7}
WEIGHT_BITS = (0, 4, 8)   # 0 = native fp
KV_BITS = (0, 4, 8)

# parameter-tree keys eligible for weight-only quantisation: the dense
# projection matmuls (attention q/k/v/out, MLP, lm_head).  Routers, norms,
# biases, embeddings, MoE expert banks (einsum over a leading expert axis)
# and MLA factor tensors stay fp.
QUANT_PARAM_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"})


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def pack_int4(codes: jax.Array, axis: int = -1) -> jax.Array:
    """Pack int4 codes (int8 values in [-8, 7]) two-per-byte along ``axis``
    as adjacent pairs: byte ``i`` holds code ``2i`` (low nibble) and code
    ``2i+1`` (high nibble).  The axis length must be even."""
    c = jnp.moveaxis(codes, axis, -1)
    if c.shape[-1] % 2:
        raise ValueError(f"pack axis length {c.shape[-1]} must be even")
    lo = c[..., 0::2]
    hi = c[..., 1::2]
    packed = (lo & jnp.int8(0x0F)) | jnp.left_shift(hi, 4).astype(jnp.int8)
    return jnp.moveaxis(packed.astype(jnp.int8), -1, axis)


def int4_planes(packed: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sign-extended ``(low, high)`` nibble planes of packed int4 codes, as
    int32 of the packed shape: ``low[i]`` is code ``2i`` and ``high[i]``
    code ``2i+1`` of the packing axis.  The Pallas kernels contract each
    plane against the even / odd half of the other operand, which needs no
    in-kernel interleave (a lane-splitting reshape Mosaic cannot lower)."""
    p = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)    # arithmetic: sign-ext
    hi = jnp.right_shift(p, 4)
    return lo, hi


def unpack_int4(packed: jax.Array, axis: int = -1) -> jax.Array:
    """Inverse of :func:`pack_int4` — sign-extending nibble unpack."""
    p = jnp.moveaxis(packed, axis, -1)
    lo, hi = int4_planes(p)
    c = jnp.stack([lo, hi], axis=-1).reshape(p.shape[:-1] + (p.shape[-1] * 2,))
    return jnp.moveaxis(c.astype(jnp.int8), -1, axis)


# ---------------------------------------------------------------------------
# weight-only quantisation
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantTensor:
    """A quantised (..., K, N) weight matrix.

    ``q``     — int8 codes; for ``bits=4`` two codes per byte packed along
                the contraction axis (shape (..., K/2, N));
    ``scale`` — f32 scales, (..., 1, N) per-channel or (..., K/group, N);
    ``bits``  — 8 or 4 (static aux data);
    ``group`` — rows of K per scale group (0 = one scale per column).

    Registered as a pytree so quantised params flow through jit / scan /
    vmap / donation; slicing via ``tree_map(lambda l: l[i])`` slices codes
    and scales coherently (the stacked-layer access pattern of
    ``models/transformer.run_stack``).
    """
    q: jax.Array
    scale: jax.Array
    bits: int
    group: int = 0

    @property
    def k_dim(self) -> int:
        """Original contraction length K (codes are packed for int4)."""
        return self.q.shape[-2] * (2 if self.bits == 4 else 1)

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.group)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], bits=aux[0], group=aux[1])


def quantize(w: jax.Array, bits: int = 8, *, group: int = 0) -> QuantTensor:
    """Symmetric weight-only quantisation of a (..., K, N) matrix.

    One scale per output channel (column of N), or per ``group`` rows of K
    per channel when ``group`` divides K.  ``bits=4`` packs the codes along
    K (which must be even)."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    K = w.shape[-2]
    if group and K % group:
        raise ValueError(f"group {group} must divide K {K}")
    if bits == 4 and K % 2:
        raise ValueError(f"int4 packing needs even K, got {K}")
    qmax = QMAX[bits]
    wf = w.astype(jnp.float32)
    if group:
        g = wf.reshape(wf.shape[:-2] + (K // group, group, wf.shape[-1]))
        scale = jnp.max(jnp.abs(g), axis=-2) / qmax          # (..., K/g, N)
        scale = jnp.maximum(scale, 1e-12)
        expand = jnp.repeat(scale, group, axis=-2)
    else:
        scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / qmax
        scale = jnp.maximum(scale, 1e-12)                    # (..., 1, N)
        expand = scale
    codes = jnp.clip(jnp.round(wf / expand), -qmax, qmax).astype(jnp.int8)
    if bits == 4:
        codes = pack_int4(codes, axis=-2)
    return QuantTensor(codes, scale, bits=bits, group=group)


def dequantize(qt: QuantTensor) -> jax.Array:
    """(..., K, N) f32 reconstruction of a :class:`QuantTensor`."""
    codes = unpack_int4(qt.q, axis=-2) if qt.bits == 4 else qt.q
    if qt.group:
        scale = jnp.repeat(qt.scale, qt.group, axis=-2)
    else:
        scale = qt.scale
    return codes.astype(jnp.float32) * scale


def quantize_params(params, bits: int, *, group: int = 0):
    """Weight-only quantisation of a model parameter tree.

    Replaces every dense projection leaf (``QUANT_PARAM_KEYS``, 2-D at the
    top level or 3-D stacked under a scan group) by a :class:`QuantTensor`;
    everything else — biases, norms, embeddings, routers, MoE expert banks,
    MLA factors — is returned untouched.  Leaves whose contraction dim is
    incompatible (odd K for int4, K not a multiple of ``group``) stay fp
    rather than failing the whole tree."""
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")

    def visit(path, leaf):
        key = str(getattr(path[-1], "key", getattr(path[-1], "name", "")))
        if key not in QUANT_PARAM_KEYS:
            return leaf
        if not hasattr(leaf, "ndim") or leaf.ndim not in (2, 3):
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        K = leaf.shape[-2]
        g = group if (group and K % group == 0) else 0
        if bits == 4 and K % 2:
            return leaf
        return quantize(leaf, bits, group=g)

    return jax.tree_util.tree_map_with_path(visit, params)


def fake_quantize_params(params, bits: int, *, group: int = 0):
    """Quantise-dequantise round trip of :func:`quantize_params`: the same
    weights the quantised path computes with, materialised back as fp
    leaves.  An fp engine running these params is the exact oracle for the
    quantised engine's weight path (weight-only quantisation changes the
    *values* once, offline — not the arithmetic)."""
    qp = quantize_params(params, bits, group=group)
    return jax.tree_util.tree_map(
        lambda leaf: dequantize(leaf) if isinstance(leaf, QuantTensor) else leaf,
        qp, is_leaf=lambda leaf: isinstance(leaf, QuantTensor))


# ---------------------------------------------------------------------------
# crossbar-tile quantisation (PIM-MVM contract)
# ---------------------------------------------------------------------------

def quantize_weights(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(K, N) float -> (int8 values, (K/128, N/128) f32 per-tile scales).

    Symmetric per-crossbar-tile quantisation: each 128×128 tile gets one
    scale = max|w|/127 — the granularity a bit-sliced crossbar imposes
    (all cells in a crossbar share the DAC/ADC range).
    """
    K, N = w.shape
    if K % XBAR or N % XBAR:
        raise ValueError(f"weights {(K, N)} must tile {XBAR}x{XBAR} crossbars")
    t = w.astype(jnp.float32).reshape(K // XBAR, XBAR, N // XBAR, XBAR)
    t = t.transpose(0, 2, 1, 3)                      # (Kt, Nt, 128, 128)
    scales = jnp.max(jnp.abs(t), axis=(2, 3)) / 127.0
    scales = jnp.maximum(scales, 1e-12)
    q = jnp.round(t / scales[:, :, None, None]).astype(jnp.int8)
    q = q.transpose(0, 2, 1, 3).reshape(K, N)
    return q, scales


# ---------------------------------------------------------------------------
# KV-row quantisation (slot-pool caches)
# ---------------------------------------------------------------------------

def quantize_kv(x: jax.Array, bits: int) -> tuple[jax.Array, jax.Array]:
    """Quantise KV rows (..., hd) with one symmetric scale per row — the
    per-(token, head) granularity of the slot-pool cache.  Returns
    ``(codes, scale)`` with codes (..., hd) int8, packed to (..., hd/2)
    for ``bits=4``; all-zero rows (empty slots) get the floor scale and
    zero codes, so dequantisation reproduces exact zeros."""
    if bits not in (4, 8):
        raise ValueError(f"kv bits must be 4 or 8, got {bits}")
    qmax = QMAX[bits]
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / qmax, 1e-12)
    codes = jnp.clip(jnp.round(xf / scale[..., None]), -qmax, qmax)
    codes = codes.astype(jnp.int8)
    if bits == 4:
        codes = pack_int4(codes, axis=-1)
    return codes, scale


def dequantize_kv(codes: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Inverse of :func:`quantize_kv` — (..., hd) f32."""
    c = unpack_int4(codes, axis=-1) if bits == 4 else codes
    return c.astype(jnp.float32) * scale[..., None]


def quantize_kv_cache(cache: dict, bits: int) -> dict:
    """Quantise a freshly-prefilled fp KV cache ``{"k", "v", "pos"}`` into
    the quantised slot-pool layout ``{"k_q", "k_s", "v_q", "v_s", "pos"}``
    (per-(entry, head) scales).  Empty entries are zeros and stay exact."""
    k_q, k_s = quantize_kv(cache["k"], bits)
    v_q, v_s = quantize_kv(cache["v"], bits)
    return {"k_q": k_q, "k_s": k_s, "v_q": v_q, "v_s": v_s,
            "pos": cache["pos"]}


def kv_cache_bits(cache: dict, head_dim: int) -> int:
    """Bit-width of a quantised slot-pool cache, inferred from the packed
    head dim (int4 halves it)."""
    return 4 if cache["k_q"].shape[-1] != head_dim else 8
