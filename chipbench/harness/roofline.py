"""The yardstick: chip peaks and the operations and bytes that the work of
a call needs.

The counts are of the work the algorithm needs, never of what one
implementation sweeps: a decode call reads only the live KV entries of the
slots that decode, and prefill attention counts only the unmasked
(causal, same-prompt) query/key pairs.  The same work then gets the same
yardstick whatever kernel computes it, and a share of a roofline cannot
pass 100% unless the time leaves out part of the work.
"""
from __future__ import annotations

from typing import Iterable

# Published peaks of one chip, keyed by ``device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "hbm_byte_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM2 at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r}; "
                       f"add it to chipbench/harness/roofline.py with its "
                       f"source") from None


def least_time(flops: float, nbytes: float, pk: dict) -> float:
    """Seconds the chip needs at best: the larger of the compute and the
    memory bound."""
    return max(flops / pk["bf16_flop_per_s"], nbytes / pk["hbm_byte_per_s"])


# -- attention kernels (per layer) -----------------------------------------

def decode_attention(live_lens: Iterable[int], m: dict, elem: int = 2):
    """(flops, bytes) of one layer's decode attention: each decoding slot's
    one query against its ``live_len`` KV entries (its own included).
    Bytes: the live K and V entries read, the query read and the output
    written, at ``elem`` bytes an element."""
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    flops = nbytes = 0.0
    for n in live_lens:
        flops += 2.0 * hq * (hd + hd) * n            # QK^T and PV
        nbytes += elem * (n * hkv * 2 * hd + 2 * hq * hd)
    return flops, nbytes


def prefill_attention(segments: Iterable[tuple[int, int]], m: dict,
                      elem: int = 2):
    """(flops, bytes) of one layer's prefill attention over prompt pieces
    ``(start, count)``: tokens at positions ``start .. start+count-1`` of
    one prompt, each attending causally to every earlier position of its
    own prompt.  Bytes: the pieces' Q, K, V read and output written (a
    piece that continues a prompt also reads the ``start`` cached K/V
    entries before it)."""
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["head_dim"]
    flops = nbytes = 0.0
    for start, count in segments:
        pairs = count * start + count * (count + 1) / 2.0
        flops += 2.0 * hq * (hd + hd) * pairs
        nbytes += elem * (count * (2 * hq * hd + 2 * hkv * hd)
                          + start * 2 * hkv * hd)
    return flops, nbytes


# -- the model step -------------------------------------------------------

def model_flops(fam, m: dict, tokens: Iterable[tuple[int, int]],
                logits_rows: int) -> float:
    """FLOPs the model needs for prompt or output tokens given as pieces
    ``(start, count)`` of one sequence each (positions ``start ..
    start+count-1``), with ``logits_rows`` rows sent through the output
    head (one per sampled token).  Each token: two FLOPs a weight that the
    family says one token uses in each layer
    (``fam.matmul_params_per_layer``), and attention to itself and every
    earlier position."""
    L = m["num_hidden_layers"]
    per_tok = 2.0 * fam.matmul_params_per_layer(m) * L
    hq, hd = m["num_attention_heads"], m["head_dim"]
    total = 0.0
    for start, count in tokens:
        pairs = count * start + count * (count + 1) / 2.0
        total += per_tok * count + 2.0 * hq * (hd + hd) * pairs * L
    total += 2.0 * m["hidden_size"] * m["vocab_size"] * logits_rows
    return total
