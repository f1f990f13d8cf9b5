"""Shared arithmetic of the per-layer readers in ``chipbench/metrics/``.

A reader takes the run's context and returns a number, or ``None`` where
the run gives it nothing to read (no such program or kernel in the trace,
no prompt token in the traced slice); a run fails where a metric declared
for its cell reads ``None``.  A reader never returns 0 for a share of a
roofline or of a peak in place of a missing reading.

On a mesh the trace holds one device plane a chip.  A program's or a
kernel's seconds are summed over the chips (chip-seconds), so a share of
one chip's peak or roofline over them is the share of all the chips'; a
time per step or per token is per chip; the traced slice counts once a
chip.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from harness import roofline
from harness.stats import percentile

FUSED, PACKED, CHUNK = "_fused_step_fn", "_packed_prefill_fn", "_chunk_step_fn"


def shapes(engine) -> dict:
    """The sizes the program was built with, read from the live engine:
    the pool's slots and depth, and the prefill chunk."""
    return {"max_batch": engine.ecfg.max_batch,
            "kv_len": engine.ecfg.kv_len, "chunk": engine._chunk}


@dataclasses.dataclass
class Context:
    m: dict                        # the configuration's model block
    fam: object                    # its family file's module
    shapes: dict                   # shapes(engine)
    pk: dict                       # chip peaks
    run: object                    # driver.Run
    calls: Optional[object]        # driver.Calls (traced runs)
    trace: Optional[object]        # xplane.Trace (traced runs)

    @property
    def window_s(self) -> float:
        a, b = self.run.trace_span
        return b - a

    def module(self, key: str):
        """(seconds, executions) of the programs whose name holds key."""
        hits = [v for k, v in self.trace.modules.items() if key in k]
        if not hits:
            return None
        return sum(v[0] for v in hits), sum(v[1] for v in hits)

    def kernel_s(self, module: str, operand: str) -> float:
        """Device seconds of the Pallas kernel calls in the programs whose
        name holds ``module`` and whose call takes an operand of type
        ``operand`` (the trace names no kernel, so a kernel is known by
        its program and its operands)."""
        return sum(v[0] for (mod, text), v in self.trace.kernels.items()
                   if module in mod and operand in text)

    def attempted(self):
        r = self.run
        return [x for x in r.recs if r.w0 <= x.due < r.w1]


def queue_wait_p50_ms(ctx: Context):
    """Median wait from due time to admission of the requests due in the
    window before the profiler stopped: stopping it holds the host loop
    for seconds, and the requests due after it wait out that stall and
    the backlog it leaves, which untraced runs never see."""
    stop = ctx.run.trace_span[1]
    waits = [((x.req.t_admit if x.req.t_admit > 0 else ctx.run.end) - x.due)
             for x in ctx.attempted() if x.due < stop]
    p = percentile(waits, 50)
    return None if p is None else p * 1e3


def decode_step_ms(ctx: Context):
    got = ctx.module(FUSED)
    if not got or not got[1]:
        return None
    return got[0] / got[1] * 1e3


def prefill_ms_per_ktok(ctx: Context):
    """Device time a chip spends in the prefill programs per 1,000 prompt
    tokens."""
    secs = [ctx.module(k) for k in (PACKED, CHUNK)]
    secs = sum(s[0] for s in secs if s) / ctx.trace.n_chips
    a, b = ctx.run.prefill_tokens
    if b <= a or not secs:
        return None
    return secs * 1e3 / ((b - a) / 1e3)


def _share(kernel_s: float, least_s: float):
    if kernel_s <= 0 or least_s <= 0:
        return None
    return least_s / kernel_s * 100.0


def decode_attn_roofline(ctx: Context):
    """The decode attention kernel: in the fused step, the call that takes
    the pool's ``(max_batch, 1, kv_len)`` int32 positions."""
    B, S = ctx.shapes["max_batch"], ctx.shapes["kv_len"]
    k = ctx.kernel_s(FUSED, f"s32[{B},1,{S}]")
    L = ctx.m["num_hidden_layers"]
    least = sum(L * roofline.least_time(
        *roofline.decode_attention(live, ctx.m), ctx.pk)
        for live in ctx.calls.decode_live if live)
    return _share(k, least)


def prefill_attn_roofline(ctx: Context):
    """The packed prefill's attention kernel: in the packed prefill, the
    call that takes the stream's ``(1, C)`` int32 segment ids.  The chunk
    step runs XLA's attention, no kernel, and is not counted here."""
    k = ctx.kernel_s(PACKED, f"s32[1,{ctx.shapes['chunk']}]")
    L = ctx.m["num_hidden_layers"]
    least = sum(L * roofline.least_time(
        *roofline.prefill_attention(pieces, ctx.m), ctx.pk)
        for pieces in ctx.calls.prefill_pieces if pieces)
    return _share(k, least)


def _mfu(flops: float, seconds: float, pk: dict):
    if flops <= 0 or seconds <= 0:
        return None
    return flops / (seconds * pk["bf16_flop_per_s"]) * 100.0


def _decode_flops(ctx: Context) -> float:
    live = ctx.calls.decode_live
    return roofline.model_flops(ctx.fam, ctx.m,
                                [(n - 1, 1) for s in live for n in s],
                                sum(len(s) for s in live))


def _prefill_flops(ctx: Context) -> float:
    c = ctx.calls
    pieces = [p for call in c.prefill_pieces + c.chunk_pieces for p in call]
    return roofline.model_flops(ctx.fam, ctx.m, pieces, c.final_rows)


def step_mfu_pct(ctx: Context):
    """The decode step's share of the chip's peak: model FLOPs of the
    tokens the fused steps of the traced slice decoded, over those steps'
    device time."""
    got = ctx.module(FUSED)
    return _mfu(_decode_flops(ctx), got[0] if got else 0.0, ctx.pk)


def prefill_mfu_pct(ctx: Context):
    """The prefill programs' share of the chip's peak: model FLOPs of the
    prompt tokens that packed prefills and chunk steps processed in the
    traced slice, over those programs' device time."""
    secs = sum(s[0] for s in (ctx.module(PACKED), ctx.module(CHUNK)) if s)
    return _mfu(_prefill_flops(ctx), secs, ctx.pk)


def slice_mfu_pct(ctx: Context):
    """The chips' share of their peak over the whole traced slice: model
    FLOPs of every prompt and output token processed, over the slice on
    every chip."""
    return _mfu(_decode_flops(ctx) + _prefill_flops(ctx),
                ctx.window_s * ctx.trace.n_chips, ctx.pk)


def idle_pct(ctx: Context):
    if ctx.window_s <= 0:
        return None
    return (1.0 - ctx.trace.busy_s / ctx.window_s) * 100.0


def batch_occupancy_pct(ctx: Context):
    h0, h1 = ctx.run.hist0, ctx.run.hist1
    d = {n: h1.get(n, 0) - h0.get(n, 0) for n in h1}
    steps = sum(d.values())
    if not steps:
        return None
    mean = sum(n * c for n, c in d.items()) / steps
    return mean / ctx.shapes["max_batch"] * 100.0


def kv_live_pct(ctx: Context):
    kv = ctx.calls.kv_live
    if not kv:
        return None
    cap = ctx.shapes["max_batch"] * ctx.shapes["kv_len"]
    return sum(kv) / len(kv) / cap * 100.0
