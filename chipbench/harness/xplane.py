"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer readers take.

- device busy: the union of the intervals in which an operation ran on
  the device (the ops line of each chip's plane);
- program time: device time summed by XLA module name, with the number of
  executions;
- kernel time: device time of each Pallas kernel call (a
  ``tpu_custom_call``), summed by the program it runs in and its call text
  (operand shapes), since the trace does not carry the kernel's name;
- op time: each operation's self time (less the operations nested in it,
  as a loop's body is in the loop), by a short name;
- idle gaps: every stretch between device operations, labelled by the
  innermost host span that encloses its middle.

It reads the trace with ``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Iterable

# host spans the benchmark writes (driver.instrument)
HOST_SPANS = ("engine.step", "engine.submit", "executor.fused_step",
              "executor.packed_prefill", "executor.chunk_step",
              "executor.fetch", "driver.wait")
CUSTOM = "tpu_custom_call"       # a Mosaic (Pallas) kernel's call target


@dataclasses.dataclass
class Trace:
    busy_s: float                  # union of device op intervals (per chip)
    span_s: float                  # first to last device op
    modules: dict                  # name -> [seconds, executions]
    ops: dict                      # short op name -> self seconds
    kernels: dict                  # (module, op text) -> [seconds, calls]
    #                                 of every Pallas kernel call
    gaps: dict                     # host span label -> idle seconds
    longest_gaps: list             # [(label, seconds)] longest first
    n_chips: int


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def _union(intervals: Iterable[tuple[float, float]]):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def short(text: str) -> str:
    """``%name = shape op(...)...`` -> ``%name op shape`` without layouts,
    with a custom call's target."""
    lhs, _, rhs = text.partition(" = ")
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    head = rhs.split("(", 1)[0].strip()
    shape, _, op = head.rpartition(" ")
    m = re.search(r'custom_call_target="([^"]+)"', text)
    tail = f" {m.group(1)}" if m else ""
    return f"{lhs} {op}{tail} {shape}".strip()


def _self_times(events):
    """Self time of each (name, start, dur): its duration less that of the
    events nested inside it on the same line."""
    out = []
    stack = []                      # indices into out of open events
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][1] + out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= d
        out.append([name, s, d, d])
        stack.append(len(out) - 1)
    return [(name, own) for name, _, _, own in out]


def _enclosing(spans, starts, t, depth: int = 64) -> str:
    """Name of the innermost host span around time ``t``: spans on one
    thread nest, so the enclosing ones are among the last few that start
    before ``t``."""
    i = bisect.bisect_right(starts, t)
    best = None
    for s, e, name in spans[max(0, i - depth):i]:
        if e >= t and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def device_planes(planes):
    return [p for p in planes if p.name.startswith("/device:TPU:")
            and "SparseCore" not in p.name]


def _line(plane, want: str):
    for line in plane.lines:
        if line.name == want:
            return line
    return None


def reduce(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    devs = device_planes(planes)
    if not devs:
        raise ValueError(f"no TPU device plane in {path}: "
                         f"{[p.name for p in planes]}")
    modules = collections.defaultdict(lambda: [0.0, 0])
    ops = collections.defaultdict(float)
    kernels = collections.defaultdict(lambda: [0.0, 0])
    busy = 0.0
    first, last = float("inf"), 0.0
    dev_iv = []
    for plane in devs:
        mline, oline = _line(plane, "XLA Modules"), _line(plane, "XLA Ops")
        mods = []
        if mline is not None:
            for name, s, d, _ in _events(mline):
                base = name.split("(")[0]
                modules[base][0] += d * 1e-9
                modules[base][1] += 1
                mods.append((s, s + d, base))
        mods.sort()
        mstart = [m[0] for m in mods]
        if oline is None:
            continue
        iv, evs = [], []
        for name, s, d, _ in _events(oline):
            evs.append((name, s, d))
            iv.append((s, s + d))
            if CUSTOM in name:
                i = bisect.bisect_right(mstart, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else ""
                k = kernels[(mod, name)]
                k[0] += d * 1e-9
                k[1] += 1
        for name, own in _self_times(evs):
            ops[short(name)] += own * 1e-9
        merged = _union(iv)
        busy += sum(b - a for a, b in merged) * 1e-9
        if merged:
            first, last = min(first, merged[0][0]), max(last, merged[-1][1])
        if plane is devs[0]:
            dev_iv = merged
    n = len(devs)

    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, d, _ in _events(line):
                if name in HOST_SPANS:
                    spans.append((s, s + d, name))
    spans.sort()
    starts = [sp[0] for sp in spans]
    gaps = collections.defaultdict(float)
    each = []
    for (_, a), (b, _) in zip(dev_iv, dev_iv[1:]):
        label = _enclosing(spans, starts, (a + b) / 2)
        gaps[label] += (b - a) * 1e-9
        each.append((label, (b - a) * 1e-9))
    each.sort(key=lambda x: -x[1])
    return Trace(busy_s=busy / n, span_s=(last - first) * 1e-9 if dev_iv
                 else 0.0, modules=dict(modules), ops=dict(ops),
                 kernels=dict(kernels), gaps=dict(gaps),
                 longest_gaps=each[:10], n_chips=n)
