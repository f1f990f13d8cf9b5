"""The serving program's host spans in a profiler trace (``.xplane.pb``),
and the split of the device's idle time between programs.

The program stamps its own spans (``repro.serving.tracing``), with its
counters as attributes: ``engine.step``, ``engine.admit``,
``engine.chunks``, ``engine.commit``, ``executor.<program>`` around each
dispatch and ``executor.fetch`` around each blocking device-to-host copy.

Host and device stamps are not on one clock.  The runtime stamps each
program run twice on the host, with the ``run_id`` the device's ``XLA
Modules`` event carries: ``DoEnqueueProgram`` (the host hands the run to
the device, which cannot start it before) and ``CompleteCallbacks`` (the
host learns that the run ended, which it cannot do before the device
ends it).  Pairing each run with its two host stamps brackets the offset
of the host clock over the device clock:

    max(enqueue - device start) <= offset <= min(complete - device end).

No alignment is needed for the split of a gap between two consecutive
runs, since it takes differences on one clock alone: the host part runs
from the end of the ``executor.fetch`` that returned the earlier run's
outputs (the first to end after that run's enqueue) to the host enqueue
of the next run, clipped to the gap; the wait part, the device done while
its outputs travel to Python, is the rest of the gap.  Each gap belongs to
the ``engine.step`` in which the next run was enqueued.

It reads the trace with ``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import warnings
from typing import Optional

PREFIXES = ("engine.", "executor.")      # the program's span names
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"


@dataclasses.dataclass
class Span:
    name: str
    start: float                   # host clock, ns
    end: float
    attrs: dict


@dataclasses.dataclass
class Run:
    """One execution of a program on the device."""
    run_id: int
    module: str
    start: float                   # device clock, ns
    end: float
    enqueue: Optional[float]       # host clock, ns
    complete: Optional[float]


@dataclasses.dataclass
class Gap:
    """The device idle between two consecutive runs, split."""
    after: int                     # run_id of the earlier run
    before: int                    # run_id of the later run
    fetch_end: float               # host clock, ns: the earlier run's fetch
    seconds: float
    host_s: float
    wait_s: float
    step: int                      # index into Spans.steps


@dataclasses.dataclass
class Spans:
    spans: list                    # [Span], by start
    runs: list                     # [Run] of the first chip, by start
    offset: tuple                  # (lower, upper) bound, ns, of host
    #                                clock minus device clock
    gaps: list                     # [Gap] between consecutive paired runs
    steps: list                    # [Span] engine.step owning some gap
    idle_s: float                  # device idle, first run start to last
    #                                run end (gaps in the ops' union)

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]


def _stats(event) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def _idle(ops: list, a: float, b: float) -> float:
    """Seconds in [a, b] with no op interval, ns in."""
    busy, last = 0.0, a
    for s, e in sorted(ops):
        s, e = max(s, last), min(e, b)
        if e > s:
            busy += e - s
            last = e
    return ((b - a) - busy) * 1e-9


def read(path: str) -> Spans:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    dev = next((p for p in planes if p.name.startswith("/device:TPU:")
                and "SparseCore" not in p.name), None)
    if dev is None:
        raise ValueError(f"no TPU device plane in {path}")
    runs, ops = [], []
    for line in dev.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                st = _stats(e)
                runs.append(Run(int(st.get("run_id", -1)),
                                e.name.split("(")[0], e.start_ns, e.end_ns,
                                None, None))
        elif line.name == "XLA Ops":
            ops = [(e.start_ns, e.end_ns) for e in line.events]
    runs.sort(key=lambda r: r.start)

    spans, enq, done = [], {}, {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    spans.append(Span(e.name, e.start_ns, e.end_ns,
                                      _stats(e)))
                elif e.name in (ENQUEUE, COMPLETE):
                    rid = _stats(e).get("run_id")
                    book = enq if e.name == ENQUEUE else done
                    if rid is not None and rid not in book:
                        book[int(rid)] = e.start_ns
    spans.sort(key=lambda s: (s.start, -s.end))
    for r in runs:
        r.enqueue, r.complete = enq.get(r.run_id), done.get(r.run_id)

    lo = max((r.enqueue - r.start for r in runs if r.enqueue is not None),
             default=float("-inf"))
    hi = min((r.complete - r.end for r in runs if r.complete is not None),
             default=float("inf"))

    fetches = sorted(s.end for s in spans if s.name == "executor.fetch")
    steps = [s for s in spans if s.name == "engine.step"]
    owners, gaps = [], []
    for prev, nxt in zip(runs, runs[1:]):
        gap = nxt.start - prev.end
        if gap <= 0 or prev.enqueue is None or nxt.enqueue is None:
            continue
        i = bisect.bisect_right(fetches, prev.enqueue)
        if i == len(fetches):
            continue
        host = min(max(nxt.enqueue - fetches[i], 0.0), gap)
        step = _innermost(steps, nxt.enqueue)
        if step is None:
            continue
        if step not in owners:
            owners.append(step)
        gaps.append(Gap(prev.run_id, nxt.run_id, fetches[i], gap * 1e-9,
                        host * 1e-9, (gap - host) * 1e-9,
                        owners.index(step)))
    idle = _idle(ops, runs[0].start, runs[-1].end) if runs else 0.0
    return Spans(spans, runs, (lo, hi), gaps, owners, idle)


def _innermost(spans: list, t: float) -> Optional[Span]:
    """The shortest of ``spans`` around host time ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or
                                      s.end - s.start < best.end - best.start):
            best = s
    return best


def host_ms_per_iter(sp: Spans) -> Optional[float]:
    """Host part of the gaps between programs, per engine iteration."""
    if not sp.steps:
        return None
    return sum(g.host_s for g in sp.gaps) / len(sp.steps) * 1e3


def d2h_wait_ms_per_iter(sp: Spans) -> Optional[float]:
    """Wait part of the gaps between programs, per engine iteration."""
    if not sp.steps:
        return None
    return sum(g.wait_s for g in sp.gaps) / len(sp.steps) * 1e3


def chunk_fill_pct(sp: Spans) -> Optional[float]:
    """Prompt tokens over the positions the chunk steps computed."""
    chunks = [s.attrs for s in sp.named("engine.chunks")
              if "positions" in s.attrs]
    positions = sum(a["positions"] for a in chunks)
    if not positions:
        return None
    return sum(a["prompt_tokens"] for a in chunks) / positions * 100.0
