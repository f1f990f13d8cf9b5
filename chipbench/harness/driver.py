"""The benchmark's own load driver: it offers a cell's traffic to
``ServingEngine`` and stamps every token on the host.

Open loop: each request is submitted at its due time, whether or not
earlier ones have finished, and every latency runs from the due time, so a
stall that delays later submissions counts against them.  Closed loop: a
fixed number of clients, each submitting its next request as its last one
completes; a request is due when it is submitted.

Traffic starts ``warmup_s`` before the window, so the slot pool is at the
occupancy of its load when the window opens.  Requests due in the window
are the run's attempted requests.  Submission stops when the window
closes; the engine then steps on until every attempted request has its
first token (or ``drain_s`` has passed), and stops.

The driver calls ``engine.submit`` and ``engine.step`` the way
``ServingFrontend.play`` does, on the engine's clock (``time.monotonic``).
For a traced run, ``instrument`` wraps the engine's and executor's entry
points in host spans for the profiler and records, before each device
call, the work the call needs (for the roofline and MFU readers); ``drive``
switches that record on for the traced slice only.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax

clock = time.monotonic


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it."""
    req: object                    # the engine's Request
    due: float
    submitted: float
    times: list = dataclasses.field(default_factory=list)   # token stamps

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Calls:
    """Work of the device calls made while ``on`` (the traced slice)."""
    on: bool = False
    decode_live: list = dataclasses.field(default_factory=list)
    #   per fused step: live length of each decoding slot
    prefill_pieces: list = dataclasses.field(default_factory=list)
    #   per packed prefill: [(start, count), ...] of its prompt pieces
    chunk_pieces: list = dataclasses.field(default_factory=list)
    #   per chunk step: [(start, count), ...]
    final_rows: int = 0            # prefill rows that sampled a first token
    kv_live: list = dataclasses.field(default_factory=list)
    #   per fused step: live KV entries over every occupied slot


def _span(fn: Callable, name: str) -> Callable:
    def call(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return call


def instrument(engine, calls: Calls):
    """Host spans around the engine's and executor's entry points, and
    recorders of each call's work (host-side reads only)."""
    import numpy as np

    ex, pool = engine.executor, engine.pool

    fused, packed, chunk = ex.fused_step, ex.packed_prefill, ex.chunk_step

    def fused_step(cache, state):
        if calls.on:
            live, kv = [], 0
            for i, r in enumerate(pool.slot_req):
                if r is None:
                    continue
                if i in pool.prefilling:
                    kv += pool.prefilling[i][0]
                    continue
                n = len(r.prompt) + len(r.output)
                live.append(n)
                kv += n
            calls.decode_live.append(live)
            calls.kv_live.append(kv)
        return fused(cache, state)

    def packed_prefill(cache, state, *args):
        if calls.on:
            len_v, fin_v, act_v = (np.asarray(args[5]), np.asarray(args[6]),
                                   np.asarray(args[8]))
            calls.prefill_pieces.append(
                [(0, int(n)) for n, a in zip(len_v, act_v) if a])
            calls.final_rows += int((fin_v & act_v).sum())
        return packed(cache, state, *args)

    def chunk_step(cache, state, *args):
        if calls.on:
            pos, fin_v = np.asarray(args[1]), np.asarray(args[3])
            pieces = []
            for row in pos:
                real = row[row >= 0]
                if real.size:
                    pieces.append((int(real.min()), int(real.size)))
            calls.chunk_pieces.append(pieces)
            calls.final_rows += int(fin_v.sum())
        return chunk(cache, state, *args)

    ex.fused_step = _span(fused_step, "executor.fused_step")
    ex.packed_prefill = _span(packed_prefill, "executor.packed_prefill")
    ex.chunk_step = _span(chunk_step, "executor.chunk_step")
    ex.fetch = _span(ex.fetch, "executor.fetch")
    engine.submit = _span(engine.submit, "engine.submit")
    engine.step = _span(engine.step, "engine.step")


def warm(engine, chunked: bool):
    """Compile (or load from the persistent cache) the programs this cell's
    traffic runs, with every slot inactive so nothing is written: the
    packed prefill ``(1, C)``, the fused decode step, and the chunk step
    ``(max_batch, C)`` where prompts outrun one chunk."""
    import jax.numpy as jnp
    import numpy as np

    ex, pool = engine.executor, engine.pool
    B, C = engine.ecfg.max_batch, engine._chunk
    z1 = jnp.asarray(np.zeros((1, C), np.int32))
    neg1 = jnp.asarray(np.full((1, C), -1, np.int32))
    zb = jnp.asarray(np.zeros((B,), np.int32))
    ob = jnp.asarray(np.ones((B,), np.int32))
    fb = jnp.asarray(np.zeros((B,), bool))
    pool.cache, pool.state, out = ex.packed_prefill(
        pool.cache, pool.state, z1, z1, neg1, zb, zb, zb, fb, ob, fb)
    jax.block_until_ready(out)
    if chunked:
        zc = jnp.asarray(np.zeros((B, C), np.int32))
        nc = jnp.asarray(np.full((B, C), -1, np.int32))
        pool.cache, pool.state, out = ex.chunk_step(
            pool.cache, pool.state, zc, nc, zb, fb, ob)
        jax.block_until_ready(out)
    pool.cache, pool.state, out = ex.fused_step(pool.cache, pool.state)
    jax.block_until_ready(out)


@dataclasses.dataclass
class Run:
    recs: list
    t0: float                      # traffic start
    w0: float                      # window open
    w1: float                      # window close
    end: float                     # driver stopped
    lateness: list                 # open loop: submit - due, per request
    hist0: dict                    # engine.active_slot_hist at w0 / w1
    hist1: dict
    prefill_tokens: tuple = (0, 0)  # engine.prefill_tokens at trace on/off
    trace_span: tuple = (0.0, 0.0)  # host clock when tracing started/stopped
    last_step: float = 0.0         # host clock after the last engine step


def drive(engine, stream, mix: dict, seconds: float, *,
          on_window: Callable = lambda: None,
          on_close: Callable = lambda: None,
          trace: Optional[tuple] = None, calls: Optional[Calls] = None,
          drain_s: float = 60.0) -> Run:
    """Offer ``stream`` to ``engine`` for ``warmup_s`` and then a window of
    ``seconds``.  ``trace = (start, stop)`` are callables run at the middle
    slice of the window (``trace_s`` long, from the mix)."""
    open_loop = mix["loop"] == "open"
    clients = int(mix.get("clients", 0))
    t0 = clock()
    w0 = t0 + float(mix["warmup_s"])
    w1 = w0 + seconds
    slice_s = min(float(mix.get("trace_s", 4.0)), seconds / 2)
    ts = w0 + (seconds - slice_s) / 2
    te = ts + slice_s
    recs, live, lateness = [], [], []
    tracing = traced = False
    hist0 = hist1 = {}
    pf = [0, 0]
    span = [0.0, 0.0]
    last_step = t0
    window_open = window_closed = False
    submit = engine.submit
    wait = (_span(time.sleep, "driver.wait") if calls is not None
            else time.sleep)

    def add(r, due):
        req = submit(r.prompt, r.max_new_tokens)
        now = clock()
        rec = Rec(req, due, now)
        recs.append(rec)
        live.append(rec)
        if open_loop:
            lateness.append(now - due)

    while True:
        now = clock()
        if not window_open and now >= w0:
            window_open = True
            hist0 = dict(engine.active_slot_hist)
            on_window()
        if trace is not None and not traced:
            # an open loop's slice opens on an arrival, so that it holds a
            # prefill as well as decode steps, unless none comes before
            # the latest start that still ends the slice in the window
            if not tracing and now >= ts and (
                    not open_loop or t0 + stream.peek().t <= now
                    or now >= w1 - slice_s):
                trace[0]()
                tracing = True
                span[0] = clock()
                te = span[0] + slice_s
                pf[0] = engine.prefill_tokens
                if calls is not None:
                    calls.on = True
            elif tracing and now >= te:
                if calls is not None:
                    calls.on = False
                span[1] = clock()
                pf[1] = engine.prefill_tokens
                trace[1]()
                tracing, traced = False, True
                continue
        if open_loop:
            # every request due in the window is submitted, late if the
            # loop was held up past its close
            while t0 + stream.peek().t <= min(now, w1) and \
                    t0 + stream.peek().t < w1:
                r = stream.pop()
                add(r, t0 + r.t)
        elif now < w1:
            while len(live) < clients:
                add(stream.pop(), clock())
        if now >= w1:
            if not window_closed:
                window_closed = True
                hist1 = dict(engine.active_slot_hist)
                on_close()
            pending = [r for r in recs if w0 <= r.due < w1 and not r.times]
            if not pending or now > w1 + drain_s:
                break
        busy = engine.queue or engine.pool.occupied()
        if not busy:
            if open_loop and now < w1:
                nxt = min(t0 + stream.peek().t, w1)
                if tracing:
                    nxt = min(nxt, te)
                elif trace is not None and not traced and now < ts:
                    nxt = min(nxt, ts)
                wait(max(0.0, nxt - clock()))
            continue
        engine.step()
        now = last_step = clock()
        still = []
        for rec in live:
            req = rec.req
            n = len(req.output)
            k = n - len(rec.times)
            if k > 0:
                if not rec.times:
                    rec.times.append(req.t_first_token)
                    k -= 1
                rec.times.extend([now] * k)
            if not req.terminal:
                still.append(rec)
        live[:] = still
    if tracing:                    # never leave the profiler running
        if calls is not None:
            calls.on = False
        span[1] = clock()
        pf[1] = engine.prefill_tokens
        trace[1]()
    return Run(recs, t0, w0, w1, clock(), lateness, hist0, hist1,
               tuple(pf), tuple(span), last_step)
