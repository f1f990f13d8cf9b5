"""Batched serving engine: the wiring layer of the serving stack.

The engine is deliberately thin.  Policy, device execution and slot
lifecycle live in three sibling layers with narrow interfaces::

    scheduler.py   admission + slot policy (Scheduler protocol:
                   FifoScheduler / SloScheduler) — who is admitted next,
                   may prefill preempt decode this iteration
    executor.py    the jitted device programs (fused decode step, packed
                   ragged prefill, chunked continuation, sequential
                   baselines) + the single device→host transfer point
    pool.py        the slotted (optionally quantised) KV cache, per-slot
                   decode state, slot lifecycle and its serialization API

``ServingEngine`` owns only the request queue, terminal bookkeeping and
the iteration loop that drives the three layers.  Each iteration runs:

1. **admission** — the scheduler picks queued requests (FIFO by
   default); all picked prompts pack back-to-back into one ragged
   ``(1, C)`` stream and prefill in a **single** jitted call, with one
   donated multi-slot scatter insert.  Prompts longer than ``C``
   contribute their first ``≤ C`` tokens and enter the *prefilling*
   state;
2. **chunked-prefill continuation** — every prefilling slot advances by
   at most one ``C``-token chunk per iteration, so a long prompt can
   never stall the decode pool for more than one chunk budget.  An
   SLO-aware scheduler may *defer* steps 1–2 while decode slack is too
   thin (slack-gated preemption); the default FIFO never does;
3. **decode** — one jitted, cache-donated step over the full slot pool;
   the only device→host traffic per iteration is one packed
   ``(K, 3, max_batch)`` int32 of ``(next_token, done, anomaly)``.

Hardening (defaults off → bit-identical to the plain engine):
per-request deadlines (``deadline_ms``), bounded-queue shedding
(``max_queue`` → retriable ``REJECTED``), NaN/inf logit quarantine
(``anomaly_retries``), and explicit ``run_until_drained`` failure
semantics (``EngineStallError`` — never a silent partial drain).  Every
submitted request ends in a terminal state.

``packed=False`` preserves the sequential admission baseline (one
bucket-padded batch-1 prefill+insert call per request) and
``fused=False`` the original host-looped decode step — both kept as
measurement baselines for ``benchmarks/perf_serving.py``.

The engine is mesh-aware: pass ``mesh=`` to shard the slot pool and run
the decode step over a pod (the executor activates the serving plans
from ``repro.parallel.sharding``).  Under the default config (FIFO, no
SLOs) token streams, ``stats()`` and checkpoint round-trips are
bit-identical to the pre-layering monolithic engine — pinned by
``tests/test_serving.py`` golden token streams and the HEAD snapshot
fixture in ``tests/data/``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.serving.executor import Executor
from repro.serving.pool import SlotPool
from repro.serving.scheduler import FifoScheduler, Scheduler
from repro.serving.tracing import Spans


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8            # KV slot pool size
    kv_len: int = 256             # per-slot KV depth
    max_new_tokens: int = 32
    temperature: float = 0.0      # 0 → greedy
    eos_token: int = -1           # -1 → never stops early
    impl: str = "ref"             # attention impl ("flash" → Pallas kernels)
    seed: int = 0
    fused: bool = True            # zero-host-sync decode step (False = seed path)
    packed: bool = True           # packed ragged prefill + chunked prefill
    #   (False = sequential admission: one batch-1 prefill per request)
    prefill_chunk: int = 0        # packed-stream / chunk budget in tokens
    #   (0 → min(128, kv_len)); also the padding quantum for non-packable
    #   architectures, so every prefill shape is static
    decode_chunk: int = 1         # device decode iterations per step() —
    #   >1 runs a lax.scan of decode→sample on device (multi-step
    #   scheduling): host sync cost is amortised over the chunk, at the
    #   price of admitting new requests only at chunk boundaries
    weight_bits: int = 0          # 0 = native fp; 8/4 = weight-only
    #   quantisation (per-channel int8 / packed int4, repro.quant) of the
    #   dense projections — the fp path is bit-identical to weight_bits=0
    weight_group: int = 0         # rows of K per scale group (0 = per-channel)
    kv_bits: int = 0              # 0 = fp pool; 8/4 = quantised slot-pool KV
    #   cache (per-(token, head) scales, quantise-on-commit / dequantise-
    #   on-read; the jitted step never materialises an fp cache)
    deadline_ms: float = 0.0      # per-request TTL from submit (0 = none):
    #   expired requests are evicted (queued or mid-decode) and marked
    #   FAILED_DEADLINE instead of decoding forever
    max_queue: int = 0            # bounded-queue admission (0 = unbounded):
    #   submits beyond the bound are shed with the retriable REJECTED
    #   status instead of growing the backlog without bound
    anomaly_retries: int = 1      # NaN/inf-logit quarantine: a slot whose
    #   logits go non-finite is frozen (no token, no pos/budget advance)
    #   and retried this many times before only that request is failed —
    #   the rest of the batch keeps decoding
    spec_k: int = 0               # speculative decoding: draft this many
    #   tokens per step and verify them in ONE batched multi-position call
    #   (0 = off — token streams and stats() bit-identical to the
    #   non-speculative engine).  Requires the fused+packed path,
    #   decode_chunk == 1 and a packable stack; spec_k+1 must fit the
    #   smallest cache ring (min(window, kv_len))
    spec_draft: str = "self"      # "self": a quantised copy of the engine's
    #   own serving params drafts (precision spec_draft_bits); "model": a
    #   separate small draft model passed as ServingEngine(draft=(cfg,
    #   params)), with its own KV pool kept in lockstep
    spec_draft_bits: int = 8      # self-draft precision (8 / 4; 0 = draft
    #   with the serving params themselves — greedy acceptance rate 1,
    #   the bit-identity test configuration)
    clock: Callable[[], float] = time.monotonic
    #   the engine's time source for request timestamps and deadline
    #   arithmetic — injectable so deadline/eviction tests advance a fake
    #   clock instead of sleeping.  Every stats() latency is a difference
    #   of clock readings, so any monotonic float-seconds source works.
    trace: bool = False           # per-iteration wall-clock tracer
    #   (repro.profile measured-cost hook): every decode iteration
    #   appends {"prefill_s", "decode_s", "d2h_s", "step_s", "iters"} to
    #   ``ServingEngine.trace`` and stats() surfaces aggregates under
    #   trace_* keys — present only when tracing, so the dormant
    #   engine's stats() stay bit-identical (the spec_k contract).
    #   Durations come from time.perf_counter (real wall clock) at the
    #   boundaries of the engine's host spans (serving/tracing.py),
    #   independent of ``clock=``, which fake-clock tests may drive.


class EngineStallError(RuntimeError):
    """``run_until_drained`` exhausted ``max_iters`` with requests still in
    flight.  Every stranded request has been marked ``FAILED_MAX_ITERS``
    (terminal) before this is raised — nothing is silently dropped."""


# Request terminal states (Request.status).  A submitted request always
# ends in exactly one of the terminal states below — queue/slot limbo is
# never silent.
QUEUED = "queued"
ACTIVE = "active"
DONE = "done"
FAILED_DEADLINE = "failed_deadline"    # missed its EngineConfig.deadline_ms
FAILED_ANOMALY = "failed_anomaly"      # non-finite logits past the retries
FAILED_MAX_ITERS = "failed_max_iters"  # stranded at max_iters exhaustion
REJECTED = "rejected"                  # shed at submit (bounded queue) —
#                                        retriable: resubmit later
TERMINAL = (DONE, FAILED_DEADLINE, FAILED_ANOMALY, FAILED_MAX_ITERS,
            REJECTED)


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray                       # (prompt_len,) int32
    max_new_tokens: Optional[int] = None
    priority: int = 0                        # scheduling class (larger =
    #                                          more urgent; FIFO ignores it)
    # -- filled by the engine -------------------------------------------------
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = QUEUED
    deadline: float = float("inf")           # absolute wall-clock bound
    t_enqueue: float = 0.0
    t_admit: float = 0.0                     # left the queue (slot assigned):
    #                                          t_admit - t_enqueue is pure
    #                                          scheduling delay, separable
    #                                          from prefill/decode service
    t_first_token: float = 0.0
    t_done: float = 0.0

    @property
    def terminal(self) -> bool:
        return self.status in TERMINAL


# prompt-length buckets for the sequential (packed=False) baseline path:
# one prefill compile per bucket, not per length
_MIN_BUCKET = 8


def _bucket_len(plen: int, kv_len: int) -> int:
    b = _MIN_BUCKET
    while b < plen:
        b *= 2
    return min(b, kv_len)


def _percentiles(xs) -> tuple:
    """(p50, p95, p99) of a sample list.  An empty class yields
    ``(None, None, None)`` — *absent*, not 0.0: a zero here used to be
    rendered by ``report.py`` as a real 0 ms latency."""
    if not xs:
        return (None, None, None)
    p = np.percentile(np.asarray(xs, np.float64), (50.0, 95.0, 99.0))
    return (float(p[0]), float(p[1]), float(p[2]))


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: Optional[EngineConfig] = None,
                 *, mesh=None, scheduler: Optional[Scheduler] = None,
                 draft: Optional[tuple] = None):
        # NOTE: default built per-instance — a dataclass default argument
        # would be one shared mutable EngineConfig across all engines.
        self.cfg = cfg
        self.ecfg = ecfg = ecfg if ecfg is not None else EngineConfig()
        if ecfg.weight_bits not in (0, 4, 8):
            raise ValueError(f"weight_bits must be 0, 4 or 8, got {ecfg.weight_bits}")
        if ecfg.kv_bits not in (0, 4, 8):
            raise ValueError(f"kv_bits must be 0, 4 or 8, got {ecfg.kv_bits}")
        if ecfg.spec_k:
            if ecfg.spec_k < 0:
                raise ValueError(f"spec_k must be >= 0, got {ecfg.spec_k}")
            if not (ecfg.fused and ecfg.packed):
                raise ValueError("speculative decoding requires the "
                                 "fused=True, packed=True path")
            if ecfg.decode_chunk != 1:
                raise ValueError("spec_k > 0 requires decode_chunk == 1 "
                                 "(the spec step IS the multi-token step)")
            if ecfg.spec_draft not in ("self", "model"):
                raise ValueError(f"spec_draft must be 'self' or 'model', "
                                 f"got {ecfg.spec_draft!r}")
            if ecfg.spec_draft_bits not in (0, 4, 8):
                raise ValueError(f"spec_draft_bits must be 0, 4 or 8, "
                                 f"got {ecfg.spec_draft_bits}")
            caps = [ecfg.kv_len] + [cfg.window for k in cfg.layer_kinds
                                    if k == "local"]
            if ecfg.spec_k + 1 > min(caps):
                raise ValueError(
                    f"spec_k+1 ({ecfg.spec_k + 1}) exceeds the smallest "
                    f"cache ring ({min(caps)}): the saved-column rollback "
                    f"needs unique ring indices")
            if ecfg.spec_draft == "model" and draft is None:
                raise ValueError(
                    "spec_draft='model' needs draft=(draft_cfg, draft_params)")

        # the three layers: policy / device programs / slot lifecycle
        self.scheduler: Scheduler = scheduler if scheduler is not None \
            else FifoScheduler()
        self.executor = Executor(cfg, params, ecfg, mesh=mesh)
        self.pool = SlotPool(cfg, ecfg, shard_ctx=self.executor.shard_ctx)

        # indexed FIFO admission queue: popleft is O(1) however deep the
        # backlog; the scheduler picks *which* entry leaves it
        self.queue: collections.deque[Request] = collections.deque()
        self.finished: list[Request] = []
        self.failed: list[Request] = []      # terminal failures (deadline /
        #                                      anomaly / max_iters)
        self.rejected: list[Request] = []    # shed at submit (retriable)
        self._uid = 0

        # prefill / schedule accounting (benchmarks/perf_serving.py)
        self.decode_steps = 0
        self.prefill_tokens = 0           # prompt tokens pushed through prefill
        self.prefill_time = 0.0           # host wall time spent in admission
        self.prefill_calls = 0
        self.max_stall_tokens = 0         # max prefill tokens between decodes
        self._stall_tokens = 0
        # crash-safety accounting (repro.serving.checkpoint)
        self.checkpoints_written = 0      # snapshots committed for this engine
        self.restores = 0                 # times this engine state was revived
        self.replayed_requests = 0        # journal-tail requests resubmitted
        # per-decode-iteration active-slot histogram {n_active: count} — the
        # measured slot-pool utilisation the Plane-B co-simulation batches
        # its decode steps with (repro.core.cosim.mix_from_stats)
        self.active_slot_hist: collections.Counter = collections.Counter()
        # per-iteration wall-clock records (EngineConfig(trace=)) — one
        # dict per decode iteration; the measured step times the
        # calibration plane (repro.profile) replays through Plane B
        self.trace: list[dict] = []
        # host spans for the profiler, whose boundaries also time the
        # records above
        self.spans = Spans(wall=ecfg.trace)

        # packed-stream / chunk budget (also the padding quantum)
        S = ecfg.kv_len
        self._chunk = min(ecfg.prefill_chunk or min(128, S), S)

        # pow2-bucketing (sequential baseline) is exact only when cache
        # index == token position for every self-attention cache.  The
        # packed path instead relies on length-exact prefill state for
        # every layer kind, so it never needs this distinction.
        self._bucketed = all(k in ("global", "cross") for k in cfg.layer_kinds)

        # multi-prompt packing / chunked continuation need (a) attention-only
        # stacks — SSM/recurrent state would integrate across prompt
        # boundaries — and (b) no MoE: packed prompts would compete for
        # expert capacity, breaking packed==sequential equivalence
        self._packable = (all(k in ("global", "local") for k in cfg.layer_kinds)
                          and not cfg.n_experts
                          and not cfg.cross_attn_decoder
                          and not cfg.n_encoder_layers)

        # speculative decoding wiring: acceptance accounting + (for
        # draft-model speculation) the draft params/cache attachment
        self.spec_steps = 0          # speculative steps run (== weight streams)
        self.spec_drafted = 0        # draft tokens proposed (spec_k per step/row)
        self.spec_accepted = 0       # draft tokens the verify pass accepted
        self.spec_committed = 0      # tokens actually committed (accepted
        #                              prefix + the correction token, after
        #                              budget/eos/depth caps)
        if ecfg.spec_k:
            if not self._packable:
                raise ValueError(
                    "speculative decoding needs a packable stack (attention-"
                    "only, no MoE/cross/encoder) — the verify step reuses "
                    "the segmented-prefill chunk path")
            if ecfg.spec_draft == "model":
                dcfg, dparams = draft
                if dcfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab ({dcfg.vocab_size}) != target vocab "
                        f"({cfg.vocab_size})")
                if not all(k in ("global", "local") for k in dcfg.layer_kinds):
                    raise ValueError("draft model must be attention-only")
                self.executor.set_draft(dcfg, dparams)
                self.pool.init_draft(dcfg)

        # seed-compat sampling key (fused=False host path)
        self._key = jax.random.PRNGKey(ecfg.seed)

    # -- layer delegation (stable public/test surface) -------------------------
    @property
    def params(self):
        return self.executor.params

    @property
    def cache(self):
        return self.pool.cache

    @cache.setter
    def cache(self, value):
        self.pool.cache = value

    @property
    def _state(self):
        return self.pool.state

    @_state.setter
    def _state(self, value):
        self.pool.state = value

    @property
    def slot_req(self):
        return self.pool.slot_req

    @slot_req.setter
    def slot_req(self, value):
        self.pool.slot_req = list(value)

    @property
    def _prefilling(self):
        return self.pool.prefilling

    @_prefilling.setter
    def _prefilling(self, value):
        self.pool.prefilling = dict(value)

    @property
    def _slot_anomalies(self):
        return self.pool.anomalies

    @_slot_anomalies.setter
    def _slot_anomalies(self, value):
        self.pool.anomalies = list(value)

    @property
    def host_transfers(self):
        return self.executor.host_transfers

    @host_transfers.setter
    def host_transfers(self, value):
        self.executor.host_transfers = value

    @property
    def host_bytes(self):
        return self.executor.host_bytes

    @host_bytes.setter
    def host_bytes(self, value):
        self.executor.host_bytes = value

    # compiled-program handles (compile-count regression tests)
    @property
    def _jit_step(self):
        return self.executor.jit_step

    @property
    def _jit_prefill_insert(self):
        return self.executor.jit_prefill_insert

    @property
    def _jit_packed_prefill(self):
        return self.executor.jit_packed_prefill

    @property
    def _jit_chunk_step(self):
        return self.executor.jit_chunk_step

    def _now(self) -> float:
        """Engine time (``EngineConfig.clock`` — monotonic seconds)."""
        return self.ecfg.clock()

    def _fetch(self, x) -> np.ndarray:
        with self.spans.span("executor.fetch") as sp:
            arr = self.executor.fetch(x)
            sp.set(lambda: {"bytes": arr.nbytes})
        return arr

    # -- public API -------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: Optional[int] = None,
               *, priority: int = 0) -> Request:
        """Validate and enqueue one request.

        Malformed inputs (empty / over-long prompts, non-integer dtype,
        wrong ndim, negative budget) raise ``ValueError`` here — at submit
        time, not deep inside a jitted step.  When the bounded queue
        (``EngineConfig.max_queue``) is full the request is shed: returned
        with the retriable ``REJECTED`` status instead of enqueued.
        ``priority`` is the scheduling class (larger = more urgent) an
        SLO-aware scheduler orders by; the default FIFO ignores it."""
        arr = np.asarray(prompt)
        if arr.ndim != 1:
            raise ValueError(f"prompt must be 1-D, got ndim={arr.ndim}")
        if arr.size == 0:
            raise ValueError("prompt must hold at least one token")
        if arr.dtype.kind not in "iu":
            raise ValueError(
                f"prompt must be integer token ids, got dtype={arr.dtype}")
        if arr.size + 1 >= self.ecfg.kv_len:
            raise ValueError(
                f"prompt ({arr.size}) ≥ kv_len ({self.ecfg.kv_len}): no room "
                f"for even one generated token in the KV budget")
        if max_new_tokens is not None and max_new_tokens < 0:
            raise ValueError(
                f"max_new_tokens must be >= 0, got {max_new_tokens}")
        now = self._now()
        req = Request(uid=self._uid, prompt=arr.astype(np.int32),
                      max_new_tokens=max_new_tokens, priority=int(priority),
                      t_enqueue=now)
        if self.ecfg.deadline_ms > 0:
            req.deadline = now + self.ecfg.deadline_ms / 1e3
        self._uid += 1
        if self.ecfg.max_queue > 0 and len(self.queue) >= self.ecfg.max_queue:
            req.status = REJECTED
            req.t_done = now
            self.rejected.append(req)
            return req
        self.queue.append(req)
        return req

    def step(self) -> int:
        """One engine iteration: deadline eviction + (scheduler-gated)
        admission + chunked prefill continuation + one decode step over
        the slot pool.  Returns the number of occupied slots."""
        self.spans.begin()
        with self.spans.span("engine.step", self._step_attrs):
            if self.ecfg.deadline_ms > 0:
                self._evict_expired()
            if self.ecfg.spec_k:
                return self._step_spec()
            if self.ecfg.fused:
                return self._step_fused()
            return self._step_host()

    # -- span attributes (built only while the profiler records) ---------------
    def _step_attrs(self) -> dict:
        return {"it": self.spans.it, "queue": len(self.queue),
                "occupied": self.pool.occupied(),
                "decoding": len(self.pool.decoding())}

    def _decode_attrs(self) -> dict:
        """The decoding requests, and the live KV entries over every
        occupied slot once this step has written (a prefilling slot counts
        the prompt positions written so far)."""
        pool, uids, kv = self.pool, [], 0
        for i, req in enumerate(pool.slot_req):
            if req is None:
                continue
            if i in pool.prefilling:
                kv += pool.prefilling[i][0]
                continue
            uids.append(req.uid)
            kv += len(req.prompt) + len(req.output)
        return {"uids": uids, "decoding": len(uids), "kv_live": kv}

    def _commit_attrs(self, tokens: int, f0: int) -> Callable[[], dict]:
        """Attributes of a commit loop: the tokens it committed and the
        requests it finished (``self.finished`` from index ``f0``)."""
        return lambda: {"tokens": tokens,
                        "finished": [r.uid for r in self.finished[f0:]]}

    def _trace_iteration(self, t0: float, dt: float, dispatch: str,
                         iters: int) -> None:
        """One ``EngineConfig(trace=)`` record, from the wall clock at the
        boundaries of this iteration's spans: the decode ``dispatch`` and
        the ``executor.fetch`` that followed it (dispatch is asynchronous,
        so the fetch waits on the device step and decode_s + d2h_s is the
        step's wall time)."""
        (a, b), c = self.spans.marks[dispatch], \
            self.spans.marks["executor.fetch"][1]
        self.trace.append({"prefill_s": dt, "decode_s": b - a,
                           "d2h_s": c - b, "step_s": c - t0, "iters": iters})

    # -- failure plumbing ------------------------------------------------------
    def _fail(self, req: Request, status: str, now: Optional[float] = None):
        """Move a request to a terminal failure state (never ``finished``)."""
        req.status = status
        req.t_done = now if now is not None else self._now()
        self.failed.append(req)

    def _evict_expired(self):
        """Fail every queued or in-flight request past its deadline —
        expired work is dropped before it spends another admission or
        decode step (the slot frees for a request that can still make it)."""
        with self.spans.span("engine.evict") as sp:
            f0 = len(self.failed)
            now = self._now()
            if self.queue:
                kept = collections.deque()
                for req in self.queue:
                    if now > req.deadline:
                        self._fail(req, FAILED_DEADLINE, now)
                    else:
                        kept.append(req)
                self.queue = kept
            for i, req in enumerate(self.pool.slot_req):
                if req is not None and now > req.deadline:
                    self._fail(req, FAILED_DEADLINE, now)
                    self.pool.kill(i)
            sp.set(lambda: {"uids": [r.uid for r in self.failed[f0:]]})

    # -- scheduler seams -------------------------------------------------------
    def _prefill_allowed(self) -> bool:
        """Ask the scheduler whether prefill (admission + chunk
        continuation) may preempt decode this iteration.  Only consulted
        when there is both prefill work to run and decode work to stall —
        an idle pool is never gated, so no policy can deadlock the
        drain."""
        if not (self.queue or self.pool.prefilling):
            return True
        decoding = self.pool.decoding()
        if not decoding:
            return True
        return self.scheduler.allow_prefill(decoding, self._now())

    def _pop_admissible(self) -> Optional[tuple]:
        """Pop the scheduler's next admissible queued request.  Requests
        asking for 0 tokens finish immediately; over-long prompts raise."""
        while self.queue:
            idx = self.scheduler.select(self.queue, self._now())
            if idx is None:
                return None
            req = self.queue[idx]
            del self.queue[idx]
            # a request may ask for fewer tokens than the engine default —
            # including 0 (`or` would silently swap in the default)
            budget = req.max_new_tokens if req.max_new_tokens is not None \
                else self.ecfg.max_new_tokens
            if budget <= 0:
                req.done = True
                req.status = DONE
                req.t_admit = req.t_first_token = req.t_done = self._now()
                self.finished.append(req)
                continue
            plen = len(req.prompt)
            if plen + 1 >= self.ecfg.kv_len:
                raise ValueError(f"prompt ({plen}) ≥ kv_len ({self.ecfg.kv_len})")
            return req, plen, budget
        return None

    # -- iteration loop --------------------------------------------------------
    def _admission(self, admit: Callable[[], None]) -> tuple[float, float]:
        """The scheduler-gated admission phase of an iteration: its wall
        clock start and duration, which feed ``prefill_time`` and the
        scheduler's prefill cost estimate."""
        t0 = time.perf_counter()
        calls0 = self.prefill_calls
        if self._prefill_allowed():
            admit()
        dt = time.perf_counter() - t0
        self.prefill_time += dt
        if self.prefill_calls > calls0:
            self.scheduler.observe_prefill(dt)
        return t0, dt

    def _step_fused(self) -> int:
        t0, dt = self._admission(self._admit_packed if self.ecfg.packed
                                 else self._admit_fused)
        occupied = self.pool.occupied()
        if occupied == len(self.pool.prefilling):
            # no live slot: nothing to decode (and nothing being stalled —
            # mid-prefill-only iterations just advance their chunks)
            self._stall_tokens = 0
            return occupied
        with self.spans.span("executor.fused_step", self._decode_attrs):
            self.pool.cache, self.pool.state, packed = \
                self.executor.fused_step(self.pool.cache, self.pool.state)
        arr = self._fetch(packed)                 # ONE d2h transfer
        if self.ecfg.trace:
            self._trace_iteration(t0, dt, "executor.fused_step",
                                  int(arr.shape[0]))
        self.decode_steps += arr.shape[0]
        self.max_stall_tokens = max(self.max_stall_tokens, self._stall_tokens)
        self._stall_tokens = 0
        with self.spans.span("engine.commit") as sp:
            f0 = len(self.finished)
            tokens = self._commit_fused(arr)
            sp.set(self._commit_attrs(tokens, f0))
        return self.pool.occupied()

    def _commit_fused(self, arr: np.ndarray) -> int:
        """Commit a fused step's fetched ``(K, 3, B)`` tokens; returns how
        many were committed."""
        tokens = 0
        now = self._now()
        for it in range(arr.shape[0]):            # decode_chunk iterations
            # zero-active iterations (slots all finished mid-chunk) are real
            # device work — recording them keeps Σhist == decode_steps and
            # lets the occupancy mean discount the dead tail of a chunk
            self.active_slot_hist[int((arr[it, 0] >= 0).sum())] += 1
            for i, req in enumerate(self.pool.slot_req):
                if req is None or i in self.pool.prefilling:
                    continue
                if arr[it, 2, i]:                 # non-finite logits: the
                    # device froze the slot (no token, no pos advance) and
                    # will retry the identical step; quarantine after the
                    # configured retries — only this request fails, the
                    # rest of the batch keeps decoding
                    self.pool.anomalies[i] += 1
                    if self.pool.anomalies[i] > self.ecfg.anomaly_retries:
                        self._fail(req, FAILED_ANOMALY, now)
                        self.pool.kill(i)
                    continue
                if arr[it, 0, i] < 0:
                    continue
                self.pool.anomalies[i] = 0        # clean step: retry budget
                #                                   resets (transient fault)
                tok = int(arr[it, 0, i])
                if not req.output:
                    req.t_first_token = now
                req.output.append(tok)
                tokens += 1
                if arr[it, 1, i]:
                    req.done = True
                    req.status = DONE
                    req.t_done = now
                    self.finished.append(req)
                    self.pool.release(i)     # slot freed → continuous batching
        return tokens

    def _step_spec(self) -> int:
        """One speculative iteration: admission (same packed path), then a
        single draft+verify step over the slot pool.  One device→host
        transfer — a packed ``(spec_k+1, 4, B)`` of (token | -1, done,
        anomaly, n_accepted) — commits up to ``spec_k + 1`` tokens per
        slot per weight stream."""
        t0, dt = self._admission(self._admit_packed)
        occupied = self.pool.occupied()
        if occupied == len(self.pool.prefilling):
            self._stall_tokens = 0
            return occupied
        with self.spans.span("executor.spec_step", self._decode_attrs):
            self.pool.cache, dcache, self.pool.state, packed = \
                self.executor.spec_step(self.pool.cache, self.pool.state,
                                        self.pool.draft_cache)
        if self.pool.draft_cache is not None:
            self.pool.draft_cache = dcache
        arr = self._fetch(packed)                 # ONE d2h transfer
        if self.ecfg.trace:
            self._trace_iteration(t0, dt, "executor.spec_step", 1)
        self.decode_steps += 1                    # one target weight stream
        self.spec_steps += 1
        self.max_stall_tokens = max(self.max_stall_tokens, self._stall_tokens)
        self._stall_tokens = 0
        with self.spans.span("engine.commit") as sp:
            f0, c0 = len(self.finished), self.spec_committed
            self._commit_spec(arr)
            sp.set(self._commit_attrs(self.spec_committed - c0, f0))
        return self.pool.occupied()

    def _commit_spec(self, arr: np.ndarray) -> None:
        """Commit a speculative step's fetched ``(spec_k+1, 4, B)``."""
        now = self._now()
        K = self.ecfg.spec_k
        # occupancy accounting mirrors the fused step: slots that committed
        # a token this iteration (frozen/anomalous slots are not active)
        self.active_slot_hist[int((arr[0, 0] >= 0).sum())] += 1
        for i, req in enumerate(self.pool.slot_req):
            if req is None or i in self.pool.prefilling:
                continue
            if arr[0, 2, i]:                      # non-finite verify logits:
                # the device restored all spec_k+1 columns and left the
                # state untouched — identical retry semantics to the fused
                # step's frozen slots
                self.pool.anomalies[i] += 1
                if self.pool.anomalies[i] > self.ecfg.anomaly_retries:
                    self._fail(req, FAILED_ANOMALY, now)
                    self.pool.kill(i)
                continue
            if arr[0, 0, i] < 0:
                continue
            self.pool.anomalies[i] = 0
            self.spec_drafted += K
            self.spec_accepted += int(arr[0, 3, i])
            for it in range(arr.shape[0]):        # committed prefix, in order
                if arr[it, 0, i] < 0:
                    break
                tok = int(arr[it, 0, i])
                if not req.output:
                    req.t_first_token = now
                req.output.append(tok)
                self.spec_committed += 1
                if arr[it, 1, i]:
                    req.done = True
                    req.status = DONE
                    req.t_done = now
                    self.finished.append(req)
                    self.pool.release(i)
                    break

    def _step_host(self) -> int:
        """Original per-token host round-trip step (measurement baseline)."""
        t0, dt = self._admission(self._admit_host)
        live = [i for i, r in enumerate(self.pool.slot_req) if r is not None]
        if not live:
            return 0
        host = self.pool.ensure_host()
        self.active_slot_hist[len(live)] += 1
        tokens = jnp.asarray(host["last_token"])
        pos = jnp.asarray(host["slot_pos"])
        with self.spans.span("executor.decode", self._decode_attrs):
            logits, self.pool.cache = self.executor.decode(self.pool.cache,
                                                           tokens, pos)
        self.decode_steps += 1
        self.max_stall_tokens = max(self.max_stall_tokens, self._stall_tokens)
        self._stall_tokens = 0
        # the host-path d2h is the sampling round trip that waits on the
        # decode dispatch — the same split as the fused path
        nxt = self._sample_host(logits)
        if self.ecfg.trace:
            self._trace_iteration(t0, dt, "executor.decode", 1)
        with self.spans.span("engine.commit") as sp:
            f0 = len(self.finished)
            self._commit_host(live, nxt, host)
            sp.set(self._commit_attrs(len(live), f0))
        return self.pool.occupied()

    def _sample_host(self, logits) -> np.ndarray:
        with self.spans.span("executor.fetch"):
            nxt, self._key = self.executor.sample_host(logits, self._key)
        return nxt

    def _commit_host(self, live: list, nxt: np.ndarray, host: dict) -> None:
        now = self._now()
        for i in live:
            req = self.pool.slot_req[i]
            tok = int(nxt[i])
            if not req.output:
                req.t_first_token = now
            req.output.append(tok)
            host["last_token"][i] = tok
            host["slot_pos"][i] += 1
            host["slot_budget"][i] -= 1
            hit_eos = (self.ecfg.eos_token >= 0 and tok == self.ecfg.eos_token)
            if host["slot_budget"][i] <= 0 or hit_eos or \
                    host["slot_pos"][i] >= self.ecfg.kv_len:
                req.done = True
                req.status = DONE
                req.t_done = now
                self.finished.append(req)
                self.pool.release(i)     # slot freed → continuous batching

    def run_until_drained(self, max_iters: int = 10_000) -> list[Request]:
        """Step until every request reaches a terminal state.

        Exhausting ``max_iters`` is an explicit failure, never a silent
        partial drain: every request still queued or in a slot is marked
        ``FAILED_MAX_ITERS`` (terminal, listed in ``self.failed``) and
        ``EngineStallError`` is raised."""
        it = 0
        while (self.queue or any(r is not None for r in self.pool.slot_req)):
            self.step()
            it += 1
            if it > max_iters:
                now = self._now()
                stranded = list(self.queue) + [r for r in self.pool.slot_req
                                               if r is not None]
                for req in self.queue:
                    self._fail(req, FAILED_MAX_ITERS, now)
                self.queue.clear()
                for i, req in enumerate(self.pool.slot_req):
                    if req is not None:
                        self._fail(req, FAILED_MAX_ITERS, now)
                        self.pool.kill(i)
                raise EngineStallError(
                    f"engine did not drain in {max_iters} iterations; "
                    f"{len(stranded)} request(s) marked "
                    f"{FAILED_MAX_ITERS}")
        return self.finished

    # -- admission: packed ragged prefill + chunked continuation ---------------
    def _pad_len(self, plen: int) -> int:
        """Smallest chunk multiple >= plen (capped at kv_len) — the static
        shape set for per-request prefill."""
        C = self._chunk
        return min(-(-max(plen, 1) // C) * C, self.ecfg.kv_len)

    def _admit_packed(self):
        if self.pool.prefilling:
            self._continue_chunks()
        free = self.pool.free_slots()
        if not free or not self.queue:
            return
        if not self._packable:
            self._admit_padded(free)
            return
        with self.spans.span("engine.admit") as sp:
            self._admit_stream(free, sp)

    def _admit_stream(self, free: list, sp) -> None:
        """Pack the scheduler's picks into one ``(1, C)`` stream, prefill
        it in one call and commit each complete prompt's first token."""
        B, C = self.ecfg.max_batch, self._chunk
        segs = []                      # (req, slot, off, take, final, budget)
        used = 0
        try:
            while free and used < C:
                nxt = self._pop_admissible()
                if nxt is None:
                    break
                req, plen, budget = nxt
                if plen > C - used and used > 0:
                    # whole prompt doesn't fit the remaining stream: don't
                    # fragment it — a tail-sized first chunk would buy
                    # little and cost an extra continuation call; re-queue
                    # at the head (FIFO preserved) and admit next iteration
                    self.queue.appendleft(req)
                    break
                take = min(plen, C - used)
                slot = free.pop(0)
                segs.append((req, slot, used, take, take == plen, budget))
                used += take
        except ValueError:
            # an over-long prompt mid-burst must not strand the requests
            # already popped into this stream — put them back (FIFO) first
            for req, *_ in reversed(segs):
                self.queue.appendleft(req)
            raise
        if not segs:
            return

        def uids():
            return [s[0].uid for s in segs]

        sp.set(lambda: {"uids": uids(), "prompt_tokens": used, "stream": C})

        toks = np.zeros((1, C), np.int32)
        seg = np.full((1, C), -1, np.int32)
        pos = np.zeros((1, C), np.int32)
        gather = np.zeros((B,), np.int32)
        off_v = np.zeros((B,), np.int32)
        len_v = np.zeros((B,), np.int32)
        fin_v = np.zeros((B,), bool)
        bud_v = np.ones((B,), np.int32)
        act_v = np.zeros((B,), bool)
        t_adm = self._now()               # left the queue: scheduling delay
        #                                   ends here, service time begins
        for req, slot, off, take, final, budget in segs:
            req.t_admit = t_adm
            toks[0, off:off + take] = req.prompt[:take]
            seg[0, off:off + take] = slot
            pos[0, off:off + take] = np.arange(take)
            gather[slot] = off + take - 1
            off_v[slot], len_v[slot] = off, take
            fin_v[slot], bud_v[slot], act_v[slot] = final, budget, True

        args = [jnp.asarray(a) for a in (toks, pos, seg, gather, off_v,
                                         len_v, fin_v, bud_v, act_v)]
        with self.spans.span("executor.packed_prefill",
                             lambda: {"uids": uids()}):
            self.pool.cache, self.pool.state, first = \
                self.executor.packed_prefill(self.pool.cache,
                                             self.pool.state, *args)
        arr = self._fetch(first)                  # one d2h per admission burst
        self.prefill_tokens += used
        self.prefill_calls += 1
        self._stall_tokens += used
        with self.spans.span("engine.commit") as cm:
            f0 = len(self.finished)
            tokens = self._commit_stream(segs, arr)
            cm.set(self._commit_attrs(tokens, f0))

    def _commit_stream(self, segs: list, arr: np.ndarray) -> int:
        """Commit a packed prefill's first tokens; returns how many."""
        tokens = 0
        now = self._now()
        for req, slot, off, take, final, budget in segs:
            if final:
                tok = int(arr[slot])
                req.output = [tok]
                req.t_first_token = now
                tokens += 1
                if budget == 1:     # the prefill sample was the whole budget
                    req.done = True
                    req.status = DONE
                    req.t_done = now
                    self.finished.append(req)
                    continue
                req.status = ACTIVE
                self.pool.slot_req[slot] = req
                self._draft_ingest(req, slot)
            else:                   # long prompt: first chunk only
                req.status = ACTIVE
                self.pool.slot_req[slot] = req
                self.pool.prefilling[slot] = (take, budget)
        return tokens

    def _continue_chunks(self):
        """Advance every mid-prefill slot by one <= C-token chunk (one
        batched jitted call), activating rows whose prompt completed."""
        with self.spans.span("engine.chunks") as sp:
            self._chunk_rows(sp)

    def _chunk_rows(self, sp) -> None:
        B, C = self.ecfg.max_batch, self._chunk
        toks = np.zeros((B, C), np.int32)
        pos = np.full((B, C), -1, np.int32)
        take_idx = np.zeros((B,), np.int32)
        fin_v = np.zeros((B,), bool)
        bud_v = np.ones((B,), np.int32)
        plan = []                                  # (slot, start, c, budget)
        for slot, (start, budget) in self.pool.prefilling.items():
            req = self.pool.slot_req[slot]
            plen = len(req.prompt)
            c = min(plen - start, C)
            toks[slot, :c] = req.prompt[start:start + c]
            pos[slot, :c] = start + np.arange(c)
            take_idx[slot] = c - 1
            fin_v[slot] = start + c == plen
            bud_v[slot] = budget
            plan.append((slot, start, c, budget))
        total = sum(c for _, _, c, _ in plan)

        def uids():
            return [self.pool.slot_req[slot].uid for slot, *_ in plan]

        # the chunk step computes every row of the (B, C) block: prompt
        # tokens over positions is the share of it that is useful work
        sp.set(lambda: {"uids": uids(), "rows": len(plan),
                        "prompt_tokens": total, "positions": B * C})

        args = [jnp.asarray(a) for a in (toks, pos, take_idx, fin_v, bud_v)]
        with self.spans.span("executor.chunk_step", lambda: {"uids": uids()}):
            self.pool.cache, self.pool.state, first = \
                self.executor.chunk_step(self.pool.cache, self.pool.state,
                                         *args)
        arr = self._fetch(first)
        self.prefill_tokens += total
        self.prefill_calls += 1
        self._stall_tokens += C                    # one batched chunk call
        with self.spans.span("engine.commit") as cm:
            f0 = len(self.finished)
            tokens = self._commit_chunks(plan, arr)
            cm.set(self._commit_attrs(tokens, f0))

    def _commit_chunks(self, plan: list, arr: np.ndarray) -> int:
        """Commit the first token of each prompt a chunk step completed;
        returns how many."""
        tokens = 0
        now = self._now()
        for slot, start, c, budget in plan:
            req = self.pool.slot_req[slot]
            if start + c == len(req.prompt):       # prompt complete
                del self.pool.prefilling[slot]
                tok = int(arr[slot])
                req.output = [tok]
                req.t_first_token = now
                tokens += 1
                if budget == 1:
                    req.done = True
                    req.status = DONE
                    req.t_done = now
                    self.finished.append(req)
                    self.pool.release(slot)
                else:
                    self._draft_ingest(req, slot)
            else:
                self.pool.prefilling[slot] = (start + c, budget)
        return tokens

    def _draft_ingest(self, req, slot: int) -> None:
        """Draft-model speculation: mirror a completed prompt into the
        draft-model KV pool (one padded batch-1 draft prefill + insert) so
        the draft decodes with the same context as the target.  No-op for
        self-speculation (shared cache)."""
        if self.pool.draft_cache is None:
            return
        plen = len(req.prompt)
        pad = self._pad_len(plen)
        toks = np.zeros((1, pad), np.int32)
        toks[0, :plen] = req.prompt
        self.pool.draft_cache = self.executor.draft_prefill(
            self.pool.draft_cache, jnp.asarray(toks), jnp.int32(slot),
            jnp.int32(plen))

    def _admit_one(self, req, slot: int, plen: int, budget: int, pad: int):
        """One right-padded batch-1 prefill+insert call and its bookkeeping
        (shared by the chunk-padded and pow2-bucketed sequential paths)."""
        spans = self.spans
        with spans.span("engine.admit",
                        lambda: {"uids": [req.uid], "prompt_tokens": plen}):
            req.t_admit = self._now()
            toks = np.zeros((1, pad), np.int32)
            toks[0, :plen] = req.prompt
            args = (jnp.asarray(toks), jnp.int32(slot), jnp.int32(plen),
                    jnp.int32(budget))
            with spans.span("executor.prefill_insert",
                            lambda: {"uids": [req.uid]}):
                self.pool.cache, self.pool.state, first = \
                    self.executor.prefill_insert(self.pool.cache,
                                                 self.pool.state, *args)
            tok = int(self._fetch(first))
            self.prefill_tokens += plen
            self.prefill_calls += 1
            self._stall_tokens += pad
            with spans.span("engine.commit") as cm:
                f0 = len(self.finished)
                req.output = [tok]
                req.t_first_token = self._now()
                if budget == 1:     # the prefill sample was the whole budget
                    req.done = True
                    req.status = DONE
                    req.t_done = req.t_first_token
                    self.finished.append(req)
                else:
                    req.status = ACTIVE
                    self.pool.slot_req[slot] = req
                cm.set(self._commit_attrs(1, f0))

    def _admit_padded(self, free):
        """Per-request admission for non-packable architectures: prompts
        right-padded to a chunk multiple with length-exact prefill state —
        static shapes, no compile-per-distinct-length."""
        while free and self.queue:
            nxt = self._pop_admissible()
            if nxt is None:
                break
            req, plen, budget = nxt
            self._admit_one(req, free.pop(0), plen, budget,
                            self._pad_len(plen))

    # -- admission: sequential baselines ---------------------------------------
    def _next_request(self, slot: int) -> Optional[tuple]:
        """Pop the next admissible queued request and its padded prompt, or
        None (sequential baseline paths)."""
        if self.pool.slot_req[slot] is not None:
            return None
        nxt = self._pop_admissible()
        if nxt is None:
            return None
        req, plen, budget = nxt
        pad = _bucket_len(plen, self.ecfg.kv_len) if self._bucketed else plen
        toks = np.zeros((1, pad), np.int32)
        toks[0, :plen] = req.prompt
        return req, toks, plen, budget

    def _admit_fused(self):
        for slot in range(self.ecfg.max_batch):
            nxt = self._next_request(slot)
            if nxt is None:
                continue
            req, toks, plen, budget = nxt
            self._admit_one(req, slot, plen, budget, toks.shape[1])

    def _admit_host(self):
        host = self.pool.ensure_host()
        for slot in range(self.ecfg.max_batch):
            nxt = self._next_request(slot)
            if nxt is None:
                continue
            req, toks, plen, budget = nxt
            with self.spans.span("engine.admit", lambda: {
                    "uids": [req.uid], "prompt_tokens": plen}):
                self._admit_host_one(req, slot, toks, plen, budget, host)

    def _admit_host_one(self, req, slot: int, toks: np.ndarray, plen: int,
                        budget: int, host: dict) -> None:
        req.t_admit = self._now()
        with self.spans.span("executor.prefill", lambda: {"uids": [req.uid]}):
            logits, pcache = self.executor.prefill(jnp.asarray(toks),
                                                   jnp.int32(plen))
            self.pool.cache = self.executor.insert(
                self.pool.cache, pcache, jnp.int32(slot), jnp.int32(plen))
        first = self._sample_host(logits)
        self.prefill_tokens += plen
        self.prefill_calls += 1
        self._stall_tokens += toks.shape[1]
        with self.spans.span("engine.commit") as cm:
            f0 = len(self.finished)
            req.output = [int(first[0])]
            req.t_first_token = self._now()
            if budget == 1:         # the prefill sample was the whole budget
                req.done = True
                req.status = DONE
                req.t_done = req.t_first_token
                self.finished.append(req)
            else:
                req.status = ACTIVE
                self.pool.slot_req[slot] = req
                host["slot_pos"][slot] = plen
                host["slot_budget"][slot] = budget - 1
                host["last_token"][slot] = int(first[0])
            cm.set(self._commit_attrs(1, f0))

    # -- crash safety ---------------------------------------------------------
    @classmethod
    def restore(cls, cfg: ModelConfig, params, ckpt_dir: str, *,
                ecfg: Optional[EngineConfig] = None, mesh=None,
                scheduler: Optional[Scheduler] = None,
                replay: bool = True, draft: Optional[tuple] = None
                ) -> "ServingEngine":
        """Revive an engine from its newest intact snapshot in
        ``ckpt_dir`` (written by ``repro.serving.checkpoint``), resuming
        mid-decode bit-identically and replaying journal-tail requests
        admitted after the snapshot.  See
        :func:`repro.serving.checkpoint.restore_engine`."""
        from repro.serving.checkpoint import restore_engine
        return restore_engine(cfg, params, ckpt_dir, ecfg=ecfg, mesh=mesh,
                              scheduler=scheduler, replay=replay,
                              draft=draft)

    # -- stats ---------------------------------------------------------------
    def _failure_stats(self) -> dict:
        by_status: collections.Counter = collections.Counter(
            r.status for r in self.failed)
        return {
            "failed": len(self.failed),
            "rejected": len(self.rejected),
            "failed_deadline": by_status.get(FAILED_DEADLINE, 0),
            "failed_anomaly": by_status.get(FAILED_ANOMALY, 0),
            "failed_max_iters": by_status.get(FAILED_MAX_ITERS, 0),
            # crash-safety counters (repro.serving.checkpoint): snapshots
            # committed, revivals of this engine state, journal-tail
            # requests resubmitted during restore
            "checkpoints_written": self.checkpoints_written,
            "restores": self.restores,
            "replayed_requests": self.replayed_requests,
        }

    def stats(self) -> dict:
        # per decode / chunk step: layers whose K/V rows commit in place
        # into the stacked pool, and layers that write back a whole state
        commit = {"kv_rows_inplace_layers": self.executor.kv_rows_inplace_layers,
                  "kv_writeback_layers": self.executor.kv_writeback_layers}
        done = self.finished
        if not done:
            return {"finished": 0, **commit, **self._failure_stats()}
        lat = [r.t_done - r.t_enqueue for r in done]
        ttft = [r.t_first_token - r.t_enqueue for r in done]
        # per-token cadence after the first token (needs >= 2 tokens);
        # queue wait is pure scheduling delay (enqueue → slot assignment),
        # separable from prefill/decode service time.  t_admit may be
        # unset (0.0) on requests restored from pre-layering snapshots.
        tpot = [(r.t_done - r.t_first_token) / (len(r.output) - 1)
                for r in done if len(r.output) > 1]
        qwait = [r.t_admit - r.t_enqueue for r in done if r.t_admit > 0.0]
        lat_p = _percentiles(lat)
        ttft_p = _percentiles(ttft)
        tpot_p = _percentiles(tpot)
        qwait_p = _percentiles(qwait)
        toks = sum(len(r.output) for r in done)
        span = max(r.t_done for r in done) - min(r.t_enqueue for r in done)
        # speculative-decoding acceptance accounting — keys present only
        # when spec_k > 0, so the dormant engine's stats() stay
        # bit-identical to the non-speculative engine's
        spec: dict = {}
        if self.ecfg.spec_k:
            spec = {
                "spec_k": self.ecfg.spec_k,
                "spec_draft": self.ecfg.spec_draft,
                "spec_draft_bits": self.ecfg.spec_draft_bits,
                "spec_steps": self.spec_steps,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "spec_committed": self.spec_committed,
                # per-draft acceptance probability (the Plane-B traffic
                # model's alpha) and tokens committed per slot per target
                # weight stream (the amortisation the fabric sees;
                # drafted / spec_k == participating row-steps)
                "spec_acceptance": (self.spec_accepted / self.spec_drafted
                                    if self.spec_drafted else None),
                "spec_tokens_per_step": (
                    self.spec_committed * self.ecfg.spec_k / self.spec_drafted
                    if self.spec_drafted else None),
            }
        # measured per-iteration wall clock (EngineConfig(trace=)) — keys
        # present only when tracing, mirroring the spec_k dormancy
        # contract; empty sample classes report None, never a fake 0.0
        trace: dict = {}
        if self.ecfg.trace:
            steps = [t["decode_s"] + t["d2h_s"] for t in self.trace]
            step_p = _percentiles(steps)
            trace = {
                "trace_iterations": len(self.trace),
                "trace_prefill_s": float(sum(t["prefill_s"]
                                             for t in self.trace)),
                "trace_decode_s": float(sum(t["decode_s"]
                                            for t in self.trace)),
                "trace_d2h_s": float(sum(t["d2h_s"] for t in self.trace)),
                # wall time of one decode iteration — dispatch plus the
                # d2h fetch that waits on it: the measured analogue of
                # the simulator's decode_step_s
                "trace_decode_step_s": (float(np.mean(steps))
                                        if steps else None),
                "trace_decode_step_p50_s": step_p[0],
                "trace_decode_step_p95_s": step_p[1],
            }
        return {
            "finished": len(done),
            "tokens": toks,
            "tokens_per_s": toks / max(span, 1e-9),
            "mean_latency_s": float(np.mean(lat)),
            "mean_ttft_s": float(np.mean(ttft)),
            # empty sample classes report None (absent), never a fake 0.0:
            # every finished request with gen_len <= 1 has no TPOT sample,
            # and pre-layering snapshots may carry no t_admit stamps
            "mean_tpot_s": float(np.mean(tpot)) if tpot else None,
            "mean_queue_wait_s": float(np.mean(qwait)) if qwait else None,
            "latency_p50_s": lat_p[0],
            "latency_p95_s": lat_p[1],
            "latency_p99_s": lat_p[2],
            "ttft_p50_s": ttft_p[0],
            "ttft_p95_s": ttft_p[1],
            "ttft_p99_s": ttft_p[2],
            "tpot_p50_s": tpot_p[0],
            "tpot_p95_s": tpot_p[1],
            "tpot_p99_s": tpot_p[2],
            "queue_wait_p50_s": qwait_p[0],
            "queue_wait_p95_s": qwait_p[1],
            "queue_wait_p99_s": qwait_p[2],
            "decode_steps": self.decode_steps,
            "host_transfers": self.host_transfers,
            "host_bytes": self.host_bytes,
            "host_bytes_per_token": self.host_bytes / max(toks, 1),
            "prefill_tokens": self.prefill_tokens,
            "prefill_calls": self.prefill_calls,
            "prefill_time_s": self.prefill_time,
            "prefill_tokens_per_s": self.prefill_tokens / max(self.prefill_time, 1e-9),
            "max_stall_tokens": self.max_stall_tokens,
            # per-request episode shape + schedule, consumed by the Plane-B
            # co-simulation bridge (repro.core.cosim.mix_from_stats)
            "prompt_lens": [len(r.prompt) for r in done],
            "gen_lens": [len(r.output) for r in done],
            "prefill_chunk": self._chunk,
            "max_batch": self.ecfg.max_batch,
            # measured serving precision (16 = native fp16-class), consumed
            # by the Plane-B bridge so quantisation propagates into the
            # traffic model (repro.core.cosim.mix_from_stats)
            "weight_bits": self.ecfg.weight_bits or 16,
            "kv_bits": self.ecfg.kv_bits or 16,
            # {n_active_slots: decode iterations at that occupancy} — the
            # measured continuous-batching utilisation of the slot pool
            "active_slots_hist": dict(sorted(self.active_slot_hist.items())),
            **commit,
            **spec,
            **trace,
            **self._failure_stats(),
        }
