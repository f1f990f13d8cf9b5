"""Serving engine: continuous batching, slot reuse, decode==teacher-forced
consistency, stats."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config, reduce_config
from repro.models import transformer as T
from repro.serving.engine import EngineConfig, ServingEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    defaults = dict(max_batch=3, kv_len=48, max_new_tokens=6, impl="ref")
    defaults.update(kw)
    return ServingEngine(cfg, params, EngineConfig(**defaults))


def test_engine_drains_all_requests(small_model):
    cfg, params = small_model
    eng = _engine(cfg, params)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(0, cfg.vocab_size, size=8))
            for _ in range(7)]
    done = eng.run_until_drained()
    assert len(done) == 7
    assert all(r.done and len(r.output) == 6 for r in reqs)


def test_continuous_batching_reuses_slots(small_model):
    """More requests than slots: the engine must cycle slots (finished →
    freed → re-admitted) rather than waiting for a full drain."""
    cfg, params = small_model
    eng = _engine(cfg, params, max_batch=2)
    rng = np.random.default_rng(1)
    for _ in range(5):
        eng.submit(rng.integers(0, cfg.vocab_size, size=4))
    live_trace = []
    while eng.queue or any(r is not None for r in eng.slot_req):
        live_trace.append(eng.step())
    assert len(eng.finished) == 5
    assert max(live_trace) <= 2                 # never exceeds the pool
    assert sum(1 for x in live_trace if x == 2) >= 2  # pool actually shared


def test_greedy_decode_matches_teacher_forcing(small_model):
    """Engine greedy outputs == argmax chain from repeated full forwards."""
    cfg, params = small_model
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    eng = _engine(cfg, params, max_batch=1, max_new_tokens=5)
    eng.submit(prompt)
    eng.run_until_drained()
    got = eng.finished[0].output

    toks = list(prompt)
    want = []
    for _ in range(5):
        logits, _ = T.prefill(params, cfg,
                              {"tokens": jnp.asarray([toks], jnp.int32)},
                              kv_cap=48, compute_dtype=jnp.bfloat16)
        nxt = int(jnp.argmax(logits[0]))
        want.append(nxt)
        toks.append(nxt)
    assert got == want, (got, want)


def test_prompt_too_long_rejected(small_model):
    """Over-long prompts are rejected at submit time (clear ValueError),
    not deep inside a jitted step."""
    cfg, params = small_model
    eng = _engine(cfg, params, kv_len=16)
    with pytest.raises(ValueError, match="kv_len"):
        eng.submit(np.arange(20) % cfg.vocab_size)
    assert not eng.queue                     # nothing enqueued


def test_stats(small_model):
    cfg, params = small_model
    eng = _engine(cfg, params)
    eng.submit(np.asarray([1, 2, 3]))
    eng.run_until_drained()
    s = eng.stats()
    assert s["finished"] == 1
    assert s["tokens"] == 6
    assert s["tokens_per_s"] > 0
    assert s["mean_ttft_s"] <= s["mean_latency_s"]


def test_temperature_sampling_varies(small_model):
    cfg, params = small_model
    outs = set()
    for seed in range(3):
        eng = _engine(cfg, params, temperature=5.0, seed=seed, max_batch=1)
        eng.submit(np.asarray([1, 2, 3]))
        eng.run_until_drained()
        outs.add(tuple(eng.finished[0].output))
    assert len(outs) > 1


def _outputs_by_uid(eng):
    return [r.output for r in sorted(eng.finished, key=lambda r: r.uid)]


def _drain_workload(cfg, params, **kw):
    eng = _engine(cfg, params, **kw)
    rng = np.random.default_rng(7)
    for i in range(6):
        eng.submit(rng.integers(0, cfg.vocab_size, size=3 + 2 * i))
    eng.run_until_drained()
    return eng


def test_fused_step_matches_host_path(small_model):
    """The fused on-device step must reproduce the seed engine's outputs
    exactly (greedy, fixed seed, slot churn across 6 requests / 2 slots)."""
    cfg, params = small_model
    host = _drain_workload(cfg, params, max_batch=2, fused=False)
    fused = _drain_workload(cfg, params, max_batch=2, fused=True)
    assert _outputs_by_uid(host) == _outputs_by_uid(fused)


def test_flash_engine_matches_ref_engine(small_model):
    """impl='flash' (Pallas decode kernel, interpret on CPU) end-to-end
    against impl='ref' through the same fused engine."""
    cfg, params = small_model
    ref = _drain_workload(cfg, params, max_batch=2)
    fl = _drain_workload(cfg, params, max_batch=2, impl="flash")
    assert _outputs_by_uid(ref) == _outputs_by_uid(fl)


def test_decode_chunk_matches_unchunked(small_model):
    """decode_chunk>1 (multi-step scheduling: one lax.scan of K decode
    iterations per host sync) must emit token-for-token identical outputs,
    including requests that finish mid-chunk."""
    cfg, params = small_model
    one = _drain_workload(cfg, params, max_batch=2, max_new_tokens=5)
    chk = _drain_workload(cfg, params, max_batch=2, max_new_tokens=5,
                          decode_chunk=4)
    assert _outputs_by_uid(one) == _outputs_by_uid(chk)


def test_single_host_transfer_per_decode_iteration(small_model):
    """Steady-state decode makes exactly one device→host transfer per
    iteration (the packed (2,B) token/done array); everything else is
    fenced off by a d2h transfer guard."""
    cfg, params = small_model
    eng = _engine(cfg, params, max_batch=2, max_new_tokens=8)
    eng.submit(np.asarray([1, 2, 3, 4]))
    eng.submit(np.asarray([5, 6, 7]))
    eng.step()                       # admissions + first decode
    base = eng.host_transfers
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            eng.step()
    assert eng.host_transfers - base == 3
    assert eng.host_bytes > 0


def test_no_recompilation_across_drain(small_model):
    """Sequential path: one compiled fused step for the whole drain;
    prefill compiles at most once per prompt-length bucket.  (The packed
    default compiles once total — tests/test_packed_prefill.py.)"""
    cfg, params = small_model
    eng = _engine(cfg, params, max_batch=3, max_new_tokens=4, packed=False)
    rng = np.random.default_rng(3)
    for plen in (3, 5, 8, 10, 12, 4):          # buckets: 8, 16
        eng.submit(rng.integers(0, cfg.vocab_size, size=plen))
    eng.run_until_drained()
    assert eng._jit_step._cache_size() == 1
    assert eng._jit_prefill_insert._cache_size() <= 2


def test_max_new_tokens_zero_and_one(small_model):
    """A request's own budget wins over the engine default — including 0
    (the seed's ``or`` swapped in the default) and 1 (off-by-one)."""
    cfg, params = small_model
    eng = _engine(cfg, params)                 # engine default: 6
    r0 = eng.submit(np.asarray([1, 2, 3]), max_new_tokens=0)
    r1 = eng.submit(np.asarray([1, 2, 3]), max_new_tokens=1)
    r2 = eng.submit(np.asarray([1, 2, 3]), max_new_tokens=3)
    eng.run_until_drained()
    assert r0.done and r0.output == []
    assert r1.done and len(r1.output) == 1
    assert r2.done and len(r2.output) == 3


def test_moe_arch_serves(small_model):
    cfg = reduce_config(get_config("qwen3-moe-30b-a3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(1),
                           param_dtype=jnp.float32)
    eng = _engine(cfg, params, max_batch=2, max_new_tokens=4)
    eng.submit(np.asarray([1, 2, 3, 4]))
    eng.submit(np.asarray([4, 3, 2, 1]))
    done = eng.run_until_drained()
    assert len(done) == 2
    assert all(len(r.output) == 4 for r in done)


# ---------------------------------------------------------------------------
# resilience: validation, shedding, deadlines, anomaly quarantine, stall
# ---------------------------------------------------------------------------

def test_submit_validation(small_model):
    """Malformed submissions fail loudly at submit(), never inside a
    jitted step: wrong rank, empty, float dtype, negative budget."""
    cfg, params = small_model
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.asarray([[1, 2], [3, 4]]))
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.asarray([], np.int32))
    with pytest.raises(ValueError, match="integer"):
        eng.submit(np.asarray([1.0, 2.0]))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.asarray([1, 2, 3]), max_new_tokens=-1)
    assert not eng.queue


def test_bounded_queue_sheds_not_strands(small_model):
    """With max_queue set, overload is shed as retriable REJECTED at
    submit; admitted requests still finish — every request terminal."""
    from repro.serving.engine import DONE, REJECTED
    cfg, params = small_model
    eng = _engine(cfg, params, max_batch=1, max_new_tokens=2, max_queue=2)
    reqs = [eng.submit(np.asarray([1, 2, 3])) for _ in range(5)]
    statuses = [r.status for r in reqs]
    assert statuses.count(REJECTED) == 3
    eng.run_until_drained()
    assert [r.status for r in reqs].count(DONE) == 2
    assert all(r.terminal for r in reqs)
    assert all(r.output == [] for r in reqs if r.status == REJECTED)
    s = eng.stats()
    assert s["rejected"] == 3 and s["finished"] == 2


class FakeClock:
    """Injectable EngineConfig(clock=): deterministic, no sleeping."""

    def __init__(self, t: float = 100.0, auto_advance: float = 0.0):
        self.t, self.auto = t, auto_advance

    def __call__(self) -> float:
        self.t += self.auto
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def test_deadline_expires_queued_request(small_model):
    """A request whose deadline passes while still queued is evicted as
    FAILED_DEADLINE on the next step — it never occupies a slot.  Driven
    by an injected fake clock: no wall-clock sleeps."""
    from repro.serving.engine import FAILED_DEADLINE
    cfg, params = small_model
    clk = FakeClock()
    eng = _engine(cfg, params, max_batch=1, deadline_ms=20, clock=clk)
    r = eng.submit(np.asarray([1, 2, 3]))
    clk.advance(0.05)
    eng.step()
    assert r.status == FAILED_DEADLINE and r.terminal
    assert not eng.queue and all(x is None for x in eng.slot_req)
    assert eng.stats()["failed_deadline"] == 1


def test_deadline_evicts_mid_decode(small_model):
    """An in-flight request past its deadline is evicted mid-decode with
    whatever tokens it produced — the drain terminates.  The fake clock
    self-advances per reading, so expiry is deterministic in iterations
    rather than host speed."""
    from repro.serving.engine import FAILED_DEADLINE
    cfg, params = small_model
    clk = FakeClock(auto_advance=0.005)
    eng = _engine(cfg, params, max_batch=1, deadline_ms=30,
                  max_new_tokens=200_000, clock=clk)
    r = eng.submit(np.asarray([1, 2, 3, 4]))
    eng.run_until_drained()
    assert r.status == FAILED_DEADLINE and r.terminal
    assert len(r.output) < 200_000


def test_clock_injection_defaults_to_monotonic(small_model):
    """Default EngineConfig wires time.monotonic; an injected clock is
    the one the engine actually reads."""
    import time
    cfg, params = small_model
    assert _engine(cfg, params).ecfg.clock is time.monotonic
    clk = FakeClock(t=42.0)
    eng = _engine(cfg, params, clock=clk)
    r = eng.submit(np.asarray([1, 2, 3]))
    assert r.t_enqueue == clk.t


def test_run_until_drained_marks_stranded(small_model):
    """max_iters exhaustion is an explicit failure: EngineStallError, and
    every stranded request lands in FAILED_MAX_ITERS (regression for the
    silent-partial-drain bug)."""
    from repro.serving.engine import FAILED_MAX_ITERS, EngineStallError
    cfg, params = small_model
    eng = _engine(cfg, params, max_batch=1, max_new_tokens=50)
    reqs = [eng.submit(np.asarray([1, 2, 3])) for _ in range(4)]
    with pytest.raises(EngineStallError, match="did not drain"):
        eng.run_until_drained(max_iters=2)
    assert all(r.terminal for r in reqs)
    assert any(r.status == FAILED_MAX_ITERS for r in reqs)
    assert not eng.queue and all(x is None for x in eng.slot_req)
    assert eng.stats()["failed_max_iters"] >= 1


def _poison_slot(cache, slot):
    """NaN one slot's KV pages (batch axis 1 of every stacked leaf)."""
    return jax.tree_util.tree_map(
        lambda x: x.at[:, slot].set(jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, cache)


def test_nan_quarantine_spares_the_batch(small_model):
    """A slot producing non-finite logits is quarantined and failed alone;
    the co-resident request's output stays bit-identical to a clean run."""
    from repro.serving.engine import DONE, FAILED_ANOMALY
    cfg, params = small_model
    good_prompt = np.asarray([1, 2, 3, 4])
    bad_prompt = np.asarray([7, 8, 9])

    ref = _engine(cfg, params, max_batch=2, max_new_tokens=5)
    ref.submit(good_prompt)
    ref.run_until_drained()
    want = ref.finished[0].output

    eng = _engine(cfg, params, max_batch=2, max_new_tokens=5)
    good = eng.submit(good_prompt)
    bad = eng.submit(bad_prompt)
    eng.step()                                   # both admitted + 1 decode
    victim = eng.slot_req.index(bad)
    eng.cache = _poison_slot(eng.cache, victim)
    eng.run_until_drained()
    assert bad.status == FAILED_ANOMALY and bad.terminal
    assert good.status == DONE and good.output == want
    assert eng.stats()["failed_anomaly"] == 1


def test_transient_anomaly_retries_and_recovers(small_model):
    """A transient non-finite step within the retry budget freezes the
    slot (same position, no token emitted) and retries: once the fault
    clears the request completes with the clean-run output, exactly."""
    cfg, params = small_model
    prompt = np.asarray([1, 2, 3, 4])

    ref = _engine(cfg, params, max_batch=1, max_new_tokens=6)
    ref.submit(prompt)
    ref.run_until_drained()
    want = ref.finished[0].output

    eng = _engine(cfg, params, max_batch=1, max_new_tokens=6,
                  anomaly_retries=3)
    r = eng.submit(prompt)
    eng.step()
    snap = jax.tree_util.tree_map(jnp.copy, eng.cache)
    eng.cache = _poison_slot(eng.cache, 0)
    eng.step()                                   # anomaly: frozen, no token
    eng.cache = snap                             # fault clears
    eng.run_until_drained()
    assert r.done and r.output == want
    assert eng.stats()["failed_anomaly"] == 0


def test_default_config_has_no_failure_paths(small_model):
    """Defaults (no deadline, unbounded queue) leave the failure machinery
    dormant: all DONE, zero failure counters."""
    from repro.serving.engine import DONE
    cfg, params = small_model
    eng = _drain_workload(cfg, params, max_batch=2)
    assert all(r.status == DONE for r in eng.finished)
    s = eng.stats()
    assert s["failed"] == 0 and s["rejected"] == 0


def test_launcher_functions_build_and_drain():
    """``python -m repro.launch.serve`` and ``chip_smoke.py`` share these
    functions: parsed arguments -> engine -> seeded prompts -> drain."""
    from repro.launch import serve

    args = serve.parse_args(["--arch", "qwen2.5-3b", "--reduced",
                             "--max-batch", "2", "--kv-len", "64",
                             "--max-new-tokens", "3", "--impl", "flash"])
    assert serve.parse_mesh("") is None
    with pytest.raises(SystemExit):
        serve.parse_mesh("2by2")
    cfg, eng = serve.build_engine(args)
    reqs = serve.submit_prompts(eng, 3, 4, 20, seed=0)
    assert all(4 <= r.prompt.size < 20 for r in reqs)
    eng.run_until_drained()
    assert [r.status for r in reqs] == ["done"] * 3
    assert all(len(r.output) == 3 for r in reqs)
