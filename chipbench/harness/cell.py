"""One run of one cell: set-up, the window, the readings, the check."""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from harness import driver, readers, reference, roofline, spec, weights
from harness.clock import CompileClock
from harness.stats import percentile


def program_config(root: Path, conf: dict, fam):
    """The program's ``ModelConfig`` for ``conf``, checked by the family
    against the configuration file's model block, so the file states what
    runs."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.configs  # noqa: F401  (registers every architecture)
    from repro.config import get_config

    cfg = dataclasses.replace(get_config(conf["arch"]),
                              **conf["arch_overrides"])
    try:
        fam.program_keys(conf["model"], cfg)
    except ValueError as e:
        raise ValueError(f"{conf['name']}: {conf['arch']}: {e}") from None
    return cfg


def build_engine(cfg, fam, w: dict, m: dict, engine: dict, seed: int,
                 mesh=None):
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, ServingEngine

    params = fam.program_tree(w, m)
    want = jax.eval_shape(
        lambda k: T.init_params(cfg, k, param_dtype=jnp.bfloat16),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    def sig(tree):
        return jax.tree.map(lambda x: (x.shape, str(x.dtype)), tree)

    if sig(params) != sig(want):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter tree")
    return ServingEngine(cfg, params, EngineConfig(
        **engine, seed=seed % (2 ** 31 - 1)), mesh=mesh)


def _free(engine):
    """Delete the program's own device state (the KV pool and slot
    state); the weights are the benchmark's and stay for the check."""
    import jax

    for x in jax.tree.leaves((engine.pool.cache, engine.pool.state)):
        x.delete()


def _peaks(devices) -> list:
    return [(x.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for x in devices]


def _device(devices, traced, tr=None):
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices), "memory_peak_bytes": max(_peaks(devices))}
    if traced and tr is not None:
        out["busy_s"], out["window_s"] = tr
    return out


def end_to_end(run: driver.Run) -> dict:
    """Every end-to-end reading of a run; a cell reports those of its own
    metrics.  Latencies run from the due time; a request that never got
    its first token counts with the time it had waited when the run
    stopped, beyond every one that did."""
    w0, w1 = run.w0, run.w1
    att = [r for r in run.recs if w0 <= r.due < w1]
    ttft = [(r.first if r.first is not None else run.end) - r.due
            for r in att]
    itl, toks = [], 0
    for r in run.recs:
        for a, b in zip(r.times, r.times[1:]):
            if w0 <= b < w1:
                itl.append(b - a)
        toks += sum(1 for t in r.times if w0 <= t < w1)
    return {
        "ttft_p50_ms": _ms(percentile(ttft, 50)),
        "itl_p95_ms": _ms(percentile(itl, 95)),
        "output_tokens_per_s": toks / (w1 - w0),
        "_counts": {"ttft": len(ttft), "itl": len(itl), "tokens": toks},
    }


def _ms(x):
    return None if x is None else x * 1e3


def sample(recs: list, k: int, seed: int) -> list:
    """``k`` finished requests drawn from ``seed``, the one with the most
    served tokens always among them."""
    done = [r for r in recs if r.req.status == "done" and r.req.output]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.req.output))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 23])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def stalled(run: driver.Run, stall_s: float) -> int:
    """Requests still in flight at the last engine step whose last token
    is more than ``stall_s`` older than that step: a slot that stopped
    decoding while the engine went on stepping."""
    return sum(1 for r in run.recs
               if r.times and not r.req.terminal
               and run.last_step - r.times[-1] > stall_s)


def check(fam, w: dict, m: dict, recs: list, k: int, seed: int,
          limit: Optional[float], failed: int, stuck: int,
          gaps_of: Callable = reference.gaps) -> tuple[bool, dict]:
    """Reference gaps of the sampled requests' served tokens, against the
    limit; every attempted request must have had its first token, and no
    request in flight may have stopped getting tokens.  ``gaps_of`` is
    the reading; a control puts its own in the program's place."""
    got = sample(recs, k, seed)
    gaps = [gaps_of(fam, w, m, r.req.prompt, r.req.output) for r in got]
    n = int(sum(len(g) for g in gaps))
    gap = float(max((g.max() for g in gaps), default=float("nan")))
    ok = (n > 0 and limit is not None and gap <= limit and failed == 0
          and stuck == 0)
    return ok, {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "stalled_requests": {"value": stuck, "limit": 0},
        "tokens_compared": {"value": n, "limit": "> 0"},
    }


@dataclasses.dataclass
class Setup:
    """What a run sets up before traffic."""
    conf: dict                     # the configuration file
    mix: dict                      # the traffic mix
    rate: Optional[float]          # an open-loop cell's offered rate
    fam: object                    # the family file's module
    cfg: object                    # the program's ModelConfig
    mesh: object                   # the configuration's mesh, or None
    w: dict                        # the weights, the benchmark's layout
    engine: object                 # the program's ServingEngine

    @property
    def m(self) -> dict:
        return self.conf["model"]

    def new_engine(self, seed: int):
        return build_engine(self.cfg, self.fam, self.w, self.m,
                            self.conf["engine"], seed, self.mesh)


def prepare(root: Path, bench: dict, cell: dict, seed: int,
            devices: list) -> Setup:
    """Everything a run sets up before traffic: the configuration, its
    family, mix and rate; the weights from ``seed``, over the
    configuration's mesh of ``devices`` where it states one; the engine
    with the programs this cell's traffic runs compiled (or read from the
    cache)."""
    import jax

    conf, fam = spec.check(root, bench, cell)
    mix = spec.traffic(root, cell["traffic"])
    rate = spec.rate(root, cell["name"])["rate_rps"] \
        if mix["loop"] == "open" else None
    cfg = program_config(root, conf, fam)
    mesh = weights.mesh(conf, devices)
    w = weights.make(fam, conf["model"], seed, mesh)
    jax.block_until_ready(w)
    s = Setup(conf, mix, rate, fam, cfg, mesh, w, None)
    s.engine = s.new_engine(seed)
    driver.warm(s.engine, mix["prompt"]["max"] > s.engine._chunk)
    return s


def run(root: Path, bench: dict, cell: dict, *, seed: int, seconds: float,
        traced: bool, t_start: float, devices: list,
        patch: Optional[Callable] = None, drain_s: float = 60.0) -> dict:
    """One run.  ``patch`` (called with the engine before traffic starts)
    exists for the fault tests."""
    import jax

    clock = CompileClock()
    setup = prepare(root, bench, cell, seed, devices)
    conf, mix, m, engine = setup.conf, setup.mix, setup.m, setup.engine
    pk = roofline.peaks(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    calls = None
    trace_cb = None
    tdir = None
    if traced:
        calls = driver.Calls()
        driver.instrument(engine, calls)
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only, no Python calls
        trace_cb = (lambda: jax.profiler.start_trace(
            tdir, profiler_options=opts), jax.profiler.stop_trace)
    if patch is not None:
        patch(engine)
    from harness.traffic import RequestStream

    stream = RequestStream(mix, seed, m["vocab_size"], setup.rate)
    marks = {}

    def on_window():
        marks["setup_s"] = time.monotonic() - t_start
        marks["compile0"] = clock.snapshot()

    run_ = driver.drive(engine, stream, mix, seconds, on_window=on_window,
                        trace=trace_cb, calls=calls, drain_s=drain_s)
    c0, c1 = marks["compile0"], clock.snapshot()
    e2e = end_to_end(run_)
    att = [r for r in run_.recs if run_.w0 <= r.due < run_.w1]
    failed = sum(1 for r in att if r.first is None)
    tr = None
    if traced:
        from harness import xplane
        path = xplane.find(tdir)
        keep = os.environ.get("CHIPBENCH_KEEP_TRACE")
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, os.path.join(keep, f"{cell['name']}.{seed}"
                                                 f".xplane.pb"))
        tr = xplane.reduce(path)
        shutil.rmtree(tdir, ignore_errors=True)
    device = _device(devices, traced, None if tr is None else
                     (tr.busy_s, run_.trace_span[1] - run_.trace_span[0]))
    late = sorted(run_.lateness)
    print(f"set-up {marks['setup_s']:.3f} s; window {seconds} s; "
          f"compiles in the window and drain: {c1[1] - c0[1]} "
          f"({c1[0] - c0[0]:.3f} s); whole run {c1[1]} compiles, "
          f"{c1[0]:.2f} s, {c1[2]} cache hits", file=sys.stderr)
    if late:
        print(f"generator lateness: p50 {percentile(late, 50) * 1e3:.3f} ms, "
              f"p99 {percentile(late, 99) * 1e3:.3f} ms, max "
              f"{late[-1] * 1e3:.3f} ms over {len(late)} submissions",
              file=sys.stderr)
    print(f"attempted {len(att)}, failed {failed}; samples {e2e['_counts']}; "
          f"peak device memory {device['memory_peak_bytes']} B, by chip "
          f"{_peaks(devices)}", file=sys.stderr, flush=True)

    metrics = {}
    ctx = readers.Context(m=m, fam=setup.fam, shapes=readers.shapes(engine),
                          pk=pk, run=run_, calls=calls, trace=tr)
    for spec_m in spec.metrics(bench, cell["name"], traced):
        name = spec_m["name"]
        if traced:
            value = spec.reader(root, name)(ctx)
        elif name == "setup_s":
            value = marks["setup_s"]
        else:
            value = e2e[name]
        if value is None:
            # a metric declared for this cell that reads nothing: the
            # program no longer runs what its reader looks for
            raise RuntimeError(f"{cell['name']}: {name} read nothing in "
                               f"this run")
        metrics[name] = {"value": value, "unit": spec_m["unit"]}

    _free(engine)
    del engine
    setup.engine = None
    ok, checks = check(setup.fam, setup.w, m, run_.recs,
                       int(mix["check_requests"]), seed,
                       conf["check"]["max_logit_gap"], failed,
                       stalled(run_, float(mix["stall_s"])))
    result = {"correct": ok, "attempted": len(att), "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        ops = sorted(tr.ops.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in sorted(
                tr.gaps.items(), key=lambda kv: -kv[1])[:10]],
        }
    result["check"] = checks
    return result
