"""The serving loop's host spans (``repro.serving.tracing``): a tiny engine
drained under ``jax.profiler`` on the CPU, its trace read back with
``ProfileData``.  The spans nest as the engine iterates, and their
attributes add up to what the engine served."""
import glob
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.config import get_config, reduce_config
from repro.models import transformer as T
from repro.serving import tracing
from repro.serving.engine import EngineConfig, ServingEngine

PATHS = {
    "packed": {},
    "sequential": {"packed": False},
    "host": {"fused": False},
    "spec": {"spec_k": 2, "spec_draft_bits": 0},
}
# the decode dispatch of each path
DISPATCH = {"packed": "executor.fused_step",
            "sequential": "executor.fused_step",
            "host": "executor.decode", "spec": "executor.spec_step"}
# the children of each span, in the order the engine runs them
ORDER = {
    "engine.step": ["engine.evict", "engine.chunks", "engine.admit",
                    "executor.fused_step", "executor.spec_step",
                    "executor.decode", "executor.fetch", "engine.commit"],
    "engine.chunks": ["executor.chunk_step", "executor.fetch",
                      "engine.commit"],
    "engine.admit": ["executor.packed_prefill", "executor.prefill_insert",
                     "executor.prefill", "executor.fetch", "engine.commit"],
}


@pytest.fixture(scope="module")
def model():
    cfg = reduce_config(get_config("qwen2.5-3b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           param_dtype=jnp.float32)
    return cfg, params


def _prompts(cfg):
    # lengths on both sides of the 8-token chunk, so prompts pack into
    # one stream and long ones continue in chunk steps
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg.vocab_size, size=n)
            for n in (5, 19, 3, 11, 7, 26, 4)]


def _engine(model, **kw):
    cfg, params = model
    return ServingEngine(cfg, params, EngineConfig(
        max_batch=3, kv_len=48, max_new_tokens=5, impl="ref",
        prefill_chunk=8, **kw))


def _drain(eng, prompts):
    for p in prompts:
        eng.submit(p)
    eng.run_until_drained()
    return eng


def _host_spans(path):
    """The program's spans on the host, each with its children."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "executor.")):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore", DeprecationWarning)
                        attrs = dict(e.stats)
                    out.append({"name": e.name, "start": e.start_ns,
                                "end": e.end_ns, "attrs": attrs,
                                "children": []})
    out.sort(key=lambda s: (s["start"], -s["end"]))
    stack = []
    for s in out:
        while stack and stack[-1]["end"] < s["end"]:
            stack.pop()
        if stack:
            stack[-1]["children"].append(s)
        stack.append(s)
    return out


def _live_after(eng):
    """Wrap the fused step: after each call, the live cache entries over
    the slots that were occupied when it was dispatched."""
    live, step = [], eng.executor.fused_step

    def fused_step(cache, state):
        occupied = [i for i, r in enumerate(eng.pool.slot_req)
                    if r is not None]
        out = step(cache, state)
        eng.pool.cache = out[0]
        live.append(sum(eng.pool.valid_len(i) for i in occupied))
        return out

    eng.executor.fused_step = fused_step
    return live


@pytest.fixture(scope="module", params=list(PATHS))
def traced(request, model, tmp_path_factory):
    """The same requests drained with the profiler off and on."""
    kw = PATHS[request.param]
    prompts = _prompts(model[0])
    off = _drain(_engine(model, **kw), prompts)
    on = _engine(model, **kw)
    live = _live_after(on) if "executor.fused_step" in DISPATCH[
        request.param] else None
    tdir = str(tmp_path_factory.mktemp(f"trace-{request.param}"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        _drain(on, prompts)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))[-1]
    return request.param, off, on, _host_spans(path), live


def _uids(value):
    return [int(u) for u in str(value).split()]


def test_every_step_nests_its_children_in_order(traced):
    _, _, on, spans, _ = traced
    steps = [s for s in spans if s["name"] == "engine.step"]
    assert [s["attrs"]["it"] for s in steps] == \
        list(range(1, len(steps) + 1))
    assert any(s["children"] for s in steps)
    for s in spans:
        if s["name"] not in ORDER:
            continue
        rank = [ORDER[s["name"]].index(c["name"]) for c in s["children"]]
        assert rank == sorted(rank), (s["name"],
                                      [c["name"] for c in s["children"]])
    top = {s["name"] for s in spans} - {c["name"] for s in spans
                                        for c in s["children"]}
    assert top == {"engine.step"}


def test_uids_match_the_requests_admitted_and_finished(traced):
    path, _, on, spans, _ = traced
    admitted = [u for s in spans if s["name"] == "engine.admit"
                for u in _uids(s["attrs"]["uids"])]
    assert sorted(admitted) == sorted(r.uid for r in on.finished)
    finished = [u for s in spans if s["name"] == "engine.commit"
                for u in _uids(s["attrs"].get("finished", ""))]
    assert finished == [r.uid for r in on.finished]
    # a request's uid is on the dispatch that decodes it
    decoded = {u for s in spans if s["name"] == DISPATCH[path]
               for u in _uids(s["attrs"]["uids"])}
    assert decoded == {r.uid for r in on.finished}


def test_committed_tokens_add_up_to_the_tokens_served(traced):
    _, _, on, spans, _ = traced
    committed = sum(s["attrs"]["tokens"] for s in spans
                    if s["name"] == "engine.commit")
    assert committed == sum(len(r.output) for r in on.finished)


def test_prompt_tokens_add_up_to_the_prefill_tokens(traced):
    path, _, on, spans, _ = traced
    prompt = [s["attrs"]["prompt_tokens"] for s in spans
              if s["name"] in ("engine.admit", "engine.chunks")]
    assert sum(prompt) == on.prefill_tokens
    chunks = [s["attrs"] for s in spans if s["name"] == "engine.chunks"]
    # long prompts continue in chunk steps on the packed admission path
    assert bool(chunks) == (path in ("packed", "spec"))
    C, B = on._chunk, on.ecfg.max_batch
    for a in chunks:
        assert a["positions"] == B * C
        assert a["rows"] == len(_uids(a["uids"]))
        assert 0 < a["prompt_tokens"] <= a["rows"] * C


@pytest.mark.parametrize("traced", ["packed", "sequential"], indirect=True)
def test_kv_live_is_the_pools_live_entries(traced):
    _, _, _, spans, live = traced
    got = [s["attrs"]["kv_live"] for s in spans
           if s["name"] == "executor.fused_step"]
    assert got and got == live


def test_profiler_leaves_streams_and_stats_unchanged(traced):
    _, off, on, _, _ = traced
    assert [r.output for r in off.finished] == [r.output for r in on.finished]
    a, b = off.stats(), on.stats()
    assert set(a) == set(b)
    for k in a:
        if not k.endswith("_s"):           # wall-clock times and rates
            assert a[k] == b[k], k


@pytest.mark.parametrize("wall", [False, True])
def test_no_attribute_is_built_without_a_profiler_session(model,
                                                          monkeypatch,
                                                          wall):
    built = []
    real = tracing._format
    monkeypatch.setattr(tracing, "_format",
                        lambda attrs: built.append(attrs) or real(attrs))
    eng = _drain(_engine(model, trace=wall), _prompts(model[0])[:3])
    assert eng.finished and not built
    assert len(eng.trace) == (eng.decode_steps if wall else 0)


def test_lists_are_written_space_separated():
    assert tracing._format({"uids": [4, 5], "n": 2}) == {"uids": "4 5",
                                                        "n": 2}
    assert tracing._format({"finished": []}) == {"finished": ""}
