"""The program's spans and the split of the device idle between programs
(``harness/spans.py``), on two short traces recorded on the chip:
``data/decode.xplane.pb`` (three fused steps of
``qwen2.5-3b.decode-heavy``, with the benchmark's wrapper spans only) and
``data/spans.xplane.pb`` (``qwen2.5-3b.chat-saturated`` with the
program's own spans, beside the benchmark's call records of the same
steps in ``data/spans.calls.json``)."""
import json
from pathlib import Path

import pytest

from harness import spans, xplane

DATA = Path(__file__).resolve().parent / "data"
TRACES = ["decode.xplane.pb", "spans.xplane.pb"]


@pytest.fixture(scope="module", params=TRACES)
def read(request):
    return spans.read(str(DATA / request.param))


@pytest.fixture(scope="module")
def program():
    return spans.read(str(DATA / "spans.xplane.pb"))


def test_runs_pair_with_their_host_stamps(read):
    paired = [r for r in read.runs if r.enqueue is not None]
    assert len(paired) >= len(read.runs) - 1 >= 2
    # each run ends on the device before the host hears of it
    assert all(r.complete is None or r.complete > r.enqueue for r in paired)


def test_the_offset_bracket_holds_the_gap_split_pairing(read):
    lo, hi = read.offset
    assert 0 < lo <= hi
    runs = {r.run_id: r for r in read.runs}
    assert read.gaps
    for g in read.gaps:
        prev, nxt = runs[g.after], runs[g.before]
        # for every offset in the bracket, the fetch that returned the
        # earlier run ends after that run ends on the device, and the
        # next run starts on the device after the host enqueued it
        assert g.fetch_end - hi >= prev.end
        assert nxt.enqueue - lo <= nxt.start


def test_host_and_wait_parts_make_up_each_gap(read):
    for g in read.gaps:
        assert g.host_s >= 0 and g.wait_s >= 0
        assert g.host_s + g.wait_s == pytest.approx(g.seconds, abs=1e-12)
    # the gaps between programs are nearly all of the device's idle time
    total = sum(g.seconds for g in read.gaps)
    assert 0.9 * read.idle_s <= total <= read.idle_s + 1e-9


def test_per_iteration_split_of_the_recorded_decode_steps():
    sp = spans.read(str(DATA / "decode.xplane.pb"))
    assert sp.offset[0] == pytest.approx(1.233482e6, abs=1.0)
    assert sp.offset[1] == pytest.approx(1.874566e6, abs=1.0)
    # two gaps between three fused steps, one per engine.step
    assert [(g.after, g.before) for g in sp.gaps] == [(705, 706), (706, 707)]
    assert [g.host_s for g in sp.gaps] == pytest.approx(
        [0.580106e-3, 0.612916e-3], abs=1e-9)
    assert spans.host_ms_per_iter(sp) == pytest.approx(0.596511, abs=1e-6)
    assert spans.d2h_wait_ms_per_iter(sp) == pytest.approx(1.25734,
                                                           abs=1e-6)
    assert spans.chunk_fill_pct(sp) is None        # no program spans


def test_program_spans_carry_their_counters(program):
    names = {s.name for s in program.spans}
    assert {"engine.step", "engine.commit", "executor.fetch",
            "executor.fused_step"} <= names
    for s in program.named("engine.step"):
        if "it" in s.attrs:
            assert {"queue", "occupied", "decoding"} <= set(s.attrs)
    for s in program.named("executor.fetch"):
        if s.attrs:
            assert s.attrs["bytes"] > 0


def test_kv_live_is_what_the_benchmark_counts(program):
    calls = json.loads((DATA / "spans.calls.json").read_text())
    got = [s.attrs["kv_live"] for s in program.named("executor.fused_step")
           if "kv_live" in s.attrs]
    assert got and got == calls["kv_live"]


def test_chunk_fill_reads_the_chunk_spans(program):
    chunks = [s.attrs for s in program.named("engine.chunks")]
    assert chunks
    fill = spans.chunk_fill_pct(program)
    assert 0 < fill <= 100
    assert fill == pytest.approx(
        100 * sum(a["prompt_tokens"] for a in chunks)
        / sum(a["positions"] for a in chunks))


def test_the_breakdown_reads_the_program_spans_as_the_wrappers(program):
    """The program's spans that share a name with the benchmark's
    wrappers wrap the same calls, so idle gaps keep their labels."""
    t = xplane.reduce(str(DATA / "spans.xplane.pb"))
    assert set(t.gaps) <= set(xplane.HOST_SPANS) | {"no host span"}
