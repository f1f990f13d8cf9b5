"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a short trace recorded on the chip
(``data/decode.xplane.pb``: a slice of the fused decode step of
``qwen2.5-3b.decode-heavy``)."""
from pathlib import Path

import pytest

from harness import xplane

DATA = Path(__file__).resolve().parent / "data"


def test_union_merges_overlaps_and_nesting():
    got = xplane._union([(0, 10), (5, 12), (20, 30), (21, 22), (30, 31)])
    assert got == [[0, 12], [20, 31]]


def test_self_time_subtracts_nested_events():
    evs = [("loop", 0, 100), ("a", 10, 20), ("b", 40, 30), ("c", 120, 5)]
    own = dict(xplane._self_times(evs))
    assert own == {"loop": 50, "a": 20, "b": 30, "c": 5}


def test_gap_label_is_the_innermost_enclosing_span():
    spans = sorted([(0, 100, "engine.step"), (10, 50, "executor.fetch"),
                    (60, 70, "executor.fused_step")])
    starts = [s[0] for s in spans]
    assert xplane._enclosing(spans, starts, 20) == "executor.fetch"
    assert xplane._enclosing(spans, starts, 55) == "engine.step"
    assert xplane._enclosing(spans, starts, 150) == "no host span"


def test_short_op_names():
    text = ('%closed_call.12 = bf16[32,2,1,8,128]{4,3,2,1,0:T(8,128)} '
            'custom-call(bf16[32,2,8,128]{3,2,1,0} %a), '
            'custom_call_target="tpu_custom_call", x={y={}}')
    assert xplane.short(text) == ("%closed_call.12 custom-call "
                                  "tpu_custom_call bf16[32,2,1,8,128]")


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(str(DATA / "decode.xplane.pb"))


def test_recorded_trace_busy_and_programs(recorded):
    t = recorded
    assert t.n_chips == 1
    assert 0 < t.busy_s <= t.span_s
    secs, runs = t.modules["jit__fused_step_fn"]
    # the slice holds three fused steps and nothing else: their program
    # time spans the busy time, with the few ns between ops inside them
    assert runs == 3
    assert t.busy_s <= secs <= t.span_s
    # op self times add up to no more than the busy time
    assert sum(t.ops.values()) <= t.busy_s * 1.0001


def test_recorded_trace_kernels_sit_in_their_programs(recorded):
    mods = {mod for mod, _ in recorded.kernels}
    assert "jit__fused_step_fn" in mods
    decode = [v for (mod, text), v in recorded.kernels.items()
              if mod == "jit__fused_step_fn" and "s32[16,1,4096]" in text]
    assert decode and decode[0][1] % 36 == 0      # one call per layer


def test_recorded_trace_gaps_are_labelled_by_host_spans(recorded):
    labels = set(recorded.gaps)
    assert labels <= set(xplane.HOST_SPANS) | {"no host span"}
    assert labels & set(xplane.HOST_SPANS)
    idle = sum(recorded.gaps.values())
    assert idle <= recorded.span_s - recorded.busy_s + 1e-6
