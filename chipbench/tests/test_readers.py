"""The per-layer readers' arithmetic on a hand-made run and trace."""
import pytest

from conftest import REPO
from harness import driver, readers, roofline, spec, xplane

M = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
     "vocab_size": 512, "mlp": "silu_glu"}
SHAPES = {"max_batch": 4, "kv_len": 256, "chunk": 128}
PK = {"bf16_flop_per_s": 1e12, "hbm_byte_per_s": 1e10}
DENSE = spec.family(REPO, "dense")


class _Req:
    def __init__(self, t_admit):
        self.t_admit = t_admit


def _ctx(**trace):
    run = driver.Run(recs=[driver.Rec(_Req(1.5), 1.0, 1.0, [2.0]),
                           driver.Rec(_Req(0.0), 2.0, 2.0, []),
                           driver.Rec(_Req(9.0), 20.0, 20.0, [])],
                     t0=0.0, w0=0.5, w1=10.0, end=12.0, lateness=[],
                     hist0={1: 5}, hist1={1: 5, 2: 10, 4: 10},
                     prefill_tokens=(100, 1100), trace_span=(3.0, 5.0))
    calls = driver.Calls(decode_live=[[10, 20], [11, 21]],
                         prefill_pieces=[[(0, 8), (0, 16)]],
                         chunk_pieces=[[(128, 64)]], final_rows=2,
                         kv_live=[40, 60])
    t = xplane.Trace(busy_s=1.5, span_s=1.9, ops={}, gaps={},
                     longest_gaps=[], n_chips=1,
                     modules={"jit__fused_step_fn": [0.2, 2],
                              "jit__packed_prefill_fn": [0.05, 1],
                              "jit__chunk_step_fn": [0.15, 1]},
                     kernels=trace.get("kernels", {}))
    return readers.Context(m=M, fam=DENSE, shapes=SHAPES, pk=PK, run=run,
                           calls=calls, trace=t)


def test_queue_wait_counts_the_unadmitted_with_their_whole_wait():
    # due in window: 1.0 (admitted at 1.5) and 2.0 (never: waits to 12.0)
    assert readers.queue_wait_p50_ms(_ctx()) == pytest.approx(
        (0.5 + 10.0) / 2 * 1e3)


def test_queue_wait_leaves_out_requests_due_after_the_profiler_stopped():
    c = _ctx()
    c.run.recs.append(driver.Rec(_Req(9.0), 6.0, 6.0, [9.1]))  # due > 5.0
    assert readers.queue_wait_p50_ms(c) == pytest.approx(
        (0.5 + 10.0) / 2 * 1e3)


def test_program_times():
    c = _ctx()
    assert readers.decode_step_ms(c) == pytest.approx(100.0)
    assert readers.prefill_ms_per_ktok(c) == pytest.approx(200.0 / 1.0)
    assert readers.idle_pct(c) == pytest.approx(25.0)


def test_occupancy_and_live_kv():
    c = _ctx()
    assert readers.batch_occupancy_pct(c) == pytest.approx(
        (2 * 10 + 4 * 10) / 20 / 4 * 100)
    assert readers.kv_live_pct(c) == pytest.approx(50 / 1024 * 100)


def test_decode_roofline_reads_the_kernel_in_its_program():
    text = "%k = custom-call(s32[4,1,256]{2,1,0} %p) tpu_custom_call"
    c = _ctx(kernels={("jit__fused_step_fn", text): [0.01, 4],
                      ("jit__other", text): [5.0, 1]})
    least = 2 * sum(roofline.least_time(
        *roofline.decode_attention(live, M), PK)
        for live in ([10, 20], [11, 21]))
    assert readers.decode_attn_roofline(c) == pytest.approx(
        least / 0.01 * 100)
    assert readers.prefill_attn_roofline(c) is None     # no such kernel


def test_mfu_splits_by_program():
    c = _ctx()
    dec = roofline.model_flops(DENSE, M, [(9, 1), (19, 1), (10, 1), (20, 1)],
                               4)
    pre = roofline.model_flops(DENSE, M, [(0, 8), (0, 16), (128, 64)], 2)
    assert readers.step_mfu_pct(c) == pytest.approx(dec / 0.2 / 1e12 * 100)
    assert readers.prefill_mfu_pct(c) == pytest.approx(
        pre / 0.2 / 1e12 * 100)
    assert readers.slice_mfu_pct(c) == pytest.approx(
        (dec + pre) / 2.0 / 1e12 * 100)


@pytest.mark.parametrize("chunk", [64, 128])
def test_prefill_roofline_finds_the_kernel_by_the_engine_chunk(chunk):
    text = f"%k = custom-call(s32[1,{chunk}]{{1,0}} %seg) tpu_custom_call"
    c = _ctx(kernels={("jit__packed_prefill_fn", text): [0.02, 2]})
    c.shapes = dict(SHAPES, chunk=chunk)
    least = 2 * roofline.least_time(
        *roofline.prefill_attention([(0, 8), (0, 16)], M), PK)
    assert readers.prefill_attn_roofline(c) == pytest.approx(
        least / 0.02 * 100)
    c.shapes = dict(SHAPES, chunk=chunk * 2)
    assert readers.prefill_attn_roofline(c) is None


def _plane(i: int) -> str:
    """One chip's plane: a 2 ms fused step from 0, a 1 ms packed prefill
    from 3 ms, each one fusion; the decode kernel runs 0.5 ms in the
    step."""
    kern = "%k = custom-call(s32[4,1,256]{2,1,0} %p) tpu_custom_call"
    return f'''planes {{ id: {i + 1} name: "/device:TPU:{i}"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }}
    events {{ metadata_id: 2 offset_ps: 3000000000 duration_ps: 1000000000 }}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 2000000000 }}
    events {{ metadata_id: 4 offset_ps: 0 duration_ps: 500000000 }}
    events {{ metadata_id: 5 offset_ps: 3000000000 duration_ps: 1000000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit__fused_step_fn(1)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit__packed_prefill_fn(2)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.1 = f32[2] fusion()" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "{kern.replace('"', "'")}" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "%fusion.2 = f32[2] fusion()" }} }}
}}
'''


def _read_trace(tmp_path, chips: int):
    from jax.profiler import ProfileData

    text = "".join(_plane(i) for i in range(chips))
    path = tmp_path / f"chips{chips}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return xplane.reduce(str(path))


READERS = ["decode_step_ms", "prefill_ms_per_ktok", "idle_pct",
           "step_mfu_pct", "prefill_mfu_pct", "slice_mfu_pct",
           "decode_attn_roofline"]


def test_readers_on_a_mesh_count_every_chip_once(tmp_path):
    """Two chips that each run the one-chip trace's programs for as long:
    a time per step or per token, and the idle share, read as on one
    chip; a share of the peak or of a roofline reads half, since twice
    the chips did the same work in the same time."""
    one, two = _ctx(), _ctx()
    one.trace, two.trace = _read_trace(tmp_path, 1), _read_trace(tmp_path, 2)
    assert (one.trace.n_chips, two.trace.n_chips) == (1, 2)
    assert two.trace.modules["jit__fused_step_fn"] == pytest.approx(
        [0.004, 2])
    a = {r: getattr(readers, r)(one) for r in READERS}
    b = {r: getattr(readers, r)(two) for r in READERS}
    assert a["decode_step_ms"] == pytest.approx(2.0)
    assert a["prefill_ms_per_ktok"] == pytest.approx(1.0)
    assert a["idle_pct"] == pytest.approx((1 - 0.003 / 2.0) * 100)
    for r in ("decode_step_ms", "prefill_ms_per_ktok", "idle_pct"):
        assert b[r] == pytest.approx(a[r]), r
    for r in ("step_mfu_pct", "prefill_mfu_pct", "slice_mfu_pct",
              "decode_attn_roofline"):
        assert a[r] > 0 and b[r] == pytest.approx(a[r] / 2), r
