"""The per-layer readers' arithmetic on a hand-made run and trace."""
import pytest

from harness import driver, readers, roofline, xplane

M = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
     "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
     "vocab_size": 512, "mlp": "silu_glu"}
SHAPES = {"max_batch": 4, "kv_len": 256, "chunk": 128}
PK = {"bf16_flop_per_s": 1e12, "hbm_byte_per_s": 1e10}


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
    return readers.Context(m=M, shapes=SHAPES, pk=PK, run=run, calls=calls,
                           trace=t)


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
    dec = roofline.model_flops(M, [(9, 1), (19, 1), (10, 1), (20, 1)], 4)
    pre = roofline.model_flops(M, [(0, 8), (0, 16), (128, 64)], 2)
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
