"""HLO-text analyzer unit tests against hand-written HLO, plus roofline
term arithmetic, the structured cost_analysis normaliser, and the
walked-HLO-vs-traffic-model byte agreement pin."""
import numpy as np
import pytest

from repro.roofline.analysis import V5E, roofline_terms
from repro.roofline.hlo import analyze_hlo_text, normalize_cost_analysis

HLO_DOT = """
HloModule test

ENTRY %main (a: f32[128,256], b: f32[256,512]) -> f32[128,512] {
  %a = f32[128,256]{1,0} parameter(0)
  %b = f32[256,512]{1,0} parameter(1)
  ROOT %dot = f32[128,512]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""


def test_dot_flops():
    c = analyze_hlo_text(HLO_DOT, num_devices=1)
    assert c.flops == 2.0 * 128 * 512 * 256


HLO_COLLECTIVES = """
HloModule test

ENTRY %main (p: bf16[64,1024]) -> bf16[64,1024] {
  %p = bf16[64,1024]{1,0} parameter(0)
  %ag = bf16[256,1024]{1,0} all-gather(%p), replica_groups=[64,4]<=[256], dimensions={0}
  %ar = bf16[64,1024]{1,0} all-reduce(%p), replica_groups={}, to_apply=%add
  %rs = bf16[16,1024]{1,0} reduce-scatter(%p), replica_groups=[64,4]<=[256], dimensions={0}
  %cp = bf16[64,1024]{1,0} collective-permute(%p), source_target_pairs={{0,1}}
  ROOT %out = bf16[64,1024]{1,0} add(%ar, %cp)
}
"""


def test_collective_wire_bytes():
    c = analyze_hlo_text(HLO_COLLECTIVES, num_devices=256)
    bytes_p = 64 * 1024 * 2
    # all-gather: out 4x input over group 4 -> out*(g-1)/g
    assert c.collective_bytes["all-gather"] == 4 * bytes_p * 3 / 4
    # all-reduce over all 256 devices: 2*bytes*(g-1)/g
    assert abs(c.collective_bytes["all-reduce"]
               - 2 * bytes_p * 255 / 256) < 1.0
    # reduce-scatter: in_bytes*(g-1)/g
    assert c.collective_bytes["reduce-scatter"] == bytes_p * 3 / 4
    # collective-permute: out bytes
    assert c.collective_bytes["collective-permute"] == bytes_p


HLO_WHILE = """
HloModule test

%body (x: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %x = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%x), index=0
  %m = f32[64,64]{1,0} get-tuple-element(%x), index=1
  %d = f32[64,64]{1,0} dot(%m, %m), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,64]) tuple(%ip, %d)
}

%cond (x: (s32[], f32[64,64])) -> pred[] {
  %x = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%x), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (m0: f32[64,64]) -> f32[64,64] {
  %m0 = f32[64,64]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,64]) tuple(%zero, %m0)
  %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_count_multiplies_flops():
    """cost_analysis counts loop bodies once; ours multiplies by the trip
    count parsed from the condition — the scan-over-layers fix."""
    c = analyze_hlo_text(HLO_WHILE, num_devices=1)
    one_iter = 2.0 * 64 * 64 * 64
    assert c.flops == 12 * one_iter
    assert c.n_while == 1


def test_roofline_terms_math():
    rep = roofline_terms(HLO_DOT, arch="x", shape="y", mesh_name="single",
                         n_devices=4, model_flops=1e9)
    flops = 2.0 * 128 * 512 * 256
    assert np.isclose(rep.compute_s, flops / V5E.peak_flops)
    assert rep.bottleneck in ("compute", "memory", "collective")
    assert rep.step_s == max(rep.compute_s, rep.memory_s, rep.collective_s)
    assert rep.roofline_frac <= 1.0


def test_bottleneck_identification():
    # memory-bound: big operands, tiny flops (no dot at all)
    hlo = """
HloModule t

ENTRY %main (p: f32[4096,4096]) -> f32[4096,4096] {
  %p = f32[4096,4096]{1,0} parameter(0)
  ROOT %f = f32[4096,4096]{1,0} fusion(%p), kind=kLoop, calls=%fc
}
"""
    rep = roofline_terms(hlo, arch="x", shape="y", mesh_name="single",
                         n_devices=1, model_flops=1.0)
    assert rep.bottleneck == "memory"


# ---------------------------------------------------------------------------
# normalize_cost_analysis: the dry-run's structured per-op estimate
# ---------------------------------------------------------------------------

_ZERO_CA = {"flops": 0.0, "bytes": 0.0, "transcendentals": 0.0,
            "operand_bytes": {}, "output_bytes": 0.0, "utilization": {}}


def test_normalize_cost_analysis_none_and_empty():
    """A backend with no cost model (None), an empty module ({}), and a
    map of only unrecognised keys all normalise to the all-zero record."""
    assert normalize_cost_analysis(None) == _ZERO_CA
    assert normalize_cost_analysis({}) == _ZERO_CA
    assert normalize_cost_analysis({"optimal_seconds": 1.0}) == _ZERO_CA


def test_normalize_cost_analysis_structured():
    ca = {"flops": 1056.0, "bytes accessed": 1152.0,
          "bytes accessed0{}": 640.0, "bytes accessed1{}": 384.0,
          "bytes accessedout{}": 256.0,
          "utilization0{}": 2.0, "utilization1{}": 2.0}
    d = normalize_cost_analysis(ca)
    assert d["flops"] == 1056.0 and d["bytes"] == 1152.0
    assert d["operand_bytes"] == {0: 640.0, 1: 384.0}
    assert d["output_bytes"] == 256.0
    assert d["utilization"] == {0: 2.0, 1: 2.0}
    # the installed jax hands back the map itself, not a list around it
    import jax
    import jax.numpy as jnp
    live = jax.jit(lambda a: a @ a).lower(jnp.ones((8, 8))).compile()
    assert isinstance(live.cost_analysis(), dict)
    assert normalize_cost_analysis(live.cost_analysis())["flops"] > 0


def test_normalize_cost_analysis_missing_keys():
    """Partial maps (some backends omit operand/output breakdowns) fill
    with zeros instead of raising."""
    d = normalize_cost_analysis({"flops": 7.0})
    assert d["flops"] == 7.0
    assert d["bytes"] == 0.0 and d["output_bytes"] == 0.0
    assert d["operand_bytes"] == {} and d["utilization"] == {}
    # unknown keys are ignored, not misparsed as operand entries
    d = normalize_cost_analysis({"bytes accessedout{}": 3.0,
                                 "optimal_seconds": 1.0})
    assert d["output_bytes"] == 3.0 and d["bytes"] == 0.0


# ---------------------------------------------------------------------------
# walked-HLO bytes vs the traffic model: the byte terms the calibration
# plane fits against must be the bytes a compiled dot actually moves
# ---------------------------------------------------------------------------

def _dot_hlo(n: int, k: int, m: int) -> str:
    return f"""
HloModule t

ENTRY %main (x: f16[{n},{k}], w: f16[{k},{m}]) -> f16[{n},{m}] {{
  %x = f16[{n},{k}]{{1,0}} parameter(0)
  %w = f16[{k},{m}]{{1,0}} parameter(1)
  ROOT %dot = f16[{n},{m}]{{1,0}} dot(%x, %w), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
}}
"""


@pytest.mark.parametrize("arch", ["bert-base", "gemma2-9b"])
def test_hlo_bytes_agree_with_traffic_phase_bytes(arch):
    """``traffic.phase_bytes`` for the kqv and score phases must equal the
    walked-HLO bytes of the dots those phases model (in + weights + out of
    ``f16[N,D] @ f16[D,(1+2f)D]`` resp. the ``[D,D]`` out-proj), within a
    pinned 2% — gemma2-9b covers the GQA-shrunk K/V path."""
    from repro.config import get_config
    from repro.core.traffic import (Workload, phase_bytes,
                                    transformer_phases)

    N = 64
    w = Workload.from_config(get_config(arch), seq_len=N)
    D = w.d_model
    fused = round((1 + 2 * w.n_kv_heads / w.n_heads) * D)
    phases = {p.name: p for p in transformer_phases(w)}

    for name, (k_dim, n_dim) in (("kqv", (D, fused)), ("score", (D, D))):
        walked = analyze_hlo_text(_dot_hlo(N, k_dim, n_dim)).bytes_hbm
        # the score phase's QK^T/softmax/.V ride on SM-local buffers; its
        # byte fields are exactly the out-projection dot
        modeled = phase_bytes(phases[name])
        assert walked > 0
        assert abs(walked - modeled) <= 0.02 * modeled, \
            f"{arch}/{name}: HLO walks {walked:.0f}B, traffic models " \
            f"{modeled:.0f}B"
