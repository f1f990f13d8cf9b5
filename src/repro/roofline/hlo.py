"""Optimized-HLO text analyzer for the dry-run roofline.

XLA's ``compiled.cost_analysis()`` counts each while-loop *body once*,
ignoring trip counts — useless for scan-over-layers models.  This module
re-derives per-device costs from the post-SPMD optimized HLO text:

- ``dot`` FLOPs from operand/output shapes (symbol table per computation),
- collective wire-bytes per device (ring-model factors, replica-group size
  parsed from both iota ``[G,S]<=[N]`` and explicit ``{{...}}`` forms),
- while-loop trip counts parsed from the loop-condition comparison constant,
  applied multiplicatively through the call graph (fusion/call/while),
- an HBM-traffic estimate (dot + fusion operand/result bytes).

Everything here is pure text processing — no jax imports — so it is unit
testable against hand-written HLO.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\](?:\{[^}]*\})?")
# out type is either a tuple "(s32[], bf16[..]{..}, /*index=5*/ ...)" — which
# may contain '=' inside /*index=N*/ comments but never a ')' before its own
# close — or a single non-space token.
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s+([\w\-]+)\((.*)$")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")
_TRIP_CFG_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _parse_shape(s: str):
    """'bf16[4,128]{1,0}' -> (bytes_total, dtype, dims). Tuples -> summed."""
    total = 0
    dims_all = []
    dt = None
    for m in _SHAPE_RE.finditer(s):
        dtype, dimstr = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        dims = [int(x) for x in dimstr.split(",") if x] if dimstr else []
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dtype]
        if dt is None:
            dt = dtype
            dims_all = dims
    return total, dt, dims_all


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    out_shape: str
    body: str          # text after the opening paren


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    ops: list
    symbols: dict      # value name -> out_shape string


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes_hbm: float = 0.0
    collective_bytes: dict = dataclasses.field(default_factory=dict)
    collective_ops: list = dataclasses.field(default_factory=list)
    n_while: int = 0
    trip_counts: dict = dataclasses.field(default_factory=dict)

    @property
    def total_collective_bytes(self):
        return sum(self.collective_bytes.values())


def _split_computations(text: str) -> list[Computation]:
    comps = []
    cur = None
    entry = False
    for line in text.splitlines():
        m = _COMP_HDR_RE.match(line)
        if m and "{" in line:
            cur = Computation(m.group(2), bool(m.group(1)), [], {})
            comps.append(cur)
            continue
        if cur is None:
            continue
        if line.startswith("}"):
            cur = None
            continue
        om = _OP_RE.match(line)
        if om:
            name, shape, opcode, rest = om.groups()
            cur.ops.append(Op(name, opcode, shape, rest))
            cur.symbols[name] = shape
    return comps


_CALL_ATTR_RE = re.compile(
    r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_ATTR_RE = re.compile(r"condition=%?([\w.\-]+)")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_OPERANDS_RE = re.compile(r"%([\w.\-]+)")


def _group_size(body: str, num_devices: int) -> int:
    m = _GROUPS_IOTA_RE.search(body)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPL_RE.search(body)
    if m:
        return len(m.group(1).split(","))
    if "replica_groups={}" in body:
        return num_devices
    return num_devices


def _trip_count(comp: Computation) -> int:
    """Max integer constant in a while-condition computation (the loop bound
    in canonical `i < N` conditions produced by lax.scan/map)."""
    best = 1
    for op in comp.ops:
        if op.opcode == "constant":
            mm = re.match(r"(\d+)\)", op.body)
            if mm:
                best = max(best, int(mm.group(1)))
    return best


def _dot_flops(op: Op, symbols: dict) -> float:
    out_bytes, out_dt, out_dims = _parse_shape(op.out_shape)
    operands = _OPERANDS_RE.findall(op.body.split(", lhs_contracting")[0])
    if not operands:
        return 0.0
    lhs_shape = symbols.get(operands[0])
    if lhs_shape is None:
        return 0.0
    _, _, lhs_dims = _parse_shape(lhs_shape)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", op.body)
    contract = 1
    if m and m.group(1):
        for d in m.group(1).split(","):
            contract *= lhs_dims[int(d)] if int(d) < len(lhs_dims) else 1
    out_elems = 1
    for d in out_dims:
        out_elems *= d
    return 2.0 * out_elems * contract


def _operand_names(op: Op) -> list[str]:
    head = op.body.split("), ")[0] if "), " in op.body else op.body
    return _OPERANDS_RE.findall(head)


def _operand_bytes(op: Op, symbols: dict) -> float:
    total = 0.0
    for name in _operand_names(op):
        s = symbols.get(name)
        if s:
            total += _parse_shape(s)[0]
    return total


def _param_slice_bytes(comp: Computation) -> dict[int, float]:
    """For a fused computation: parameter index -> HBM bytes actually read.

    A parameter whose only use is a (dynamic-)slice reads just the slice —
    the pattern scan bodies produce when indexing stacked per-layer
    buffers; counting the full buffer per iteration overstates HBM traffic
    by the layer count."""
    param_idx: dict[str, int] = {}
    uses: dict[str, list[Op]] = {}
    for o in comp.ops:
        if o.opcode == "parameter":
            m = re.match(r"(\d+)\)", o.body)
            if m:
                param_idx[o.name] = int(m.group(1))
        else:
            for nm in _OPERANDS_RE.findall(o.body):
                uses.setdefault(nm, []).append(o)
    out: dict[int, float] = {}
    for pname, idx in param_idx.items():
        use = uses.get(pname, [])
        if use and all(u.opcode in ("dynamic-slice", "slice") for u in use):
            out[idx] = sum(_parse_shape(u.out_shape)[0] for u in use)
    return out


def _fusion_bytes(op: Op, symbols: dict, by_name: dict) -> float:
    """HBM traffic at a fusion boundary: output + per-operand reads, with
    slice-only operands counted at slice size."""
    out_b = _parse_shape(op.out_shape)[0]
    names = _operand_names(op)
    sub = None
    m = _CALL_ATTR_RE.search(op.body)
    if m:
        sub = by_name.get(m.group(1))
    slice_bytes = _param_slice_bytes(sub) if sub is not None else {}
    total = out_b
    for i, nm in enumerate(names):
        s = symbols.get(nm)
        if not s:
            continue
        full = _parse_shape(s)[0]
        total += min(full, slice_bytes.get(i, full))
    return total


def _collective_wire_bytes(op: Op, symbols: dict, num_devices: int) -> float:
    """Per-device bytes crossing links (ring model)."""
    g = _group_size(op.body, num_devices)
    if g <= 1:
        return 0.0
    out_bytes, _, _ = _parse_shape(op.out_shape)
    in_bytes = _operand_bytes(op, symbols)
    kind = op.opcode.replace("-start", "")
    if kind == "all-gather":
        return out_bytes * (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * out_bytes * (g - 1) / g
    if kind == "reduce-scatter":
        return in_bytes * (g - 1) / g
    if kind == "all-to-all":
        return in_bytes * (g - 1) / g
    if kind == "collective-permute":
        return out_bytes
    return 0.0


def analyze_hlo_text(text: str, num_devices: int = 1) -> HloCost:
    comps = _split_computations(text)
    by_name = {c.name: c for c in comps}
    cost = HloCost()

    # while bodies -> trip counts: prefer the compiler's own
    # backend_config known_trip_count; fall back to parsing the condition
    body_trips: dict[str, int] = {}
    for c in comps:
        for op in c.ops:
            if op.opcode == "while":
                tm = _TRIP_CFG_RE.search(op.body)
                bm = None
                for attr in _CALL_ATTR_RE.finditer(op.body):
                    if attr.group(0).startswith("body="):
                        bm = attr
                        break
                bm = bm or _CALL_ATTR_RE.search(op.body)
                if tm:
                    trips = int(tm.group(1))
                else:
                    cm = _COND_ATTR_RE.search(op.body)
                    trips = (_trip_count(by_name[cm.group(1)])
                             if cm and cm.group(1) in by_name else 1)
                if bm:
                    body_trips[bm.group(1)] = trips
                    cost.trip_counts[bm.group(1)] = trips
                cost.n_while += 1

    memo: dict[str, tuple] = {}

    def comp_cost(name: str) -> tuple:
        if name in memo:
            return memo[name]
        c = by_name.get(name)
        if c is None:
            return (0.0, 0.0, {})
        memo[name] = (0.0, 0.0, {})  # cycle guard
        flops = 0.0
        hbm = 0.0
        coll: dict[str, float] = defaultdict(float)
        for op in c.ops:
            if op.opcode == "dot":
                f = _dot_flops(op, c.symbols)
                flops += f
                hbm += _operand_bytes(op, c.symbols) + _parse_shape(op.out_shape)[0]
            elif op.opcode == "fusion":
                hbm += _fusion_bytes(op, c.symbols, by_name)
            elif op.opcode in ("dynamic-slice", "slice"):
                hbm += 2 * _parse_shape(op.out_shape)[0]   # read + write slice
            elif op.opcode == "dynamic-update-slice":
                # reads the update operand, writes the slice region
                names = _operand_names(op)
                upd = (symbols_b := c.symbols).get(names[1]) if len(names) > 1 else None
                hbm += 2 * (_parse_shape(upd)[0] if upd else 0.0)
            elif op.opcode == "custom-call":
                hbm += _operand_bytes(op, c.symbols) + _parse_shape(op.out_shape)[0]
            elif op.opcode == "convolution":
                out_b, _, out_dims = _parse_shape(op.out_shape)
                ops_names = _OPERANDS_RE.findall(op.body.split(",")[0])
                rhs = c.symbols.get(ops_names[1]) if len(ops_names) > 1 else None
                k_elems = 1
                if rhs:
                    _, _, rd = _parse_shape(rhs)
                    for d in rd:
                        k_elems *= d
                out_elems = 1
                for d in out_dims:
                    out_elems *= d
                o_feat = out_dims[-1] if out_dims else 1
                flops += 2.0 * out_elems * (k_elems / max(o_feat, 1))
                hbm += _operand_bytes(op, c.symbols) + out_b
            base = op.opcode.replace("-start", "")
            if base in COLLECTIVES:
                b = _collective_wire_bytes(op, c.symbols, num_devices)
                coll[base] += b
                cost.collective_ops.append(
                    (c.name, base, op.out_shape.strip(), b))
            # recurse into called computations
            for attr in _CALL_ATTR_RE.finditer(op.body):
                sub = attr.group(1)
                if sub == name or sub not in by_name:
                    continue
                mult = body_trips.get(sub, 1) if op.opcode == "while" else 1
                sf, sh, sc = comp_cost(sub)
                flops += sf * mult
                hbm += sh * mult
                for k, v in sc.items():
                    coll[k] += v * mult
        memo[name] = (flops, hbm, dict(coll))
        return memo[name]

    for c in comps:
        if c.is_entry:
            f, h, col = comp_cost(c.name)
            cost.flops = f
            cost.bytes_hbm = h
            cost.collective_bytes = col
            break
    return cost


_OPERAND_BYTES_RE = re.compile(r"^bytes accessed(\d+)\{\}$")
_UTILIZATION_RE = re.compile(r"^utilization(\d+)\{\}$")


def normalize_cost_analysis(ca) -> dict:
    """Normalise ``Compiled.cost_analysis()`` into a structured dict.

    XLA's estimate arrives as a flat property map (``None`` when the
    backend doesn't implement it) with per-operand keys spelled
    ``"bytes accessed0{}"`` / ``"bytes accessedout{}"``.  Returns::

        {"flops": float, "bytes": float, "transcendentals": float,
         "operand_bytes": {0: ..., 1: ...}, "output_bytes": float,
         "utilization": {0: ..., 1: ...}}

    Missing keys become 0.0 / empty maps — an empty module (or a backend
    with no cost model) yields the all-zero record, never a KeyError.
    jax-free on purpose: the parsing is testable without a compile.
    """
    if ca is None:
        ca = {}
    operand_bytes: dict[int, float] = {}
    utilization: dict[int, float] = {}
    for key, val in ca.items():
        m = _OPERAND_BYTES_RE.match(key)
        if m:
            operand_bytes[int(m.group(1))] = float(val)
            continue
        m = _UTILIZATION_RE.match(key)
        if m:
            utilization[int(m.group(1))] = float(val)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "operand_bytes": operand_bytes,
        "output_bytes": float(ca.get("bytes accessedout{}", 0.0)),
        "utilization": utilization,
    }


_CONVERT_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*f32(\[[\d,]*\])(?:\{[^}]*\})?\s+convert\(%?([\w.\-]+)\)")


def cpu_bf16_promotion_bytes(text: str, min_bytes: int = 1 << 26) -> float:
    """XLA:CPU float-normalization promotes loop-carried bf16 buffers to
    f32 work copies (bf16 compute is unsupported on CPU).  On TPU these
    buffers stay bf16 and the extra f32 copy does not exist.

    Two modes (caller picks by step kind):
    - ``strict=True`` (training): only converts in entry / while-body
      computations — backward-pass f32 gradient upcasts are REAL on TPU
      too, so fusion-internal converts must not be subtracted;
    - ``strict=False`` (prefill/decode): forward-only steps hold no
      legitimate large f32 state, so every large f32-convert-of-bf16
      (deduped by source) is a CPU promotion artifact.  Callers floor the
      corrected liveness at args+outputs.
    """
    return _promotion_bytes(text, min_bytes, strict=True)


def cpu_bf16_promotion_bytes_serving(text: str,
                                     min_bytes: int = 1 << 26) -> float:
    return _promotion_bytes(text, min_bytes, strict=False)


def _promotion_bytes(text: str, min_bytes: int, strict: bool) -> float:
    comps = _split_computations(text)
    loopish = {c.name for c in comps if c.is_entry}
    for c in comps:
        for op in c.ops:
            if op.opcode == "while":
                for m in _CALL_ATTR_RE.finditer(op.body):
                    loopish.add(m.group(1))
    seen_src: set = set()
    excess = 0.0
    for comp in comps:
        if strict and comp.name not in loopish:
            continue
        for op in comp.ops:
            if op.opcode != "convert":
                continue
            out_b, dt, _ = _parse_shape(op.out_shape)
            if dt != "f32" or out_b < min_bytes:
                continue
            srcs = _OPERANDS_RE.findall(op.body)
            if not srcs or srcs[0] in seen_src:
                continue
            src_shape = comp.symbols.get(srcs[0], "")
            if src_shape.startswith("bf16"):
                seen_src.add(srcs[0])
                excess += out_b
    return excess


def largest_tensors(text: str, top: int = 25) -> list[tuple[float, str, str]]:
    """(bytes, computation, op-line-head) for the biggest tensors in the
    module — quick memory-offender triage for the dry-run fix loop."""
    out = []
    for c in _split_computations(text):
        for op in c.ops:
            b, dt, dims = _parse_shape(op.out_shape)
            if b > 0:
                out.append((b, c.name, f"{op.name} = {op.out_shape} {op.opcode}"))
    out.sort(key=lambda t: -t[0])
    return out[:top]
