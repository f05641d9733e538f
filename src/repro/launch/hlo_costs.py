"""Trip-count-aware cost model over compiled (optimized) HLO text.

Why this exists: ``compiled.cost_analysis()`` counts the body of a
``while`` loop ONCE, but every model here scans over layers
(``lax.scan`` -> while), so XLA's FLOPs/bytes/collectives understate the
true per-step cost by ~num_layers.  The roofline must not inherit that
error, so this module re-derives the three terms from the HLO itself:

  * parse the module into computations + a per-computation symbol table
    (every HLO value's type is declared at its definition site),
  * build the call graph (while/fusion/call/conditional/to_apply) and
    propagate an *execution multiplier* down it — a while body's
    multiplier is its trip count (parsed from the loop-condition
    computation's integer constant), fusions/calls inherit the caller's,
  * FLOPs    = sum over `dot`/`convolution` ops of 2*prod(out)*K,
    scaled by the owning computation's multiplier (MXU work),
  * HBM bytes = sum over *fusion-boundary* ops (operands + result of
    each top-level op; fusion internals live in registers/VMEM), scaled,
  * collective wire bytes = ring-model bytes per device per op, scaled.

Trip-count parse: for ``while(...), condition=%c, body=%b`` the condition
computation of a lax.scan compares the induction variable against a
constant; we take the largest integer constant in %c (direction LT ->
exactly the scan length).  If none is found the multiplier falls back
to 1 and the op is recorded in ``warnings``.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
    "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0,
    "u1": 1, "s1": 1, "s2": 1, "u2": 1,
}

# Hardware peak table shared by the dryrun estimator and the serving
# roofline accountant (runtime/roofline.py), keyed by
# ``jax.Device.device_kind``.  Values are per chip.  A device kind that
# is not listed is an error (:func:`hw_peaks`): no accelerator inherits
# another's peaks.
V5E = "TPU v5 lite"
HW_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s, 1,600 Gbit/s (200 GB/s) chip-to-chip interconnect
    V5E: {"name": "tpu-v5e", "peak_flops": 197e12, "hbm_bw": 819e9,
          "ici_bw": 200e9},
    # The host CPU, for tests only: a nominal dev-box figure, neither
    # published nor measured, so CPU MBU numbers show shape, not magnitude
    "cpu": {"name": "cpu-host (test figure)", "peak_flops": 2.0e11,
            "hbm_bw": 5.0e10, "ici_bw": 1e9},
}


def hw_peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`HW_PEAKS` row of ``device_kind``; raises ``KeyError``
    for a device without published peaks in the table."""
    if device_kind not in HW_PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add its published figures to HW_PEAKS "
                       f"(have {sorted(HW_PEAKS)})")
    return HW_PEAKS[device_kind]


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float = 0.0,
                   hw: Optional[Dict[str, float]] = None) -> Dict[str, object]:
    """Classic roofline decomposition: time lower bounds per resource and
    the binding one.  ``hw`` is a row of :data:`HW_PEAKS` (default v5e);
    the same terms drive ``dryrun`` estimates and the live serving
    accountant, so "achieved vs roofline" means one thing repo-wide."""
    hw = hw or HW_PEAKS[V5E]
    compute_s = flops / hw["peak_flops"]
    memory_s = hbm_bytes / hw["hbm_bw"]
    collective_s = wire_bytes / hw["ici_bw"] if wire_bytes else 0.0
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    bottleneck = max(terms, key=lambda k: terms[k])
    terms["bound_s"] = terms[bottleneck]
    terms["bottleneck"] = bottleneck.replace("_s", "")
    return terms


_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COMP_HDR_RE = re.compile(r"^(ENTRY\s+)?%?([\w\.\-~]+)\s*\(.*\)\s*->")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-~]+)\s*=\s*(.*)$")
_CALL_ATTR_RE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w\.\-~]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLED_RE = re.compile(r"called_computations=\{([^}]*)\}")
_CONST_INT_RE = re.compile(r"constant\((\d+)\)")
_RG_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_RG_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

# ops that are free / bookkeeping at the fusion boundary.  while/call/
# conditional carries are buffer-aliased in place by XLA — the traffic is
# whatever the *body* ops actually touch, which we count separately.
_FREE_OPS = {
    "parameter", "get-tuple-element", "tuple", "constant", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "rng-bit-generator",
    "get-dimension-size", "opt-barrier", "custom-call", "while", "call",
    "conditional",
}


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a plain dict (empty when XLA
    provides nothing)."""
    return dict(compiled.cost_analysis() or {})


def _type_bytes(type_str: str) -> int:
    """Sum bytes over all array shapes inside an HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _type_dims(type_str: str) -> Tuple[Tuple[int, ...], str]:
    """First array shape inside a type string -> (dims, dtype)."""
    m = _SHAPE_RE.search(type_str)
    if not m:
        return (), ""
    dt, dims = m.groups()
    return tuple(int(d) for d in dims.split(",") if d), dt


@dataclass
class Op:
    name: str
    kind: str
    result_type: str
    operands: List[str]
    raw: str
    is_root: bool = False


@dataclass
class Computation:
    name: str
    is_entry: bool = False
    ops: List[Op] = field(default_factory=list)
    types: Dict[str, str] = field(default_factory=dict)   # value -> type str


def _split_type_and_op(rhs: str) -> Tuple[str, str, str]:
    """rhs of '=': '<type> <opname>(<args>), attrs' -> (type, op, rest)."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    type_str = rhs[:i + 1]
                    rest = rhs[i + 1:].strip()
                    break
        else:
            return "", "", ""
    else:
        # scalar/array type ends at first space that precedes the op name
        sp = rhs.find(" ")
        if sp < 0:
            return "", "", ""
        type_str = rhs[:sp]
        rest = rhs[sp + 1:].strip()
    m = re.match(r"([a-zA-Z][\w\-]*)\(", rest)
    if not m:
        return type_str, "", rest
    return type_str, m.group(1), rest


def _operand_names(rest: str, opname: str) -> List[str]:
    """Names referenced inside the op's top-level parens."""
    start = rest.find(opname + "(") + len(opname)
    depth = 0
    end = start
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    args = rest[start + 1:end]
    return re.findall(r"%([\w\.\-~]+)", args)


def parse_module(hlo_text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped.startswith("}"):
            cur = None
            continue
        if stripped.endswith("{") and "->" in stripped:
            m = _COMP_HDR_RE.match(stripped)
            if m:
                cur = Computation(m.group(2), is_entry=bool(m.group(1)))
                comps[cur.name] = cur
            continue
        if cur is None or " = " not in stripped:
            continue
        m = _OP_RE.match(stripped)
        if not m:
            continue
        name, rhs = m.groups()
        type_str, opkind, rest = _split_type_and_op(rhs)
        if not opkind:
            continue
        operands = _operand_names(rest, opkind) if opkind not in (
            "parameter", "constant", "iota") else []
        op = Op(name, opkind, type_str, operands, stripped,
                is_root=stripped.startswith("ROOT "))
        cur.ops.append(op)
        cur.types[name] = type_str
    return comps


def _callees(op: Op) -> List[str]:
    names = _CALL_ATTR_RE.findall(op.raw)
    bm = _BRANCHES_RE.search(op.raw)
    if bm:
        names += re.findall(r"%([\w\.\-~]+)", bm.group(1))
    cm = _CALLED_RE.search(op.raw)
    if cm:
        names += re.findall(r"%([\w\.\-~]+)", cm.group(1))
    return names


def _trip_count(cond: Computation, warnings: List[str]) -> int:
    consts = [int(v) for op in cond.ops
              for v in _CONST_INT_RE.findall(op.raw)]
    if not consts:
        warnings.append(f"no trip count in condition {cond.name}; using 1")
        return 1
    return max(consts)


def multipliers(comps: Dict[str, Computation]
                ) -> Tuple[Dict[str, float], List[str]]:
    """Execution multiplier per computation, propagated from ENTRY."""
    mult: Dict[str, float] = defaultdict(float)
    warnings: List[str] = []
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return {}, ["no ENTRY computation found"]

    def visit(comp: Computation, m: float):
        if m <= 0:
            return
        mult[comp.name] += m
        for op in comp.ops:
            callees = _callees(op)
            if not callees:
                continue
            if op.kind == "while":
                body = cond = None
                bm = re.search(r"body=%?([\w\.\-~]+)", op.raw)
                cm = re.search(r"condition=%?([\w\.\-~]+)", op.raw)
                body = comps.get(bm.group(1)) if bm else None
                cond = comps.get(cm.group(1)) if cm else None
                trips = _trip_count(cond, warnings) if cond else 1
                if body:
                    visit(body, m * trips)
                if cond:
                    visit(cond, m * (trips + 1))
            else:
                for cn in callees:
                    callee = comps.get(cn)
                    if callee:
                        visit(callee, m)

    visit(entry, 1.0)
    return dict(mult), warnings


def _operand_type(comp: Computation, op: Op, idx: int) -> str:
    if idx >= len(op.operands):
        return ""
    name = op.operands[idx]
    t = comp.types.get(name, "")
    if op.kind == "get-tuple-element":
        return t
    return t


def _gte_component(comp: Computation, op: Op) -> str:
    """Resolve the component type a get-tuple-element extracts."""
    return op.result_type


def _dot_flops(comp: Computation, op: Op) -> float:
    out_dims, _ = _type_dims(op.result_type)
    out_elems = math.prod(out_dims) if out_dims else 0
    lhs_t = comp.types.get(op.operands[0], "") if op.operands else ""
    lhs_dims, _ = _type_dims(lhs_t)
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", op.raw)
    k = 1
    if m and lhs_dims:
        for d in m.group(1).split(","):
            if d and int(d) < len(lhs_dims):
                k *= lhs_dims[int(d)]
    return 2.0 * out_elems * k


def _conv_flops(comp: Computation, op: Op) -> float:
    out_dims, _ = _type_dims(op.result_type)
    out_elems = math.prod(out_dims) if out_dims else 0
    # rhs = kernel (O, I, spatial...) under dim_labels; approximate with
    # kernel elems / out_channels as the per-output contraction length
    rhs_t = comp.types.get(op.operands[1], "") if len(op.operands) > 1 else ""
    rhs_dims, _ = _type_dims(rhs_t)
    if not rhs_dims:
        return 0.0
    k = math.prod(rhs_dims) / max(max(rhs_dims), 1)  # drop the largest (O)
    return 2.0 * out_elems * k


def _group_size(raw: str, total_devices: int) -> int:
    m = _RG_IOTA_RE.search(raw)
    if m:
        return int(m.group(2))
    m = _RG_LIST_RE.search(raw)
    if m:
        return len(m.group(1).split(","))
    return total_devices


def _collective_wire(op: Op, kind: str, total_devices: int) -> Tuple[float, float]:
    """(tensor_bytes, wire_bytes_per_device) for one collective op."""
    nbytes = _type_bytes(op.result_type)
    g = _group_size(op.raw, total_devices)
    frac = (g - 1) / g if g > 1 else 0.0
    if kind == "all-gather":
        wire = nbytes * frac                  # result = gathered tensor
    elif kind == "reduce-scatter":
        wire = nbytes * max(g - 1, 0)         # result = shard
    elif kind == "all-reduce":
        wire = 2.0 * nbytes * frac            # RS + AG
    elif kind == "all-to-all":
        wire = nbytes * frac
    else:                                     # collective-permute
        wire = float(nbytes)
    return float(nbytes), wire


_FUSION_KINDS = {"fusion"}


def _op_hbm_bytes(comp: Computation, op: Op,
                  comps: Dict[str, Computation]) -> float:
    """HBM traffic of one fusion-boundary op.

    Slice-aware: ``dynamic-slice`` reads only the slice; ``dynamic-update-
    slice`` writes only the update (XLA performs it in place).  For fusion
    ops, an operand whose every use inside the called computation is a
    dynamic-slice contributes slice-sized reads, and a ROOT that is a
    dynamic-update-slice contributes update-sized writes — this is exactly
    the lax.scan per-iteration slice/stack pattern, which would otherwise
    be overcounted by ~trip_count x tensor size.
    """
    kind = op.kind
    result = _type_bytes(op.result_type)
    if kind == "dynamic-slice":
        return 2.0 * result                       # read slice + write slice
    if kind == "dynamic-update-slice":
        upd = _type_bytes(comp.types.get(op.operands[1], "")) \
            if len(op.operands) > 1 else result
        return 2.0 * upd                          # read update + write in place
    if kind in _FUSION_KINDS:
        callee = None
        for cn in _callees(op):
            callee = comps.get(cn)
            break
        if callee is None:
            nbytes = result
            for on in op.operands:
                nbytes += _type_bytes(comp.types.get(on, ""))
            return float(nbytes)
        # map parameter index -> sliced-only?
        param_ops: Dict[int, str] = {}
        uses: Dict[str, List[Op]] = defaultdict(list)
        for iop in callee.ops:
            if iop.kind == "parameter":
                m = re.search(r"parameter\((\d+)\)", iop.raw)
                if m:
                    param_ops[int(m.group(1))] = iop.name
            for on in iop.operands:
                uses[on].append(iop)
        nbytes = 0.0
        root = next((o for o in callee.ops if o.is_root),
                    callee.ops[-1] if callee.ops else None)
        for i, on in enumerate(op.operands):
            full = _type_bytes(comp.types.get(on, ""))
            pname = param_ops.get(i)
            puses = uses.get(pname, []) if pname else []
            if not puses:
                nbytes += full
                continue
            # per-use accounting: a big buffer touched only through
            # dynamic-slice reads and/or in-place dynamic-update-slice
            # writes costs slice-sized traffic, not the full buffer
            acc = 0.0
            sliced_only = True
            for u in puses:
                if u.kind == "dynamic-slice":
                    acc += _type_bytes(u.result_type)
                elif (u.kind == "dynamic-update-slice" and u.operands
                      and u.operands[0] == pname):
                    upd = _type_bytes(callee.types.get(u.operands[1], "")) \
                        if len(u.operands) > 1 else full
                    acc += upd
                else:
                    sliced_only = False
                    break
            nbytes += acc if sliced_only else full
        # result side: an in-place dynamic-update-slice root (possibly
        # through elementwise/convert wrappers) writes only the update
        rroot = root
        seen = set()
        while rroot is not None and rroot.kind in ("convert", "bitcast",
                                                   "copy", "tuple") \
                and rroot.operands and rroot.name not in seen:
            seen.add(rroot.name)
            nxt = None
            for o2 in callee.ops:
                if o2.name == rroot.operands[0]:
                    nxt = o2
                    break
            rroot = nxt
        if rroot is not None and rroot.kind == "dynamic-update-slice":
            upd = _type_bytes(callee.types.get(rroot.operands[1], "")) \
                if len(rroot.operands) > 1 else result
            nbytes += upd
        else:
            nbytes += result
        return nbytes
    nbytes = float(result)
    for on in op.operands:
        nbytes += _type_bytes(comp.types.get(on, ""))
    return nbytes


def analyze(hlo_text: str, total_devices: int) -> Dict[str, object]:
    """Full trip-count-aware analysis of one compiled HLO module.

    Returns dict with: flops (MXU, per device), hbm_bytes (fusion-boundary,
    per device), collectives {kind: {count, executions, tensor_bytes,
    wire_bytes}}, wire_bytes total, warnings, dot_count.
    """
    comps = parse_module(hlo_text)
    mult, warnings = multipliers(comps)

    # computations reached via fusion `calls=` are VMEM-internal: exclude
    # them from byte accounting (their boundary is the fusion op itself)
    fusion_internal: set = set()
    for comp in comps.values():
        for op in comp.ops:
            if op.kind in _FUSION_KINDS:
                for cn in _callees(op):
                    fusion_internal.add(cn)

    flops = 0.0
    hbm_bytes = 0.0
    dot_count = 0
    coll: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "executions": 0.0, "tensor_bytes": 0.0,
                 "wire_bytes": 0.0})

    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m <= 0:
            continue
        boundary = comp.name not in fusion_internal
        for op in comp.ops:
            kind = op.kind
            if kind in ("dot",):
                flops += m * _dot_flops(comp, op)
                dot_count += 1
            elif kind == "convolution":
                flops += m * _conv_flops(comp, op)
            base = kind.replace("-start", "")
            if base in COLLECTIVE_OPS and not kind.endswith("-done"):
                tb, wire = _collective_wire(op, base, total_devices)
                if tb > 0:
                    st = coll[base]
                    st["count"] += 1
                    st["executions"] += m
                    st["tensor_bytes"] += m * tb
                    st["wire_bytes"] += m * wire
            if boundary and kind not in _FREE_OPS \
                    and not kind.endswith("-done"):
                hbm_bytes += m * _op_hbm_bytes(comp, op, comps)

    return {
        "flops": flops,
        "hbm_bytes": hbm_bytes,
        "dot_count": dot_count,
        "collectives": {k: dict(v) for k, v in coll.items()},
        "wire_bytes": sum(v["wire_bytes"] for v in coll.values()),
        "warnings": warnings,
        "num_computations": len(comps),
    }
