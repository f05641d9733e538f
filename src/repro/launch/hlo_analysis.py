"""Collective-traffic extraction from compiled HLO text.

``compiled.cost_analysis()`` reports FLOPs and HBM bytes but not collective
bytes, so the roofline's third term is derived by scanning the optimized
HLO for all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops, summing their tensor sizes, and converting to
per-link wire bytes with the standard ring factors:

    all-gather      output_bytes * (g-1)/g      (each chip receives this)
    reduce-scatter  input_bytes  * (g-1)/g
    all-reduce      2 * bytes * (g-1)/g         (RS + AG)
    all-to-all      bytes * (g-1)/g
    collective-permute  bytes

where g is the participant-group size parsed from replica_groups.
"""
from __future__ import annotations

import math
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# iota format: replica_groups=[8,64]<=[...]  -> 8 groups of 64
_RG_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
# explicit format: replica_groups={{0,1,2},{3,4,5}}
_RG_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")


def _shape_bytes(type_str: str) -> int:
    m = _SHAPE_RE.match(type_str)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _result_bytes(line: str) -> int:
    """Sum the bytes of the result type(s) on an HLO op line."""
    lhs = line.split(" = ", 1)
    if len(lhs) != 2:
        return 0
    rhs = lhs[1]
    # result type is everything up to the op name
    for op in _COLLECTIVES:
        idx = rhs.find(f" {op}")
        if idx < 0:
            idx = rhs.find(f"{op}(")
        if idx >= 0:
            type_part = rhs[:idx]
            return sum(_shape_bytes(s.group(0))
                       for s in _SHAPE_RE.finditer(type_part))
    return 0


def _group_size(line: str, total_devices: int) -> int:
    m = _RG_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _RG_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return total_devices


def analyze_collectives(hlo_text: str, total_devices: int
                        ) -> Dict[str, Dict[str, float]]:
    """Returns {op_kind: {count, tensor_bytes, wire_bytes_per_device}}."""
    stats: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "tensor_bytes": 0.0, "wire_bytes": 0.0})
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        for op in _COLLECTIVES:
            # match the op invocation, not a variable name
            if f"{op}(" not in s and f"{op}-start(" not in s \
                    and f"{op}-done(" not in s:
                continue
            if f"{op}-done(" in s:
                continue  # count start (has the shape) not done
            nbytes = _result_bytes(s)
            if nbytes == 0:
                continue
            g = _group_size(s, total_devices)
            frac = (g - 1) / g if g > 1 else 0.0
            if op == "all-gather":
                # result is the gathered tensor; each device receives
                # (g-1)/g of it over the wire
                wire = nbytes * frac
            elif op == "reduce-scatter":
                # result is the scattered shard; wire = shard * (g-1)
                wire = nbytes * max(g - 1, 0)
            elif op == "all-reduce":
                wire = 2.0 * nbytes * frac
            elif op == "all-to-all":
                wire = nbytes * frac
            else:  # collective-permute
                wire = float(nbytes)
            st = stats[op]
            st["count"] += 1
            st["tensor_bytes"] += nbytes
            st["wire_bytes"] += wire
            break
    return dict(stats)


def total_wire_bytes(stats: Dict[str, Dict[str, float]]) -> float:
    return sum(v["wire_bytes"] for v in stats.values())


def count_op(hlo_text: str, opname: str) -> int:
    return len(re.findall(rf"\b{re.escape(opname)}\(", hlo_text))


# an instruction with an array result: name, dims, opcode, operands
_DEF_RE = re.compile(r"^\s*(?:ROOT )?%(\S+) = [a-z0-9]+\[([0-9,]*)\]\S* "
                     r"([a-z][a-z0-9-]*)\(([^)]*)\)")
# what may have a whole buffer's shape without moving the buffer: the
# plumbing of tuples, loops and calls, a bitcast, a kernel, and an update
# in place (a dynamic-update-slice, or a fusion around one, whose inner
# instructions are checked as well)
_IN_PLACE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "while", "call", "conditional", "custom-call",
                 "opt-barrier", "fusion", "dynamic-update-slice"}


def whole_buffer_ops(hlo_text: str, shapes) -> List[str]:
    """Instructions of optimized HLO text that move a whole array of one
    of ``shapes`` (dims tuples, e.g. a KV pool and one layer of it): a
    copy, transpose, slice, conversion or any other instruction with such
    a result, and a dynamic-update-slice whose update is such an array
    (a layer written back).  Returns ``"<opcode> %<name> [dims]"`` for
    each; an empty list means the buffers are only updated in place."""
    shapes = {tuple(s) for s in shapes}
    dims, found = {}, []
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, d, op, args = m.groups()
        d = tuple(int(x) for x in d.split(",") if x)
        dims[name] = d
        update = re.findall(r"%([^\s,)]+)", args)[1:2] \
            if op == "dynamic-update-slice" else []
        if (d in shapes and op not in _IN_PLACE_OPS) or \
                any(dims.get(u) in shapes for u in update):
            found.append(f"{op} %{name} {list(d)}")
    return found
