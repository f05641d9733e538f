"""Reduces a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take: device busy time, time per compiled program and per device
operation, and the longest idle gaps with what the host was doing in
them.

The device side is read from the TPU planes (``/device:TPU:<n>``): their
``XLA Modules`` line holds one event per execution of a compiled program
(named after the jitted function, e.g. ``jit__step(<id>)``), and their
``XLA Ops`` line one event per device operation, named by its HLO
instruction (``%decode_attention_paged.7``; the trace gives the whole
instruction text, which is cut to the name).  Busy time is the union of
the operation intervals, averaged over the chips traced.  Control-flow
operations (a scan's ``%while``) span the operations of their body, so
they count towards busy time but not in the per-operation totals.  The
host side is the harness's own spans (``window.ANNOTATIONS``).
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_SPANS = ("submit", "tick", "retire-check")
CONTAINERS = ("%while", "%conditional", "%call")


def load(path) -> dict:
    """Events of the trace at ``path``: ``{"ops": {chip: [...]},
    "modules": {chip: [...]}, "host": [...]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"ops": {}, "modules": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            chip = plane.name[len(DEVICE_PREFIX):]
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    out[key][chip] = [(e.name.split(" = ")[0], e.start_ns,
                                       e.duration_ns) for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if e.name in HOST_SPANS]
    return out


def union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged ``(start_ns, end_ns)`` intervals covered by the events."""
    spans = sorted((s, s + d) for _, s, d in events)
    merged: List[List[float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _totals(events: List[Event]) -> Dict[str, List[float]]:
    t: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, _, d in events:
        t[name][0] += 1
        t[name][1] += d / 1e9
    return dict(t)


def _label(gap: Tuple[float, float], host: List[Event]) -> str:
    """The host span that overlaps ``gap`` most, or ``"none"``."""
    best, label = 0.0, "none"
    for name, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, label = ov, name
    return label


def reduce(ev: dict, window_s: float, top: int = 10) -> dict:
    """Busy seconds per chip (averaged), per-program and per-op totals
    (summed over chips), and the breakdown the result line carries."""
    chips = sorted(ev["ops"])
    busy = []
    ops: List[Event] = []
    modules: List[Event] = []
    gaps: List[Tuple[float, float, float]] = []
    for c in chips:
        iv = union(ev["ops"][c])
        busy.append(sum(e - s for s, e in iv) / 1e9)
        ops += [o for o in ev["ops"][c] if not o[0].startswith(CONTAINERS)]
        modules += ev["modules"].get(c, [])
        gaps += [(s1 - e0, e0, s1) for (_, e0), (s1, _) in zip(iv, iv[1:])]
    op_t = _totals(ops)
    gaps = sorted(gaps, reverse=True)[:top]
    return {
        "chips": len(chips),
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "window_s": window_s,
        "modules": _totals(modules),
        "ops": op_t,
        "breakdown": {
            "device_ops": [[n, s] for n, (_, s) in sorted(
                op_t.items(), key=lambda kv: -kv[1][1])[:top]],
            "idle_gaps": [[_label((s, e), ev["host"]), d / 1e9]
                          for d, s, e in gaps],
        },
    }


def _time(totals: dict, part: str) -> Tuple[int, float]:
    n, s = 0, 0.0
    for name, (c, t) in totals.items():
        if part in name:
            n, s = n + c, s + t
    return n, s


def module_time(tr: dict, part: str) -> Tuple[int, float]:
    """(executions, seconds) of the compiled programs whose name holds
    ``part``."""
    return _time(tr["modules"], part)


def op_time(tr: dict, part: str) -> Tuple[int, float]:
    """(executions, seconds) of the device operations whose name holds
    ``part``."""
    return _time(tr["ops"], part)
