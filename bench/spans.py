"""Puts the device's idle time in a traced slice down to the scheduler
phase the host was in.

The program marks its scheduler phases as ``sched.*`` profiler spans
(``repro.runtime.telemetry.SPANS``) on the host plane of the trace, on
the same clock as the device's operations.  For each chip this walks the
gaps between consecutive device busy intervals (the union that
``trace_reduce`` computes) and gives each instant of a gap to the
innermost ``sched.*`` span open at that instant, or to ``untraced``
when none is open.  Three groups sum to the slice's inter-op idle:

- ``admit``: a ``sched.admit`` span is open (the instant may sit in one
  of its children: prefix lookup, page allocation, prefix registration,
  page-table updates);
- ``tick``: some other ``sched.*`` span is open;
- ``untraced``: none is (the harness's or the engine's own host code).

Idle at the slice's two edges, before the first operation and after the
last, is in no gap.  A program that writes no ``sched.*`` span gives no
attribution at all (None), and its readers report nothing.  The idle
time by innermost span is printed on stderr, not in the result line.
"""
from __future__ import annotations

import bisect
import pathlib
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import trace_reduce

try:
    from repro.runtime.telemetry import SPANS
except ImportError:             # a program that marks no scheduler phase
    SPANS = ()

ADMIT = "sched.admit"
# admission's device dispatches, left out of its host time
DISPATCHES = ("sched.prefill", "sched.suffix_prefill")
GROUPS = ("admit", "tick", "untraced")

Span = Tuple[str, float, float]                 # name, start_ns, end_ns


def load(path) -> dict:
    """From the trace at ``path``: the ``sched.*`` spans of the host
    plane, and per chip the device operations (containers included, as
    ``trace_reduce`` counts busy time)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out = {"spans": [], "ops": {}}
    for plane in pd.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            chip = plane.name[len(trace_reduce.DEVICE_PREFIX):]
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    out["ops"][chip] = [(e.name, e.start_ns, e.duration_ns)
                                        for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events if e.name in SPANS]
    return out


def flatten(spans: List[Span]) -> List[Tuple[float, float, str, bool]]:
    """The host timeline as ``(start, end, innermost, under_admit)``
    pieces, one wherever some span is open: ``innermost`` is the open
    span that began last (the shorter one on a tie), ``under_admit``
    whether a ``sched.admit`` span is open too."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    starts = sorted(range(len(spans)), key=lambda i: spans[i][1])
    pieces = []
    open_: List[int] = []
    k = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(starts) and spans[starts[k]][1] <= t0:
            open_.append(starts[k])
            k += 1
        open_ = [i for i in open_ if spans[i][2] > t0]
        if not open_:
            continue
        inner = max(open_, key=lambda i: (spans[i][1], -spans[i][2]))
        pieces.append((t0, t1, spans[inner][0],
                       any(spans[i][0] == ADMIT for i in open_)))
    return pieces


def attribute_gaps(gaps: List[Tuple[float, float]], spans: List[Span]
                   ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Nanoseconds of ``gaps`` per group (``GROUPS``) and per innermost
    span (``untraced`` where no span is open)."""
    pieces = flatten(spans)
    starts = [p[0] for p in pieces]
    groups = dict.fromkeys(GROUPS, 0.0)
    by_span: Dict[str, float] = defaultdict(float)
    for g0, g1 in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(pieces) and pieces[i][0] < g1:
            p0, p1, name, under_admit = pieces[i]
            ov = min(g1, p1) - max(g0, p0)
            if ov > 0:
                covered += ov
                by_span[name] += ov
                groups["admit" if under_admit else "tick"] += ov
            i += 1
        groups["untraced"] += (g1 - g0) - covered
        by_span["untraced"] += (g1 - g0) - covered
    return groups, dict(by_span)


def admit_host_ns(spans: List[Span]) -> List[float]:
    """Each ``sched.admit`` span's length less the admission dispatches
    (``DISPATCHES``) inside it: the host's own work of one admission."""
    inner = sorted((s, e) for n, s, e in spans if n in DISPATCHES)
    starts = [s for s, _ in inner]
    out = []
    for name, s, e in spans:
        if name != ADMIT:
            continue
        i = bisect.bisect_left(starts, s)
        took = 0.0
        while i < len(inner) and inner[i][0] < e:
            took += min(e, inner[i][1]) - inner[i][0]
            i += 1
        out.append((e - s) - took)
    return out


def attribute(ev: dict, window_s: float) -> Optional[dict]:
    """The slice's idle seconds per group and per innermost span, summed
    over chips, and the host milliseconds of each admission; None when
    the trace holds no ``sched.*`` span or no device operation."""
    if not ev["spans"] or not ev["ops"]:
        return None
    gaps = []
    for chip in sorted(ev["ops"]):
        iv = trace_reduce.union(ev["ops"][chip])
        gaps += [(e0, s1) for (_, e0), (s1, _) in zip(iv, iv[1:])]
    groups, by_span = attribute_gaps(gaps, ev["spans"])
    return {"chips": len(ev["ops"]), "window_s": window_s,
            "idle_s": {k: v / 1e9 for k, v in groups.items()},
            "by_span_s": {k: v / 1e9 for k, v in by_span.items()},
            "admit_host_ms": [v / 1e6 for v in admit_host_ns(ev["spans"])]}


def _trace_path() -> Optional[pathlib.Path]:
    """The trace file of the run being read.  The reader context holds
    the reduced trace but not its file: ``run.run_cell`` keeps the
    directory in its local ``trace_dir`` while the readers run."""
    frame = sys._getframe(1)
    while frame is not None:
        d = frame.f_locals.get("trace_dir")
        if frame.f_code.co_name == "run_cell" and d is not None:
            paths = sorted(pathlib.Path(d).rglob("*.xplane.pb"))
            return paths[-1] if paths else None
        frame = frame.f_back
    return None


def _report(got: dict) -> None:
    """The slice's idle time by innermost span, on stderr."""
    w = got["chips"] * got["window_s"]
    print("idle by innermost host span (% of slice): " + ", ".join(
        f"{k} {100 * v / w:.3f}" for k, v in sorted(
            got["by_span_s"].items(), key=lambda kv: -kv[1])),
        file=sys.stderr, flush=True)


def attribution(ctx) -> Optional[dict]:
    """:func:`attribute` for the run whose reader context is ``ctx``,
    computed once per run (and reported on stderr then)."""
    if not hasattr(ctx, "_span_attribution"):
        path = _trace_path()
        got = None
        if path is not None:
            got = attribute(load(path), ctx.trace["window_s"])
            if got is not None:
                _report(got)
        ctx._span_attribution = got
    return ctx._span_attribution


def idle_share(ctx, group: str) -> Optional[float]:
    """Per cent of the slice (averaged over chips) in which the device
    sat idle between two operations with the host in ``group``."""
    got = attribution(ctx)
    if got is None or got["window_s"] <= 0:
        return None
    return 100.0 * got["idle_s"][group] / (got["chips"] * got["window_s"])
