"""Routed rows a held expert computed per call, over the run: the
program's routing counters ``moe.rows_here`` / ``moe.expert_calls``
(every layer call of prefill and decode, warm-up included), read from the
scheduler's registry, where the retirement fetches fold them.  None for
a program that keeps no such counters."""
import sys


def _scheduler():
    """The scheduler of the run being read: ``run.run_cell`` keeps it in
    its local ``sched`` while the readers run (the reader context does
    not carry it)."""
    frame = sys._getframe(1)
    while frame is not None:
        if frame.f_code.co_name == "run_cell" and "sched" in frame.f_locals:
            return frame.f_locals["sched"]
        frame = frame.f_back
    return None


def read(ctx):
    sched = _scheduler()
    if sched is None:
        return None
    snap = sched.metrics.snapshot()
    calls = snap.get("moe.expert_calls", 0)
    return snap.get("moe.rows_here", 0) / calls if calls else None
