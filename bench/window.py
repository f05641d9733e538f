"""Drives the program's scheduler through the measured window and stamps
every request on the host clock.

The window uses only the scheduler's serving surface: ``submit()``,
``tick()``, ``slots``, ``pending`` and each ``Request``'s ``output`` and
``done``; at the window's two edges it also reads the per-lane output
counts (``state["out_len"]``) after waiting for the device, so that a rate
counts exactly the tokens made inside the window.

Two arrival modes, from the mix's ``arrival``:

- ``backlog``: the queue is topped up to ``queue_per_lane`` x lanes after
  every tick.  The window opens after the first tick, once every lane
  holds a request.
- ``poisson``: requests are submitted when due (open loop).  Arrivals
  are laid out deck after deck (``traffic.py``) with a deck boundary at
  the window's start.  Arrivals start ``lead_s`` before the window (the
  tail of the deck before it), and the run goes on, arrivals included,
  until every request due in the window is done or ``drain_s`` has
  passed after the window.

With a trace, the backlog mode traces ``trace_s`` in the middle of the
window.  The Poisson mode traces ``trace_s`` of the same traffic after
the window, once every request due in it has been admitted: starting the
profiler holds the host for seconds, which would otherwise land in the
queue waits the trace run reports.

Host spans named ``submit``, ``tick`` and ``retire-check`` are written
into the profiler's trace, so that idle gaps on the device can be put
down to what the host was doing.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.serving.engine import Request

ANNOTATIONS = ("submit", "tick", "retire-check")

now = time.perf_counter


class CompileCounter:
    """Counts compilations (backend compiles and persistent-cache loads
    alike) while it is on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        self.count, self.seconds, self.on = 0, 0.0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_):
        if self.on and event in self.EVENTS:
            self.count += 1
            self.seconds += secs

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._event)


class Window:
    def __init__(self, sched, mix: dict, source, *, trace_dir=None,
                 trace_s: float = 3.0):
        self.sched, self.mix, self.source = sched, mix, source
        self.lanes = sched.max_slots
        self.stamps: Dict[int, dict] = {}
        self.requests: Dict[int, Request] = {}
        self.inflight: Dict[int, Request] = {}
        self.tick_no = 0
        self.trace_dir, self.trace_s = trace_dir, trace_s
        self.slice: Optional[dict] = None     # the traced slice, if any
        self._rec: Optional[dict] = None
        self.compiles = CompileCounter()
        self.min_queue: Optional[int] = None

    # -- host actions, each a span in the profiler's trace ------------------

    def submit(self, due: float, spec=None) -> int:
        spec = spec if spec is not None else next(self.source)
        r = Request(uid=spec.uid, prompt=spec.prompt,
                    max_new_tokens=spec.max_new,
                    temperature=float(self.mix["temperature"]))
        with jax.profiler.TraceAnnotation("submit"):
            self.sched.submit(r)
        self.requests[r.uid] = self.inflight[r.uid] = r
        self.stamps[r.uid] = {"due": due, "submit": now()}
        return r.uid

    def tick(self) -> List[int]:
        """One scheduler tick; returns the uids retired in it."""
        with jax.profiler.TraceAnnotation("tick"):
            self.sched.tick()
        t = now()
        self.tick_no += 1
        with jax.profiler.TraceAnnotation("retire-check"):
            stepped = []
            for slot, r in enumerate(self.sched.slots):
                if r is None:
                    continue
                st = self.stamps[r.uid]
                if "admit" not in st:
                    st.update(admit=t, admit_tick=self.tick_no, slot=slot)
                    if self._rec is not None:
                        self._rec["admissions"].append(len(r.prompt))
                stepped.append(r)
            done = [u for u, r in self.inflight.items() if r.done]
            for u in done:
                st = self.stamps[u]
                st.setdefault("admit", t)
                st.setdefault("admit_tick", self.tick_no)
                st.setdefault("slot", None)
                st["done"] = t
                del self.inflight[u]
                stepped.append(self.requests[u])
            if self._rec is not None and stepped:
                self._rec["steps"].append(
                    [len(r.prompt) + self.tick_no
                     - self.stamps[r.uid]["admit_tick"] + 1
                     for r in stepped])
        return done

    # -- window edges --------------------------------------------------------

    def produced(self) -> int:
        """Tokens the device has made for every request so far: waits for
        the device, then reads the live lanes' output counts."""
        state = jax.block_until_ready(self.sched.state)
        out_len = np.asarray(state["out_len"])
        live = sum(int(out_len[s]) for s, r in enumerate(self.sched.slots)
                   if r is not None)
        return live + sum(len(r.output) for r in self.requests.values()
                          if r.done)

    def _trace_edge(self, t: float, ready: bool):
        """Start the profiler once ``ready`` for ``trace_s`` seconds, each
        edge after waiting for the device."""
        if self.trace_dir is None:
            return
        if self._rec is None and self.slice is None and ready:
            jax.block_until_ready(self.sched.state)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._rec = {"t0": now(), "steps": [], "admissions": []}
        elif self._rec is not None and t >= self._rec["t0"] + self.trace_s:
            self._stop_trace()

    def _stop_trace(self):
        if self._rec is None:
            return
        jax.block_until_ready(self.sched.state)
        self._rec["t1"] = now()
        jax.profiler.stop_trace()
        self.slice, self._rec = self._rec, None

    # -- the two arrival modes ----------------------------------------------

    def run_backlog(self, seconds: float) -> dict:
        depth = self.mix["queue_per_lane"] * self.lanes

        def top_up():
            while len(self.sched.pending) < depth:
                self.submit(now())

        top_up()
        self.tick()
        top_up()
        n0 = self.produced()
        t0 = now()
        self.compiles.on = True
        trace_at = t0 + max(0.0, (seconds - self.trace_s) / 2)
        t = t0
        while t < t0 + seconds:
            self.tick()
            q = len(self.sched.pending)
            self.min_queue = q if self.min_queue is None else min(
                self.min_queue, q)
            top_up()
            t = now()
            self._trace_edge(t, t >= trace_at)
        self.compiles.on = False
        self._stop_trace()
        n1 = self.produced()
        t1 = now()
        in_window = [u for u, s in self.stamps.items()
                     if "done" in s and t0 <= s["done"] <= t1]
        return {"t0": t0, "t1": t1, "tokens": n1 - n0,
                "in_window": in_window, "unfinished": []}

    def run_poisson(self, seconds: float) -> dict:
        lead, drain = self.mix["lead_s"], self.mix["drain_s"]
        start = now()
        t0, t1 = start + lead, start + lead + seconds
        # the first deck ends where the window starts; its requests due
        # before the lead-in are never sent
        due = t0 - self.mix["deck"] / self.mix["rate_rps"]
        spec = next(self.source)
        in_window: List[int] = []
        traced = self.trace_dir is None
        while True:
            t = now()
            self.compiles.on = t0 <= t < t1
            while due <= t:
                if due >= start:
                    uid = self.submit(due, spec)
                    if t0 - 1e-9 <= due < t1 - 1e-9:
                        in_window.append(uid)
                due += spec.gap_s
                spec = next(self.source)
            traced = traced or self.slice is not None
            if t >= t1 and traced and all("done" in self.stamps[u]
                                          for u in in_window):
                break
            if t >= t1 + drain:
                break
            if self.sched.pending or any(r is not None
                                         for r in self.sched.slots):
                self.tick()
            else:
                time.sleep(min(max(due - t, 0.0), 1e-3))
            # the profiler's start holds the host for seconds: trace only
            # once the window has closed and all its requests are admitted,
            # so that no judged wait holds that stall
            self._trace_edge(now(), now() >= t1 and all(
                "admit" in self.stamps[u] for u in in_window))
        self.compiles.on = False
        self._stop_trace()
        return {"t0": t0, "t1": t1, "end": now(), "tokens": None,
                "in_window": in_window,
                "unfinished": [u for u in in_window
                               if "done" not in self.stamps[u]]}
