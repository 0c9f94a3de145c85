"""The measured window: one load generator in the process that serves.

A system under test offers ``submit(uid, due) -> bool`` (False: refused),
``pump()`` (one step of its own loop), ``busy`` (work queued or in flight),
``queued`` (admitted and not yet dispatched), ``drain()`` and
``delivered``, a dict of uid -> delivery time that it fills as answers are
handed back. Times are ``time.monotonic`` seconds.

``closed``: ``outstanding`` requests are kept in the system; each answer is
replaced by a new request at once. Its metric is answers delivered inside
the window over the window's length.

``open``: requests are due on a schedule from ``arrivals``, stamped with
their due time whether or not the generator is on time, and submitted when
due. Every scheduled request falls due inside the window; one that is due
when the window closes but was not yet offered (the last tick's step or
sleep can run past the close) is offered then, late. After that nothing new
is offered; what is still open is drained and counted, so a stall shows in
the tail.

With a ``tracer`` the profiler runs over the last ``trace_s`` seconds of
the window and the drain after it; ``span(name)`` marks there what the host
is doing: ``submit``, ``step`` and ``gen_sleep`` inside one ``window`` span
that covers the traced part of the window, then ``drain``.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from harness import arrivals

POLL_S = 5e-4  # the generator's sleep while the system has work
TRACE_S = 8.0  # traced seconds at the end of a window


def no_span(name):
    return contextlib.nullcontext()


class Closed:
    def __init__(self, system, traffic: dict, seconds: float, seed: int):
        self.system, self.n_out = system, int(traffic["outstanding"])
        self.uid = 0

    def begin(self, t0: float, span) -> None:
        with span("submit"):
            for _ in range(self.n_out):
                self.system.submit(self.uid, t0)
                self.uid += 1

    def tick(self, end: float, span) -> None:
        with span("step"):
            self.system.pump()
        open_now = self.uid - len(self.system.delivered)
        if open_now < self.n_out:
            with span("submit"):
                now = time.monotonic()
                for _ in range(self.n_out - open_now):
                    self.system.submit(self.uid, now)
                    self.uid += 1

    def close(self, end: float) -> dict:
        done = sum(1 for t in self.system.delivered.values() if t < end)
        return {"offered": self.uid, "completed_in_window": done}


class Open:
    def __init__(self, system, traffic: dict, seconds: float, seed: int):
        self.system = system
        self.due = arrivals.schedule(traffic, seconds, seed)
        self.lag = np.zeros(len(self.due))
        self.refused, self.i = [], 0

    def begin(self, t0: float, span) -> None:
        self.due_at = t0 + self.due

    def _offer_due(self, upto: float, span) -> None:
        """Submit every request not yet offered that is due by ``upto``."""
        n, due_at = len(self.due_at), self.due_at
        now = time.monotonic()
        if self.i < n and due_at[self.i] <= upto:
            with span("submit"):
                while self.i < n and due_at[self.i] <= upto:
                    self.lag[self.i] = now - due_at[self.i]
                    if not self.system.submit(self.i, float(due_at[self.i])):
                        self.refused.append(self.i)
                    self.i += 1

    def tick(self, end: float, span) -> None:
        n, due_at = len(self.due_at), self.due_at
        self._offer_due(time.monotonic(), span)
        with span("step"):
            self.system.pump()
        now = time.monotonic()
        wait = (due_at[self.i] if self.i < n else end) - now
        if self.system.busy:
            wait = min(wait, POLL_S)
        wait = min(wait, end - now)
        if wait > 0:
            with span("gen_sleep"):
                time.sleep(wait)

    def close(self, end: float) -> dict:
        before = self.i
        self._offer_due(end, no_span)
        n = len(self.due)
        return {"offered": n, "due_at": self.due_at, "refused": self.refused,
                "offered_at_close": self.i - before,
                "open_at_end": n - len(self.refused)
                - len(self.system.delivered),
                "queued_at_end": self.system.queued,
                "submit_lag_p95_s": float(np.percentile(self.lag, 95))
                if n else 0.0}


def run(system, traffic: dict, seconds: float, seed: int, tracer=None,
        span=no_span, trace_s: float = TRACE_S) -> dict:
    """Drive ``seconds`` of ``traffic``; ``tracer`` is a context manager
    that profiles what runs inside it."""
    drive = {"closed": Closed, "open": Open}[traffic["loop"]](
        system, traffic, seconds, seed)
    t0 = time.monotonic()
    end = t0 + seconds
    traced_from = max(t0, end - trace_s) if tracer else end
    drive.begin(t0, no_span)
    while time.monotonic() < traced_from:
        drive.tick(end, no_span)
    with tracer or contextlib.nullcontext():
        with span("window"):
            while time.monotonic() < end:
                drive.tick(end, span)
        out = drive.close(end)
        with span("drain"):
            system.drain()
    out.update(t0=t0, seconds=seconds)
    return out
