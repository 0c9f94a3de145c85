"""The open loop offers every request of its schedule, also one that falls
due while the window's last step runs past the close."""
import time

import numpy as np

from harness import arrivals, loop


class SlowSystem:
    """Answers each request at the next pump; a pump takes ``step_s``."""

    def __init__(self, step_s: float):
        self.step_s, self.delivered, self.open = step_s, {}, []
        self.queued = 0

    def submit(self, uid, due):
        self.open.append(uid)
        return True

    def pump(self):
        time.sleep(self.step_s)
        now = time.monotonic()
        for uid in self.open:
            self.delivered[uid] = now
        self.open = []

    @property
    def busy(self):
        return bool(self.open)

    def drain(self):
        self.pump()


def test_request_due_at_the_close_is_offered_and_answered(monkeypatch):
    seconds = 0.3
    due = [0.0, 0.1, seconds - 1e-4]  # the last due as the last step runs
    monkeypatch.setattr(arrivals, "schedule", lambda t, s, seed: np.array(due))
    system = SlowSystem(step_s=0.08)
    out = loop.run(system, {"loop": "open"}, seconds, seed=1)
    assert out["offered"] == 3 and not out["refused"]
    assert sorted(system.delivered) == [0, 1, 2]
    assert out["offered_at_close"] == 1
    assert all(system.delivered[i] >= out["due_at"][i] for i in range(3))
