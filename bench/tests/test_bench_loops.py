"""Client loops: the open loop times from due times, the closed loop
keeps its clients busy."""
import threading
import time

import numpy as np

from bench import loops


class FakeService:
    """Answers each request ``delay`` s after it is submitted, one at a
    time on a thread, and stalls once for ``stall`` s at ``stall_at``."""

    def __init__(self, delay=0.002, stall_at=None, stall=0.0):
        self.results = {}
        self.lock = threading.Lock()
        self.queue = []
        self.delay, self.stall_at, self.stall = delay, stall_at, stall
        self.stop = False
        self.t0 = time.perf_counter()
        self.th = threading.Thread(target=self._loop, daemon=True)
        self.th.start()

    def submit(self, rid):
        with self.lock:
            self.queue.append(rid)
        return None

    def take_result(self, rid):
        with self.lock:
            return self.results.pop(rid, None)

    def _loop(self):
        while not self.stop:
            with self.lock:
                batch, self.queue = self.queue, []
            if not batch:
                time.sleep(0.0005)
                continue
            if self.stall_at is not None and \
                    time.perf_counter() - self.t0 >= self.stall_at:
                self.stall_at = None
                time.sleep(self.stall)
            time.sleep(self.delay)
            with self.lock:
                for rid in batch:
                    self.results[rid] = "answer"


def _latencies(rec):
    return {r: rec.done[r] - rec.due[r] for r in rec.due}


def test_open_loop_times_from_due_times_so_a_stall_delays_later_requests():
    due = np.arange(0.0, 0.6, 0.01)
    calm = FakeService()
    rec = loops.run_open(calm, lambda rid: rid, due, 0.6)
    calm.stop = True
    assert len(rec.done) == len(due)
    assert max(_latencies(rec).values()) < 0.05
    svc = FakeService(stall_at=0.2, stall=0.25)
    rec = loops.run_open(svc, lambda rid: rid, due, 0.6)
    svc.stop = True
    lat = _latencies(rec)
    assert len(rec.done) == len(due)
    # requests due during the stall wait for it: the one due at its start
    # waits the whole stall, and later ones the rest of it
    during = [lat[i] for i in range(len(due)) if 0.21 <= due[i] <= 0.4]
    assert min(during) > 0.02 and max(during) > 0.2
    # and each is timed from its due time, not from when it was sent
    late = rec.late_ms()
    assert np.percentile(late, 50) < 5.0


def test_closed_loop_keeps_every_client_busy():
    svc = FakeService(delay=0.005)
    rec = loops.run_closed(svc, lambda rid: rid, 8, 0.3)
    svc.stop = True
    assert not rec.refused
    assert len(rec.done) == len(rec.sent)
    # every answer is followed at once by its client's next request
    assert len(rec.sent) >= 8 * 10
    in_flight = len(rec.sent) - sum(1 for r, t in rec.done.items()
                                    if t < rec.t_end)
    assert in_flight <= 8
