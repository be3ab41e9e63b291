"""Client loops that drive a service: open (requests sent at due times,
whatever the backlog) and closed (each client sends its next request when
its answer arrives).

A service here is anything with ``submit(req) -> None | refusal``, a
``results`` dict of finished request ids and ``take_result(req_id)``:
``CubeGraphService`` in a run, a fake in the tests.  A request's latency
runs from the moment it was due (open loop) or sent (closed loop) to the
moment the loop sees its result, so a stall delays every request due
during it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import numpy as np

POLL_S = 0.0005


@dataclasses.dataclass
class Record:
    """What the client saw of each request it sent, by request id."""

    due: Dict[int, float] = dataclasses.field(default_factory=dict)
    sent: Dict[int, float] = dataclasses.field(default_factory=dict)
    done: Dict[int, float] = dataclasses.field(default_factory=dict)
    refused: Dict[int, object] = dataclasses.field(default_factory=dict)
    result: Dict[int, object] = dataclasses.field(default_factory=dict)
    t0: float = 0.0
    t_end: float = 0.0

    def late_ms(self) -> np.ndarray:
        """How late each request was sent after it was due, in ms."""
        return np.asarray([(self.sent[i] - self.due[i]) * 1e3
                           for i in self.sent], np.float64)


def _collect(svc, rec: Record, on_done: Optional[Callable] = None) -> int:
    n = 0
    for rid in list(svc.results):
        res = svc.take_result(rid)
        if res is None or rid not in rec.sent or rid in rec.refused:
            continue
        rec.done[rid] = time.perf_counter()
        rec.result[rid] = res
        n += 1
        if on_done is not None:
            on_done(rid)
    return n


def drain(svc, rec: Record, timeout_s: float = 60.0) -> None:
    """Wait, up to ``timeout_s``, for every sent request to come back."""
    deadline = time.perf_counter() + timeout_s
    pending = len(rec.sent) - len(rec.done) - len(rec.refused)
    while pending > 0 and time.perf_counter() < deadline:
        pending -= _collect(svc, rec)
        time.sleep(POLL_S)


def run_open(svc, make_req: Callable[[int], object], due: np.ndarray,
             seconds: float, first_id: int = 0) -> Record:
    """Send request ``first_id + i`` at ``due[i]`` seconds after the
    start, for ``seconds``; then wait for the answers (see :func:`drain`).
    Every request due in the window is in ``Record.due``."""
    rec = Record()
    rec.t0 = t0 = time.perf_counter()
    rec.t_end = t0 + seconds
    i, n = 0, len(due)
    while True:
        now = time.perf_counter()
        while i < n and t0 + due[i] <= now:
            rid = first_id + i
            rec.due[rid] = t0 + due[i]
            rec.sent[rid] = time.perf_counter()
            refusal = svc.submit(make_req(rid))
            if refusal is not None:
                rec.refused[rid] = refusal
            i += 1
        _collect(svc, rec)
        if i >= n or now >= rec.t_end:
            break
        wake = min(t0 + due[i], time.perf_counter() + POLL_S)
        time.sleep(max(wake - time.perf_counter(), 0.0))
    drain(svc, rec)
    return rec


def run_closed(svc, make_req: Callable[[int], object], clients: int,
               seconds: float, first_id: int = 0) -> Record:
    """``clients`` clients, each sending its next request as soon as its
    answer (or refusal) arrives, for ``seconds``; then drain."""
    rec = Record()
    rec.t0 = time.perf_counter()
    rec.t_end = rec.t0 + seconds
    next_id = [first_id]

    def send() -> None:
        # a refused client sends its next request at once, as a batch
        # caller that backs off nothing would
        while time.perf_counter() < rec.t_end:
            rid = next_id[0]
            next_id[0] += 1
            rec.sent[rid] = rec.due[rid] = time.perf_counter()
            refusal = svc.submit(make_req(rid))
            if refusal is None:
                return
            rec.refused[rid] = refusal

    def on_done(_rid) -> None:
        if time.perf_counter() < rec.t_end:
            send()

    for _ in range(clients):
        send()
    while time.perf_counter() < rec.t_end:
        _collect(svc, rec, on_done)
        time.sleep(POLL_S)
    drain(svc, rec)
    return rec
