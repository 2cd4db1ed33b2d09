"""The client: the user of the service, in one process with few threads.

A request counts as served only when its output is a host numpy array,
which forces the lazy device arrays async ingest hands back.  The open
loop times each request from when it was due, not from when it was sent,
and records how late the generator sent it, so that a starved generator
is not read as a fast server.  Each served output is kept as a digest
(dtype, shape, CRC-32 of its bytes), taken after its time is stamped, so
that every answer of the window is compared with the reference once the
window has closed.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import zlib
from typing import Callable, Dict, Optional, Tuple

import numpy as np

#: How long past the window's close a run waits for answers still due.
GRACE_S = 60.0


@dataclasses.dataclass
class Record:
    k: int
    due: float            # perf_counter the request was due (closed: sent)
    sent: float
    done: Optional[float] = None
    error: Optional[str] = None
    digest: Optional[Tuple] = None


def digest(a: np.ndarray) -> Tuple:
    """What a comparison needs of an answer: dtype, shape and a CRC-32 of
    its bytes."""
    a = np.ascontiguousarray(a)
    return a.dtype.str, a.shape, zlib.crc32(a)


class Load:
    """Shared state of one window: the records and the request cursor."""

    def __init__(self, submit: Callable):
        self.submit = submit          # k -> JobHandle
        self.records: Dict[int, Record] = {}
        self._lock = threading.Lock()
        self._next = 0

    def take(self) -> int:
        with self._lock:
            k, self._next = self._next, self._next + 1
            return k

    def finish(self, rec: Record, handle, deadline: float) -> None:
        """Wait for one answer, bring it to the host and record it."""
        try:
            out = np.asarray(handle.result(
                timeout=max(deadline - time.perf_counter(), 1e-3)))
            rec.done = time.perf_counter()
            rec.digest = digest(out)
        except Exception as exc:  # noqa: BLE001 -- a failed request is counted, not raised
            rec.error = f"{type(exc).__name__}: {exc}"

    def send(self, k: int, due: float):
        rec = Record(k, due, time.perf_counter())
        with self._lock:
            self.records[k] = rec
        try:
            return rec, self.submit(k)
        except Exception as exc:  # noqa: BLE001 -- a shed request is counted, not raised
            rec.error = f"{type(exc).__name__}: {exc}"
            return rec, None


def open_loop(load: Load, times: np.ndarray, t0: float, seconds: float,
              collectors: int) -> None:
    """Send request k at ``t0 + times[k]``; ``collectors`` threads wait
    for the answers in sending order."""
    deadline = t0 + seconds + GRACE_S
    sent: "queue.Queue" = queue.Queue()

    def collect():
        while True:
            item = sent.get()
            if item is None:
                return
            load.finish(*item, deadline)

    threads = [threading.Thread(target=collect, name=f"collector-{i}")
               for i in range(collectors)]
    for t in threads:
        t.start()
    try:
        for k, at in enumerate(times):
            due = t0 + float(at)
            pause = due - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            rec, handle = load.send(load.take(), due)
            if handle is not None:
                sent.put((rec, handle))
    finally:
        for _ in threads:
            sent.put(None)
        for t in threads:
            t.join()


def closed_loop(load: Load, clients: int, t0: float, seconds: float) -> None:
    """``clients`` callers, each sending its next request when its last
    answer is on the host, until the window closes."""
    t_end = t0 + seconds
    deadline = t_end + GRACE_S

    def client():
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            rec, handle = load.send(load.take(), now)
            if handle is None:
                return
            load.finish(rec, handle, deadline)
            if rec.error is not None:
                return

    pause = t0 - time.perf_counter()
    if pause > 0:
        time.sleep(pause)
    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
