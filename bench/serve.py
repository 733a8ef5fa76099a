"""Drives the engine through one measured window.

A generator thread submits each request at its due time; the main thread
pumps ``engine.step()`` (the call the engine's own serve loop makes),
collects results, and schedules each stream's next request when its
previous reply ends.  Every request is timed from when it was due.  The
host spans written here (``bench.step``, ``bench.wait``) land in the
profiler's trace, so idle gaps on the device can be put down to what the
host was doing.
"""

from __future__ import annotations

import dataclasses
import gc
import heapq
import threading
import time

import jax
import numpy as np

from bench.traffic import Req, Traffic

#: how long the window's last requests may take to finish before the
#: run gives up on them (they then count as failed)
DRAIN_LIMIT_S = 240.0


@dataclasses.dataclass
class Record:
    req: Req
    due: float                  # perf_counter seconds
    submit: float = 0.0
    counted: bool = False
    first: float = 0.0          # perf_counter of the first token
    finish: float = 0.0
    tokens: np.ndarray | None = None
    status: str = "pending"


class _Generator(threading.Thread):
    def __init__(self, engine, records: dict[int, Record], make_request):
        super().__init__(name="bench-generator", daemon=True)
        self.engine = engine
        self.records = records
        self.make_request = make_request
        self.heap: list[tuple[float, int, Req]] = []
        self.cv = threading.Condition()
        self.stopped = False
        self.submitted = threading.Event()
        self.error: BaseException | None = None

    def push(self, due: float, req: Req) -> None:
        with self.cv:
            self.records[req.rid] = Record(req, due)
            heapq.heappush(self.heap, (due, req.rid, req))
            self.cv.notify()

    def earliest_due(self) -> float | None:
        with self.cv:
            return self.heap[0][0] if self.heap else None

    def stop(self) -> None:
        with self.cv:
            self.stopped = True
            self.cv.notify()

    def run(self) -> None:
        try:
            while True:
                with self.cv:
                    while not self.stopped and (
                            not self.heap
                            or self.heap[0][0] > time.perf_counter()):
                        wait = (self.heap[0][0] - time.perf_counter()
                                if self.heap else None)
                        self.cv.wait(wait)
                    if self.stopped:
                        return
                    _, _, req = heapq.heappop(self.heap)
                rec = self.records[req.rid]
                rec.submit = time.perf_counter()
                self.engine.submit(self.make_request(req))
                self.submitted.set()
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            self.error = e
            self.submitted.set()


@dataclasses.dataclass
class Window:
    records: list[Record]
    start: float                # perf_counter at the window's opening
    end: float                  # and at its close
    drained: float              # when the last counted request ended
    steps: int                  # engine.step() calls inside the window
    #: the longest engine.step() call and the longest pause of Python's
    #: garbage collector inside the window (seconds), to find host stalls
    longest_step: float = 0.0
    longest_gc: float = 0.0


def run_window(engine, traffic: Traffic, make_request, *,
               on_open=None, on_close=None) -> Window:
    """Serve ``traffic`` until every request due in its window has ended.

    ``on_open``/``on_close`` run when the window opens and closes (the
    traced run starts and stops the profiler there)."""
    records: dict[int, Record] = {}
    gen = _Generator(engine, records, make_request)
    t0 = time.perf_counter()
    w_open, w_close = t0 + traffic.window[0], t0 + traffic.window[1]
    for s in traffic.streams:
        gen.push(t0 + s[0].offset, s[0])
    by_rid = {r.rid: (si, j) for si, s in enumerate(traffic.streams)
              for j, r in enumerate(s)}
    gen.start()
    opened = closed = False
    steps = 0
    drained = 0.0
    longest = [0.0, 0.0]        # engine.step(), garbage collection
    gc_start = [0.0]

    def on_gc(phase, _info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        elif opened and not closed:
            longest[1] = max(longest[1], time.perf_counter() - gc_start[0])

    gc.callbacks.append(on_gc)
    try:
        while True:
            now = time.perf_counter()
            if not opened and now >= w_open:
                opened = True
                if on_open:
                    on_open()
            if opened and not closed and now >= w_close:
                closed = True
                if on_close:
                    on_close()
            if closed:
                due = gen.earliest_due()
                waiting = due is not None and due < w_close
                # every request due before the close, so that tokens of
                # the window's requests and of those before it all count
                open_reqs = [r for r in records.values()
                             if r.due < w_close and r.status == "pending"]
                if not waiting and not open_reqs:
                    drained = now
                    break
                if now - w_close > DRAIN_LIMIT_S:
                    for r in open_reqs:
                        r.status = "unfinished"
                    drained = now
                    break
            t_step = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                active = engine.step()
            if opened and not closed:
                steps += 1
                longest[0] = max(longest[0], time.perf_counter() - t_step)
            for res in engine.drain_results():
                rec = records[res.rid]
                rec.first = rec.submit + res.ttft_s
                rec.finish = rec.submit + res.latency_s
                rec.tokens = np.asarray(res.tokens)
                rec.status = res.status
                si, j = by_rid[res.rid]
                stream = traffic.streams[si]
                if j + 1 < len(stream):
                    follow = stream[j + 1]
                    gen.push(rec.finish + follow.offset, follow)
            if gen.error is not None:
                raise gen.error
            if active == 0:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    gen.submitted.wait(0.005)
                    gen.submitted.clear()
    finally:
        gc.callbacks.remove(on_gc)
        gen.stop()
        gen.join(timeout=10.0)
    if gen.is_alive():
        raise RuntimeError("the load generator did not stop")
    out = []
    for r in records.values():
        r.counted = w_open <= r.due < w_close
        out.append(r)
    return Window(out, w_open, w_close, drained, steps, *longest)
