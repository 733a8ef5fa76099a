"""Request-lifecycle tracing: typed events in a bounded in-memory ring.

Every request flowing through the continuous engine emits a fixed
vocabulary of lifecycle events (``EVENT_TYPES``): submit → admit →
prefill_chunk × N → first_token → decode/verify steps → preempt →
resume → finish.  Each event carries a monotonic host timestamp
(``time.perf_counter`` seconds), the engine step index at emission, the
``step()`` call index (``tick``), the request id and slot, and an
optional duration (span events).

Inside ``engine.step()`` the engine opens nested spans with
:meth:`Tracer.span` (``step`` → ``admit_pass`` / ``chunk_batch`` /
``decode`` → ``prep`` / ``launch`` / ``sync``, then ``retire``, with
``pool_*`` spans around the KV pool's entry points).  Each span records
its enclosing open span's sequence number as ``parent`` and also opens a
``jax.profiler.TraceAnnotation`` named ``engine.<etype>``, so that under
a running profiler session the same span lies on the device trace's own
clock.  Host pauses the engine did not make — XLA compiles and Python's
garbage collector — are recorded as ``compile`` and ``gc`` spans under
whatever span is open, through one process-wide hook per kind.

Storage is a bounded ``collections.deque`` ring — old events fall off
the front under sustained load (``dropped`` counts them) so tracing can
stay on for long serving runs without growing memory.  Counter samples
(pool utilization, batch occupancy, queue depth) live in their own
ring.

``chrome_trace()`` converts the rings into Chrome trace-event JSON
(the ``{"traceEvents": [...]}`` object form) that Perfetto and
``chrome://tracing`` open directly:

  * pid 1 ("serving") holds one track per slot (tid 100+slot) with the
    per-request lifecycle, a tid-0 "engine" track for batch-level
    decode/verify/chunk spans and the spans inside ``step()``, a tid-1
    "queue" track for submits, and a tid-2 "host pauses" track for
    ``compile``/``gc``;
  * counter tracks (``ph: "C"``) for pool utilization / batch
    occupancy / queue depth;
  * pid 2 ("profiler") holds dispatch spans emitted by
    :mod:`repro.obs.profile` with modeled-vs-measured args attached.

All hooks are host-side only: the tracer never touches a jax array and
is always called OUTSIDE jit boundaries.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import json
import threading
import time
import weakref
from dataclasses import dataclass, field

import jax

#: the lifecycle event vocabulary (instant events unless noted)
EVENT_TYPES = (
    "submit",         # request entered the pending queue
    "admit",          # slot assigned, prefill scheduled (fresh prompt)
    "resume",         # re-admission of a previously preempted request
    "prefill_chunk",  # span: one chunk of this slot's prefill
    "first_token",    # first emitted token for this request (TTFT mark)
    "decode",         # span: one batched vanilla decode step (engine)
    "verify",         # span: one batched speculative verify step
    "chunk_batch",    # span: one batched prefill-chunk dispatch
    "preempt",        # victim released mid-flight, re-queued
    "finish",         # request completed, Result emitted
    "dispatch",       # span: profiled jitted dispatch (obs.profile)
    # resilience-plane events (docs/RELIABILITY.md); all instants
    "fault_injected",  # the fault plane fired a scheduled fault
    "retry",          # transient failure, will retry (dispatch/admission)
    "cancel",         # request cancelled via ContinuousEngine.cancel
    "timeout",        # request exceeded its TTFT/total deadline
    "shed",           # request rejected at submit: queue at bound
    "quarantine",     # deterministically failing request isolated
    "degrade",        # live spec_k lowered/recovered under pool pressure
    "restore",        # warm-restart: snapshot entries re-admitted
    # spans inside engine.step() (Tracer.span; docs/OBSERVABILITY.md)
    "step",           # span: one working step() call (the root)
    "admit_pass",     # span: policy selection and admissions
    "pool_admit",     # span: KVPool.admit + block-table upload
    "pool_register",  # span: KVPool.register_prefix of a prefilled prompt
    "pool_release",   # span: KVPool.release_slot + block-table upload
    "prep",           # span: host inputs, ensure_writable, COW copies
    "launch",         # span: the jitted call, until it returns
    "sync",           # span: waiting for the sampled tokens
    "retire",         # span: appending tokens, finishing, registering
    # host pauses not of the engine's making, under whatever span is open
    "compile",        # span: one XLA backend compile (back-dated)
    "gc",             # span: one pass of Python's garbage collector
)

_LIFECYCLE_SPANS = frozenset(
    {"prefill_chunk", "decode", "verify", "chunk_batch"})
_STEP_SPANS = frozenset(
    {"step", "admit_pass", "pool_admit", "pool_register", "pool_release",
     "prep", "launch", "sync", "retire"})
_PAUSES = frozenset({"compile", "gc"})
_SPAN_TYPES = _LIFECYCLE_SPANS | _STEP_SPANS | _PAUSES | {"dispatch"}

#: what hot paths enter in place of ``tracer.span(...)`` when tracing is
#: off: ``with (tr.span("x") if tr.enabled else NO_SPAN):`` builds no
#: event, annotation or argument dict
NO_SPAN = contextlib.nullcontext()

#: the monitoring event JAX emits after each XLA backend compile
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass(slots=True)
class Event:
    """One trace event; ``dur == 0`` renders as an instant."""

    etype: str
    ts: float                   # perf_counter seconds
    rid: int = -1               # request id (-1: engine-level event)
    slot: int = -1              # slot index (-1: not slot-bound)
    step: int = -1              # engine step index at emission
    dur: float = 0.0            # span duration in seconds
    args: dict = field(default_factory=dict)
    seq: int = -1               # emission number within the tracer
    parent: int = -1            # seq of the enclosing open span (-1: root)
    tick: int = -1              # engine step() call index at emission


class _Span:
    """One :meth:`Tracer.span`: the event enters the ring when the span
    opens (so the ring holds spans in start order, as the profiler's host
    plane does), and its duration is stamped when it closes."""

    __slots__ = ("_tr", "_ev", "_ann")

    def __init__(self, tr: "Tracer", ev: Event):
        self._tr, self._ev = tr, ev

    def __enter__(self) -> Event:
        ev, tr = self._ev, self._tr
        self._ann = jax.profiler.TraceAnnotation("engine." + ev.etype)
        self._ann.__enter__()
        ev.ts = time.perf_counter()
        if not tr._open:
            tr._owner = threading.get_ident()
        tr._record(ev)
        tr._open.append(ev.seq)
        return ev

    def __exit__(self, *exc) -> None:
        ev = self._ev
        ev.dur = time.perf_counter() - ev.ts
        self._tr._open.pop()
        self._ann.__exit__(*exc)


class Tracer:
    """Bounded lifecycle-event ring with Chrome trace-event export."""

    def __init__(self, capacity: int = 65536, enabled: bool = True):
        self.enabled = enabled
        self.capacity = capacity
        self.events: collections.deque[Event] = collections.deque(
            maxlen=capacity)
        self.counters: collections.deque = collections.deque(
            maxlen=capacity)
        self.emitted = 0            # lifetime count, incl. dropped
        #: the engine's step() call index, stamped on every event
        self.tick = -1
        #: seqs of the open spans, innermost last, and the thread that
        #: opened them (one engine thread pumps step(); an event from any
        #: other thread is a root)
        self._open: list[int] = []
        self._owner = -1
        #: ``gc`` passes not yet in the ring: a collection can start while
        #: some thread iterates the ring, so its event waits here and
        #: enters the ring just before the tracer's next record
        self._pauses: collections.deque[Event] = collections.deque(
            maxlen=1024)
        self._t0 = time.perf_counter()
        if enabled:
            _watch(self)

    @property
    def dropped(self) -> int:
        return self.emitted - len(self.events)

    def event(self, etype: str, *, rid: int = -1, slot: int = -1,
              step: int = -1, ts: float | None = None, dur: float = 0.0,
              **args) -> None:
        """Record one event (no-op when disabled).

        Callers on hot paths should guard with ``if tracer.enabled:``
        to skip kwarg packing entirely; this check is the backstop.
        """
        if not self.enabled:
            return
        if ts is None:
            ts = time.perf_counter()
        self._record(Event(etype, ts, rid=rid, slot=slot, step=step,
                           dur=dur, args=args))

    def span(self, etype: str, *, rid: int = -1, slot: int = -1,
             step: int = -1, **args):
        """Context manager recording one span: an event stamped with its
        start, its duration, and the enclosing open span as ``parent``;
        it also opens ``jax.profiler.TraceAnnotation("engine.<etype>")``.
        Disabled, it returns :data:`NO_SPAN` (hot paths skip even this
        call behind ``tracer.enabled``)."""
        if not self.enabled:
            return NO_SPAN
        return _Span(self, Event(etype, 0.0, rid=rid, slot=slot, step=step,
                                 args=args))

    def _parent(self) -> int:
        open_ = self._open
        return (open_[-1] if open_ and threading.get_ident() == self._owner
                else -1)

    def _record(self, ev: Event) -> None:
        while self._pauses:
            self._append(self._pauses.popleft())
        ev.parent = self._parent()
        ev.tick = self.tick
        self._append(ev)

    def _append(self, ev: Event) -> None:
        ev.seq = self.emitted
        self.events.append(ev)
        self.emitted += 1

    def _pause(self, etype: str, ts: float, **args) -> Event:
        """A host pause seen from a hook, under the calling thread's open
        span; it enters the ring at the next record."""
        ev = Event(etype, ts, parent=self._parent(), tick=self.tick,
                   args=args)
        self._pauses.append(ev)
        return ev

    def counter(self, name: str, value: float, step: int = -1,
                ts: float | None = None) -> None:
        """Record one counter-track sample (no-op when disabled)."""
        if not self.enabled:
            return
        if ts is None:
            ts = time.perf_counter()
        self.counters.append((name, float(value), step, ts))

    def __len__(self) -> int:
        return len(self.events)

    # -- export --------------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The ring as a Chrome trace-event JSON object.

        Timestamps are microseconds relative to the earliest recorded
        stamp, so traces start at t=0 in Perfetto.
        """
        stamps = [e.ts for e in self.events]
        stamps += [c[3] for c in self.counters]
        base = min(stamps) if stamps else self._t0

        def us(t: float) -> float:
            return (t - base) * 1e6

        tracks: dict[tuple[int, int], str] = {
            (1, 0): "engine", (1, 1): "queue", (1, 2): "host pauses"}
        out: list[dict] = []
        for e in self.events:
            if e.etype == "dispatch":
                pid, tid = 2, 0
                tracks.setdefault((2, 0), "dispatches")
            elif e.etype == "submit":
                pid, tid = 1, 1
            elif e.etype in _PAUSES:
                pid, tid = 1, 2
            elif e.slot >= 0:
                pid, tid = 1, 100 + e.slot
                tracks.setdefault((pid, tid), f"slot {e.slot}")
            else:
                pid, tid = 1, 0
            args = {"etype": e.etype, "rid": e.rid, "step": e.step,
                    "seq": e.seq, "parent": e.parent, "tick": e.tick}
            args.update(e.args)
            ev: dict = {"name": e.etype, "pid": pid, "tid": tid,
                        "ts": us(e.ts), "args": args}
            if e.etype in _SPAN_TYPES:
                ev["ph"] = "X"
                ev["dur"] = e.dur * 1e6
                ev["cat"] = ("lifecycle" if e.etype in _LIFECYCLE_SPANS
                             else "pause" if e.etype in _PAUSES
                             else "engine" if e.etype in _STEP_SPANS
                             else "dispatch")
            else:
                ev["ph"] = "i"
                ev["s"] = "t"
                ev["cat"] = "lifecycle"
            out.append(ev)
        for name, value, step, ts in self.counters:
            out.append({"name": name, "ph": "C", "pid": 1, "tid": 0,
                        "ts": us(ts), "cat": "counter",
                        "args": {"value": value, "step": step}})
        meta: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
             "args": {"name": "serving"}},
            {"name": "process_name", "ph": "M", "pid": 2, "tid": 0,
             "args": {"name": "profiler"}},
        ]
        for (pid, tid), label in sorted(tracks.items()):
            meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                         "tid": tid, "args": {"name": label}})
        return {"traceEvents": meta + out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")


# -- host pauses: one process-wide hook per kind ------------------------------

#: weak references to every tracer constructed enabled; the hooks below
#: forward to those still alive and enabled (a tuple, replaced whole, so
#: a hook running in another thread never sees it change under it)
_LIVE: tuple = ()
_HOOKED = False
_WATCH_LOCK = threading.Lock()
#: the open ``gc`` pass: its annotation and one event per tracer
_GC_OPEN: list = []


def _live() -> list[Tracer]:
    return [t for r in _LIVE if (t := r()) is not None and t.enabled]


def _watch(tracer: Tracer) -> None:
    """Forward compiles and garbage collections to ``tracer``; installs the
    two process-wide hooks on first use."""
    global _LIVE, _HOOKED
    with _WATCH_LOCK:
        _LIVE = tuple(r for r in _LIVE if r() is not None) + (
            weakref.ref(tracer),)
        if not _HOOKED:
            _HOOKED = True
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            gc.callbacks.append(_on_gc)


def _on_duration(event: str, duration_secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    end = time.perf_counter()
    for tr in _live():
        tr.event("compile", ts=end - duration_secs, dur=duration_secs)


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        tracers = _live()
        if not tracers:
            return
        ann = jax.profiler.TraceAnnotation("engine.gc")
        ann.__enter__()
        now = time.perf_counter()
        _GC_OPEN[:] = [ann, [tr._pause("gc", now,
                                       generation=info["generation"])
                             for tr in tracers]]
    elif _GC_OPEN:
        ann, evs = _GC_OPEN
        _GC_OPEN.clear()
        now = time.perf_counter()
        for ev in evs:
            ev.dur = now - ev.ts
            ev.args["collected"] = info["collected"]
        ann.__exit__(None, None, None)


def validate_chrome_trace(doc: dict) -> list[str]:
    """Structural check against the Chrome trace-event format.

    Returns a list of problems (empty == valid).  Checks the object
    form, per-event required keys by phase, and numeric timestamps —
    the subset Perfetto's importer actually requires.
    """
    errs: list[str] = []
    if not isinstance(doc, dict):
        return ["top level is not a JSON object"]
    evs = doc.get("traceEvents")
    if not isinstance(evs, list):
        return ["missing traceEvents array"]
    if not evs:
        errs.append("traceEvents is empty")
    for i, ev in enumerate(evs):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errs.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ph, str) or not ph:
            errs.append(f"{where}: missing ph")
            continue
        if ev.get("name") in (None, ""):
            errs.append(f"{where}: missing name")
        for key in ("pid", "tid"):
            if not isinstance(ev.get(key), int):
                errs.append(f"{where}: {key} not an int")
        if ph != "M":
            if not isinstance(ev.get("ts"), (int, float)):
                errs.append(f"{where}: ts not numeric")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{where}: complete event needs dur >= 0")
        if ph == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not args:
                errs.append(f"{where}: counter event needs args")
            elif not all(isinstance(v, (int, float))
                         for k, v in args.items() if k != "step"):
                errs.append(f"{where}: counter args must be numeric")
    return errs
