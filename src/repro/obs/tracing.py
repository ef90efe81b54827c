"""Span tracing with Chrome trace-event export (Perfetto-loadable).

Second pillar of ``repro.obs``: every chunk's lifecycle (ingest -> H2D
-> seed -> linear -> affine -> traceback -> D2H -> SAM emit) is recorded
as **complete spans** carrying chunk/shard attribution, and exported as
Chrome trace-event JSON (the ``{"traceEvents": [...]}`` container) that
loads directly in Perfetto / ``chrome://tracing``.

The central integration point is ``repro.core.streaming.timed``: every
per-stage wall-time accumulation *also* emits a span from the **same
two clock reads**, so the exported trace's per-stage durations and the
legacy ``stage_times_s`` dict are identical by construction — the
acceptance property ``tests/test_obs.py`` locks.

Attribution rides a thread-local context (``set_ctx(chunk=i)``): a FASTQ
stream stamps each chunk's ordinal, and the streaming engine carries it
(or, where the caller set none, the in-flight chunk index) to whichever
thread (dispatch or fetch) runs each phase, so overlapping chunks
untangle in the viewer.  Memory is bounded by ``max_events`` — a long
run drops and counts excess events rather than growing without limit.

The program's layers mark their work with ``span(name, **counts)``
(names under ``repro/``: ``repro/fastq``, ``repro/map`` and its
``prep``/``dispatch``/``sync``/``fetch`` children, ``repro/pairing``,
``repro/pairing/rescue``, ``repro/sam``).  A span goes to every sink
that is recording: a ``jax.profiler`` trace (profiler server,
``jax.profiler.trace`` or ``start_trace``), where it is a
``TraceAnnotation`` on the host plane, on the device ops' clock, with
the thread's context and the counts as event stats; and the armed
``Tracer``, for ``--trace-out``.

Like the registry, this module is a leaf with a module-global ``ACTIVE``
tracer: with no sink recording, a span costs the profiler's flag check
and one attribute load.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "enable_tracing", "disable_tracing", "tracer",
           "set_ctx", "get_ctx", "clear_ctx", "span"]

_tls = threading.local()


def set_ctx(**kw) -> None:
    """Replace this thread's span-attribution context (e.g. chunk=3)."""
    _tls.ctx = kw


def get_ctx() -> dict | None:
    return getattr(_tls, "ctx", None)


def clear_ctx() -> None:
    _tls.ctx = None


class Tracer:
    """Bounded in-memory span collector with Chrome trace-event export."""

    def __init__(self, max_events: int = 1_000_000):
        self.max_events = max_events
        self.epoch = time.perf_counter()
        self.dropped = 0
        self._events: list[tuple] = []   # (name, tid, t0, t1, args)
        self._lock = threading.Lock()

    def add(self, name: str, t0: float, t1: float,
            args: dict | None = None) -> None:
        """Record a complete span from two ``perf_counter`` reads; the
        calling thread's context (``set_ctx``) merges into ``args``."""
        ctx = getattr(_tls, "ctx", None)
        if ctx:
            args = {**ctx, **args} if args else dict(ctx)
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(
                (name, threading.get_ident(), t0, t1, args))

    def __len__(self) -> int:
        return len(self._events)

    def stage_totals(self) -> dict:
        """Summed span seconds by name — ``stage_times_s``, re-derived
        from the trace (bit-equal where both exist: same clock reads)."""
        out: dict[str, float] = {}
        with self._lock:
            events = list(self._events)
        for name, _tid, t0, t1, _args in events:
            out[name] = out.get(name, 0.0) + (t1 - t0)
        return out

    def chrome(self) -> dict:
        """The trace as a Chrome trace-event JSON object."""
        with self._lock:
            events = list(self._events)
        pid = os.getpid()
        tids: dict[int, int] = {}
        out = [{"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
                "args": {"name": "repro"}}]
        for name, ident, t0, t1, args in events:
            tid = tids.setdefault(ident, len(tids))
            ev = {"ph": "X", "name": name, "pid": pid, "tid": tid,
                  "ts": (t0 - self.epoch) * 1e6,
                  "dur": (t1 - t0) * 1e6}
            if args:
                ev["args"] = args
            out.append(ev)
        for ident, tid in tids.items():
            out.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "args": {"name": f"thread-{tid}"}})
        meta = {"dropped_events": self.dropped} if self.dropped else {}
        return {"traceEvents": out, "displayTimeUnit": "ms", **meta}

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome(), f)
            f.write("\n")


# --------------------------------------------------------------- global
ACTIVE: Tracer | None = None


def enable_tracing(max_events: int = 1_000_000,
                   tracer_: Tracer | None = None) -> Tracer:
    """Arm the process-wide tracer (idempotent; pass ``tracer_`` to
    install a specific instance)."""
    global ACTIVE
    if tracer_ is not None:
        ACTIVE = tracer_
    elif ACTIVE is None:
        ACTIVE = Tracer(max_events=max_events)
    return ACTIVE


def disable_tracing() -> Tracer | None:
    """Disarm; returns the tracer that was active (for a final export)."""
    global ACTIVE
    t, ACTIVE = ACTIVE, None
    return t


def tracer() -> Tracer | None:
    return ACTIVE


class _NullSpan:
    """The span of a process with no sink recording: does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **counts) -> None:
        pass


_NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "args", "tracer", "profiling", "ann", "t0")

    def __init__(self, name: str, args: dict, tracer_, profiling: bool):
        self.name, self.args, self.tracer = name, args, tracer_
        self.profiling, self.ann = profiling, None

    def __enter__(self):
        ctx = getattr(_tls, "ctx", None)
        if ctx:
            self.args = {**ctx, **self.args}
        if self.profiling:
            self.ann = TraceAnnotation(self.name, **self.args)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set_metadata(self, **counts) -> None:
        """Counts known only at the end of the span."""
        self.args.update(counts)
        if self.ann is not None:
            self.ann.set_metadata(**counts)

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if self.tracer is not None:
            self.tracer.add(self.name, self.t0, t1, self.args)
        return False


def span(name: str, **counts):
    """A context manager marking one layer's work (per chunk or per call,
    never per record): a ``jax.profiler.TraceAnnotation`` named ``name``
    while a profiler records, with the thread's context (``chunk``) and
    ``counts`` as its stats, and a span of the armed ``Tracer`` from the
    same two clock reads.  ``set_metadata(**counts)`` on the entered span
    adds counts known only at its end.  With neither recording it does
    nothing beyond the profiler's flag check."""
    tr = ACTIVE
    profiling = TraceAnnotation.is_enabled()
    if tr is None and not profiling:
        return _NULL_SPAN
    return _Span(name, counts, tr, profiling)
