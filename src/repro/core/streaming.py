"""Async double-buffered chunk streaming (throughput mode, paper Sec. V-C).

DART-PIM's controller hierarchy keeps every crossbar busy by refilling the
Reads-FIFOs while earlier batches compute: read routing, FIFO fill and WF
execution overlap instead of taking turns.  On the JAX side the same
overlap falls out of async dispatch — every jit call is a non-blocking
enqueue — *if* the host never stalls the queue.  The chunk loop here keeps
three chunks in flight:

  chunk i+1   host pad/encode + H2D transfer + seeding dispatch (phase 1)
  chunk i     capacity-count sync + WF stage dispatch         (phase 2)
  chunk i-1   device->host result fetch, on a fetch thread    (phase 3)

``stream_map`` runs that schedule.  The only host-blocking points are the
bucket-capacity count syncs of phase 2 and the D2H copies of phase 3; both
now overlap with the neighbouring chunks' device work instead of
serializing the pipeline.

``sync_map`` is the fully synchronous debugging path (``stream=False``):
it blocks at every stage boundary and records per-stage wall times, which
is what makes the double-buffering win *measurable* (see
``benchmarks/pipeline_bench.py --chunk-sweep``) — and what makes a failure
attributable to one stage instead of an async soup.

Both paths call the exact same jitted stages with the same static bucket
capacities, so their outputs are bit-identical (asserted in
``tests/test_streaming.py``).

The driver is the ``Mapper`` session of ``repro.core.mapper``: its
compacted-engine plans execute ``pipeline._ChunkPipeline`` phases through
``stream_map``/``sync_map``, and ``Mapper.map_async`` stacks a
caller-facing future on top of this chunk-level overlap.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeoutError

import jax

from ..obs import registry as _metrics
from ..obs import tracing as _tracing

__all__ = ["stream_map", "sync_map", "donatable_argnums", "timed",
           "FetchStallError"]


class FetchStallError(RuntimeError):
    """The fetch thread exceeded the streaming watchdog (``watchdog_s``).

    A D2H copy that never completes — a wedged device queue, a deadlocked
    transfer — previously hung ``stream_map`` forever in the final
    ``f.result()``.  With a watchdog armed, the stall surfaces as this
    error instead, which the resilience layer treats like any other block
    failure (retry, then quarantine).

    Defined here (not in ``core.resilience``) so the streaming layer has
    no upward imports; ``resilience`` re-exports it as part of the error
    taxonomy.
    """


def donatable_argnums(*argnums: int) -> tuple[int, ...]:
    """``argnums`` where buffer donation is implemented, else ``()``.

    The streaming engine donates single-consumer chunk buffers into the WF
    stages (``jax.jit(..., donate_argnums=...)``) so each in-flight chunk
    reuses the previous chunk's device allocations instead of growing the
    arena.  The CPU backend does not implement donation and warns on every
    call, so donation is requested only where it exists.
    """
    return argnums if jax.default_backend() in ("tpu", "gpu") else ()


def timed(times: dict | None, key: str, t0: float) -> float:
    """Accumulate ``now - t0`` into ``times[key]``; returns a fresh t0.

    No-op (beyond the clock read) when ``times`` is None, so the phase
    functions can share one code path between the streamed and the
    synchronous engines.

    This is also the observability layer's stage hook: when the
    ``repro.obs`` tracer/registry are armed, the *same two clock reads*
    emit a span (with the calling thread's chunk context) and accrue the
    per-stage seconds counter — so the exported trace, the metrics
    snapshots and ``stage_times_s`` can never disagree on a duration.
    """
    t1 = time.perf_counter()
    if times is not None:
        times[key] = times.get(key, 0.0) + (t1 - t0)
        tr = _tracing.ACTIVE
        if tr is not None:
            tr.add(key, t0, t1)
        reg = _metrics.ACTIVE
        if reg is not None:
            reg.counter("repro_stage_seconds_total", stage=key).inc(t1 - t0)
    return t1


def stream_map(items: list, phase1, phase2, fetch,
               times: dict | None = None, *, injector=None,
               watchdog_s: float | None = None) -> list:
    """Double-buffered streaming execution over ``items`` (one per chunk).

    phase1(item)   -> state   : host prep + H2D + first async dispatch
    phase2(state)  -> outs    : count syncs + remaining stage dispatch
    fetch(outs)    -> result  : blocking device->host copy (fetch thread)

    phase1 of chunk i+1 is issued *before* phase2 of chunk i blocks on its
    capacity counts, so the next chunk's transfer+seeding are already in
    the device queue during the sync; fetches run on a worker thread so
    D2H copies of chunk i-1 overlap chunk i's compute.  Results come back
    in submission order.

    ``times``, when given (``MapperConfig.profile``), is handed to the
    ``fetch`` calls only — the dispatch phases stay non-blocking, and the
    fetch thread records per-stage *completion-time* offsets by blocking
    on the stage milestone arrays phase2 attached (the stage that the
    device queue is actually waiting on accrues the time).  It is only
    ever mutated from the single fetch worker, so no locking is needed.

    Fault tolerance: a fetch that fails used to surface only at the final
    ``f.result()`` drain — every later chunk was still dispatched and
    fetched first.  The dispatch loop now polls completed fetch futures
    and re-raises the first failure *promptly*, before dispatching more
    work.  ``watchdog_s`` bounds each fetch's wall time (a wedged fetch
    thread raises ``FetchStallError`` instead of hanging the caller
    forever) and ``injector`` is the chaos hook: each fetch first runs
    ``injector.sleep("fetch_stall")`` / ``injector.check("fetch_error")``
    on the fetch thread.  Both default off and add one branch per chunk.
    """
    n = len(items)
    if n == 0:
        return []

    if injector is None:
        run_fetch = fetch
    else:
        def run_fetch(outs, times_):
            injector.sleep("fetch_stall")
            injector.check("fetch_error")
            return fetch(outs, times_)

    # chunk attribution for spans: each phase call runs under the chunk's
    # context on whichever thread runs it, so overlapping chunks untangle
    # in a trace.  A caller that set a ``chunk`` (a FASTQ stream's ordinal)
    # keeps it for every phase; otherwise each phase gets its index here.
    caller = _tracing.get_ctx()

    def ctx(i):
        if caller is not None and "chunk" in caller:
            return caller
        return {**(caller or {}), "chunk": i}

    def fetch_job(ctx_i, outs):
        _tracing.set_ctx(**ctx_i)
        return run_fetch(outs, times)

    reg = _metrics.ACTIVE
    if reg is not None:
        reg.counter("repro_chunks_total", mode="stream").inc(n)

    futs = [None] * n
    pool = ThreadPoolExecutor(max_workers=1,
                              thread_name_prefix="stream-fetch")
    try:
        _tracing.set_ctx(**ctx(0))
        state = phase1(items[0])
        for i in range(n):
            # prompt propagation: if an already-completed fetch failed,
            # raise now instead of dispatching the rest of the stream
            for f in futs[:i]:
                if f is not None and f.done():
                    f.result()
            _tracing.set_ctx(**ctx(i + 1))
            nxt = phase1(items[i + 1]) if i + 1 < n else None
            _tracing.set_ctx(**ctx(i))
            outs = phase2(state)
            futs[i] = pool.submit(fetch_job, ctx(i), outs)
            state = nxt
        out = []
        for i, f in enumerate(futs):
            try:
                out.append(f.result(timeout=watchdog_s))
            except FutureTimeoutError:
                # don't join the wedged worker — cancel what we can and
                # abandon the pool so the caller gets the error, not a
                # second hang in shutdown
                pool.shutdown(wait=False, cancel_futures=True)
                pool = None
                raise FetchStallError(
                    f"fetch of chunk {i}/{n} exceeded the streaming "
                    f"watchdog ({watchdog_s}s); device queue or fetch "
                    f"thread is stalled") from None
        return out
    finally:
        _tracing.set_ctx(**(caller or {}))
        if pool is not None:
            pool.shutdown(wait=True)


def sync_map(items: list, phase1, phase2, fetch,
             times: dict | None = None) -> list:
    """Fully synchronous chunk execution (the ``stream=False`` debug path).

    Runs one chunk end-to-end at a time.  When the phase functions are
    handed a ``times`` dict they block at each stage boundary and record
    per-stage wall seconds into it (host_prep / h2d / seed / linear /
    affine / traceback / d2h).
    """
    reg = _metrics.ACTIVE
    if reg is not None:
        reg.counter("repro_chunks_total", mode="sync").inc(len(items))
    caller = _tracing.get_ctx()
    out = []
    try:
        for i, item in enumerate(items):
            if caller is None or "chunk" not in caller:
                _tracing.set_ctx(**(caller or {}), chunk=i)
            out.append(fetch(phase2(phase1(item, times=times), times=times),
                             times=times))
    finally:
        _tracing.set_ctx(**(caller or {}))
    return out
