"""The FASTQ -> SAM chunk loop of ``repro.launch.map_fastq``, strict mode.

A copy of the loop in ``map_fastq._run``: the same public calls in the same
order, for one pass over a FASTQ.gz read set:

1. the next chunk from the ``repro.io.fastq`` stream (``_open_stream``'s
   layouts: one file single-end, ``--r1``/``--r2`` paired);
2. ``Mapper.map`` or ``Mapper.map_pairs``;
3. for pairs, ``repro.core.pairing.resolve_pairs`` with one
   ``InsertSizeTracker`` for the pass;
4. ``emit_alignments`` or ``emit_paired_alignments``, each record written
   to the SAM file, which is flushed once a chunk.

Around each call the harness records a span (``Spans``): its own
``perf_counter`` reads and, when a profiler trace is being taken, a
``jax.profiler.TraceAnnotation`` of the same name, so the trace reducer can
say which layer the host was in while the device sat idle.  A change made
only inside ``_run``'s own loop is not measured here.
"""
from __future__ import annotations

import contextlib
import time

LAYERS = ("ingest", "mapper", "pairing", "sam_emit")


class Spans:
    """Per-layer host seconds of the measured window."""

    def __init__(self, annotate: bool = False):
        self.seconds = {name: 0.0 for name in LAYERS}
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name] += time.perf_counter() - t0


def open_stream(files: list[str], paired: bool, chunk_reads: int):
    """The stream ``map_fastq._open_stream`` builds for these inputs, strict
    on malformed records, with no rejects sink and no fault injector."""
    from repro.io.fastq import FastqStream, PairedFastqStream
    kw = dict(read_len=None, chunk_reads=chunk_reads, on_error="strict",
              rejects=None, injector=None)
    if paired:
        return PairedFastqStream(files[0], files[1], **kw)
    return FastqStream(files[0], **kw)


def map_pass(files, paired: bool, chunk_reads: int, mapper, cfg, ref,
             refmap, contig_starts, out, spans: Spans, on_chunk=None,
             stop=None) -> int:
    """One pass over the read set.  ``on_chunk(n_reads, res, pr, tracker)``
    is called after each chunk's records are written (``res`` is the
    mapper's result, both mates' for pairs, and ``pr`` the pair
    resolution); ``stop()`` is asked after each chunk and ends the pass
    early when true.  Returns the reads written."""
    from repro.core.pairing import InsertSizeTracker, resolve_pairs
    from repro.io.sam import emit_alignments, emit_paired_alignments

    stream = open_stream(files, paired, chunk_reads)
    tracker = InsertSizeTracker()
    it = iter(stream)
    written = 0
    while True:
        with spans.span("ingest"):
            chunk = next(it, None)
        if chunk is None:
            return written
        pr = None
        if paired:
            c1, c2 = chunk
            with spans.span("mapper"):
                res1, res2 = mapper.map_pairs(c1.reads, c2.reads)
            with spans.span("pairing"):
                pr = resolve_pairs(res1, res2, cfg=cfg, tracker=tracker,
                                   ref=ref, reads1=c1.reads,
                                   reads2=c2.reads,
                                   contig_starts=contig_starts)
            with spans.span("sam_emit"):
                for rec in emit_paired_alignments(
                        pr, c1.names, c1.reads, c1.quals, c2.reads,
                        c2.quals, refmap, seqs1=c1.seqs, seqs2=c2.seqs):
                    out.write(rec + "\n")
                out.flush()
            n_new, res = 2 * len(c1), (res1, res2)
        else:
            with spans.span("mapper"):
                res = mapper.map(chunk.reads)
            with spans.span("sam_emit"):
                for rec in emit_alignments(res, chunk.names, chunk.reads,
                                           chunk.quals, refmap,
                                           seqs=chunk.seqs):
                    out.write(rec + "\n")
                out.flush()
            n_new = len(chunk)
        written += n_new
        if on_chunk is not None:
            on_chunk(n_new, res, pr, tracker)
        if stop is not None and stop():
            return written
