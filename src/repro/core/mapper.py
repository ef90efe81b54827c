"""Unified ``Mapper`` session API — one planner/executor front-end.

DART-PIM's controller hierarchy (paper Sec. V, Fig. 6) is a *single*
planned dataflow from indexing to the final reduce: the main controller
owns data placement, batch routing, and stage dispatch, and the crossbar
controllers merely execute what was planned.  The repo's execution paths
mirror that split here:

  ``Mapper(index, cfg)``   — the session object.  It owns the device
      placement of the (possibly sharded) index, a **plan cache** of
      pre-built per-bucket/per-chunk executables, and the running
      plan-cache hit/miss counters.
  ``Mapper.plan(spec)``    — the planning layer: returns a ``MappingPlan``
      describing exactly what a ``run`` would execute (chunk sizes, lane
      capacities, shard routing, send/survivor capacities) *before* any
      compute is dispatched.
  ``Mapper.run(plan, r)``  — the executor: runs reads through the plan's
      cached executable.
  ``Mapper.map(reads)``    — plan + run in one call.
  ``Mapper.map_async(r)``  — same, as a ``concurrent.futures.Future``
      (submissions execute in order on a session worker thread, each one
      driving the async double-buffered streaming engine internally).
  ``Mapper.serve()``       — a ``MappingService`` request batcher wired to
      this session.

``topology=`` selects the back-end behind the same result schema:

  ``"single"`` — the single-shard pipeline of ``repro.core.pipeline``
      (padded or candidate-compacted engine, streamed chunks).
  ``"mesh"``   — the distributed all_to_all mapper of
      ``repro.core.distributed`` over a flat device mesh.  Reads are
      zero-padded up to a shard multiple and results trimmed back, so
      arbitrary batch sizes work; stage B never tracebacks, so the
      traceback fields of ``MappingResult`` are ``None`` on this path.

``MapperConfig.both_strands`` makes strand a planning dimension on both
topologies: plans are sized for the forward + reverse-complement
encodings of every read (2n), the engine executes them as one stacked
batch, and the per-read winner — lower affine distance, ties keep
forward — is reduced host-side into ``MappingResult.strand`` /
``MapperStats.reverse_best`` (see ``repro.io`` for the FASTQ/SAM
boundary this feeds).

Every run reports a unified ``MapperStats`` (replacing the old divergent
``stats`` dict vs ``with_stats=True`` tuple shapes).  ``MapperStats`` is
dict-compatible (``stats["survivors"]``) for the legacy per-path keys and
additionally exposes the unified fields as attributes, including the
session's plan-cache hit counters — the observable for "no recompiles
after warm-up" assertions.

The old free functions ``pipeline.map_reads`` and
``distributed.distributed_map_reads`` remain as thin deprecation shims
that forward here and stay bit-identical.
"""
from __future__ import annotations

import dataclasses
import time
from concurrent.futures import Future, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import registry as _metrics
from ..obs import tracing as _tracing
from . import streaming
from .distributed import (AXIS, ShardedIndex, _cached_mapper, shard_index,
                          stage_b_affine_capacity)
from .encoding import revcomp
from .index import GenomeIndex, device_position_dtype
from .pipeline import (LazyTraceback, MapperConfig, MappingResult,
                       _ChunkPipeline, _merge_stats, map_reads_jax)

TOPOLOGIES = ("single", "mesh")

__all__ = ["Mapper", "MapperStats", "MappingPlan", "TOPOLOGIES",
           "accumulate_partition_stats", "split_result",
           "totals_from_registry"]


_PER_READ_FIELDS = ("position", "distance", "distance2", "mapped", "strand",
                    "ops", "op_count", "linear_dist", "n_candidates",
                    "failed")


def split_result(res: MappingResult, n: int,
                 ) -> tuple[MappingResult, MappingResult]:
    """Split one stacked ``MappingResult`` into ``(first n, rest)``.

    The paired-end path maps both mates as one stacked batch (R1 rows
    then R2 rows — one plan, one engine dispatch, shared chunking) and
    splits here.  Both halves share the run's ``stats`` object (its
    ``reads`` counts the full stacked batch).  Raw attribute access keeps
    a ``cigar_mode="lazy"`` result lazy: the pending traceback holder is
    sliced, not materialized.
    """
    lt = object.__getattribute__(res, "lazy_tb")

    def half(lo, hi):
        def raw(f):
            v = object.__getattribute__(res, f)
            return v[lo:hi] if v is not None else None
        return MappingResult(**{f: raw(f) for f in _PER_READ_FIELDS},
                             stats=res.stats,
                             lazy_tb=lt[lo:hi] if lt is not None else None)
    return half(0, n), half(n, len(res.position))


@dataclasses.dataclass
class MapperStats:
    """Unified per-run statistics schema shared by every topology.

    The named fields are the topology-independent accounting (what was
    seeded, what survived the filter, what the affine stage actually
    executed, what fixed-capacity buffers dropped) plus the session's
    cumulative plan-cache counters at the time of the run.  ``extra``
    carries the legacy per-path keys (``candidates_valid`` /
    ``stage_times_s`` / ``d2h_syncs``, the blocking device-to-host reads,
    on the single-shard path, ``stage_b_*`` /
    ``send_dropped`` on the mesh path) and backs the dict-style access
    (``stats["survivors"]``, ``dict(stats)``) the pre-``Mapper`` API
    exposed.
    """
    topology: str
    engine: str
    reads: int                     # real reads mapped (padding excluded)
    candidates: int                # seeded candidates / stage-B entries
    survivors: int                 # filter survivors admitted to affine
    affine_instances: int          # affine WF instances actually executed
    padded_affine_instances: int   # what the padded reference would run
    dropped_send: int = 0          # mesh: send-FIFO overflow drops
    dropped_affine: int = 0        # mesh: survivor-capacity overflow drops
    reverse_best: int = 0          # dual-strand runs: reads whose best
    #                                alignment used the reverse complement
    plan_cache_hits: int = 0       # session cumulative, sampled at run time
    plan_cache_misses: int = 0
    retries: int = 0               # resilience: block retries this run
    failed_reads: int = 0          # resilience: reads quarantined this run
    extra: dict = dataclasses.field(default_factory=dict)

    # -- dict-compatibility with the legacy stats shapes ------------------
    def __getitem__(self, key):
        return self.extra[key]

    def __contains__(self, key):
        return key in self.extra

    def get(self, key, default=None):
        return self.extra.get(key, default)

    def keys(self):
        return self.extra.keys()

    def as_dict(self) -> dict:
        return dict(self.extra)


_PART_SUM_KEYS = ("chunks_routed", "partition_loads", "partition_evictions",
                  "partition_compactions",
                  "h2d_bytes", "prefetch_loads", "prefetch_hits",
                  "minis_routed_per_partition",
                  "minis_found_per_partition", "survivors_per_partition")


def accumulate_partition_stats(totals: dict, stats) -> dict:
    """Merge a run's per-partition accounting (``stats["partitions"]``,
    present on sharded-index sessions, single and mesh) into
    ``totals["partitions"]``.  Counters and per-partition count vectors
    sum across runs; static descriptors (arena size, occurrence layout,
    current residency) take the latest run's value."""
    if not isinstance(stats, MapperStats):
        return totals
    part = stats.get("partitions")
    if not part:
        return totals
    acc = totals.setdefault("partitions", {})
    for k, v in part.items():
        if k in _PART_SUM_KEYS:
            if isinstance(v, list):
                prev = acc.get(k)
                acc[k] = ([a + b for a, b in zip(prev, v)] if prev
                          else list(v))
            else:
                acc[k] = acc.get(k, 0) + v
        else:
            acc[k] = v
    return totals


def accumulate_stats(totals: dict, stats, fields=None) -> dict:
    """Sum ``MapperStats`` fields into a running ``totals`` dict — the
    one home for the per-batch accumulation loop used by the serving
    layer and the launchers.  ``fields`` defaults to ``totals``'s own
    keys; a non-``MapperStats`` stats (padded engine: None) is a no-op.
    """
    if isinstance(stats, MapperStats):
        for k in (fields if fields is not None else tuple(totals)):
            totals[k] = totals.get(k, 0) + getattr(stats, k)
    return totals


# MapperStats fields mirrored into the metrics registry per run, and the
# fields ``totals_from_registry`` re-derives — keep the two in lockstep
# so registry-sourced closing stats byte-match the legacy accumulation
_METRIC_RUN_FIELDS = ("reads", "candidates", "survivors",
                      "affine_instances", "padded_affine_instances",
                      "dropped_send", "dropped_affine", "reverse_best")


def _record_run_metrics(stats: MapperStats) -> None:
    """Mirror one run's ``MapperStats`` into the active registry (no-op
    when metrics are disabled).  Summing these counters across runs is
    exactly ``accumulate_stats`` over the same fields, which is what
    lets the launchers re-emit their closing stats from the registry."""
    reg = _metrics.ACTIVE
    if reg is None:
        return
    lab = dict(topology=stats.topology)
    reg.counter("repro_runs_total", **lab).inc()
    for f in _METRIC_RUN_FIELDS:
        v = int(getattr(stats, f))
        if v:
            reg.counter(f"repro_{f}_total", **lab).inc(v)


def totals_from_registry(topology: str, reg=None) -> dict | None:
    """The engine-accounting totals dict re-derived from the metrics
    registry (None when metrics are disabled).  With a clean run this
    byte-matches the ``accumulate_stats`` path (property-tested); under
    faults the registry is the truthful one — it counts every engine
    run including retried and bisected blocks."""
    reg = reg if reg is not None else _metrics.ACTIVE
    if reg is None:
        return None
    return {f: reg.counter(f"repro_{f}_total", topology=topology).value
            for f in _METRIC_RUN_FIELDS}


@dataclasses.dataclass(frozen=True)
class MappingPlan:
    """What a ``Mapper.run`` will execute, decided before any dispatch.

    Single topology: ``chunk`` is the static chunk quantum (the jit shape
    every chunk is padded to), ``chunk_sizes`` the real per-chunk read
    counts, and ``lin_cap_max``/``aff_cap_max`` the ceilings the measured
    per-chunk bucket capacities are clamped to (the capacities themselves
    are data-dependent and picked host-side between the jitted stages).

    Mesh topology: ``padded_reads`` is the global batch shape (reads are
    zero-padded up to a multiple of ``n_shards``), ``send_cap`` the
    per-destination send-FIFO capacity of the all_to_all exchange, and
    ``stage_b_affine_cap`` the negotiated per-shard survivor capacity the
    compiled stage B executes.
    """
    topology: str
    engine: str
    n_reads: int                   # batch size the plan was sized for
    chunk: int                     # single: chunk quantum; mesh: padded R
    chunk_sizes: tuple             # single: per-chunk real read counts
    lin_cap_max: int = 0
    aff_cap_max: int = 0
    n_shards: int = 1
    send_cap: int = 0
    stage_b_affine_cap: int = 0
    padded_reads: int = 0
    both_strands: bool = False     # engine executes 2*n_reads encodings
    #                                (forward + reverse complement); results
    #                                are strand-reduced back to n_reads

    @property
    def n_chunks(self) -> int:
        return len(self.chunk_sizes)

    @property
    def key(self) -> tuple:
        """Plan-cache key: plans sharing a key share one executable (and
        therefore its compiled programs — equal keys cannot recompile).
        The mesh key includes the negotiated stage-B survivor capacity:
        static configs derive it deterministically from (batch, send_cap)
        so repeated plans still hit, while ``stage_b_adaptive`` sessions
        recompile exactly when the provisioned capacity moves."""
        if self.topology == "mesh":
            return ("mesh", self.padded_reads, self.send_cap,
                    self.stage_b_affine_cap)
        if self.engine == "padded":
            return ("single", "padded", self.n_reads)
        return ("single", self.engine, self.chunk)


def make_auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    the compiler) — the one place meshes are built; ``launch.mesh`` uses
    it too."""
    from jax.sharding import AxisType
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def _flat_mesh(n_shards: int | None):
    """Default flat shard mesh (``launch.mesh.make_genomics_mesh`` without
    the core->launch dependency)."""
    n = n_shards or len(jax.devices())
    return make_auto_mesh((n,), (AXIS,))


def _host_positions(pos):
    """Result-boundary position dtype: unsigned device positions (the
    uint32 arena of references past 2^31 without x64) become int64 with
    the all-ones BIG sentinel rewritten to the public -1.  Keyed on the
    sentinel value itself, not ``mapped`` — the two can disagree on
    degenerate candidates and -1 must mean exactly "no position won"."""
    if pos is None or pos.dtype.kind != "u":
        return pos
    big = np.iinfo(pos.dtype).max
    out = pos.astype(np.int64)
    out[pos == big] = -1
    return out


def _reduce_strands(res: MappingResult, n: int) -> MappingResult:
    """Fold a stacked fwd-then-rc result of 2n reads to the per-read best.

    The winner is the strand with the smaller affine-WF distance; ties
    (including both-unmapped) keep the forward strand, so single-strand
    workloads are bit-identical with or without ``both_strands``.  Every
    per-read field (traceback ops included) follows the winner, and the
    stats are re-expressed over the n real reads.
    """
    rev_wins = res.distance[n:] < res.distance[:n]

    def pick(a):
        if a is None:
            return None
        m = rev_wins.reshape((-1,) + (1,) * (a.ndim - 1))
        return np.where(m, a[n:], a[:n])

    mapped = pick(res.mapped)
    stats = res.stats
    if isinstance(stats, MapperStats):
        stats = dataclasses.replace(
            stats, reads=n, reverse_best=int(np.sum(rev_wins & mapped)),
            extra={**stats.extra, "both_strands": True})
    # the runner-up across both strands: the winner strand's own second
    # locus, or the loser strand's best alignment — whichever is closer.
    # (An opposite-strand hit is a genuinely competing alignment even at
    # the same locus, so no distance-to-winner exclusion applies here.)
    d2 = None
    if res.distance2 is not None:
        lose_d1 = np.where(rev_wins, res.distance[:n], res.distance[n:])
        d2 = np.minimum(pick(res.distance2), lose_d1).astype(
            res.distance2.dtype)
    return MappingResult(
        position=pick(res.position), distance=pick(res.distance),
        distance2=d2, mapped=mapped, strand=rev_wins.astype(np.int8),
        ops=pick(res.ops), op_count=pick(res.op_count),
        linear_dist=pick(res.linear_dist),
        n_candidates=pick(res.n_candidates), stats=stats)


class Mapper:
    """Read-mapping session: placed index + plan cache + executor.

    Parameters
    ----------
    index : GenomeIndex | ShardedIndex
        The reference index.  ``topology="mesh"`` accepts either — a
        ``GenomeIndex`` is sharded across the mesh on construction.
    cfg : MapperConfig, optional
        Defaults to ``MapperConfig.from_index(index)``.
    topology : "single" | "mesh"
        Back-end selection; see the module docstring.
    mesh : jax mesh, optional
        Mesh topology only.  Defaults to a flat mesh over ``n_shards``
        devices (all local devices when ``n_shards`` is None).
    n_shards, send_cap : int, optional
        Mesh topology only: shard count for the default mesh, and a fixed
        send-FIFO capacity (default: scaled from each plan's batch size).
    injector : FaultInjector, optional
        Chaos hook threaded into the streaming engine's fetch thread
        (``core.resilience``).  Runtime state, deliberately NOT part of
        ``MapperConfig`` — the config is a static jit argument and must
        stay hashable/value-comparable.
    watchdog_s : float, optional
        Streaming fetch watchdog: a chunk fetch exceeding this wall time
        raises ``streaming.FetchStallError`` instead of hanging the
        session.  None (default) disables the bound.
    memory_budget_bytes : int, optional
        Single topology with a ``repro.index.ShardedGenomeIndex`` only:
        device budget for the partition arena.  Partitions are loaded
        lazily per chunk and LRU-evicted under this bound
        (``repro.index.residency``).  None keeps every partition
        resident (the budget is the full index).
    prefetch : bool, optional
        Shard-routed single topology only: stage the next chunk's host
        seeding and partition uploads on a background worker while the
        current chunk computes (``repro.index.residency``).  Results are
        bit-identical to synchronous loading; only streamed runs
        (``cfg.stream=True``) actually overlap.

    Both topologies also accept a ``repro.index.ShardedGenomeIndex``:
    on ``"single"`` chunks are shard-routed through the residency arena;
    on ``"mesh"`` partition *i* is placed on shard *i* directly — the
    on-disk partitioning IS the mesh placement, no runtime re-hashing.
    """

    def __init__(self, index, cfg: MapperConfig | None = None, *,
                 topology: str = "single", mesh=None,
                 n_shards: int | None = None, send_cap: int | None = None,
                 injector=None, watchdog_s: float | None = None,
                 memory_budget_bytes: int | None = None,
                 prefetch: bool = False):
        if topology not in TOPOLOGIES:
            raise ValueError(f"unknown topology {topology!r}; "
                             f"expected one of {TOPOLOGIES}")
        if watchdog_s is not None and watchdog_s <= 0:
            raise ValueError(f"watchdog_s={watchdog_s!r} must be > 0 "
                             f"(or None to disable)")
        self.cfg = cfg or MapperConfig.from_index(index)
        self.topology = topology
        self.send_cap = send_cap
        self.injector = injector
        self.watchdog_s = watchdog_s
        self._plan_cache: dict[tuple, object] = {}
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        self._pool: ThreadPoolExecutor | None = None
        # rolling per-run stage-B survivor fractions (survivors / bucket
        # entries), fed by _run_mesh; drives adaptive capacity planning
        from collections import deque
        self._survivor_hist = deque(maxlen=self.cfg.stage_b_history)

        from ..index.sharded import ShardedGenomeIndex
        self.part_index = index if isinstance(index, ShardedGenomeIndex) \
            else None
        self.router = None
        if memory_budget_bytes is not None and not (
                topology == "single" and self.part_index is not None):
            raise ValueError(
                "memory_budget_bytes only applies to topology=\"single\" "
                "with a repro.index.ShardedGenomeIndex — the mesh topology "
                "places one whole partition per device, and a flat "
                "GenomeIndex is always fully resident")
        self.prefetch = bool(prefetch)
        if self.prefetch and not (topology == "single"
                                  and self.part_index is not None):
            raise ValueError(
                "prefetch=True only applies to topology=\"single\" with a "
                "repro.index.ShardedGenomeIndex — only the shard-routed "
                "arena path has per-chunk partition uploads to overlap")

        if topology == "single":
            if isinstance(index, ShardedIndex):
                raise ValueError('topology="single" needs a GenomeIndex, '
                                 "not a ShardedIndex")
            self.sharded_index = None
            self.mesh = None
            if self.part_index is not None:
                if self.cfg.engine == "padded":
                    raise ValueError(
                        'engine="padded" needs the whole index resident as '
                        "one flat array; use the compacted/fused engines "
                        "with a ShardedGenomeIndex, or "
                        "index.to_genome_index() to flatten it")
                if self.cfg.cigar_mode == "lazy":
                    raise ValueError(
                        'cigar_mode="lazy" defers traceback past the run, '
                        "but the residency arena may evict the segment "
                        "rows a deferred traceback would read; use "
                        'cigar_mode="eager" or "off" with a '
                        "ShardedGenomeIndex")
                from ..index.residency import DeviceResidency, ShardRouter
                self.index = None
                self._dev = None
                self.router = ShardRouter(
                    index, DeviceResidency(index, memory_budget_bytes),
                    self.cfg)
            else:
                self.index = index
                # dtype-explicit uploads: jnp.asarray silently narrows
                # int64 to int32 when x64 is off, which would wrap
                # format-v2 positions past 2^31.  device_position_dtype
                # picks what the device can hold (uint32 covers GRCh38);
                # occ_idx rows are int32 everywhere, so > 2^31 occurrence
                # rows in one flat device index is structurally out.
                pos = np.asarray(index.positions)
                max_pos = int(pos.max()) if len(pos) else 0
                pdt = device_position_dtype(max_pos + 1)
                offs = np.asarray(index.offsets)
                if len(pos) > np.iinfo(np.int32).max:
                    raise ValueError(
                        f"flat index has {len(pos)} occurrence rows, past "
                        f"int32 occ_idx addressing; use a "
                        f"ShardedGenomeIndex (partition-local rows)")
                self._dev = (jnp.asarray(index.uniq_kmers),
                             jnp.asarray(offs.astype(np.int32)),
                             jnp.asarray(pos.astype(pdt)),
                             jnp.asarray(index.segments))
        else:
            self.mesh = mesh if mesh is not None else _flat_mesh(n_shards)
            S = int(self.mesh.devices.size)
            if self.part_index is not None:
                if index.num_partitions != S:
                    raise ValueError(
                        f"sharded index has {index.num_partitions} "
                        f"partitions but the mesh has {S} devices — mesh "
                        f"placement maps partition i onto shard i, so "
                        f"rebuild the index with num_partitions={S} or "
                        f"map over a {index.num_partitions}-device mesh")
                sidx = index.to_mesh_shards()
                self.index = None
            elif isinstance(index, ShardedIndex):
                if index.n_shards != S:
                    raise ValueError(
                        f"ShardedIndex has {index.n_shards} shards but the "
                        f"mesh has {S} devices")
                sidx = index
                self.index = None
            else:
                sidx = shard_index(index, S)
                self.index = index
            self.sharded_index = sidx
            self._dev = sidx.device_arrays(self.mesh)

    # ------------------------------------------------------------- planning

    def plan(self, reads_spec, *, chunk: int | None = None,
             send_cap: int | None = None) -> MappingPlan:
        """Build the execution plan for a batch (no compute dispatched).

        ``reads_spec`` is a read count or a reads array.  ``chunk``
        overrides ``cfg.chunk_reads`` for this plan (single topology);
        ``send_cap`` overrides the session / derived send capacity (mesh).
        Inspect the returned ``MappingPlan`` for the chosen chunking,
        capacities and shard routing; pass it to :meth:`run` to execute.
        """
        n = (int(reads_spec) if isinstance(reads_spec, (int, np.integer))
             else len(reads_spec))
        cfg = self.cfg
        # with both_strands the engine maps forward + reverse-complement
        # encodings of every read: capacities/chunking are sized for the
        # effective 2n batch, the strand reduce trims back to n
        bs = cfg.both_strands
        eff = 2 * n if bs else n
        if self.topology == "mesh":
            S = self.sharded_index.n_shards
            padded = max(-(-eff // S) * S, S)
            sc = send_cap or self.send_cap or \
                max(2 * (padded // S) * cfg.max_minis // S, 8)
            return MappingPlan(
                topology="mesh", engine=cfg.engine, n_reads=n,
                chunk=padded, chunk_sizes=(eff,), n_shards=S, send_cap=sc,
                stage_b_affine_cap=stage_b_affine_capacity(
                    S * sc, cfg, frac=self._stage_b_frac()),
                padded_reads=padded, both_strands=bs)
        if cfg.engine == "padded":
            return MappingPlan(topology="single", engine="padded", n_reads=n,
                               chunk=max(eff, 1), chunk_sizes=(eff,),
                               both_strands=bs)
        # compacted/fused engines chunk over the n *reads*: each chunk
        # carries its own forward + reverse-complement rows and reduces
        # them on device, so capacities are sized for 2*chunk rows while
        # the chunk schedule (and every fetched array) stays per-read
        c = chunk or cfg.chunk_reads or max(n, 1)
        sizes = tuple(min(c, n - i) for i in range(0, n, c))
        rows = 2 * c if bs else c
        return MappingPlan(topology="single", engine=cfg.engine, n_reads=n,
                           chunk=c, chunk_sizes=sizes,
                           lin_cap_max=rows * cfg.max_minis * cfg.max_pls,
                           aff_cap_max=rows * cfg.max_minis, both_strands=bs)

    def _stage_b_frac(self) -> float | None:
        """Adaptive stage-B provisioning fraction, or None for the static
        ``cfg.stage_b_survivor_frac``.  Uses the session's rolling
        quantile of observed survivor fractions with 25% headroom — a
        workload that filters harder than provisioned shrinks the
        compiled affine pass, one that stops filtering grows it instead
        of silently dropping survivors."""
        if not self.cfg.stage_b_adaptive or not self._survivor_hist:
            return None
        q = float(np.quantile(np.asarray(self._survivor_hist),
                              self.cfg.stage_b_quantile))
        return min(q * 1.25, 1.0)

    def _executable(self, plan: MappingPlan):
        """Plan-cache lookup (counting hits/misses), building on miss.

        Cache entries are the per-plan executables: the chunk pipeline of
        the compacted engine, the jitted padded reference, or the
        compiled ``shard_map`` program + negotiated survivor capacity of
        the mesh mapper.  Repeated same-key plans therefore reuse the
        exact compiled programs — a cache hit cannot recompile.
        """
        reg = _metrics.ACTIVE
        entry = self._plan_cache.get(plan.key)
        if entry is not None:
            self.plan_cache_hits += 1
            if reg is not None:
                reg.counter("repro_plan_cache_hits_total",
                            topology=self.topology).inc()
            return entry
        self.plan_cache_misses += 1
        if reg is not None:
            reg.counter("repro_plan_cache_misses_total",
                        topology=self.topology).inc()
        if plan.topology == "mesh":
            entry = _cached_mapper(self.mesh, self.cfg, plan.n_shards,
                                   plan.send_cap, plan.stage_b_affine_cap)
        elif plan.engine == "padded":
            entry = map_reads_jax
        elif self.router is not None:
            from ..index.residency import _RoutedChunkPipeline
            entry = _RoutedChunkPipeline(self.router, self.cfg,
                                         prefetch=self.prefetch)
        else:
            entry = _ChunkPipeline(self._dev, self.cfg)
        self._plan_cache[plan.key] = entry
        return entry

    # ------------------------------------------------------------ execution

    def map(self, reads: np.ndarray) -> MappingResult:
        """Plan + run one read batch; the single public mapping call.
        Traced as one ``repro/map`` span with the batch's ``reads`` and
        ``syncs``, the blocking device-to-host reads the call made."""
        reads = np.asarray(reads)
        with _tracing.span("repro/map", reads=len(reads)) as sp:
            res = self.run(self.plan(len(reads)), reads)
            if res.stats is not None and "d2h_syncs" in res.stats:
                sp.set_metadata(syncs=res.stats["d2h_syncs"])
        return res

    def map_pairs(self, reads1: np.ndarray, reads2: np.ndarray,
                  ) -> tuple[MappingResult, MappingResult]:
        """Map both mates of a paired batch in ONE stacked engine batch.

        ``reads1[i]`` and ``reads2[i]`` are the R1/R2 mates of pair
        ``i``, each in as-sequenced orientation (both_strands handles
        orientation per mate).  The stack shares a single plan — same
        chunking, same capacities, one strand reduce — and is split back
        into per-mate results, so pairing never forks the execution
        path.  Host-side pair resolution (proper pairs, rescue, MAPQ)
        lives in ``repro.core.pairing``.
        """
        reads1, reads2 = np.asarray(reads1), np.asarray(reads2)
        if reads1.shape != reads2.shape:
            raise ValueError(f"mate batches must align pairwise: "
                             f"{reads1.shape} vs {reads2.shape}")
        res = self.map(np.concatenate([reads1, reads2]))
        return split_result(res, len(reads1))

    def map_async(self, reads: np.ndarray) -> Future:
        """Submit a batch to the session worker thread; returns a Future
        of the ``MappingResult``.  Submissions execute in order, each one
        driving the double-buffered streaming engine internally, so the
        caller overlaps its own work (e.g. preparing the next batch) with
        the full mapping pipeline of this one."""
        reads = np.asarray(reads)
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="mapper-session")
        return self._pool.submit(self.map, reads)

    def serve(self, batcher=None, **kwargs):
        """A ``MappingService`` request batcher wired to this session.
        ``kwargs`` forward to ``MappingService`` (``admission=``,
        ``retry=``, ``injector=``)."""
        from .serving import BatcherConfig, MappingService
        return MappingService(self, batcher=batcher or BatcherConfig(),
                              **kwargs)

    def index_storage(self) -> dict | None:
        """Footprint accounting of the session's index — the flat
        ``storage_bytes`` dict, or the sharded one with its
        ``per_partition`` breakdown (``repro.index``).  None when the
        session holds only pre-placed device shards (a raw
        ``ShardedIndex``) with no host-side source index."""
        src = self.part_index if self.part_index is not None else self.index
        if src is None:
            return None
        return src.storage_bytes()

    def close(self):
        """Shut down the ``map_async`` worker (no-op if never used)."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def run(self, plan: MappingPlan, reads: np.ndarray) -> MappingResult:
        """Execute ``reads`` through ``plan``'s cached executable.

        ``len(reads)`` may be smaller than the plan's batch size (the
        serving path reuses one bucket-sized plan for a shorter residue):
        reads are padded to the plan's static shape and results trimmed.

        On a ``both_strands`` plan the engine executes the forward and
        reverse-complement encodings of every read.  The compacted/fused
        engines stack the two encodings *per chunk* and reduce the
        per-read winner on device before anything is fetched (see
        ``pipeline._strand_stage``); the padded reference and the mesh
        topology run one stacked fwd-then-rc batch and reduce host-side
        (``_reduce_strands``).  Either way: lower distance wins, ties
        prefer the forward strand — bit-identical results.
        """
        reads = np.asarray(reads)
        if plan.both_strands and (plan.topology == "mesh"
                                  or plan.engine == "padded"):
            n_real = len(reads)
            reads = np.concatenate([reads, revcomp(reads)])
            res = self._run_strand(plan, reads)
            return _reduce_strands(res, n_real)
        return self._run_strand(plan, reads)

    def _run_strand(self, plan: MappingPlan, reads: np.ndarray,
                    ) -> MappingResult:
        n = len(reads)
        entry = self._executable(plan)
        if plan.topology == "mesh":
            return self._run_mesh(plan, entry, reads, n)
        if plan.engine == "padded":
            out = entry(*self._dev, jnp.asarray(reads), self.cfg)
            return MappingResult(
                position=_host_positions(np.asarray(out["position"])),
                distance=np.asarray(out["distance"]),
                distance2=np.asarray(out["distance2"]),
                mapped=np.asarray(out["mapped"]),
                ops=np.asarray(out["ops"]),
                op_count=np.asarray(out["op_count"]),
                linear_dist=np.asarray(out["linear_dist"]),
                n_candidates=np.asarray(out["n_candidates"]), stats=None)
        return self._run_chunks(plan, entry, reads, n)

    def _run_chunks(self, plan: MappingPlan, pipe: _ChunkPipeline,
                    reads: np.ndarray, n: int) -> MappingResult:
        cfg = self.cfg
        items = [(reads[c0 : c0 + plan.chunk], plan.chunk)
                 for c0 in range(0, n, plan.chunk)]
        pipe.begin_run(items)
        if cfg.stream:
            times = {} if cfg.profile else None
            fetched = streaming.stream_map(items, pipe.phase1, pipe.phase2,
                                           pipe.fetch, times=times,
                                           injector=self.injector,
                                           watchdog_s=self.watchdog_s)
        else:
            times = {}
            fetched = streaming.sync_map(items, pipe.phase1, pipe.phase2,
                                         pipe.fetch, times=times)
        parts = [out for out, _ in fetched]
        raw = _merge_stats([st for _, st in fetched])
        raw["stream"] = cfg.stream
        if cfg.both_strands:
            raw["both_strands"] = True
        if times is not None:
            # full precision: stage times feed a 5 ms-noise-floor CI gate
            # and the trace-agreement check; rounding happens only at
            # display/serialization (benchmarks, logs)
            raw["stage_times_s"] = dict(times)
        if getattr(pipe, "router", None) is not None:
            raw["partitions"] = pipe.router.drain_stats()

        def cat(k):
            if k not in parts[0]:
                return None
            if len(parts) > 1:  # concatenate copies -> always writable
                return np.concatenate([np.asarray(p[k]) for p in parts])
            a = np.asarray(parts[0][k])
            # a single chunk's fetch is a zero-copy read-only view of the
            # device buffer; results are caller-owned, so hand out a
            # writable copy (callers mutate e.g. `mapped` in pair rescue)
            return a if a.flags.writeable else a.copy()

        mapped = cat("mapped")
        lazy = None
        if cfg.cigar_mode == "lazy":
            lazy = LazyTraceback(self._dev[3], cfg, cat("_tb_reads"),
                                 cat("_tb_occ"), cat("_tb_mpos"), mapped)
        stats = MapperStats(
            topology="single", engine=cfg.engine, reads=n,
            candidates=raw["candidates_valid"], survivors=raw["survivors"],
            affine_instances=raw["affine_dist_instances"],
            padded_affine_instances=raw["padded_affine_instances"],
            reverse_best=raw.get("reverse_best", 0),
            plan_cache_hits=self.plan_cache_hits,
            plan_cache_misses=self.plan_cache_misses, extra=raw)
        _record_run_metrics(stats)
        return MappingResult(position=_host_positions(cat("position")),
                             distance=cat("distance"),
                             distance2=cat("distance2"),
                             mapped=mapped, strand=cat("strand"),
                             ops=cat("ops"), op_count=cat("op_count"),
                             linear_dist=cat("linear_dist"),
                             n_candidates=cat("n_candidates"), stats=stats,
                             lazy_tb=lazy)

    def _run_mesh(self, plan: MappingPlan, entry, reads: np.ndarray,
                  n: int) -> MappingResult:
        if n > plan.padded_reads:
            raise ValueError(f"{n} reads exceed the plan's padded batch "
                             f"shape {plan.padded_reads}; re-plan")
        if n < plan.padded_reads:
            pad = np.zeros((plan.padded_reads - n, reads.shape[1]),
                           reads.dtype)
            reads = np.concatenate([reads, pad])
        fn, aff_cap = entry
        # the mesh path has no chunk pipeline, so its stage accounting is
        # the two host-visible boundaries: the async dispatch enqueue and
        # the blocking D2H fetch.  Same ``streaming.timed`` hook as the
        # single topology: when tracing is armed the spans and the
        # ``stage_times_s`` values come from identical clock reads.
        times = ({} if (self.cfg.profile or _tracing.ACTIVE is not None)
                 else None)
        t0 = time.perf_counter()
        with _tracing.span("repro/map/dispatch", stage="mesh"):
            pos, dist, dist2, dropped, n_surv, aff_drop = fn(
                *self._dev, jnp.asarray(reads))
        t0 = streaming.timed(times, "dispatch", t0)
        pos = np.asarray(pos)[:n]
        dist = np.asarray(dist)[:n]
        dist2 = np.asarray(dist2)[:n]
        dropped = np.asarray(dropped)
        streaming.timed(times, "d2h", t0)
        S = plan.n_shards
        surv = int(np.asarray(n_surv).sum())
        n_aff_drop = int(np.asarray(aff_drop).sum())
        entries = S * S * plan.send_cap
        self._survivor_hist.append(surv / max(entries, 1))
        raw = dict(stage_b_entries=entries, stage_b_survivors=surv,
                   stage_b_affine_capacity=aff_cap,
                   stage_b_affine_instances=S * aff_cap,
                   stage_b_padded_affine_instances=entries,
                   stage_b_affine_dropped=n_aff_drop,
                   send_dropped=int(dropped.sum()),
                   send_dropped_per_shard=dropped,
                   stage_b_survivors_per_shard=np.asarray(n_surv),
                   padded_reads=plan.padded_reads)
        if times is not None:
            raw["stage_times_s"] = dict(times)
        if self.part_index is not None:
            # partition i IS shard i: the on-disk partitioning routed the
            # mesh, so per-shard counters are per-partition counters
            raw["partitions"] = dict(
                num_partitions=S,
                occurrences_per_partition=[p.n_occurrences
                                           for p in self.part_index.parts],
                survivors_per_partition=np.asarray(n_surv).tolist())
        stats = MapperStats(
            topology="mesh", engine=self.cfg.engine, reads=n,
            candidates=entries, survivors=surv,
            affine_instances=S * aff_cap, padded_affine_instances=entries,
            dropped_send=int(dropped.sum()), dropped_affine=n_aff_drop,
            plan_cache_hits=self.plan_cache_hits,
            plan_cache_misses=self.plan_cache_misses, extra=raw)
        _record_run_metrics(stats)
        return MappingResult(position=pos, distance=dist, distance2=dist2,
                             mapped=pos >= 0, stats=stats)
