"""End-to-end read mapping (paper Secs. V-B .. V-E), single-shard version.

Stages (numbers = the circled steps of paper Fig. 6):
  (1)(2) seeding     — minimizer lookup, candidate PLs       (seeding.py)
  (3)    linear WF   — banded distance for every candidate   (filtering.py)
  (4)    min extract — best PL per (read, minimizer)
  (5)(6) affine WF   — alignment + traceback for the winners (affine_wf.py)
  (7)    reduce      — best PL per read across minimizers

Two execution engines share these semantics bit-for-bit:

``engine="padded"`` — the fully-jit reference: one compiled program that
runs the linear WF over every slot of the static ``(R, M, P)`` candidate
tensor (invalid ones included) and the affine WF over every ``(R, M)``
winner, direction planes and all.

``engine="compacted"`` (default) — the candidate-compacted engine that
mirrors DART-PIM's actual dataflow: seeding output is flattened and
compacted to valid-only candidates in a static power-of-two, lane-aligned
capacity bucket (``repro.core.compaction``); the linear WF runs on just
those instances; the filter threshold is applied *before* the affine stage,
which then runs a distance-only pass on the compacted survivors; the
dirs-emitting affine pass + traceback run solely on the one winner per
read.  Capacities are chosen host-side from the measured counts, so jit
recompiles are bounded by the number of distinct bucket sizes.  Large read
batches stream through in ``chunk_reads``-sized chunks instead of
materializing one giant window tensor; with ``stream=True`` (default) the
chunks run on the async double-buffered engine of ``repro.core.streaming``
— chunk i+1's transfer+seeding and chunk i-1's result fetch overlap chunk
i's WF compute — while ``stream=False`` is the fully synchronous debug
path that records per-stage wall times in ``stats["stage_times_s"]``.
Both paths execute the same jitted stages with the same capacities and
are bit-identical.

Both engines run their WF inner loops on the backend selected by
``MapperConfig.wf_backend``: the pure-jnp reference or the Pallas kernels
of ``repro.kernels`` (interpret mode on CPU, compiled on TPU).

The distributed version in ``repro.core.distributed`` wraps the same
stages with an all_to_all seeding exchange over the device mesh.

Callers should not drive these stages directly: the public front-end is
the ``Mapper`` session of ``repro.core.mapper``, which owns device
placement, the plan cache, and topology selection (``map_reads`` below is
its deprecation shim).
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import affine_wf
from . import streaming
from . import wf_backend as wfb
from ..obs import tracing as _tracing
from .compaction import bucket_capacity, compact_indices, scatter_to
from .encoding import revcomp
from .filtering import collapse_candidates, gather_windows, linear_wf_filter
from .index import GenomeIndex, validate_geometry
from .linear_wf import banded_wf
from .seeding import SeedParams, seed_reads


@dataclasses.dataclass(frozen=True)
class MapperConfig:
    read_len: int = 150
    k: int = 12
    w: int = 30
    eth: int = 6            # band half-width (linear + affine) — Table III
    sat_affine: int = 32    # affine value saturation (5-bit cells) — Table III
    max_minis: int = 16
    max_pls: int = 32       # linear WF buffer rows per crossbar
    filter_threshold: int = 6
    max_ops: int | None = None
    engine: str = "compacted"     # "compacted" | "fused" | "padded"
    wf_backend: str = "jnp"       # "jnp" | "pallas"  (see core.wf_backend)
    cigar_mode: str = "eager"     # "eager" | "lazy" | "off": when the
    #                               dirs-emitting traceback pass runs.
    #                               eager = with the batch (default);
    #                               lazy  = deferred until the first
    #                               MappingResult.ops/op_count access;
    #                               off   = never (distance-only consumers;
    #                               SAM CIGARs degrade to "*")
    lin_block_r: int = 512        # linear kernel lanes; linear bucket align
    aff_block_r: int = 256        # affine kernel lanes; affine bucket align
    chunk_reads: int | None = None  # stream reads in chunks of this size
    both_strands: bool = False    # map forward + reverse-complement encodings
    #                               of every read; best (pos, dist, strand)
    #                               wins (see repro.core.mapper)
    stream: bool = True           # double-buffered chunk overlap (compacted
    #                               engine); False = fully synchronous debug
    #                               path with per-stage wall times in stats
    stage_b_survivor_frac: float = 0.5  # distributed stage-B: static affine
    #                               capacity as a fraction of bucket entries
    profile: bool = False         # streamed path: record per-stage
    #                               completion-time offsets into
    #                               stats["stage_times_s"] (the sync path
    #                               always records exclusive wall times)
    stage_b_adaptive: bool = False  # mesh: derive the stage-B survivor
    #                               capacity from the session's observed
    #                               survivor-fraction history instead of
    #                               the static stage_b_survivor_frac
    stage_b_quantile: float = 0.9   # rolling quantile of that history
    stage_b_history: int = 32       # history window (runs)

    ENGINES = ("compacted", "fused", "padded")
    WF_BACKENDS = ("jnp", "pallas")
    CIGAR_MODES = ("eager", "lazy", "off")

    def __post_init__(self):
        """Reject invalid configurations at construction time, with errors
        that name the field — instead of failing deep inside jit tracing
        (or worse, silently misaligning kernel lanes)."""
        validate_geometry(read_len=self.read_len, k=self.k, w=self.w,
                          eth=self.eth)
        if self.engine not in self.ENGINES:
            raise ValueError(f"unknown engine {self.engine!r}; expected one "
                             f"of {self.ENGINES}")
        if self.wf_backend not in self.WF_BACKENDS:
            raise ValueError(f"unknown wf_backend {self.wf_backend!r}; "
                             f"expected one of {self.WF_BACKENDS}")
        for name in ("lin_block_r", "aff_block_r"):
            v = getattr(self, name)
            if not (isinstance(v, int) and v >= 1 and (v & (v - 1)) == 0):
                raise ValueError(
                    f"{name}={v!r} must be a positive power of two: it is "
                    f"the Pallas kernel lane block and the bucket-capacity "
                    f"alignment (see repro.core.compaction)")
        if self.chunk_reads is not None and self.chunk_reads < 1:
            raise ValueError(f"chunk_reads={self.chunk_reads!r} must be "
                             f">= 1 (or None for unchunked)")
        if self.cigar_mode not in self.CIGAR_MODES:
            raise ValueError(f"unknown cigar_mode {self.cigar_mode!r}; "
                             f"expected one of {self.CIGAR_MODES}")
        if self.engine == "padded" and self.cigar_mode != "eager":
            raise ValueError(
                'engine="padded" is the fully-eager reference and only '
                f'supports cigar_mode="eager", got {self.cigar_mode!r}')
        if not 0.0 <= self.stage_b_quantile <= 1.0:
            raise ValueError(f"stage_b_quantile={self.stage_b_quantile!r} "
                             f"must be within [0, 1]")
        if self.stage_b_history < 1:
            raise ValueError(f"stage_b_history={self.stage_b_history!r} "
                             f"must be >= 1")

    @classmethod
    def from_index(cls, index, **overrides) -> "MapperConfig":
        """Config matching an index's geometry (``read_len``/``k``/``w``/
        ``eth``), with ``overrides`` applied on top.  Accepts a
        ``GenomeIndex`` or a ``distributed.ShardedIndex`` — the single
        place where index geometry flows into a config, so launchers
        cannot drift out of sync by hand-copying fields."""
        base = dict(read_len=index.read_len, k=index.k, w=index.w,
                    eth=index.eth)
        base.update(overrides)
        return cls(**base)

    @property
    def seed_params(self) -> SeedParams:
        return SeedParams(k=self.k, w=self.w, max_minis=self.max_minis,
                          max_pls=self.max_pls)


@dataclasses.dataclass
class MappingResult:
    """Unified mapping output across every execution path.

    The traceback/accounting fields are ``None`` on paths that do not
    produce them (the mesh topology's stage B computes distances and
    positions only — see ``repro.core.mapper``).  ``stats`` is a
    ``mapper.MapperStats`` on the compacted/mesh paths (dict-compatible
    for the legacy keys) and ``None`` on the padded reference engine.

    With ``cigar_mode="lazy"`` the ``ops``/``op_count`` fields start as
    ``None`` and a ``lazy_tb`` holder carries the per-read winner metadata;
    the first attribute access of either field dispatches the deferred
    on-device traceback and fills both in (reads that never ask for CIGARs
    never pay for them).
    """
    position: np.ndarray   # (R,) int best mapping position, -1 if unmapped
    #                      (int32 device-side up to 2^31-1 bases; int64 on
    #                      the host past that — see core.index.
    #                      device_position_dtype)
    distance: np.ndarray   # (R,) int32 affine WF distance
    mapped: np.ndarray     # (R,) bool
    distance2: np.ndarray | None = None  # (R,) int32 runner-up affine WF
    #                      distance at a *different* locus (beyond the band
    #                      half-width from the winner; ``sat_affine`` when no
    #                      competing locus exists) — the best-vs-second-best
    #                      gap that feeds the MAPQ model (repro.core.pairing)
    strand: np.ndarray | None = None  # (R,) int8 0=forward 1=reverse-
    #                      complement winner; None on single-strand runs
    ops: np.ndarray | None = None   # (R, max_ops) traceback ops (END-aligned)
    op_count: np.ndarray | None = None  # (R,) int32
    linear_dist: np.ndarray | None = None  # (R, M, P) candidate linear dists
    n_candidates: np.ndarray | None = None  # (R,) valid PLs seeded
    stats: object | None = None  # MapperStats (compacted/mesh) | None
    failed: np.ndarray | None = None  # (R,) bool quarantine mask set by the
    #                      resilience layer: True rows exhausted retry +
    #                      bisection and carry synthesized unmapped values
    #                      (position=-1, mapped=False); None on healthy runs
    lazy_tb: object | None = None  # LazyTraceback (cigar_mode="lazy") —
    #                      consumed (set back to None) on materialization

    def __getattribute__(self, name):
        if name in ("ops", "op_count"):
            lt = object.__getattribute__(self, "lazy_tb")
            if lt is not None:
                object.__setattr__(self, "lazy_tb", None)
                ops, cnt = lt.materialize()
                object.__setattr__(self, "ops", ops)
                object.__setattr__(self, "op_count", cnt)
        return object.__getattribute__(self, name)


@partial(jax.jit, static_argnames=("cfg",))
def map_reads_jax(uniq_kmers, offsets, positions, segments, reads,
                  cfg: MapperConfig):
    """The padded-reference jit pipeline.  Index arrays are device arrays;
    reads (R, rl).  Every (R, M, P) slot is executed, valid or not."""
    R = reads.shape[0]
    seeds = seed_reads(uniq_kmers, offsets, reads, cfg.seed_params)
    occ_idx, occ_valid = seeds["occ_idx"], seeds["occ_valid"]
    mini_pos = seeds["mini_pos"]  # (R, M)

    # (3) linear WF over every candidate
    windows = gather_windows(segments, occ_idx, mini_pos[..., None],
                             read_len=cfg.read_len, k=cfg.k, eth=cfg.eth)
    lin_end, _ = linear_wf_filter(reads, windows, occ_valid, eth=cfg.eth,
                                  backend=cfg.wf_backend,
                                  block_r=cfg.lin_block_r)

    # (4) min extraction per (read, minimizer); filter threshold
    best_pl, _, pass_filter = collapse_candidates(lin_end,
                                                  cfg.filter_threshold)

    # (5)+(6) affine WF on the per-minimizer winners
    sel_win = jnp.take_along_axis(
        windows, best_pl[..., None, None], axis=2)[:, :, 0]      # (R, M, wlen)
    s1 = jnp.broadcast_to(reads[:, None, :],
                          (R, cfg.max_minis, cfg.read_len))
    aff_end, _, dirs = wfb.affine_wf_dirs(s1, sel_win, eth=cfg.eth,
                                          sat=cfg.sat_affine,
                                          backend=cfg.wf_backend,
                                          block_r=cfg.aff_block_r)
    aff_end = jnp.where(pass_filter, aff_end, cfg.sat_affine)

    # (7) best minimizer per read — min distance, ties -> leftmost position
    # (deterministic across the single-shard and distributed mappers)
    cand_occ = jnp.take_along_axis(occ_idx,
                                   best_pl[..., None], axis=2)[:, :, 0]
    cand_pos, cand_ok = _cand_positions(positions, cand_occ, mini_pos)
    big = _pos_big(positions)
    best_aff = jnp.min(aff_end, axis=-1)
    mapped = best_aff < cfg.sat_affine
    is_best = aff_end == best_aff[:, None]
    pos_key = jnp.where(is_best & cand_ok, cand_pos, big)
    position = jnp.min(pos_key, axis=-1)
    best_m = jnp.argmin(jnp.where(pos_key == position[:, None],
                                  jnp.arange(cfg.max_minis)[None, :],
                                  cfg.max_minis), axis=-1)
    position = jnp.where(mapped & (position < big), position,
                         _pos_unmapped(positions))
    distance2 = _runner_up_distance(aff_end, cand_pos, cand_ok, position,
                                    cfg.eth, cfg.sat_affine)
    distance2 = _co_optimal_runner_up(lin_end, occ_idx, mini_pos, positions,
                                      position, best_m, best_aff,
                                      distance2, cfg)

    # traceback for the winning instance only
    sel_dirs = jnp.take_along_axis(
        dirs, best_m[:, None, None, None], axis=1)[:, 0]         # (R, n, band)
    max_ops = cfg.max_ops or 2 * cfg.read_len + 2
    ops, op_count = affine_wf.traceback(sel_dirs, cfg.eth, max_ops)
    ops = jnp.where(mapped[:, None], ops, affine_wf.OP_NONE)
    op_count = jnp.where(mapped, op_count, 0)

    return dict(position=position, distance=best_aff, distance2=distance2,
                mapped=mapped, ops=ops, op_count=op_count,
                linear_dist=lin_end,
                n_candidates=jnp.sum(occ_valid, axis=(1, 2)))


def _pos_big(positions):
    """Sentinel strictly above every real mapping position, in the
    positions dtype.  Replaces the old hardcoded ``2**30``, which real
    positions *reach* once the reference passes 2^30 bases — a mapped
    read there would have been reported unmapped."""
    return jnp.asarray(jnp.iinfo(positions.dtype).max, positions.dtype)


def _pos_unmapped(positions):
    """Device-side unmapped sentinel: -1 for signed position dtypes
    (the historical contract), the dtype max for unsigned ones (uint32
    arenas past 2^31 bases) — the host boundary rewrites it to -1."""
    if jnp.issubdtype(positions.dtype, jnp.unsignedinteger):
        return _pos_big(positions)
    return jnp.asarray(-1, positions.dtype)


def _cand_positions(positions, occ, mini_pos):
    """Candidate genome positions ``positions[occ] - mini_pos`` plus a
    validity mask, dtype-safe for signed and unsigned position arrays:
    an unsigned subtraction wraps instead of going negative, so
    validity is tested *before* the subtract (``p >= mini_pos``)."""
    p = positions[occ]
    mp = mini_pos.astype(positions.dtype)
    cp = p - mp
    if jnp.issubdtype(positions.dtype, jnp.unsignedinteger):
        ok = p >= mp
    else:
        ok = cp >= 0
    return cp, ok


def _absdiff(a, b):
    """|a - b| without a signed intermediate (unsigned-dtype-safe)."""
    return jnp.where(a > b, a - b, b - a)


def _runner_up_distance(aff_end, cand_pos, cand_ok, position, eth: int,
                        sat: int):
    """Best affine distance among candidates at a *different* locus than
    the winner (more than the band half-width away — candidates within
    ``eth`` of the winning position are the same alignment seeded from
    another minimizer, not a competitor).  ``sat`` when no competing
    locus exists; both engines share this so their ``distance2`` is
    bit-identical like the rest of the result."""
    far = _absdiff(cand_pos, position[:, None]) > eth
    key = jnp.where((aff_end < sat) & far & cand_ok, aff_end, sat)
    return jnp.min(key, axis=-1).astype(jnp.int32)


def _co_optimal_runner_up(lin_end, occ_idx, mini_pos, positions, position,
                          best_m, best_aff, distance2, cfg: MapperConfig):
    """Fold placement-level competitors into ``distance2``.

    The per-(read, minimizer) reduce collapses placements with
    ``argmin`` (ties -> lowest index), so a repeat copy whose placements
    share *all* the winner's minimizers never reaches the affine survey
    — an ambiguous read would look unique and earn maximal MAPQ.  The
    linear stage's full ``(R, M, P)`` distances still see every
    placement: any far-locus placement at most the filter threshold is a
    competitor, its affine distance estimated as the winner's plus its
    linear-distance excess (exact for exact repeat copies, where the
    excess is 0)."""
    eth, sat = cfg.eth, cfg.sat_affine
    sat_lin = jnp.int32(eth + 1)
    pos_all, _ = _cand_positions(positions, occ_idx,
                                 mini_pos[..., None])          # (R, M, P)
    far = _absdiff(pos_all, position[:, None, None]) > eth
    # min(thr, eth) keeps the linear sat value (= invalid/absent slots)
    # out even when the filter threshold is set above the band
    cand = far & (lin_end <= min(cfg.filter_threshold, eth))
    min_far = jnp.min(jnp.where(cand, lin_end, sat_lin), axis=(1, 2))
    lin_w_all = jnp.min(lin_end, axis=-1)                      # (R, M)
    lin_w = jnp.take_along_axis(lin_w_all, best_m[:, None], 1)[:, 0]
    est = jnp.minimum(best_aff + jnp.maximum(min_far - lin_w, 0), sat)
    return jnp.where(min_far < sat_lin,
                     jnp.minimum(distance2, est.astype(jnp.int32)),
                     distance2)


# --------------------------------------------------------------------------
# Compacted execution engine
# --------------------------------------------------------------------------

def _linear_stage_impl(segments, reads, occ_idx, occ_valid, mini_pos,
                       cfg: MapperConfig, cap: int):
    """(3)+(4): compact valid candidates -> linear WF on ``cap`` instances
    -> scatter distances back -> per-(read, minimizer) min + filter."""
    R = reads.shape[0]
    M, P = cfg.max_minis, cfg.max_pls
    N = R * M * P
    sat = cfg.eth + 1

    slots, slot_ok = compact_indices(occ_valid.reshape(-1), cap)
    r_idx = slots // (M * P)
    m_idx = (slots // P) % M
    occ = occ_idx.reshape(-1)[slots]
    mpos = mini_pos[r_idx, m_idx]

    wins = gather_windows(segments, occ, mpos, read_len=cfg.read_len,
                          k=cfg.k, eth=cfg.eth)                  # (cap, wlen)
    de, _ = wfb.linear_wf_dist(reads[r_idx], wins, eth=cfg.eth,
                               backend=cfg.wf_backend,
                               block_r=cfg.lin_block_r)
    de = jnp.where(slot_ok, de, sat).astype(jnp.int32)
    lin_end = scatter_to(N, slots, slot_ok, de,
                         jnp.int32(sat)).reshape(R, M, P)

    best_pl, _, pass_filter = collapse_candidates(lin_end,
                                                  cfg.filter_threshold)
    return lin_end, best_pl, pass_filter, jnp.sum(occ_valid, axis=(1, 2))


def _affine_stage_impl(segments, positions, reads, occ_idx, mini_pos, best_pl,
                       pass_filter, lin_end_full, cfg: MapperConfig,
                       cap: int):
    """(5)+(7): distance-only affine WF on the compacted filter survivors,
    then the per-read winner reduce (identical tie-breaking to the padded
    engine: min distance, ties -> leftmost position).  ``lin_end_full``
    is the linear stage's (R, M, P) distance tensor, surveyed for
    placement-level co-optimal competitors the per-minimizer collapse
    hides (see ``_co_optimal_runner_up``)."""
    R = reads.shape[0]
    M = cfg.max_minis
    sat = cfg.sat_affine

    slots, slot_ok = compact_indices(pass_filter.reshape(-1), cap)
    r_idx = slots // M
    m_idx = slots % M
    pl = best_pl.reshape(-1)[slots]
    occ = occ_idx[r_idx, m_idx, pl]
    mpos = mini_pos[r_idx, m_idx]

    wins = gather_windows(segments, occ, mpos, read_len=cfg.read_len,
                          k=cfg.k, eth=cfg.eth)                  # (cap, wlen)
    ae, _ = wfb.affine_wf_dist(reads[r_idx], wins, eth=cfg.eth, sat=sat,
                               backend=cfg.wf_backend,
                               block_r=cfg.aff_block_r)
    ae = jnp.where(slot_ok, ae, sat).astype(jnp.int32)
    aff_end = scatter_to(R * M, slots, slot_ok, ae,
                         jnp.int32(sat)).reshape(R, M)

    cand_occ = jnp.take_along_axis(occ_idx,
                                   best_pl[..., None], axis=2)[:, :, 0]
    cand_pos, cand_ok = _cand_positions(positions, cand_occ, mini_pos)
    big = _pos_big(positions)
    best_aff = jnp.min(aff_end, axis=-1)
    mapped = best_aff < sat
    is_best = aff_end == best_aff[:, None]
    pos_key = jnp.where(is_best & cand_ok, cand_pos, big)
    position = jnp.min(pos_key, axis=-1)
    best_m = jnp.argmin(jnp.where(pos_key == position[:, None],
                                  jnp.arange(M)[None, :], M), axis=-1)
    position = jnp.where(mapped & (position < big), position,
                         _pos_unmapped(positions))
    distance2 = _runner_up_distance(aff_end, cand_pos, cand_ok, position,
                                    cfg.eth, sat)
    distance2 = _co_optimal_runner_up(lin_end_full, occ_idx, mini_pos,
                                      positions, position, best_m,
                                      best_aff, distance2, cfg)
    # winner metadata (occurrence row + minimizer offset of the winning
    # instance): everything the traceback pass needs, so it no longer has
    # to re-derive the winner from the full (R, M, P) seeding tensors —
    # which is what lets the strand reduce and the lazy-CIGAR holder carry
    # two small vectors instead of the whole candidate state
    r = jnp.arange(R, dtype=jnp.int32)
    occ_w = cand_occ[r, best_m]
    mpos_w = mini_pos[r, best_m]
    return best_aff, mapped, position, best_m, distance2, occ_w, mpos_w


_linear_stage = partial(jax.jit, static_argnames=("cfg", "cap"))(
    _linear_stage_impl)
_affine_stage = partial(jax.jit, static_argnames=("cfg", "cap"))(
    _affine_stage_impl)


@functools.lru_cache(maxsize=2)
def _stage_jits(donate: bool):
    """Jitted (linear, affine) stages, optionally donating the one buffer
    each consumes exactly once (occ_valid / pass_filter) so streamed chunks
    reuse device allocations instead of growing the arena.  Donation is
    requested only on backends that implement it
    (``streaming.donatable_argnums``); everywhere else the module-level
    non-donating pair is returned so all paths share one executable cache.
    """
    lin_don = streaming.donatable_argnums(3) if donate else ()
    aff_don = streaming.donatable_argnums(6) if donate else ()
    if not lin_don and not aff_don:
        return _linear_stage, _affine_stage
    lin = jax.jit(_linear_stage_impl, static_argnames=("cfg", "cap"),
                  donate_argnums=lin_don)
    aff = jax.jit(_affine_stage_impl, static_argnames=("cfg", "cap"),
                  donate_argnums=aff_don)
    return lin, aff


def _winner_traceback(segments, reads, occ, mpos, mapped,
                      cfg: MapperConfig):
    """(6): fused affine WF + on-device banded traceback on the per-read
    winners only.  Takes the winner metadata the affine stage emits (one
    occurrence row + minimizer offset per read), so the END-aligned op
    rows and counts are the only O(max_ops) arrays that ever exist: on
    the pallas backend the (n, band) direction planes stay in VMEM
    scratch inside the kernel, on the jnp backend they fuse into one jit
    — neither ever crosses D2H."""
    wins = gather_windows(segments, occ, mpos, read_len=cfg.read_len,
                          k=cfg.k, eth=cfg.eth)                  # (R, wlen)
    max_ops = cfg.max_ops or 2 * cfg.read_len + 2
    _, _, ops, op_count = wfb.affine_traceback(
        reads, wins, eth=cfg.eth, sat=cfg.sat_affine, max_ops=max_ops,
        backend=cfg.wf_backend, block_r=cfg.aff_block_r)
    ops = jnp.where(mapped[:, None], ops, affine_wf.OP_NONE)
    op_count = jnp.where(mapped, op_count, 0)
    return ops, op_count


_traceback_stage = partial(jax.jit, static_argnames=("cfg",))(
    _winner_traceback)


def _strand_fold(distance, mapped, position, distance2, n_cand, occ_w,
                 mpos_w, reads, lin_end=None):
    """Device-side fwd-vs-rc winner fold (``mapper._reduce_strands``
    semantics, applied per chunk before anything is fetched): rows
    ``[0:n)`` are the forward encodings, ``[n:2n)`` the reverse
    complements of the same reads.  Lower affine distance wins; ties
    (including both-unmapped) keep forward, so single-strand workloads
    are bit-identical with or without ``both_strands``.  The runner-up
    becomes min(winner strand's second locus, loser strand's best) — an
    opposite-strand hit is a genuine competitor even at the same locus.
    """
    n = distance.shape[0] // 2
    rev = distance[n:] < distance[:n]

    def pick(a):
        return jnp.where(rev.reshape((-1,) + (1,) * (a.ndim - 1)),
                         a[n:], a[:n])

    lose_d1 = jnp.where(rev, distance[:n], distance[n:])
    out = dict(distance=pick(distance), mapped=pick(mapped),
               position=pick(position),
               distance2=jnp.minimum(pick(distance2),
                                     lose_d1).astype(jnp.int32),
               n_candidates=pick(n_cand), occ_w=pick(occ_w),
               mpos_w=pick(mpos_w), reads_w=pick(reads),
               strand=rev.astype(jnp.int8))
    if lin_end is not None:
        out["linear_dist"] = pick(lin_end)
    return out, rev


@partial(jax.jit, static_argnames=("cfg",))
def _strand_stage(distance, mapped, position, distance2, n_cand, occ_w,
                  mpos_w, reads, lin_end, n_real, cfg: MapperConfig):
    """Jitted strand reduce for the staged engine, plus the
    ``reverse_best`` count over the ``n_real`` non-padding reads."""
    out, rev = _strand_fold(distance, mapped, position, distance2, n_cand,
                            occ_w, mpos_w, reads, lin_end)
    n = distance.shape[0] // 2
    real = jnp.arange(n, dtype=jnp.int32) < n_real
    out["reverse_best"] = jnp.sum(rev & out["mapped"] & real)
    return out


def _fused_stage_impl(segments, positions, reads, occ_idx, occ_valid,
                      mini_pos, n_real, cfg: MapperConfig, lin_cap: int,
                      aff_cap: int):
    """The single-dispatch engine: seeding output -> compaction -> linear
    WF -> filter -> affine WF -> strand reduce -> traceback, one jit.

    The staged engine syncs the measured survivor count between the
    linear and affine stages to size the affine bucket; here the affine
    capacity is *bounded* host-side from the candidate count alone (each
    valid candidate contributes at most one surviving (read, minimizer)
    group, and a filter threshold above the linear band disables the
    filter entirely), so the whole back half of the pipeline dispatches
    without a second host sync.  The bound can only over-provision, never
    drop — results stay bit-identical to the staged engine; the trade is
    that the scattered (R, M, P) ``linear_dist`` tensor is not
    materialized for the host (``MappingResult.linear_dist`` is None).

    Per-read accounting (candidate/survivor/reverse-best counts) is
    reduced on device over the ``n_real`` non-padding rows and fetched as
    scalars with the results.
    """
    R = reads.shape[0]
    half = R // 2 if cfg.both_strands else R
    real = (jnp.arange(R, dtype=jnp.int32) % half) < n_real

    lin_end, best_pl, pass_filter, n_cand = _linear_stage_impl(
        segments, reads, occ_idx, occ_valid, mini_pos, cfg, lin_cap)
    (best_aff, mapped, position, best_m, distance2, occ_w,
     mpos_w) = _affine_stage_impl(segments, positions, reads, occ_idx,
                                  mini_pos, best_pl, pass_filter, lin_end,
                                  cfg, aff_cap)
    out = dict(survivors=jnp.sum(pass_filter & real[:, None]))
    reads_w = reads
    if cfg.both_strands:
        fold, rev = _strand_fold(best_aff, mapped, position, distance2,
                                 n_cand, occ_w, mpos_w, reads)
        best_aff, mapped, position = (fold["distance"], fold["mapped"],
                                      fold["position"])
        distance2, n_cand = fold["distance2"], fold["n_candidates"]
        occ_w, mpos_w, reads_w = fold["occ_w"], fold["mpos_w"], \
            fold["reads_w"]
        out["strand"] = fold["strand"]
        out["reverse_best"] = jnp.sum(rev & mapped & real[:half])
    out.update(position=position, distance=best_aff, distance2=distance2,
               mapped=mapped, n_candidates=n_cand)
    if cfg.cigar_mode == "eager":
        out["ops"], out["op_count"] = _winner_traceback(
            segments, reads_w, occ_w, mpos_w, mapped, cfg)
    elif cfg.cigar_mode == "lazy":
        out.update(_tb_reads=reads_w, _tb_occ=occ_w, _tb_mpos=mpos_w)
    return out


_fused_stage = partial(jax.jit,
                       static_argnames=("cfg", "lin_cap", "aff_cap"))(
    _fused_stage_impl)


def fused_affine_capacity(n_valid: int, R: int, cfg: MapperConfig) -> int:
    """Affine-survivor capacity for the fused engine, bounded without a
    post-filter sync: survivors are (read, minimizer) groups whose best
    linear distance clears the threshold, so there are at most
    ``min(n_valid, R*M)`` of them (empty groups scatter the linear sat
    value ``eth+1`` and cannot pass a threshold <= eth), and exactly
    ``R*M`` when the threshold disables the filter.  Never smaller than
    the true survivor count -> the fused engine never drops."""
    M = cfg.max_minis
    bound = R * M if cfg.filter_threshold > cfg.eth else min(n_valid, R * M)
    return bucket_capacity(bound, align=cfg.aff_block_r, cap_max=R * M)


class LazyTraceback:
    """Deferred winners-only traceback (``cigar_mode="lazy"``).

    Holds the per-read winner metadata fetched with the batch (read
    encoding, winning occurrence row, minimizer offset, mapped mask) plus
    the session's device-resident segments; ``materialize`` dispatches
    the same jitted traceback stage the eager mode runs.  Slicing and
    concatenation keep results lazy through ``mapper.split_result`` and
    the serving layer's per-request reassembly.
    """

    def __init__(self, segments, cfg: MapperConfig, reads, occ, mpos,
                 mapped):
        self.segments = segments        # device array, shared not copied
        self.cfg = cfg
        self.reads, self.occ, self.mpos = reads, occ, mpos
        self.mapped = mapped

    def __len__(self):
        return len(self.occ)

    def __getitem__(self, sl):
        return LazyTraceback(self.segments, self.cfg, self.reads[sl],
                             self.occ[sl], self.mpos[sl], self.mapped[sl])

    @classmethod
    def concat(cls, parts: list["LazyTraceback"]) -> "LazyTraceback":
        first = parts[0]
        if len(parts) == 1:
            return first
        return cls(first.segments, first.cfg,
                   np.concatenate([p.reads for p in parts]),
                   np.concatenate([p.occ for p in parts]),
                   np.concatenate([p.mpos for p in parts]),
                   np.concatenate([p.mapped for p in parts]))

    def materialize(self):
        ops, cnt = _traceback_stage(self.segments, jnp.asarray(self.reads),
                                    jnp.asarray(self.occ),
                                    jnp.asarray(self.mpos),
                                    jnp.asarray(self.mapped), self.cfg)
        # copies: np.asarray of a device buffer is a read-only view, and
        # materialized fields are caller-owned like their eager twins
        return np.array(ops), np.array(cnt)


def _host_int(x) -> int:
    """A count read back from the device: a blocking device-to-host sync."""
    with _tracing.span("repro/map/sync"):
        return int(x)


class _ChunkPipeline:
    """Phase-split per-chunk execution for the streaming engine.

    Host code between the jit stages measures candidate/survivor counts and
    picks static bucket capacities (``bucket_capacity``), so each jit sees a
    fixed shape.  The phases map onto ``streaming.stream_map``'s schedule:

      phase1: host pad -> H2D transfer -> seeding dispatch
      phase2: capacity-count syncs -> linear/affine/traceback dispatch
      fetch:  device->host copies + padding trim (fetch thread)

    When a ``times`` dict is passed (the ``stream=False`` sync path), every
    phase blocks at its stage boundaries and records per-stage wall
    seconds; without it each stage is a non-blocking async enqueue.
    Candidate/survivor accounting and the padded-equivalent baselines
    exclude the zero-padding reads of a partial last chunk, so the
    reported pruning reflects the actual workload.
    """

    def __init__(self, dev, cfg: MapperConfig):
        self.dev = dev
        self.cfg = cfg
        self.lin_jit, self.aff_jit = _stage_jits(cfg.stream)

    def begin_run(self, items) -> None:
        """Hook called once with the full chunk list before streaming
        begins.  The flat pipeline has nothing to stage; the routed
        pipeline overrides this to start arena prefetch."""

    def phase1(self, item, times=None):
        sub, chunk = item
        n_real = len(sub)
        t0 = time.perf_counter()
        with _tracing.span("repro/map/prep"):
            if n_real < chunk:  # keep the chunk shape static; trimmed in fetch
                sub = np.concatenate(
                    [sub, np.zeros((chunk - n_real, sub.shape[1]),
                                   sub.dtype)])
            if self.cfg.both_strands:
                # rows [0:chunk) forward, [chunk:2*chunk) reverse
                # complement: each chunk carries both encodings of its own
                # reads, so the strand reduce happens on device before
                # fetch (phase 2)
                sub = np.concatenate([sub, revcomp(sub)])
            t0 = streaming.timed(times, "host_prep", t0)
            reads = jnp.asarray(sub)
            if times is not None:
                reads.block_until_ready()
            t0 = streaming.timed(times, "h2d", t0)
        with _tracing.span("repro/map/dispatch", stage="seed"):
            seeds = seed_reads(self.dev[0], self.dev[1], reads,
                               self.cfg.seed_params)
        if times is not None:
            jax.block_until_ready(seeds)
        streaming.timed(times, "seed", t0)
        return reads, seeds, n_real

    def _real_count(self, arr, total: int, n_real: int, R: int):
        """Host count of True entries in ``arr``'s non-padding rows, and
        the blocking device reads it took.  ``total`` is the known full
        count; a partial chunk re-counts over the real slice of each
        strand half."""
        half = R // 2 if self.cfg.both_strands else R
        if (2 * n_real if self.cfg.both_strands else n_real) == R:
            return total, 0
        c = jnp.sum(arr[:n_real])
        if self.cfg.both_strands:
            c = c + jnp.sum(arr[half : half + n_real])
        return _host_int(c), 1

    def chunk_index(self, seeds):
        """Device ``(positions, segments)`` that this chunk's ``occ_idx``
        rows point into.  The flat pipeline has one session-lifetime
        pair; the shard-routed pipeline (``repro.index.residency``)
        overrides this to return the per-chunk arena snapshot its host
        seeding stage routed the occurrence rows against."""
        return self.dev[2], self.dev[3]

    def phase2(self, state, times=None):
        reads, seeds, n_real = state
        cfg = self.cfg
        positions, segments = self.chunk_index(seeds)
        R = reads.shape[0]          # rows: 2*chunk when both_strands
        M, P = cfg.max_minis, cfg.max_pls
        occ_idx, occ_valid = seeds["occ_idx"], seeds["occ_valid"]
        mini_pos = seeds["mini_pos"]
        rows_real = 2 * n_real if cfg.both_strands else n_real
        profile = cfg.profile and times is None  # streamed profiling

        # count syncs happen before the stage call so the donated buffers
        # (occ_valid / pass_filter) are never read after being consumed
        t0 = time.perf_counter()
        n_valid = _host_int(seeds["n_valid"])
        lin_cap = bucket_capacity(n_valid, align=cfg.lin_block_r,
                                  cap_max=R * M * P)

        if cfg.engine == "fused":
            n_valid_real, syncs = self._real_count(occ_valid, n_valid,
                                                   n_real, R)
            aff_cap = fused_affine_capacity(n_valid, R, cfg)
            with _tracing.span("repro/map/dispatch", stage="fused",
                               lin_cap=lin_cap, aff_cap=aff_cap):
                out = _fused_stage(segments, positions, reads, occ_idx,
                                   occ_valid, mini_pos, jnp.int32(n_real),
                                   cfg, lin_cap, aff_cap)
            if times is not None:
                out["position"].block_until_ready()
            streaming.timed(times, "fused", t0)
            stats = dict(candidates_valid=n_valid_real,
                         linear_instances=lin_cap,
                         padded_linear_instances=rows_real * M * P,
                         survivors=out.pop("survivors"),
                         affine_dist_instances=aff_cap,
                         padded_affine_instances=rows_real * M,
                         affine_dirs_instances=(
                             n_real if cfg.cigar_mode == "eager" else 0),
                         d2h_syncs=1 + syncs)
            if cfg.both_strands:
                stats["reverse_best"] = out.pop("reverse_best")
            if profile:
                out["_milestones"] = (("seed", mini_pos),
                                      ("fused", out["position"]))
            return out, stats, n_real

        n_valid_real, syncs = self._real_count(occ_valid, n_valid, n_real, R)
        with _tracing.span("repro/map/dispatch", stage="linear",
                           lin_cap=lin_cap):
            lin_end, best_pl, pass_filter, n_cand = self.lin_jit(
                segments, reads, occ_idx, occ_valid, mini_pos, cfg, lin_cap)
        if times is not None:
            pass_filter.block_until_ready()
        t0 = streaming.timed(times, "linear", t0)

        n_surv = _host_int(jnp.sum(pass_filter))
        n_surv_real, surv_syncs = self._real_count(pass_filter, n_surv,
                                                   n_real, R)
        syncs += 2 + surv_syncs
        aff_cap = bucket_capacity(n_surv, align=cfg.aff_block_r,
                                  cap_max=R * M)
        with _tracing.span("repro/map/dispatch", stage="affine",
                           aff_cap=aff_cap):
            (best_aff, mapped, position, best_m, distance2, occ_w,
             mpos_w) = self.aff_jit(segments, positions, reads, occ_idx,
                                    mini_pos, best_pl, pass_filter, lin_end,
                                    cfg, aff_cap)
        reads_w, strand, reverse_best = reads, None, None
        if cfg.both_strands:
            with _tracing.span("repro/map/dispatch", stage="strand"):
                fold = _strand_stage(best_aff, mapped, position, distance2,
                                     n_cand, occ_w, mpos_w, reads, lin_end,
                                     jnp.int32(n_real), cfg)
            best_aff, mapped, position = (fold["distance"], fold["mapped"],
                                          fold["position"])
            distance2, n_cand = fold["distance2"], fold["n_candidates"]
            occ_w, mpos_w, reads_w = (fold["occ_w"], fold["mpos_w"],
                                      fold["reads_w"])
            lin_end, strand = fold["linear_dist"], fold["strand"]
            reverse_best = fold["reverse_best"]
        if times is not None:
            position.block_until_ready()
        t0 = streaming.timed(times, "affine", t0)

        out = dict(position=position, distance=best_aff,
                   distance2=distance2, mapped=mapped, linear_dist=lin_end,
                   n_candidates=n_cand)
        if strand is not None:
            out["strand"] = strand
        tb_mark = position
        if cfg.cigar_mode == "eager":
            with _tracing.span("repro/map/dispatch", stage="traceback"):
                out["ops"], out["op_count"] = _traceback_stage(
                    segments, reads_w, occ_w, mpos_w, mapped, cfg)
            tb_mark = out["ops"]
            if times is not None:
                tb_mark.block_until_ready()
        elif cfg.cigar_mode == "lazy":
            out.update(_tb_reads=reads_w, _tb_occ=occ_w, _tb_mpos=mpos_w)
        streaming.timed(times, "traceback", t0)

        stats = dict(candidates_valid=n_valid_real,
                     linear_instances=lin_cap,
                     padded_linear_instances=rows_real * M * P,
                     survivors=n_surv_real,
                     affine_dist_instances=aff_cap,
                     padded_affine_instances=rows_real * M,
                     affine_dirs_instances=(
                         n_real if cfg.cigar_mode == "eager" else 0),
                     d2h_syncs=syncs)
        if reverse_best is not None:
            stats["reverse_best"] = reverse_best
        if profile:
            out["_milestones"] = (("seed", mini_pos), ("linear", best_pl),
                                  ("affine", position),
                                  ("traceback", tb_mark))
        return out, stats, n_real

    def fetch(self, state, times=None):
        out, stats, n_real = state
        mil = out.pop("_milestones", None)
        t0 = time.perf_counter()
        with _tracing.span("repro/map/fetch") as sp:
            if mil is not None:  # streamed profiling: completion offsets
                for name, arr in mil:
                    arr.block_until_ready()
                    t0 = streaming.timed(times, name, t0)
            host = {k: np.asarray(v)[:n_real] for k, v in out.items()}
            streaming.timed(times, "d2h", t0)
            arrays = len(out) + sum(isinstance(v, jax.Array)
                                    for v in stats.values())
            stats = {k: (int(v) if isinstance(v, jax.Array) else v)
                     for k, v in stats.items()}
            sp.set_metadata(arrays=arrays)
        stats["d2h_syncs"] += arrays
        return host, stats


def _merge_stats(parts: list[dict]) -> dict:
    out = {k: sum(p[k] for p in parts) for k in parts[0]}
    out["pruning_ratio"] = (
        1.0 - out["survivors"] / max(out["candidates_valid"], 1))
    out["n_chunks"] = len(parts)
    return out


def map_reads(index: GenomeIndex, reads: np.ndarray,
              cfg: MapperConfig | None = None) -> MappingResult:
    """Host-friendly wrapper: numpy index + reads -> MappingResult.

    .. deprecated::
        Use :class:`repro.core.mapper.Mapper` —
        ``Mapper(index, cfg).map(reads)`` is the bit-identical replacement
        and keeps the index placed on device across calls (this shim
        builds a fresh one-shot session each time).  See the README's
        migration table.
    """
    warnings.warn(
        "map_reads is deprecated; use repro.core.mapper.Mapper — "
        "Mapper(index, cfg).map(reads) is the bit-identical replacement "
        "(and reuses device placement across calls)",
        DeprecationWarning, stacklevel=2)
    from .mapper import Mapper
    return Mapper(index, cfg).map(reads)


def oracle_map(ref: np.ndarray, reads: np.ndarray, eth: int = 6,
               chunk: int = 4096):
    """Exhaustive banded-WF scan over every reference position (BWA-MEM
    stand-in ground truth for accuracy tests).  O(G * R) — small inputs only.

    Returns ``(best_p, best_d)``: per-read best position (ties -> leftmost)
    and its banded-WF distance, each of shape (R,).
    """
    rl = reads.shape[1]
    G = len(ref)
    pad = np.full(G + 2 * eth + rl, 4, dtype=np.uint8)
    pad[eth : eth + G] = ref
    n_pos = G - rl + 1
    starts = np.arange(n_pos)
    best_d = np.full(len(reads), 10 ** 9, dtype=np.int64)
    best_p = np.full(len(reads), -1, dtype=np.int64)
    win = rl + 2 * eth
    for c0 in range(0, n_pos, chunk):
        c1 = min(c0 + chunk, n_pos)
        idx = starts[c0:c1, None] + np.arange(win)[None, :]
        wins = jnp.asarray(pad[idx])  # (C, win)
        d_end, _ = banded_wf(jnp.asarray(reads)[:, None, :].repeat(c1 - c0, 1),
                             jnp.broadcast_to(wins[None], (len(reads), c1 - c0,
                                                           win)), eth=eth)
        d = np.asarray(d_end)
        for r in range(len(reads)):
            m = int(d[r].argmin())
            if d[r][m] < best_d[r]:
                best_d[r] = d[r][m]
                best_p[r] = c0 + m
    return best_p, best_d
