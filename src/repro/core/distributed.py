"""Distributed read mapping (the paper's Sec. V architecture on a TPU mesh).

DART-PIM's controller hierarchy routes each read to the crossbars owning its
minimizers; results flow back to the main RISC-V for the final min-reduce.
On a TPU mesh this is:

  stage A (read owner) : minimizer extraction, destination = hash % n_shards,
                         bucket into fixed-capacity send buffers
  all_to_all           : one collective replaces the paper's 1556 GB of
                         CPU<->memory PL traffic
  stage B (index owner): local lookup -> banded linear WF over <=max_pls PLs
                         -> min-extract -> filter -> banded affine WF on the
                         compacted survivors only (static capacity from
                         ``stage_b_affine_capacity``, overflow dropped)
  all_to_all (return)  : (read_id, distance, position) echoes to the owner
  stage C (read owner) : scatter-min per read  (main-RISC-V reduce)

Fixed buffer capacities are the Reads-FIFO/maxReads mechanism: overflow
entries are *dropped*, trading accuracy for bounded latency exactly as the
paper does (measured in benchmarks/accuracy.py).

The index is sharded by minimizer hash (``shard_index``) — DART-PIM's
"crossbar per minimizer" data organization, with the same deliberate
segment duplication.

The public front-end for this path is ``repro.core.mapper.Mapper`` with
``topology="mesh"`` (``distributed_map_reads`` below is its deprecation
shim); ``make_distributed_mapper`` stays the compiled-program builder the
session's plan cache draws from.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import wf_backend as wfb
from .compaction import bucket_capacity, compact_indices, scatter_to
from .filtering import collapse_candidates, gather_windows
from .index import GenomeIndex
from .minimizers import hash32, unique_read_minimizers
from .pipeline import MapperConfig

AXIS = "shards"


def stage_b_affine_capacity(n_entries: int, cfg: MapperConfig,
                            frac: float | None = None) -> int:
    """Static survivor capacity for stage B's affine pass.

    Stage B is inside one jit (no host sync between the filter and the
    affine stage), so the survivor-bucket capacity must be *negotiated*
    up front rather than measured per batch: each of the ``n_entries``
    bucket slots contributes at most one affine candidate (its best of
    ``max_pls`` PLs), and a slot only survives when it is occupied, its
    minimizer is found, and its best linear distance clears the filter
    threshold.  ``frac`` is the provisioned fraction of that bound
    (default ``cfg.stage_b_survivor_frac``; ``stage_b_adaptive`` sessions
    pass the quantile of their observed survivor history instead — see
    ``Mapper._stage_b_frac``).  Drop-on-overflow beyond the capacity is
    the Reads-FIFO semantics; a threshold that cannot reject anything
    (``> eth``) disables the filter, so provisioning falls back to full
    capacity.
    """
    if frac is None:
        frac = cfg.stage_b_survivor_frac
    frac = 1.0 if cfg.filter_threshold > cfg.eth else \
        max(min(frac, 1.0), 0.0)
    want = int(np.ceil(n_entries * frac))
    cap = bucket_capacity(want, align=cfg.aff_block_r, cap_max=n_entries)
    # neither the lane-align floor nor the pow-2 rounding may outgrow the
    # entry count: a "compacted" pass larger than its input would be a
    # pessimization.  A non-pow2/non-aligned cap is safe here — stage B
    # compiles once per program and the kernel ops pad to the lane block
    # internally.
    return min(cap, n_entries)


@dataclasses.dataclass(frozen=True)
class ShardedIndex:
    """Per-shard padded CSR index arrays (leading axis = shard)."""
    uniq_kmers: np.ndarray   # (S, U) uint32, padded with 0xFFFFFFFF
    offsets: np.ndarray      # (S, U+1) int32
    positions: np.ndarray    # (S, O) int32
    segments: np.ndarray     # (S, O, seg_len) uint8
    n_shards: int
    read_len: int
    k: int
    w: int
    eth: int

    def device_arrays(self, mesh):
        """Place the index on ``mesh`` once, shard ``s`` on device ``s``
        (leading axis split over ``AXIS``), so the shard_map program
        reads it where it lies instead of resharding it every call."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        shard = NamedSharding(mesh, P(AXIS))
        return tuple(jax.device_put(x, shard) for x in (
            self.uniq_kmers, self.offsets, self.positions, self.segments))

    @classmethod
    def from_partitions(cls, parts, *, read_len: int, k: int, w: int,
                        eth: int, seg_len: int) -> "ShardedIndex":
        """Stack pre-partitioned per-shard CSRs into the padded layout.

        ``parts`` is a sequence of ``(kmers, offsets, positions,
        segments)`` tuples, one per shard, already assigned by the
        ``hash32(kmer) % n_shards`` crossbar rule (e.g. the partitions of
        a ``repro.index.ShardedGenomeIndex`` built offline).  This is the
        zero-re-hash path onto the mesh: no flat index is rebuilt and no
        runtime ``shard_index`` scan runs — the padding conventions here
        (uniq padded with 0xFFFFFFFF, offsets padded with the last
        offset) are exactly ``shard_index``'s, so the stacked arrays are
        bit-identical to sharding the equivalent flat index.
        """
        n_shards = len(parts)
        u_cap = max(max((len(p[0]) for p in parts), default=0), 1)
        o_cap = max(max((len(p[2]) for p in parts), default=0), 1)
        uq = np.full((n_shards, u_cap), 0xFFFFFFFF, dtype=np.uint32)
        of = np.zeros((n_shards, u_cap + 1), dtype=np.int32)
        po = np.zeros((n_shards, o_cap), dtype=np.int32)
        sg = np.zeros((n_shards, o_cap, seg_len), dtype=np.uint8)
        for s, (kmers, offsets, positions, segments) in enumerate(parts):
            nu, no = len(kmers), len(positions)
            uq[s, :nu] = kmers
            of[s, : nu + 1] = offsets
            of[s, nu + 1:] = offsets[-1] if nu else 0
            po[s, :no] = positions
            sg[s, :no] = segments
        return cls(uniq_kmers=uq, offsets=of, positions=po, segments=sg,
                   n_shards=n_shards, read_len=read_len, k=k, w=w, eth=eth)


def shard_index(index: GenomeIndex, n_shards: int) -> ShardedIndex:
    """Assign each unique minimizer to shard hash32(kmer) % n_shards."""
    kmers = index.uniq_kmers
    h = np.asarray(hash32(jnp.asarray(kmers))) % n_shards
    counts = np.diff(index.offsets)
    u_cap = max(int(np.bincount(h, minlength=n_shards).max()), 1)
    o_cap = max(int(np.bincount(h, weights=counts,
                                minlength=n_shards).max()), 1) if len(h) else 1
    U = len(kmers)
    uq = np.full((n_shards, u_cap), 0xFFFFFFFF, dtype=np.uint32)
    of = np.zeros((n_shards, u_cap + 1), dtype=np.int32)
    po = np.zeros((n_shards, o_cap), dtype=np.int32)
    sg = np.zeros((n_shards, o_cap, index.seg_len), dtype=np.uint8)
    for s in range(n_shards):
        sel = np.where(h == s)[0]
        nu, off = len(sel), 0
        uq[s, :nu] = kmers[sel]
        for i, ui in enumerate(sel):
            c = int(counts[ui])
            lo = index.offsets[ui]
            po[s, off : off + c] = index.positions[lo : lo + c]
            sg[s, off : off + c] = index.segments[lo : lo + c]
            off += c
            of[s, i + 1] = off
        of[s, nu + 1 :] = off
    return ShardedIndex(uniq_kmers=uq, offsets=of, positions=po, segments=sg,
                        n_shards=n_shards, read_len=index.read_len,
                        k=index.k, w=index.w, eth=index.eth)


def _bucket_by_dst(dst, payload, n_shards: int, cap: int):
    """Scatter entries into (n_shards, cap) buckets; overflow dropped.

    dst: (E,) int32 target shard per entry (n_shards = drop).
    payload: dict of (E, ...) arrays.  Returns dict of (n_shards, cap, ...)
    plus a valid mask and the number of dropped entries.
    """
    E = dst.shape[0]
    order = jnp.argsort(dst, stable=True)
    dsorted = dst[order]
    # rank within group: arange - index of first element of the group
    first = jnp.searchsorted(dsorted, dsorted)  # leftmost equal
    rank = jnp.arange(E, dtype=jnp.int32) - first
    ok = (dsorted < n_shards) & (rank < cap)
    slot = jnp.where(ok, dsorted * cap + rank, n_shards * cap)
    out = {}
    for name, arr in payload.items():
        a = arr[order]
        buf = jnp.zeros((n_shards * cap + 1,) + a.shape[1:], dtype=a.dtype)
        buf = buf.at[slot].set(a)
        out[name] = buf[:-1].reshape((n_shards, cap) + a.shape[1:])
    vmask = jnp.zeros((n_shards * cap + 1,), dtype=bool).at[slot].set(ok)
    out["valid"] = vmask[:-1].reshape(n_shards, cap)
    dropped = jnp.sum(dsorted < n_shards) - jnp.sum(ok)
    return out, dropped


def _stage_b(local, uniq, offsets, positions, segments, cfg: MapperConfig,
             aff_cap: int):
    """Index-owner compute: lookup -> linear WF -> min -> filter ->
    compacted affine WF.

    The affine stage runs only on the filter survivors: the ``passed``
    mask is compacted into a static ``aff_cap``-slot bucket
    (``stage_b_affine_capacity``) and the distance-only affine WF executes
    on those ``aff_cap`` instances instead of every bucket entry.
    Survivors beyond ``aff_cap`` are *dropped* (reported unmapped), the
    same bounded-latency/accuracy trade as the Reads-FIFO overflow.
    Returns per-shard (aff (S, cap), pos (S, cap), co_est (S, cap) —
    the placement-level co-optimal runner-up estimate for the distance2
    reduce, n_survivors, n_affine_dropped).
    """
    S, cap = local["kmer"].shape
    kmers = local["kmer"].reshape(-1)
    minipos = local["minipos"].reshape(-1)
    reads = local["read"].reshape(-1, cfg.read_len)
    valid = local["valid"].reshape(-1)

    idx = jnp.searchsorted(uniq, kmers)
    idx = jnp.minimum(idx, uniq.shape[0] - 1)
    found = (uniq[idx] == kmers) & valid
    start, count = offsets[idx], offsets[idx + 1] - offsets[idx]
    P = cfg.max_pls
    occ = start[:, None] + jnp.arange(P, dtype=jnp.int32)[None, :]
    occ_valid = (jnp.arange(P)[None, :] < count[:, None]) & found[:, None]
    occ = jnp.where(occ_valid, occ, 0)

    windows = gather_windows(segments, occ, minipos[:, None],
                             read_len=cfg.read_len, k=cfg.k, eth=cfg.eth)
    E = kmers.shape[0]
    s1 = jnp.broadcast_to(reads[:, None, :], (E, P, cfg.read_len))
    lin_end, _ = wfb.linear_wf_dist(s1, windows, eth=cfg.eth,
                                    backend=cfg.wf_backend,
                                    block_r=cfg.lin_block_r)
    lin_end = jnp.where(occ_valid, lin_end, cfg.eth + 1)
    best_pl, best_lin, passed = collapse_candidates(lin_end,
                                                    cfg.filter_threshold)
    n_surv = jnp.sum(passed)

    # distance-only affine on the compacted survivors: stage B never
    # tracebacks, so no (E, n, band) direction planes are materialized and
    # only aff_cap of the E bucket entries execute
    slots, slot_ok = compact_indices(passed, aff_cap)
    sel_win = jnp.take_along_axis(windows, best_pl[:, None, None], 1)[:, 0]
    aff_c, _ = wfb.affine_wf_dist(reads[slots], sel_win[slots], eth=cfg.eth,
                                  sat=cfg.sat_affine,
                                  backend=cfg.wf_backend,
                                  block_r=cfg.aff_block_r)
    sat = jnp.int32(cfg.sat_affine)
    aff_c = jnp.where(slot_ok, aff_c, sat).astype(jnp.int32)
    aff_end = scatter_to(E, slots, slot_ok, aff_c, sat)
    kept = scatter_to(E, slots, slot_ok, slot_ok, False)
    sel_occ = jnp.take_along_axis(occ, best_pl[:, None], 1)[:, 0]
    pos = positions[sel_occ] - minipos
    # placement-level co-optimal survey (pipeline._co_optimal_runner_up's
    # mesh analog): a repeat copy whose placements share this entry's
    # minimizer never leaves the per-entry argmin, so survey the full
    # (E, P) linear distances for far-locus placements at least as good
    # as the chosen one; estimate their affine distance as this entry's
    # plus the linear excess.  The estimate rides the return exchange and
    # feeds stage C's runner-up reduce.
    sat_lin = jnp.int32(cfg.eth + 1)
    pos_pl = positions[occ] - minipos[:, None]                 # (E, P)
    far_pl = jnp.abs(pos_pl - pos[:, None]) > cfg.eth
    co = far_pl & occ_valid & (lin_end
                               <= min(cfg.filter_threshold, cfg.eth))
    min_far = jnp.min(jnp.where(co, lin_end, sat_lin), axis=-1)
    co_est = jnp.minimum(aff_end + jnp.maximum(min_far - best_lin, 0), sat)
    co_est = jnp.where((min_far < sat_lin) & kept, co_est, sat)
    pos = jnp.where(kept, pos, -1)
    return (aff_end.reshape(S, cap), pos.reshape(S, cap),
            co_est.reshape(S, cap).astype(jnp.int32), n_surv,
            n_surv - jnp.sum(slot_ok))


def make_distributed_mapper(mesh, cfg: MapperConfig, n_shards: int,
                            send_cap: int, aff_cap: int | None = None):
    """Build the jitted shard_map mapping step.

    Returns ``(fn, stage_b_affine_cap)`` — the negotiated per-shard
    survivor capacity is surfaced so callers report exactly what the
    compiled program executes.  ``aff_cap`` overrides the negotiation
    (the ``Mapper`` session passes its plan's — possibly adaptively
    derived — capacity so the compiled program matches the plan).
    Call signature of ``fn``:
      fn(uniq (S,U), offsets (S,U+1), positions (S,O), segments (S,O,L),
         reads (R_global, rl), read_dst_meta...) ->
         (position (R_global,), distance (R_global,),
          distance2 (R_global,), dropped (S,),
          stage_b_survivors (S,), stage_b_affine_dropped (S,))
    """
    from jax.sharding import PartitionSpec as P

    M = cfg.max_minis
    # survivor capacity is negotiated once per program: every shard's
    # stage B sees n_shards*send_cap bucket entries after the exchange
    if aff_cap is None:
        aff_cap = stage_b_affine_capacity(n_shards * send_cap, cfg)

    def step(uniq, offsets, positions, segments, reads):
        # local shapes: uniq (1, U) ... reads (R_local, rl)
        uniq, offsets = uniq[0], offsets[0]
        positions, segments = positions[0], segments[0]
        R = reads.shape[0]

        # ---- stage A: seeding + bucketing
        kmers, minipos, valid = jax.vmap(
            lambda r: unique_read_minimizers(r, k=cfg.k, w=cfg.w, max_uniq=M)
        )(reads)
        dst = (hash32(kmers) % n_shards).astype(jnp.int32)
        dst = jnp.where(valid, dst, n_shards)  # invalid -> drop bucket
        rid = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None],
                               (R, M))
        payload = {
            "kmer": kmers.reshape(-1),
            "minipos": minipos.reshape(-1).astype(jnp.int32),
            "read": jnp.broadcast_to(reads[:, None, :],
                                     (R, M, cfg.read_len)).reshape(
                                         -1, cfg.read_len),
            "rid": rid.reshape(-1),
        }
        buckets, dropped = _bucket_by_dst(dst.reshape(-1), payload,
                                          n_shards, send_cap)

        # ---- exchange: entries travel to their minimizer's home shard
        recv = {k: jax.lax.all_to_all(v, AXIS, 0, 0, tiled=False)
                for k, v in buckets.items()}

        # ---- stage B on the index owner
        aff, pos, co_est, n_surv, aff_drop = _stage_b(
            recv, uniq, offsets, positions, segments, cfg, aff_cap)
        aff = jnp.where(recv["valid"], aff, cfg.sat_affine)
        co_est = jnp.where(recv["valid"], co_est, cfg.sat_affine)

        # ---- return trip
        back_aff = jax.lax.all_to_all(aff, AXIS, 0, 0)
        back_pos = jax.lax.all_to_all(pos, AXIS, 0, 0)
        back_co = jax.lax.all_to_all(co_est, AXIS, 0, 0)
        back_rid = buckets["rid"]  # origin kept its own copy (same order)
        back_val = buckets["valid"]

        # ---- stage C: min-reduce per read (position of the min distance)
        flat_aff = jnp.where(back_val, back_aff, cfg.sat_affine).reshape(-1)
        flat_pos = back_pos.reshape(-1)
        flat_rid = jnp.where(back_val, back_rid, R).reshape(-1)
        best = jnp.full((R + 1,), cfg.sat_affine, dtype=jnp.int32)
        best = best.at[flat_rid].min(flat_aff)
        is_best = (flat_aff == best[flat_rid]) & (flat_rid < R)
        # leftmost position among ties
        bigpos = jnp.where(is_best & (flat_pos >= 0), flat_pos, 2 ** 30)
        posr = jnp.full((R + 1,), 2 ** 30, dtype=jnp.int32)
        posr = posr.at[flat_rid].min(bigpos)
        position = jnp.where((best[:R] < cfg.sat_affine) & (posr[:R] < 2 ** 30),
                             posr[:R], -1)
        # runner-up distance at a different locus (beyond the band from
        # the winner) — same semantics as pipeline._runner_up_distance,
        # expressed as a second scatter-min over the returned entries,
        # plus the per-entry placement-level co-optimal estimates from
        # stage B (the _co_optimal_runner_up analog)
        pos_ext = jnp.concatenate([position, jnp.full((1,), -1, jnp.int32)])
        far = jnp.abs(flat_pos - pos_ext[flat_rid]) > cfg.eth
        d2_key = jnp.where(far & (flat_aff < cfg.sat_affine)
                           & (flat_pos >= 0), flat_aff, cfg.sat_affine)
        best2 = jnp.full((R + 1,), cfg.sat_affine, dtype=jnp.int32)
        best2 = best2.at[flat_rid].min(d2_key)
        flat_co = jnp.where(back_val, back_co, cfg.sat_affine).reshape(-1)
        best2 = best2.at[flat_rid].min(flat_co)
        return (position, best[:R], best2[:R], dropped[None], n_surv[None],
                aff_drop[None])

    pspec = P(AXIS)
    # check_vma off: the scan carries are created fresh inside the body
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(pspec, pspec, pspec, pspec, pspec),
                       out_specs=(pspec,) * 6, check_vma=False)
    return jax.jit(fn), aff_cap


# one compiled program per (mesh, cfg, shards, send_cap): repeated serving
# batches hit the jit cache instead of re-tracing the shard_map step
_cached_mapper = functools.lru_cache(maxsize=8)(make_distributed_mapper)


_LEGACY_STATS_KEYS = (
    "stage_b_entries", "stage_b_survivors", "stage_b_affine_capacity",
    "stage_b_affine_instances", "stage_b_padded_affine_instances",
    "stage_b_affine_dropped", "send_dropped")


def distributed_map_reads(mesh, sidx: ShardedIndex, reads: np.ndarray,
                          cfg: MapperConfig | None = None,
                          send_cap: int | None = None,
                          with_stats: bool = False):
    """Host wrapper: returns (positions, distances, dropped_per_shard),
    plus a stage-B stats dict when ``with_stats=True``.

    .. deprecated::
        Use :class:`repro.core.mapper.Mapper` with ``topology="mesh"`` —
        ``Mapper(sidx, cfg, topology="mesh", mesh=mesh).map(reads)``
        returns the same positions/distances bit-identically, as a
        ``MappingResult`` whose ``stats`` (a unified ``MapperStats``)
        always carries the stage-B accounting.  See the README's
        migration table.
    """
    import warnings

    warnings.warn(
        "distributed_map_reads is deprecated; use repro.core.mapper.Mapper "
        'with topology="mesh" — Mapper(sidx, cfg, topology="mesh", '
        "mesh=mesh).map(reads) is the bit-identical replacement",
        DeprecationWarning, stacklevel=2)
    from .mapper import Mapper

    R, S = len(reads), sidx.n_shards
    assert R % S == 0, "pad reads to a multiple of the shard count"
    mapper = Mapper(sidx, cfg, topology="mesh", mesh=mesh,
                    send_cap=send_cap)
    res = mapper.map(reads)
    st = res.stats
    dropped = st["send_dropped_per_shard"]
    if not with_stats:
        if st.dropped_affine:  # bounded-latency drop, never a *silent* one
            warnings.warn(
                f"stage B dropped {st.dropped_affine} filter survivors on "
                f"affine-capacity overflow (capacity "
                f"{st['stage_b_affine_capacity']}/shard); raise "
                f"stage_b_survivor_frac or send_cap, or pass "
                f"with_stats=True to track this", stacklevel=2)
        return res.position, res.distance, dropped
    return (res.position, res.distance, dropped,
            {k: st[k] for k in _LEGACY_STATS_KEYS})
