"""Offline indexing (paper Sec. V-B) — DART-PIM's data organization.

The reference genome is scanned for minimizer occurrences; for every
occurrence we **pre-materialize the reference segment** of length
``2*(rl + eth) - k`` centered on the occurrence, exactly as DART-PIM writes
segments into crossbar linear-WF buffers.  The ~17x storage blow-up is the
paper's deliberate trade: all later stages touch only local data.

Layout (CSR over unique minimizer k-mers, sorted for O(log U) lookup):
  uniq_kmers : (U,)   uint32  sorted unique minimizer k-mer codes
  offsets    : (U+1,) int32/int64  CSR offsets into positions/segments
  positions  : (P,)   int32/int64  k-mer start position of each occurrence
  segments   : (P, seg_len) uint8  pre-extracted reference windows
               (sentinel base 4 beyond the reference ends — never matches)

Positions past 2^31-1 (index format v2, GRCh38-scale) are int64 on the
host; :func:`device_position_dtype` picks what the device arena can
actually hold under jax's 32-bit default.

A "crossbar" in the TPU mapping is an index shard: minimizers are assigned
to shards by ``hash(kmer) % num_shards`` (see ``repro.core.distributed``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .minimizers import minimizers
import jax.numpy as jnp

SENTINEL = 4  # "N"-like base, never equal to a read base


def device_position_dtype(ref_len: int) -> np.dtype:
    """Device-side dtype for positions of a reference ending at global
    position ``ref_len - 1``.

    jax defaults to 32-bit (``jnp.asarray`` silently narrows int64 when
    x64 is off), so the choice is explicit: int32 while every position
    fits; int64 when the runtime honors it (``JAX_ENABLE_X64``);
    otherwise uint32 up to 2^32-1 — which covers GRCh38's 3.1 Gb
    spacer-concatenated reference.  Past 2^32-1 without x64 is an
    error, never a silent wrap.
    """
    import jax
    max_pos = int(ref_len) - 1
    # strict <: the dtype max itself is the device winner-reduce
    # sentinel, so the largest representable value must stay unused
    if max_pos < np.iinfo(np.int32).max:
        return np.dtype(np.int32)
    if jax.config.read("jax_enable_x64"):
        return np.dtype(np.int64)
    if max_pos < np.iinfo(np.uint32).max:
        return np.dtype(np.uint32)
    raise ValueError(
        f"reference ends at position {max_pos}, past uint32; device "
        f"arithmetic needs 64-bit ints — set JAX_ENABLE_X64=1 (or "
        f"jax.config.update('jax_enable_x64', True)) before mapping")


def validate_geometry(*, read_len: int, k: int, w: int, eth: int) -> None:
    """Reject impossible index/mapper geometry at construction time.

    The one home of the (read_len, k, w, eth) sanity rules, shared by
    ``MapperConfig``, ``build_index``, ``repro.index.build_sharded_index``
    and ``repro.index.ShardedGenomeIndex`` — so a bad geometry fails here
    with the field named, not deep inside jit tracing with a shape error.
    """
    if read_len < 1:
        raise ValueError(f"read_len={read_len!r}: read length must be >= 1")
    if not 1 <= k <= 16:
        raise ValueError(f"k={k!r}: k-mer length must be within [1, 16] — "
                         f"k-mer codes are 2-bit packed into uint32")
    if k > read_len:
        raise ValueError(f"k={k} exceeds read_len={read_len}: reads "
                         f"shorter than k produce no k-mers to seed")
    if w < 1:
        raise ValueError(f"w={w!r}: minimizer window length must be >= 1")
    if eth < 0:
        raise ValueError(f"eth={eth!r}: band half-width must be >= 0")


@dataclasses.dataclass(frozen=True)
class GenomeIndex:
    uniq_kmers: np.ndarray
    offsets: np.ndarray
    positions: np.ndarray
    segments: np.ndarray
    read_len: int
    k: int
    w: int
    eth: int

    @property
    def seg_len(self) -> int:
        return 2 * (self.read_len + self.eth) - self.k

    @property
    def pad(self) -> int:
        """Segment extent on each side of the minimizer start."""
        return self.read_len + self.eth - self.k

    def storage_bytes(self) -> dict:
        """Footprint accounting, mirroring the paper's 800MB -> 13.3GB note.

        Reports the *true on-disk* bytes of the persistent format
        (``repro.index.format``): segments are 2-bit packed per base —
        ``ceil(seg_len/4)`` bytes per occurrence row, not
        ``nbytes // 4`` (which undercounted rows whose length is not a
        multiple of 4) — plus a 1-bit-per-base sentinel mask, and the
        hash table includes the CSR offsets it is stored with.
        """
        n_occ = len(self.positions)
        seg_bytes = n_occ * ((self.seg_len + 3) // 4
                             + (self.seg_len + 7) // 8)
        hash_table = (self.uniq_kmers.nbytes + self.offsets.nbytes
                      + self.positions.nbytes)
        return {
            "hash_table_bytes": hash_table,
            "materialized_segments_bytes": seg_bytes,
            "total_bytes": hash_table + seg_bytes,
            "blowup": seg_bytes / max(hash_table, 1),
        }


def build_index(ref: np.ndarray, read_len: int = 150, k: int = 12,
                w: int = 30, eth: int = 6, max_pls_per_minimizer: int = 256,
                ) -> GenomeIndex:
    """Scan the reference, collect minimizer occurrences, materialize segments.

    ``max_pls_per_minimizer`` caps hyper-repetitive minimizers (the paper
    bounds these via the Reads-FIFO / lowTh mechanisms; capping PLs is the
    standard minimap2-style guard and keeps shapes static downstream).
    """
    validate_geometry(read_len=read_len, k=k, w=w, eth=eth)
    _, kmers, pos = minimizers(jnp.asarray(ref), k=k, w=w)
    kmers = np.asarray(kmers)
    pos = np.asarray(pos)
    # Dedup (kmer, pos) occurrence pairs (adjacent windows share minimizers)
    # as one uint64 key each — sorted like the (kmer, pos) rows, but a flat
    # sort instead of numpy's much slower row-wise unique
    occ = np.unique((kmers.astype(np.uint64) << np.uint64(32))
                    | pos.astype(np.uint64))
    kmers_u = (occ >> np.uint64(32)).astype(np.uint32)
    pos_u = (occ & np.uint64(0xFFFFFFFF)).astype(np.int32)
    # CSR by kmer (occ already sorted by kmer then pos).
    uniq, starts, counts = np.unique(kmers_u, return_index=True,
                                     return_counts=True)
    # Cap PL lists.
    keep = np.ones(len(kmers_u), dtype=bool)
    for s, c in zip(starts[counts > max_pls_per_minimizer],
                    counts[counts > max_pls_per_minimizer]):
        keep[s + max_pls_per_minimizer : s + c] = False
    kmers_u, pos_u = kmers_u[keep], pos_u[keep]
    uniq, counts = np.unique(kmers_u, return_counts=True)
    offsets = np.zeros(len(uniq) + 1, dtype=np.int32)
    offsets[1:] = np.cumsum(counts)

    pad = read_len + eth - k
    seg_len = 2 * (read_len + eth) - k
    padded = np.full(len(ref) + 2 * pad, SENTINEL, dtype=np.uint8)
    padded[pad : pad + len(ref)] = ref
    # segment for occurrence at p spans ref[p - pad : p - pad + seg_len]
    segs = np.stack([padded[p : p + seg_len] for p in pos_u]) if len(pos_u) \
        else np.zeros((0, seg_len), dtype=np.uint8)
    return GenomeIndex(uniq_kmers=uniq.astype(np.uint32), offsets=offsets,
                       positions=pos_u, segments=segs.astype(np.uint8),
                       read_len=read_len, k=k, w=w, eth=eth)


def minimizer_frequencies(index: GenomeIndex) -> np.ndarray:
    """PLs per unique minimizer — drives the lowTh RISC-V/crossbar split."""
    return np.diff(index.offsets)


def low_th_split(index: GenomeIndex, low_th: int = 3) -> dict:
    """Paper Sec. V-A: minimizers with frequency <= lowTh are offloaded
    (RISC-V in DART-PIM; the padded residual batch on TPU).

    Returns masks + the workload split statistics that drive Eq. 6/7.
    """
    freqs = minimizer_frequencies(index)
    rare = freqs <= low_th
    return {
        "rare_mask": rare,
        "n_rare_minimizers": int(rare.sum()),
        "n_minimizers": len(freqs),
        "rare_pl_fraction": float(freqs[rare].sum() / max(freqs.sum(), 1)),
        "rare_minimizer_fraction": float(rare.mean()),
    }
