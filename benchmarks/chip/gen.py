"""Reference, reads and FASTQ.gz files made from a seed, in bulk.

The reference follows its configuration file: background bases drawn at the
assembly's base composition, interspersed repeat classes laid over it (each
class a few family consensus sequences; every copy a fragment of its
family's consensus with its own substitution divergence, on a random strand,
at the copy number and length the configuration's source tables give),
then simple tandem repeats and segmental duplications copied over that.
Reads are sampled from it with per-base substitution, insertion and deletion
events as a public read simulator's Illumina model draws them, about half
of them reverse-complemented; pairs are FR with a normal fragment length.
Every event of a read set is drawn in a few array operations, so set-up
stays short at chromosome and genome scale.

Everything here is numpy on the host; nothing imports the program.
"""
from __future__ import annotations

import dataclasses
import gzip

import numpy as np

_ASCII = np.frombuffer(b"ACGT", dtype=np.uint8)
# spare steps per read beyond read_len: deletions are rare (0.005% a base),
# so a read needs more than 16 of them about never; sample() checks it
_SPARE_STEPS = 16
_LUT_BITS = 16


def revcomp(codes: np.ndarray) -> np.ndarray:
    """Reverse complement of base codes 0..3 along the last axis."""
    return np.ascontiguousarray((3 - codes)[..., ::-1])


def _base_lut(freq: dict) -> np.ndarray:
    """A table of 2**16 base codes in the proportions ``freq`` (keys A, C,
    G, T): indexing it with uniform 16-bit draws gives that composition."""
    size = 1 << _LUT_BITS
    counts = np.round(np.array([freq[b] for b in "ACGT"]) * size)
    counts = counts.astype(np.int64)
    counts[int(np.argmax(counts))] += size - int(counts.sum())
    return np.repeat(np.arange(4, dtype=np.uint8), counts)


def _bases(rng, lut: np.ndarray, shape) -> np.ndarray:
    return lut[rng.integers(0, 1 << _LUT_BITS, shape, dtype=np.uint16)]


def _mutate(rng, seqs: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Substitute each base of row i with probability d_i ~ U(lo, hi):
    a copy's divergence from the sequence it was copied from."""
    d = rng.uniform(lo, hi, (len(seqs), 1)).astype(np.float32)
    hit = rng.random(seqs.shape, dtype=np.float32) < d
    shift = rng.integers(1, 4, int(hit.sum()), dtype=np.uint8)
    seqs[hit] = (seqs[hit] + shift) % 4
    return seqs


def _orient(rng, seqs: np.ndarray) -> np.ndarray:
    """About half the rows reverse-complemented: a copy lies on either
    strand."""
    flip = rng.random(len(seqs)) < 0.5
    seqs[flip] = revcomp(seqs[flip])
    return seqs


def _class_copies(rng, cls: dict, scale: float, lut: np.ndarray):
    """The copies of one repeat class, (n, copy length) uint8: ``copies``
    and ``mb`` of its source table scaled by ``scale``; copy numbers fall
    off as 1/rank over the class's ``families``; each copy is a fragment of
    its family's consensus, at its 3' end (``anchor`` "3prime", as
    5'-truncated retrotransposons lie) or anywhere in it."""
    n = int(round(cls["copies"] * scale))
    length = int(round(cls["mb"] * 1e6 / cls["copies"]))
    cons_len = max(int(cls["consensus"]), length)
    if n == 0 or length == 0:
        return np.zeros((0, 0), np.uint8)
    fams = int(cls.get("families", 1))
    cons = _bases(rng, lut, (fams, cons_len))
    weight = 1.0 / np.arange(1, fams + 1)
    fam = rng.choice(fams, size=n, p=weight / weight.sum())
    if cls.get("anchor") == "3prime":
        off = np.full(n, cons_len - length)
    else:
        off = rng.integers(0, cons_len - length + 1, n)
    seqs = cons[fam[:, None], off[:, None] + np.arange(length)[None, :]]
    _mutate(rng, seqs, *cls["divergence"])
    return _orient(rng, seqs)


def _place(rng, ref: np.ndarray, groups: list) -> None:
    """Lay every copy of ``groups`` (arrays of equal-length rows) over the
    reference without overlap, in a random order with random gaps."""
    lens = np.concatenate([np.full(len(g), g.shape[1], np.int64)
                           for g in groups])
    total = int(lens.sum())
    if total > len(ref):
        raise ValueError("the repeat classes hold more bases than the "
                         "reference")
    order = rng.permutation(len(lens))
    gaps = np.sort(rng.integers(0, len(ref) - total + 1, len(lens)))
    starts = np.empty(len(lens), np.int64)
    starts[order] = gaps + np.cumsum(lens[order]) - lens[order]
    at = 0
    for g in groups:
        if len(g):
            s = starts[at:at + len(g)]
            ref[s[:, None] + np.arange(g.shape[1])[None, :]] = g
        at += len(g)


def _tandem(rng, ref: np.ndarray, spec: dict, lut: np.ndarray) -> None:
    """Simple tandem repeats: tracts of ``tract_len`` bases that repeat a
    random unit of ``unit_len`` (lo, hi) bases, with substitutions at
    ``divergence``, over ``share`` of the reference."""
    tl = int(spec["tract_len"])
    n = int(round(spec["share"] * len(ref) / tl))
    if n == 0:
        return
    lo, hi = spec["unit_len"]
    unit = rng.integers(lo, hi + 1, n)
    motif = _bases(rng, lut, (n, hi))
    tracts = motif[np.arange(n)[:, None],
                   np.arange(tl)[None, :] % unit[:, None]]
    _mutate(rng, tracts, *spec["divergence"])
    dst = rng.integers(0, len(ref) - tl, n)
    ref[dst[:, None] + np.arange(tl)[None, :]] = tracts


def _duplicate(rng, ref: np.ndarray, spec: dict) -> None:
    """Segmental duplications: ``share`` of the reference overwritten by
    copies of ``copy_len``-base segments taken from elsewhere in it, each
    with its own divergence, on a random strand."""
    cl = int(spec["copy_len"])
    n = int(round(spec["share"] * len(ref) / cl))
    if n == 0 or cl >= len(ref):
        return
    span = np.arange(cl)[None, :]
    src = rng.integers(0, len(ref) - cl, n)
    dst = rng.integers(0, len(ref) - cl, n)
    segs = _orient(rng, _mutate(rng, ref[src[:, None] + span],
                                *spec["divergence"]))
    ref[dst[:, None] + span] = segs


def make_reference(conf: dict) -> np.ndarray:
    """The configuration's reference, (reference_bases,) uint8 codes 0..3,
    from its fixed ``reference_seed``.  ``repeats`` scales its source
    table's copy numbers by ``reference_bases / (table_genome_mb * 1e6)``."""
    size = int(conf["reference_bases"])
    rng = np.random.default_rng([conf["reference_seed"], 1])
    lut = _base_lut(conf["base_freq"])
    ref = _bases(rng, lut, size)
    rep = conf.get("repeats")
    if rep:
        scale = size / (rep["table_genome_mb"] * 1e6)
        _place(rng, ref, [_class_copies(rng, c, scale, lut)
                          for c in rep["classes"]])
    if conf.get("tandem_repeats"):
        _tandem(rng, ref, conf["tandem_repeats"], lut)
    if conf.get("segmental_dups"):
        _duplicate(rng, ref, conf["segmental_dups"])
    return ref


def _reads_at(rng, ref: np.ndarray, starts: np.ndarray, read_len: int,
              sub_rate: float, ins_rate: float, del_rate: float):
    """Forward reads sampled at ``starts``: each step draws one event
    (substitution, insertion, deletion or match) at the traffic's per-base
    rates.  Returns the reads, (n, read_len) uint8."""
    n = len(starts)
    steps = read_len + _SPARE_STEPS
    u = rng.random((n, steps))
    sub = u < sub_rate
    ins = (u >= sub_rate) & (u < sub_rate + ins_rate)
    dele = (u >= sub_rate + ins_rate) & (u < sub_rate + ins_rate + del_rate)
    emit = ~dele
    advance = ~ins
    # reference base consumed (or faced) at each step
    rpos = starts[:, None] + np.cumsum(advance, axis=1) - advance
    base = ref[np.minimum(rpos, len(ref) - 1)]
    shift = rng.integers(1, 4, (n, steps), dtype=np.uint8)
    base = np.where(sub, (base + shift) % 4, base)
    base = np.where(ins, rng.integers(0, 4, (n, steps), dtype=np.uint8),
                    base).astype(np.uint8)
    slot = np.cumsum(emit, axis=1) - 1
    if int(slot[:, -1].min()) < read_len - 1:
        raise RuntimeError("a read ran out of sampling steps")
    keep = emit & (slot < read_len)
    reads = np.empty((n, read_len), dtype=np.uint8)
    rows = np.broadcast_to(np.arange(n)[:, None], (n, steps))
    reads[rows[keep], slot[keep]] = base[keep]
    return reads


def _quals(rng, shape) -> np.ndarray:
    return (rng.integers(30, 41, shape) + 33).astype(np.uint8)


@dataclasses.dataclass(frozen=True)
class Reads:
    """One read set with its truth.  Single-end sets leave the mate-2
    fields None.  ``pos`` is the forward-reference leftmost position and
    ``strand`` 1 where the read was sequenced reverse-complemented."""
    reads1: np.ndarray
    quals1: np.ndarray
    pos1: np.ndarray
    strand1: np.ndarray
    reads2: np.ndarray | None = None
    quals2: np.ndarray | None = None
    pos2: np.ndarray | None = None
    strand2: np.ndarray | None = None

    @property
    def paired(self) -> bool:
        return self.reads2 is not None

    @property
    def n_reads(self) -> int:
        return len(self.reads1) * (2 if self.paired else 1)


def sample(ref: np.ndarray, traffic: dict, seed: int) -> Reads:
    """Reads as a traffic file describes them (``layout`` single or
    paired)."""
    rng = np.random.default_rng([seed, 2])
    rl = traffic["read_len"]
    rates = (traffic["sub_rate"], traffic["ins_rate"], traffic["del_rate"])
    margin = rl + 16   # room for deletions consuming extra reference bases
    G = len(ref)
    if traffic["layout"] == "single":
        n = traffic["reads"]
        pos = rng.integers(0, G - margin, n).astype(np.int64)
        reads = _reads_at(rng, ref, pos, rl, *rates)
        strand = (rng.random(n) < traffic["reverse_frac"]).astype(np.int8)
        reads[strand == 1] = revcomp(reads[strand == 1])
        return Reads(reads1=reads, quals1=_quals(rng, reads.shape),
                     pos1=pos, strand1=strand)
    n = traffic["pairs"]
    isize = np.clip(np.round(rng.normal(traffic["fragment_mean"],
                                        traffic["fragment_sd"], n)),
                    rl, 2 * traffic["fragment_mean"]).astype(np.int64)
    lo = (rng.random(n) * np.maximum(G - isize - margin, 1)).astype(np.int64)
    hi = lo + isize - rl                      # downstream mate's start
    up = _reads_at(rng, ref, lo, rl, *rates)
    dn = revcomp(_reads_at(rng, ref, hi, rl, *rates))  # faces inward
    r1_up = rng.random(n) < 0.5               # R1 is the upstream mate
    sel = r1_up[:, None]
    reads1 = np.where(sel, up, dn)
    reads2 = np.where(sel, dn, up)
    return Reads(reads1=reads1, quals1=_quals(rng, reads1.shape),
                 pos1=np.where(r1_up, lo, hi),
                 strand1=(~r1_up).astype(np.int8),
                 reads2=reads2, quals2=_quals(rng, reads2.shape),
                 pos2=np.where(r1_up, hi, lo),
                 strand2=r1_up.astype(np.int8))


def read_names(n: int, mate: int | None = None) -> list[str]:
    """``r<i>``, or ``r<i>/1`` / ``r<i>/2`` for mates: the index ties a SAM
    record back to its read and truth."""
    suffix = "" if mate is None else f"/{mate}"
    return [f"r{i}{suffix}" for i in range(n)]


def write_fastq_gz(path: str, reads: np.ndarray, quals: np.ndarray,
                   names: list[str]) -> None:
    """4-line FASTQ records, gzip-compressed."""
    seqs = _ASCII[reads]
    n, rl = reads.shape
    lines = np.empty((n, 2, rl + 1), dtype=np.uint8)
    lines[:, 0, :rl] = seqs
    lines[:, 1, :rl] = quals
    lines[:, :, rl] = ord("\n")
    body = []
    for i, name in enumerate(names):
        row = lines[i]
        body.append(b"@" + name.encode() + b"\n" + row[0].tobytes()
                    + b"+\n" + row[1].tobytes())
    with gzip.open(path, "wb", compresslevel=6) as f:
        f.write(b"".join(body))
