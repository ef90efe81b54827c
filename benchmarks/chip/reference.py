"""Plain reference for what the timed path wrote, and the comparison.

The mapper's guarantees, as the configuration files state them, for every
read on both strands:

* it is mapped exactly when some candidate locus passes the linear
  Wagner-Fischer filter (banded edit distance at most ``filter_threshold``
  within the band ``eth``);
* its NM is the least affine distance (paper Eqs. 3-5: substitution 1, gap
  of length L costs 1 + L, saturated at ``sat``) over those candidates, and
  its POS and strand name a locus that has that distance;
* its CIGAR spells the read against the reference at POS, and costs NM
  with the end deletions that SAM normalisation trimmed;
* for pairs: the mate fields, TLEN and the proper-pair flag agree with the
  two records, and MAPQ follows the calibrated model from the best and
  runner-up distances; a mate placed by rescue lies within ``eth`` of the
  read, at a locus of its NM, and is capped by its anchor; rescue runs no
  traceback, so its CIGAR may be ``*`` (the program's SAM semantics).

This module recomputes all of that from the generated reference and reads
alone, by the semantics of the paper's dataflow: each strand of a read is
seeded from its window minimizers (k-mer hash, leftmost ties, the first
``max_minis`` unique ones by k-mer code); each minimizer's candidate loci
are its first ``max_pls`` occurrences, by position, among the reference's
own window minimizers; the linear filter picks each minimizer's best locus
(lowest position on ties), the affine distance ranks those, the lowest
position wins ties, and the forward strand wins a tie between strands.  The
runner-up distance behind MAPQ is the best affine distance at another locus
among the minimizers' winners, or the winner's distance plus the linear
excess of any farther locus that passes the filter, or the other strand's
best.  The banded recurrences are straightforward numpy, vectorised over
candidates.  Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SENTINEL = 4          # never equals a base code
MAPQ_MAX, GAP_SCALE, PROPER_BONUS, RESCUE_CAP = 60, 6, 8, 17

FLAG_PAIRED, FLAG_PROPER, FLAG_UNMAPPED, FLAG_MATE_UNMAPPED = 1, 2, 4, 8
FLAG_REVERSE, FLAG_MATE_REVERSE, FLAG_READ1, FLAG_READ2 = 16, 32, 64, 128


def revcomp(codes: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray((3 - codes)[..., ::-1])


def kmer_codes(seq: np.ndarray, k: int) -> np.ndarray:
    """2-bit packed codes of every k-mer of ``seq`` (last axis)."""
    n = seq.shape[-1] - k + 1
    out = np.zeros(seq.shape[:-1] + (n,), dtype=np.uint32)
    for j in range(k):
        out <<= np.uint32(2)
        out |= seq[..., j:j + n].astype(np.uint32)
    return out


def _genome_kmers(ref: np.ndarray, k: int) -> np.ndarray:
    """``kmer_codes(ref, k)`` by doubling (1-, 2-, 4-, 8-mers), a few passes
    over a chromosome-sized array instead of ``k``."""
    n = len(ref) - k + 1
    c = {1: ref.astype(np.uint32)}
    for span in (2, 4, 8):
        h = span // 2
        prev = c[h]
        c[span] = (prev[:len(prev) - h] << np.uint32(2 * h)) | prev[h:]
    out, have = np.zeros(n, dtype=np.uint32), 0
    for span in (8, 4, 2, 1):
        if have + span <= k:
            out = (out << np.uint32(2 * span)) | c[span][have:have + n]
            have += span
    return out


# --------------------------------------------------------------------------
# Banded recurrences, vectorised over N (read, window) instances
# --------------------------------------------------------------------------

def linear_distance(reads: np.ndarray, wins: np.ndarray, eth: int):
    """Banded edit distance D[n][n], saturated at ``eth + 1``.  ``wins``
    (N, n + 2 eth): position p holds the base at (start - eth + p)."""
    N, n = reads.shape
    band, sat = 2 * eth + 1, eth + 1
    rd, wn = np.ascontiguousarray(reads.T), np.ascontiguousarray(wins.T)
    B = np.full((band, N), sat, dtype=np.int32)     # band-major: rows i
    for d in range(eth, band):
        B[d] = min(d - eth, sat)
    for i in range(1, n + 1):
        prev, B = B, np.full((band, N), sat, dtype=np.int32)
        for d in range(band):
            j = i + d - eth
            if j < 0:
                continue
            up = prev[d + 1] + 1 if d + 1 < band else sat
            if j == 0:
                B[d] = np.minimum(up, sat)
                continue
            diag = prev[d] + (rd[i - 1] != wn[i + d - 1])
            left = B[d - 1] + 1 if d >= 1 else sat
            B[d] = np.minimum(np.minimum(diag, up), np.minimum(left, sat))
    return B[eth]


def affine_distance(reads: np.ndarray, wins: np.ndarray, eth: int, sat: int):
    """Banded affine distance (Gotoh, paper Eqs. 3-5, w_sub = w_op = w_ex =
    1) ending at D[n][n], values saturated at ``sat``."""
    N, n = reads.shape
    band = 2 * eth + 1
    rd, wn = np.ascontiguousarray(reads.T), np.ascontiguousarray(wins.T)
    D = np.full((band, N), sat, dtype=np.int32)
    M1 = np.full((band, N), sat, dtype=np.int32)
    M2 = np.full((band, N), sat, dtype=np.int32)
    for d in range(eth, band):
        j = d - eth
        D[d] = 0 if j == 0 else min(1 + j, sat)
        if j > 0:
            M2[d] = D[d]
    for i in range(1, n + 1):
        Dp, M1p = D, M1
        D = np.full((band, N), sat, dtype=np.int32)
        M1 = np.full((band, N), sat, dtype=np.int32)
        M2 = np.full((band, N), sat, dtype=np.int32)
        for d in range(band):
            j = i + d - eth
            if j < 0:
                continue
            if d + 1 < band:
                M1[d] = np.minimum(np.minimum(M1p[d + 1] + 1, Dp[d + 1] + 2),
                                   sat)
            if d >= 1:
                M2[d] = np.minimum(np.minimum(M2[d - 1] + 1, D[d - 1] + 2),
                                   sat)
            if j == 0:
                D[d] = M1[d]
                continue
            match = rd[i - 1] == wn[i + d - 1]
            gap = np.minimum(M1[d], M2[d])
            D[d] = np.minimum(np.where(match, Dp[d],
                                       np.minimum(Dp[d] + 1, gap)), sat)
    return D[eth]


# --------------------------------------------------------------------------
# Candidate loci from k-mer hits
# --------------------------------------------------------------------------

class Genome:
    """The generated reference with what the reference mapper needs of it:
    a sentinel-padded copy for windows and its k-mer codes for seeding."""


    def __init__(self, ref: np.ndarray, read_len: int, k: int, w: int,
                 eth: int):
        self.ref, self.rl, self.k, self.w, self.eth = ref, read_len, k, w, eth
        self.pad = read_len + 2 * eth
        self.padded = np.full(len(ref) + 2 * self.pad, SENTINEL, np.uint8)
        self.padded[self.pad:self.pad + len(ref)] = ref
        self.kmers = _genome_kmers(ref, k)

    def windows(self, starts: np.ndarray) -> np.ndarray:
        idx = (np.asarray(starts, np.int64)[:, None] - self.eth + self.pad
               + np.arange(self.rl + 2 * self.eth)[None, :])
        return self.padded[idx]

    def occurrences(self, codes: np.ndarray, max_pls: int):
        """For each k-mer code, its first ``max_pls`` positions (ascending)
        at which it is a window minimizer of the reference: a list of
        arrays.  ``g`` is a minimizer of some window of ``w`` k-mers iff the
        run of strictly larger hashes to its left and of larger-or-equal
        hashes to its right, each capped at ``w - 1``, sum to ``w - 1`` or
        more (ties go to the leftmost k-mer)."""
        w, nk = self.w, len(self.kmers)
        table = np.zeros(4 ** self.k, dtype=bool)
        table[codes] = True
        hit = np.flatnonzero(table[self.kmers])
        off = np.arange(-(w - 1), w)
        idx = hit[:, None] + off[None, :]
        inside = (idx >= 0) & (idx < nk)
        h = hash32(self.kmers[np.clip(idx, 0, nk - 1)])
        h0 = h[:, w - 1:w]
        left = ((h[:, :w - 1] > h0) & inside[:, :w - 1])[:, ::-1]
        right = (h[:, w:] >= h0) & inside[:, w:]
        run_l = np.cumprod(left, axis=1).sum(axis=1)
        run_r = np.cumprod(right, axis=1).sum(axis=1)
        hit = hit[run_l + run_r >= w - 1]
        code = self.kmers[hit]
        order = np.lexsort((hit, code))
        hit, code = hit[order], code[order]
        lo = np.searchsorted(code, codes, side="left")
        hi = np.searchsorted(code, codes, side="right")
        return [hit[a:min(b, a + max_pls)] for a, b in zip(lo, hi)]


def hash32(x: np.ndarray) -> np.ndarray:
    """The invertible 32-bit mix that orders minimizers (uint32 wraps)."""
    x = x.astype(np.uint32)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def read_minimizers(seq: np.ndarray, k: int, w: int, max_minis: int):
    """(codes, offsets) of one sequence's unique window minimizers, sorted
    by code, the first occurrence of each, at most ``max_minis``."""
    codes = kmer_codes(seq, k)
    h = hash32(codes)
    win = np.lib.stride_tricks.sliding_window_view(h, w)
    am = win.argmin(axis=1) + np.arange(len(win))
    uniq, first = np.unique(codes[am], return_index=True)
    return uniq[:max_minis], am[first][:max_minis]


# --------------------------------------------------------------------------
# SAM records
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    qname: str
    flag: int
    pos: int            # 0-based contig-local leftmost, -1 unmapped
    mapq: int
    cigar: str
    rnext: str
    pnext: int
    tlen: int
    nm: int | None

    @classmethod
    def parse(cls, line: str) -> "Record":
        f = line.rstrip("\n").split("\t")
        nm = next((int(t[5:]) for t in f[11:] if t.startswith("NM:i:")),
                  None)
        return cls(f[0], int(f[1]), int(f[3]) - 1, int(f[4]), f[5], f[6],
                   int(f[7]), int(f[8]), nm)

    @property
    def mapped(self) -> bool:
        return not self.flag & FLAG_UNMAPPED

    @property
    def reverse(self) -> bool:
        return bool(self.flag & FLAG_REVERSE)


def parse_cigar(cigar: str) -> list[tuple[int, str]]:
    out, num = [], ""
    for ch in cigar:
        if ch.isdigit():
            num += ch
        else:
            out.append((int(num), ch))
            num = ""
    return out


def cigar_ref_len(ops) -> int:
    return sum(n for n, c in ops if c in "=XDM")


def footprint(rec: "Record", rl: int) -> int:
    """Reference bases a mapped record covers: its CIGAR's, or the read's
    length for a record without one (``*``, as a rescued mate has)."""
    return rl if rec.cigar == "*" else cigar_ref_len(parse_cigar(rec.cigar))


def check_cigar(rec: Record, seq: np.ndarray, ref: np.ndarray, rl: int,
                nm: int) -> bool:
    """The CIGAR spells ``seq`` (aligned orientation) at POS: '=' bases
    equal, 'X' bases differ, the read is consumed whole, the footprint lies
    in the reference, and its cost plus that of the trimmed end deletions
    (``rl`` minus the footprint, one gap or two) is ``nm``."""
    if rec.cigar == "*" or not set(rec.cigar) <= set("0123456789=XID"):
        return False
    ops = parse_cigar(rec.cigar)
    if sum(n for n, c in ops if c in "=XI") != rl:
        return False
    trimmed = rl - cigar_ref_len(ops)
    if trimmed < 0 or rec.pos < 0 or rec.pos + cigar_ref_len(ops) > len(ref):
        return False
    i, j, cost = 0, rec.pos, 0
    for n, c in ops:
        if c in "=X":
            same = seq[i:i + n] == ref[j:j + n]
            if not (same.all() if c == "=" else (~same).all()):
                return False
            cost += n if c == "X" else 0
            i, j = i + n, j + n
        else:
            cost += 1 + n
            i, j = (i + n, j) if c == "I" else (i, j + n)
    if trimmed == 0:
        return cost == nm
    return nm - cost in ((trimmed + 1, trimmed + 2) if trimmed >= 2
                         else (trimmed + 1,))


# --------------------------------------------------------------------------
# The comparison
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Answer:
    """What the reference mapper reports for one read (both strands)."""
    mapped: bool
    dist: int        # affine distance of the winner (sat when unmapped)
    strand: int      # 1 when the reverse complement won
    pos: int         # winner's candidate start (before SAM trims it)
    dist2: int       # runner-up distance behind MAPQ


def _strand(cands, lin, aff, cfg):
    """(dist, pos, dist2) of one strand from its minimizers' candidates:
    ``cands`` per minimizer a list of (start, slot) into ``lin``/``aff``."""
    eth, sat, thr = cfg["eth"], cfg["sat_affine"], cfg["filter_threshold"]
    winners = []                                   # (aff, start, lin)
    for group in cands:
        if not group:
            continue
        best = min(range(len(group)), key=lambda t: (lin[group[t][1]], t))
        start, slot = group[best]
        if lin[slot] <= thr:
            winners.append((int(aff[slot]), start, int(lin[slot])))
    if not winners:
        return sat, -1, sat
    dist = min(a for a, _, _ in winners)
    pos = min([st for a, st, _ in winners if a == dist and st >= 0]
              or [-1])
    lin_w = next((li for a, st, li in winners if a == dist and st == pos),
                 0)
    d2 = min([a for a, st, _ in winners
              if a < sat and abs(st - pos) > eth and st >= 0] + [sat])
    far = [int(lin[slot]) for group in cands for st, slot in group
           if abs(st - pos) > eth and lin[slot] <= min(thr, eth)]
    if far:
        d2 = min(d2, min(dist + max(min(far) - lin_w, 0), sat))
    return dist, pos, d2


def solve(genome: Genome, reads: np.ndarray, cfg: dict) -> list[Answer]:
    """The reference mapper's answer for each read."""
    eth, sat = cfg["eth"], cfg["sat_affine"]
    k, w = genome.k, genome.w
    seqs = np.concatenate([reads, revcomp(reads)])
    minis = [read_minimizers(s, k, w, cfg["max_minis"]) for s in seqs]
    codes = np.unique(np.concatenate([c for c, _ in minis]))
    occ = dict(zip(codes.tolist(), genome.occurrences(codes, cfg["max_pls"])))
    rows, starts, cands = [], [], []
    for r, (cs, offs) in enumerate(minis):
        groups = []
        for c, o in zip(cs.tolist(), offs.tolist()):
            group = []
            for g in occ[c].tolist():
                group.append((g - o, len(starts)))
                rows.append(r)
                starts.append(g - o)
            groups.append(group)
        cands.append(groups)
    rows, starts = np.array(rows, np.int64), np.array(starts, np.int64)
    lin = linear_distance(seqs[rows], genome.windows(starts), eth)
    aff = np.full(len(rows), sat, dtype=np.int32)
    ok = lin <= cfg["filter_threshold"]
    if ok.any():
        aff[ok] = affine_distance(seqs[rows[ok]],
                                  genome.windows(starts[ok]), eth, sat)
    n, out = len(reads), []
    for i in range(n):
        fwd = _strand(cands[i], lin, aff, cfg)
        rev = _strand(cands[i + n], lin, aff, cfg)
        win, lose, strand = (rev, fwd, 1) if rev[0] < fwd[0] else \
            (fwd, rev, 0)
        out.append(Answer(mapped=win[0] < sat, dist=win[0], strand=strand,
                          pos=win[1], dist2=min(win[2], lose[0])))
    return out


def mapq_model(d1: int, d2: int, proper: bool, mate_mapped: bool,
               sat: int) -> int:
    gap = min(max(d2 - d1, 0), sat)
    q = min(max(GAP_SCALE * gap - d1, 0), MAPQ_MAX)
    if proper:
        return min(q + PROPER_BONUS, MAPQ_MAX)
    return q // 2 if mate_mapped else q


class Checker:
    """Judges sampled SAM records against the reference, one by one."""

    def __init__(self, genome: Genome, cfg: dict):
        self.g, self.cfg = genome, cfg
        self.reasons: dict[str, int] = {}
        self.locus: dict = {}

    def measure_loci(self, jobs) -> None:
        """Affine distances at every start a rescued record may stand for,
        in one batch: ``jobs`` is a list of (key, record, read)."""
        seqs, starts, keys = [], [], []
        for key, rec, read in jobs:
            if not rec.mapped or rec.pos < 0:
                continue
            seq = revcomp(read) if rec.reverse else read
            trimmed = max(self.g.rl - footprint(rec, self.g.rl), 0)
            for t in range(trimmed + 1):
                seqs.append(seq)
                starts.append(rec.pos - t)
                keys.append(key)
        if not seqs:
            return
        d = affine_distance(np.stack(seqs), self.g.windows(np.array(starts)),
                            self.cfg["eth"], self.cfg["sat_affine"])
        for key, v in zip(keys, d.tolist()):
            self.locus.setdefault(key, []).append(v)
        self.locus = {k: np.array(v) for k, v in self.locus.items()}

    def _fail(self, why: str) -> bool:
        self.reasons[why] = self.reasons.get(why, 0) + 1
        return False

    def _locus_has(self, rec: Record, key, nm: int) -> bool:
        """Some untrimmed start behind POS (by up to the trimmed end
        deletions) has affine distance ``nm`` (``locus`` holds them)."""
        return bool((self.locus.get(key, np.empty(0)) == nm).any())

    def alignment(self, rec: Record, key, read: np.ndarray, ans: Answer,
                  rescued: bool) -> bool:
        """One record against the reference's answer for its read.  A
        rescued mate (mapped where the reference's seeds find nothing) is
        held to what rescue promises: a locus within ``eth``."""
        if not rec.mapped:
            return self._fail("unmapped, reference maps it") \
                if ans.mapped else True
        if not ans.mapped and not rescued:
            return self._fail("mapped, reference finds no locus")
        if rec.nm is None:
            return self._fail("no NM")
        seq = revcomp(read) if rec.reverse else read
        # rescue runs no traceback: the program writes a rescued mate's
        # CIGAR as "*" (README, SAM output semantics); one it does write
        # has to spell the read
        if not (rescued and rec.cigar == "*") and not check_cigar(
                rec, seq, self.g.ref, self.g.rl, rec.nm):
            return self._fail("CIGAR does not spell the read at POS")
        trimmed = self.g.rl - footprint(rec, self.g.rl)
        if rescued:
            if rec.nm > self.cfg["eth"]:
                return self._fail("rescued beyond eth")
            if not self._locus_has(rec, key, rec.nm):
                return self._fail("rescued POS not at a locus of its NM")
            return True
        if rec.nm != ans.dist:
            return self._fail("NM")
        if int(rec.reverse) != ans.strand:
            return self._fail("strand")
        if not ans.pos <= rec.pos <= ans.pos + trimmed:
            return self._fail("POS")
        return True

    def single(self, rec: Record, key, read: np.ndarray, ans: Answer) -> bool:
        if rec.flag & ~(FLAG_UNMAPPED | FLAG_REVERSE):
            return self._fail("pair bits on a single-end record")
        if rec.mapq != (255 if rec.mapped else 0):
            return self._fail("single-end MAPQ")
        return self.alignment(rec, key, read, ans, rescued=False)

    def pair(self, recs: tuple[Record, Record], key, reads, answers) -> bool:
        """Both records of one template; True when every check holds."""
        cfg, sat = self.cfg, self.cfg["sat_affine"]
        r1, r2 = recs
        if (r1.flag & (FLAG_READ1 | FLAG_READ2 | FLAG_PAIRED)
                != FLAG_READ1 | FLAG_PAIRED
                or r2.flag & (FLAG_READ1 | FLAG_READ2 | FLAG_PAIRED)
                != FLAG_READ2 | FLAG_PAIRED):
            return self._fail("mate identity bits")
        rescued = [r.mapped and not b.mapped and o.mapped
                   for r, b, o in ((r1, answers[0], r2),
                                   (r2, answers[1], r1))]
        for mate, (m, o) in enumerate(((r1, r2), (r2, r1))):
            if not self.alignment(m, (key, mate), reads[mate],
                                  answers[mate], rescued[mate]):
                return False
            if bool(m.flag & FLAG_MATE_UNMAPPED) == o.mapped:
                return self._fail("0x8 disagrees with the mate")
            if o.mapped and bool(m.flag & FLAG_MATE_REVERSE) != o.reverse:
                return self._fail("0x20 disagrees with the mate")
            want = (("=", o.pos + 1) if m.mapped and o.mapped else
                    ("*", 0) if not o.mapped else
                    (cfg["contig"], o.pos + 1))
            if (m.rnext, m.pnext) != want:
                return self._fail("RNEXT/PNEXT")
        proper = bool(r1.flag & FLAG_PROPER)
        if proper != bool(r2.flag & FLAG_PROPER):
            return self._fail("0x2 on one mate only")
        both = r1.mapped and r2.mapped
        tlen = 0
        fr = False
        if both:
            ends = [r.pos + footprint(r, self.g.rl) for r in (r1, r2)]
            lo, hi = min(r1.pos, r2.pos), max(ends)
            tlen = hi - lo if r1.pos <= r2.pos else lo - hi
            fwd, rev = (r1, r2) if not r1.reverse else (r2, r1)
            fr = (r1.reverse != r2.reverse
                  and fwd.pos <= rev.pos + 2 * cfg["eth"])
        if (r1.tlen, r2.tlen) != (tlen, -tlen):
            return self._fail("TLEN")
        if proper and not fr:
            return self._fail("0x2 on a pair that is not FR")
        sure_lo, sure_hi = cfg["sure_proper"]
        if fr and sure_lo <= abs(tlen) <= sure_hi and not proper:
            return self._fail("FR pair of a usual insert without 0x2")
        q = []
        for m, o, ans, res in ((r1, r2, answers[0], rescued[0]),
                               (r2, r1, answers[1], rescued[1])):
            if not m.mapped:
                q.append(0)
                continue
            d2 = m.nm if res else ans.dist2
            q.append(mapq_model(m.nm, d2, proper, o.mapped, sat))
        for i in (0, 1):
            if rescued[i]:
                q[i] = min(min(q[1 - i], RESCUE_CAP), q[i])
        if (r1.mapq, r2.mapq) != tuple(q):
            return self._fail("MAPQ")
        return True


def compare(sam_path: str, reads, genome: Genome, cfg: dict,
            n_written: int, sample: int, seed: int) -> dict:
    """Draw ``sample`` records (pairs: templates) from the SAM the window
    wrote, from the seed, and count those the reference disagrees with.
    A record that is missing, or out of its place, counts as wrong."""
    paired = reads.paired
    n_set = len(reads.reads1)
    with open(sam_path) as f:
        lines = [ln for ln in f if not ln.startswith("@")]
    per = 2 if paired else 1
    n_units = n_written // per
    rng = np.random.default_rng([seed, 7])
    units = np.sort(rng.choice(n_units, size=min(sample, n_units),
                               replace=False))
    idx = units % n_set
    checker = Checker(genome, cfg)
    answers = [solve(genome, reads.reads1[idx], cfg)]
    if paired:
        answers.append(solve(genome, reads.reads2[idx], cfg))
    mates = (reads.reads1, reads.reads2)
    parsed, jobs = [], []
    for k, (u, i) in enumerate(zip(units.tolist(), idx.tolist())):
        try:
            recs = [Record.parse(lines[u * per + m]) for m in range(per)]
        except (IndexError, ValueError):
            recs = None
        parsed.append(recs)
        for m, rec in enumerate(recs or ()):
            if rec.mapped and not answers[m][k].mapped:   # rescued mates
                jobs.append(((k, m), rec, mates[m][i]))
    checker.measure_loci(jobs)
    wrong, examples = 0, []
    for k, (u, i, recs) in enumerate(zip(units.tolist(), idx.tolist(),
                                         parsed)):
        if recs is None:
            ok = checker._fail("record missing or malformed")
        elif any(r.qname != f"r{i}" for r in recs):
            ok = checker._fail("record out of place")
        elif paired:
            ok = checker.pair((recs[0], recs[1]), k,
                              (reads.reads1[i], reads.reads2[i]),
                              (answers[0][k], answers[1][k]))
        else:
            ok = checker.single(recs[0], (k, 0), reads.reads1[i],
                                answers[0][k])
        wrong += not ok
        if not ok and len(examples) < 3:
            ans = [dataclasses.astuple(a[k]) for a in answers]
            examples.append(dict(
                records=[ln.rstrip("\n").split("\t")[:9]
                         for ln in lines[u * per:(u + 1) * per]],
                reference=ans))
    return dict(wrong=wrong, checked=len(units), records=len(lines),
                reasons=checker.reasons, examples=examples)
