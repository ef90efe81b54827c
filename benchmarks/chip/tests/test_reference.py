"""The reference's rule for a mate placed by rescue: the program writes it
with CIGAR ``*`` (rescue runs no traceback), and the reference holds it to
a locus of its NM within eth."""
import numpy as np

import reference


def _genome():
    rng = np.random.default_rng(5)
    ref = rng.integers(0, 4, 20_000, dtype=np.uint8)
    return reference.Genome(ref, 150, 12, 30, 6)


def _record(pos, cigar, nm, flag=reference.FLAG_PAIRED | reference.FLAG_READ1):
    return reference.Record("r0", flag, pos, 8, cigar, "=", 0, 0, nm)


def _judge(g, rec, read):
    checker = reference.Checker(g, dict(eth=6, sat_affine=32))
    checker.measure_loci([((0, 0), rec, read)])
    unmapped = reference.Answer(mapped=False, dist=32, strand=0, pos=-1,
                                dist2=32)
    return checker.alignment(rec, (0, 0), read, unmapped, rescued=True)


def test_rescued_mate_without_cigar_is_held_to_its_locus():
    g = _genome()
    read = g.ref[5_000:5_150].copy()
    read[[20, 90]] = (read[[20, 90]] + 1) % 4          # two substitutions
    assert _judge(g, _record(5_000, "*", 2), read)
    assert not _judge(g, _record(5_003, "*", 2), read)   # wrong locus
    assert not _judge(g, _record(5_000, "*", 1), read)   # wrong NM


def test_rescued_mate_with_a_cigar_has_it_checked():
    g = _genome()
    read = g.ref[7_000:7_150].copy()
    assert _judge(g, _record(7_000, "150=", 0), read)
    assert not _judge(g, _record(7_000, "149=1X", 0), read)
