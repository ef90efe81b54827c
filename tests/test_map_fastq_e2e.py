"""End-to-end CLI smoke (the CI job's test): simulate a dual-strand read
set, write real FASTA/FASTQ files, run ``python -m repro.launch.map_fastq``
on both topologies as a subprocess, and validate the emitted SAM with the
dependency-free checker — header, mandatory columns, FLAG strand bits
against ground truth, CIGAR/SEQ consistency, and position accuracy."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.data.genome import (make_reference, sample_reads, write_fasta,
                               write_fastq)
from repro.io.cigar import cigar_query_len
from repro.io.sam import FLAG_REVERSE, FLAG_UNMAPPED, validate_sam

READ_LEN = 120
N_READS = 24


@pytest.fixture(scope="module")
def fastq_world(tmp_path_factory):
    d = tmp_path_factory.mktemp("map_fastq")
    c1 = make_reference(5_000, seed=0, repeat_frac=0.02)
    c2 = make_reference(3_000, seed=5, repeat_frac=0.0)
    c1[700:704] = 4  # an N run in the reference
    write_fasta(d / "ref.fa", [("chr1", c1), ("chr2", c2)])
    rs1 = sample_reads(c1, N_READS // 2, read_len=READ_LEN, seed=3,
                       both_strands=True)
    rs2 = sample_reads(c2, N_READS // 2, read_len=READ_LEN, seed=9,
                       both_strands=True)
    reads = np.concatenate([rs1.reads, rs2.reads])
    quals = np.concatenate([rs1.quals, rs2.quals])
    truth = [("chr1", int(p), int(s))
             for p, s in zip(rs1.true_pos, rs1.strand)]
    truth += [("chr2", int(p), int(s))
              for p, s in zip(rs2.true_pos, rs2.strand)]
    names = [f"read{i}" for i in range(N_READS)]
    write_fastq(d / "reads.fq", reads, quals, names)
    return d, dict(zip(names, truth))


def _run_map_fastq(d, out_name, *argv, chunk_reads=16):
    """Invoke the map_fastq CLI as a subprocess; argv follows the
    reference argument.  The single home for the env/subprocess
    boilerplate all the CLI tests share."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    # a CPU run: the launcher gives `--shards N` mesh runs N virtual
    # devices only when JAX is pinned to the CPU
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "repro.launch.map_fastq",
           str(d / "ref.fa"), *argv, "-o", str(d / out_name),
           "--chunk-reads", str(chunk_reads)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    return (d / out_name).read_text(), proc.stderr


def _run_cli(d, out_name, *extra):
    return _run_map_fastq(d, out_name, str(d / "reads.fq"), *extra)


def _check_sam(text, truth, *, expect_cigars):
    stats = validate_sam(text, expect_reads=N_READS)
    assert stats["contigs"] == {"chr1": 5000, "chr2": 3000}
    n_pos_strand_ok = 0
    for ln in text.splitlines():
        if ln.startswith("@"):
            continue
        f = ln.split("\t")
        qname, flag, rname, pos, cig, seq = (f[0], int(f[1]), f[2],
                                             int(f[3]), f[5], f[9])
        t_contig, t_pos, t_strand = truth[qname]
        if flag & FLAG_UNMAPPED:
            continue
        if expect_cigars:
            assert cig != "*"
            assert cigar_query_len(cig) == READ_LEN == len(seq)
        else:
            assert cig == "*"  # mesh stage B never tracebacks
        strand_bit = 1 if flag & FLAG_REVERSE else 0
        if (rname == t_contig and abs((pos - 1) - t_pos) <= 6
                and strand_bit == t_strand):
            n_pos_strand_ok += 1
    # strand-aware accuracy: position AND strand, against ground truth
    assert n_pos_strand_ok >= int(0.9 * N_READS), \
        f"only {n_pos_strand_ok}/{N_READS} correct (pos+strand)"
    assert stats["n_reverse"] > 0  # reverse-strand reads really mapped
    return stats


def test_map_fastq_single_topology(fastq_world):
    d, truth = fastq_world
    text, err = _run_cli(d, "single.sam")
    stats = _check_sam(text, truth, expect_cigars=True)
    assert stats["n_mapped"] >= int(0.9 * N_READS)
    assert "filter/affine [single]" in err


def test_map_fastq_mesh_topology(fastq_world):
    d, truth = fastq_world
    text, err = _run_cli(d, "mesh.sam", "--topology", "mesh",
                         "--shards", "2")
    _check_sam(text, truth, expect_cigars=False)
    assert "stage B [mesh]" in err


def test_map_fastq_single_strand_flag_drops_reverse(fastq_world):
    d, truth = fastq_world
    text, _ = _run_cli(d, "fwd.sam", "--single-strand")
    stats = validate_sam(text, expect_reads=N_READS)
    assert stats["n_reverse"] == 0
    n_rev_truth = sum(1 for _, _, s in truth.values() if s)
    assert stats["n_mapped"] <= N_READS - n_rev_truth + 2


def test_map_fastq_single_end_output_unchanged(fastq_world):
    """The single-end path must not drift under the paired-end feature:
    RNEXT/PNEXT/TLEN stay */0/0 and MAPQ stays the 255 placeholder."""
    d, _ = fastq_world
    text, _ = _run_cli(d, "single2.sam")
    for ln in text.splitlines():
        if ln.startswith("@"):
            continue
        f = ln.split("\t")
        assert f[6:9] == ["*", "0", "0"]
        assert f[4] == ("0" if int(f[1]) & FLAG_UNMAPPED else "255")
        assert not int(f[1]) & 0x1


# ----------------------------------------------------------- paired-end

N_PAIRS = 20


@pytest.fixture(scope="module")
def paired_world(tmp_path_factory):
    """Simulated gzip paired-end world over two contigs, with ground
    truth (positions, strands, insert sizes) for both mates."""
    from repro.data.genome import sample_pairs, write_fastq_pair

    d = tmp_path_factory.mktemp("map_fastq_paired")
    c1 = make_reference(6_000, seed=0, repeat_frac=0.0)
    c2 = make_reference(4_000, seed=5, repeat_frac=0.0)
    write_fasta(d / "ref.fa", [("chr1", c1), ("chr2", c2)])
    ps1 = sample_pairs(c1, N_PAIRS // 2, read_len=READ_LEN,
                       insert_mean=280, insert_sd=25, seed=3)
    ps2 = sample_pairs(c2, N_PAIRS // 2, read_len=READ_LEN,
                       insert_mean=280, insert_sd=25, seed=9)
    names = [f"p{i}" for i in range(N_PAIRS)]
    truth = {}
    for j, (contig, ps) in enumerate((("chr1", ps1), ("chr2", ps2))):
        for i in range(N_PAIRS // 2):
            truth[names[j * (N_PAIRS // 2) + i]] = (
                contig, int(ps.pos1[i]), int(ps.pos2[i]),
                int(ps.strand1[i]), int(ps.strand2[i]), int(ps.isize[i]))
    reads1 = np.concatenate([ps1.reads1, ps2.reads1])
    reads2 = np.concatenate([ps1.reads2, ps2.reads2])
    quals1 = np.concatenate([ps1.quals1, ps2.quals1])
    quals2 = np.concatenate([ps1.quals2, ps2.quals2])
    from repro.data.genome import write_fastq
    write_fastq(d / "r1.fastq.gz", reads1, quals1,
                [f"{n}/1" for n in names])
    write_fastq(d / "r2.fastq.gz", reads2, quals2,
                [f"{n}/2" for n in names])
    return d, truth


def _run_paired_cli(d, out_name, *extra):
    return _run_map_fastq(d, out_name, "--r1", str(d / "r1.fastq.gz"),
                          "--r2", str(d / "r2.fastq.gz"), *extra,
                          chunk_reads=10)


def _check_paired_sam(text, truth):
    """Extended-validator pass + proper-pair accuracy vs ground truth
    (position AND strand AND proper-pair for both mates)."""
    stats = validate_sam(text, expect_reads=2 * N_PAIRS, require_mapq=True)
    assert stats["n_paired"] == 2 * N_PAIRS
    recs = {}
    for ln in text.splitlines():
        if ln.startswith("@"):
            continue
        f = ln.split("\t")
        mate = 0 if int(f[1]) & 0x40 else 1
        recs[(f[0], mate)] = f
    n_ok = 0
    for name, (contig, p1, p2, s1, s2, isize) in truth.items():
        f1, f2 = recs[(name, 0)], recs[(name, 1)]
        fl1, fl2 = int(f1[1]), int(f2[1])
        ok = (not (fl1 & 0x4) and not (fl2 & 0x4)
              and f1[2] == f2[2] == contig
              and abs(int(f1[3]) - 1 - p1) <= 6
              and abs(int(f2[3]) - 1 - p2) <= 6
              and bool(fl1 & 0x10) == bool(s1)
              and bool(fl2 & 0x10) == bool(s2)
              and bool(fl1 & 0x2) and bool(fl2 & 0x2)
              and abs(abs(int(f1[8])) - isize) <= 6)
        n_ok += ok
    assert n_ok >= 0.97 * N_PAIRS, \
        f"only {n_ok}/{N_PAIRS} pairs correct (pos+strand+proper+TLEN)"
    return stats


@pytest.mark.parametrize("topo", ["single", "mesh"])
def test_map_fastq_paired_gz_topologies(paired_world, topo):
    d, truth = paired_world
    extra = () if topo == "single" else ("--topology", "mesh",
                                         "--shards", "2")
    text, err = _run_paired_cli(d, f"paired_{topo}.sam", *extra)
    stats = _check_paired_sam(text, truth)
    assert stats["n_proper"] >= int(0.97 * N_PAIRS)
    assert "pairing:" in err and "insert median" in err


def test_map_fastq_interleaved_matches_two_file(paired_world):
    """--interleaved over the same pairs produces the identical SAM body
    (modulo the @PG CL line, which records the command)."""
    import gzip as gz

    d, _ = paired_world

    def body(text):
        return [ln for ln in text.splitlines() if not ln.startswith("@PG")]

    inter = d / "inter.fastq.gz"
    with gz.open(d / "r1.fastq.gz", "rt") as f1, \
            gz.open(d / "r2.fastq.gz", "rt") as f2, \
            gz.open(inter, "wt") as out:
        while True:
            rec1 = [f1.readline() for _ in range(4)]
            rec2 = [f2.readline() for _ in range(4)]
            if not rec1[0]:
                break
            out.writelines(rec1 + rec2)
    two, _ = _run_paired_cli(d, "two.sam")
    inter_sam, _ = _run_map_fastq(d, "inter.sam", str(inter),
                                  "--interleaved", chunk_reads=10)
    assert body(inter_sam) == body(two)


# ------------------------------------------------------------ --index-dir

def _run_index_cli(d, out_name, *argv, chunk_reads=16):
    """map_fastq against a prebuilt --index-dir (no FASTA positional)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    # a CPU run: the launcher gives `--shards N` mesh runs N virtual
    # devices only when JAX is pinned to the CPU
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "repro.launch.map_fastq",
           "--index-dir", str(d / "idx"), str(d / "reads.fq"),
           *argv, "-o", str(d / out_name),
           "--chunk-reads", str(chunk_reads)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    return (d / out_name).read_text(), proc.stderr


def _sam_body(text):
    # @PG carries the command line, which legitimately differs
    return [ln for ln in text.splitlines() if not ln.startswith("@PG")]


@pytest.fixture(scope="module")
def index_dir(fastq_world):
    d, _ = fastq_world
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "repro.launch.build_index",
           str(d / "ref.fa"), "-o", str(d / "idx"), "--partitions", "2",
           "--tile-bp", "1024", "--read-len", str(READ_LEN), "--verify"]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "integrity check passed" in proc.stderr
    return d / "idx"


def test_index_dir_single_byte_identical(fastq_world, index_dir):
    """Golden e2e: mapping from the on-disk sharded index produces the
    byte-identical SAM to indexing the FASTA in memory (multi-contig,
    dual-strand), single topology."""
    d, truth = fastq_world
    mem, _ = _run_cli(d, "mem_single.sam")
    disk, err = _run_index_cli(d, "disk_single.sam")
    assert _sam_body(disk) == _sam_body(mem)
    _check_sam(disk, truth, expect_cigars=True)
    assert "partitions: routed" in err
    assert "index storage:" in err


def test_index_dir_single_budget_byte_identical(fastq_world, index_dir):
    d, _ = fastq_world
    mem, _ = _run_cli(d, "mem_single2.sam")
    disk, err = _run_index_cli(d, "disk_budget.sam",
                               "--index-budget-mb", "64")
    assert _sam_body(disk) == _sam_body(mem)


def test_index_dir_mesh_byte_identical(fastq_world, index_dir):
    """Mesh topology consumes the pre-partitioned index (partition i on
    shard i) and still byte-matches the in-memory mesh run."""
    d, truth = fastq_world
    mem, _ = _run_cli(d, "mem_mesh.sam", "--topology", "mesh",
                      "--shards", "2")
    disk, err = _run_index_cli(d, "disk_mesh.sam", "--topology", "mesh",
                               "--shards", "2")
    assert _sam_body(disk) == _sam_body(mem)
    _check_sam(disk, truth, expect_cigars=False)
    assert "partitions: 2 mesh-placed" in err


def test_index_dir_cli_validation(fastq_world, index_dir):
    d, _ = fastq_world
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..",
                                      "src") +
                         os.pathsep + env.get("PYTHONPATH", ""))

    def run_cli(*argv):
        return subprocess.run(
            [sys.executable, "-m", "repro.launch.map_fastq", *argv],
            env=env, capture_output=True, text=True, timeout=600)

    p = run_cli(str(d / "ref.fa"), str(d / "reads.fq"),
                "--index-dir", str(d / "idx"))
    assert p.returncode != 0 and "not both" in p.stderr
    p = run_cli(str(d / "reads.fq"))  # looks like a reference, none given
    assert p.returncode != 0
    p = run_cli("--index-dir", str(d / "idx"), str(d / "reads.fq"),
                "--read-len", str(READ_LEN + 1))
    assert p.returncode != 0 and "conflicts with the index" in p.stderr
