#!/usr/bin/env python3
"""Smoke run of the read mapper on one TPU chip, end to end, in one process.

    python chip_smoke.py                # every single-chip phase, real size
    python chip_smoke.py --mesh         # the 4-chip mesh path only
    JAX_PLATFORMS=cpu python chip_smoke.py --small   # CPU rehearsal

Drives the entry points a user calls -- ``repro.launch.map_fastq``,
``repro.launch.build_index`` and ``repro.launch.serve`` -- in-process, on a
reference and reads generated from ``--seed`` into a scratch directory:

  data         a 48,000,000-base reference (about GRCh38 chr21), 65,536
               single-end 150 bp reads from both strands and 16,384 pairs
  single-end   map_fastq fused+pallas, compacted+pallas, compacted+jnp:
               byte-identical SAMs, >= 0.95 of reads at their true
               position (within the band, eth = 6) and strand
  paired       fused+pallas against compacted+jnp: byte-identical SAMs
  out-of-core  build_index --partitions 4, then map_fastq --index-dir with a
               budget that holds every partition and --prefetch: the SAM
               equals the flat single-end SAM
  service      serve --service --wf-backend pallas on a 48 Mb genome: no
               retry, bisection, degrade step or quarantined read

Each phase prints its wall time, compile time (JAX's trace + lower +
compile durations), the device's peak_bytes_in_use and whether the lowered
programs held a Pallas TPU kernel (``tpu_custom_call``).  These are smoke
timings, not benchmark numbers.  ``--mesh`` maps the same kind of data
with ``Mapper(topology="mesh")`` over 4 chips against the single-topology
session, both on the pallas backend, and checks they agree read for read
up to the reads the mesh dropped (``dropped_send`` / ``dropped_affine``)
and that each chip holds its own shard of the index.

The last line of stdout is ``{"ok": ..., "device": {"platform", "kind",
"count"}}``; ``ok`` is true only on a TPU with every check passed, and the
exit code is 0 only then.  Where JAX finds no TPU (and ``--small`` is not
given) the script exits nonzero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import hashlib
import io
import json
import os
import shutil
import sys
import time
import traceback

# before JAX loads: a TPU run must never fall back to the CPU in silence
os.environ.setdefault("JAX_PLATFORMS", "tpu")

ROOT = os.path.dirname(os.path.abspath(__file__))
SIZES = {  # reference bases, single-end reads, pairs, service genome
    "full": (48_000_000, 65_536, 16_384, 48_000_000),
    "small": (400_000, 2_048, 512, 200_000),
}
MESH_READS = {"full": 16_384, "small": 1_024}


def sha(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()[:16]


def sam_records(path: str):
    with open(path) as f:
        for line in f:
            if not line.startswith("@"):
                yield line.split("\t", 10)


class Smoke:
    def __init__(self, args):
        import jax

        from repro.launch.runtime import enable_compile_cache
        self.jax = jax
        self.args = args
        self.work = args.workdir
        self.failures: list[str] = []
        self.cache_dir = enable_compile_cache()
        self.dev = jax.devices()[0]
        self.compile_s = 0.0
        self.last_kernels = 0

        def on_duration(event, secs, **_):
            if event.startswith("/jax/core/compile/"):
                self.compile_s += secs
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    # ------------------------------------------------------------ helpers

    def check(self, cond: bool, what: str) -> None:
        print(f"  check {'ok  ' if cond else 'FAIL'} {what}", flush=True)
        if not cond:
            self.failures.append(what)

    def peak_bytes(self):
        stats = self.dev.memory_stats() or {}
        return stats.get("peak_bytes_in_use", "not reported")

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time a phase, dump the StableHLO it lowers (to look for Pallas
        kernels), and turn an exception into a recorded failure so the
        remaining phases still report.  JAX's in-memory caches are
        cleared first, so every phase lowers its own programs (a repeat
        compile is then a persistent-cache hit)."""
        jax = self.jax
        jax.clear_caches()
        dump = os.path.join(self.work, "ir", name)
        jax.config.update("jax_dump_ir_to", dump)
        c0, t0 = self.compile_s, time.perf_counter()
        print(f"== {name}", flush=True)
        try:
            yield
        except Exception:  # noqa: BLE001 — report, then fail the run
            traceback.print_exc()
            self.failures.append(f"{name}: raised")
            print(f"  phase {name} FAILED", flush=True)
        finally:
            jax.config.update("jax_dump_ir_to", "")
            kernels = 0
            for dirpath, _, files in os.walk(dump):
                for fn in files:
                    with open(os.path.join(dirpath, fn), errors="replace") as f:
                        kernels += "tpu_custom_call" in f.read()
            shutil.rmtree(dump, ignore_errors=True)
            print(f"  {name}: wall {time.perf_counter() - t0:.1f} s, compile "
                  f"{self.compile_s - c0:.1f} s, peak_bytes_in_use "
                  f"{self.peak_bytes()}, lowered modules with "
                  f"tpu_custom_call: {kernels}", flush=True)
            self.last_kernels = kernels

    def launch(self, module, argv: list[str], log: str) -> int:
        """Run a launcher's ``main(argv)`` in this process, its stdout and
        stderr kept in ``log``; echo its summary lines."""
        path = os.path.join(self.work, log)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
            rc = module.main(argv)
        text = buf.getvalue()
        with open(path, "w") as f:
            f.write(text)
        keys = ("map_fastq: ", "done:", "pairing:", "partitions:",
                "resilience:", "service:", "reads /", "build_index: ",
                "filter/affine", "plan cache:")
        for line in text.splitlines():
            if line.startswith(keys) and "progress" not in line:
                print(f"    | {line[:300]}", flush=True)
        self.check(rc == 0, f"{module.__name__} exit code {rc} == 0")
        return rc

    # ------------------------------------------------------------- phases

    def make_data(self, sizes):
        from repro.data.genome import (make_reference, sample_pairs,
                                       sample_reads, write_fasta,
                                       write_fastq, write_fastq_pair)
        G, n_reads, n_pairs, _ = sizes
        seed = self.args.seed
        t0 = time.perf_counter()
        self.ref = make_reference(G, seed=seed)
        self.fasta = os.path.join(self.work, "ref.fa")
        write_fasta(self.fasta, self.ref)
        t1 = time.perf_counter()
        self.reads = sample_reads(self.ref, n_reads, seed=seed + 1,
                                  both_strands=True)
        self.fastq = os.path.join(self.work, "reads.fq")
        write_fastq(self.fastq, self.reads)
        t2 = time.perf_counter()
        pairs = sample_pairs(self.ref, n_pairs, seed=seed + 2)
        self.r1 = os.path.join(self.work, "pairs_1.fq")
        self.r2 = os.path.join(self.work, "pairs_2.fq")
        write_fastq_pair(self.r1, self.r2, pairs)
        t3 = time.perf_counter()
        print(f"  reference {G} bases {t1 - t0:.1f} s, {n_reads} reads "
              f"{t2 - t1:.1f} s, {n_pairs} pairs {t3 - t2:.1f} s "
              f"(sample_reads loops in Python per base)", flush=True)

    def accuracy(self, sam: str) -> float:
        truth_pos, truth_strand = self.reads.true_pos, self.reads.strand
        good = total = 0
        for rec in sam_records(sam):
            i = int(rec[0][len("read"):])
            flag, pos = int(rec[1]), int(rec[3]) - 1
            total += 1
            good += (not flag & 0x4 and abs(pos - int(truth_pos[i])) <= 6
                     and bool(flag & 0x10) == bool(truth_strand[i]))
        return good / max(total, 1)

    def single_end(self):
        from repro.launch import map_fastq
        runs = (("fused", "pallas"), ("compacted", "pallas"),
                ("compacted", "jnp"))
        digests = {}
        for engine, backend in runs:
            name = f"single_{engine}_{backend}"
            sam = os.path.join(self.work, f"{name}.sam")
            with self.phase(name):
                self.launch(map_fastq, [
                    self.fasta, self.fastq, "-o", sam, "--engine", engine,
                    "--wf-backend", backend], f"{name}.log")
                digests[name] = sha(sam)
                acc = self.accuracy(sam)
                print(f"  sam sha256[:16] {digests[name]}, accuracy "
                      f"{acc:.4f}", flush=True)
                self.check(acc >= 0.95, f"{name} accuracy {acc:.4f} >= 0.95")
            if backend == "pallas":
                self.check(self.last_kernels > 0,
                           f"{name} lowered a Pallas TPU kernel")
        self.check(len(set(digests.values())) == 1 and len(digests) == 3,
                   f"single-end SAMs byte-identical {digests}")
        self.flat_sam_digest = next(iter(digests.values()), None)

    def paired(self):
        from repro.launch import map_fastq
        digests = {}
        for engine, backend in (("fused", "pallas"), ("compacted", "jnp")):
            name = f"paired_{engine}_{backend}"
            sam = os.path.join(self.work, f"{name}.sam")
            with self.phase(name):
                self.launch(map_fastq, [
                    self.fasta, "--r1", self.r1, "--r2", self.r2, "-o", sam,
                    "--engine", engine, "--wf-backend", backend],
                    f"{name}.log")
                digests[name] = sha(sam)
                recs = list(sam_records(sam))
                proper = sum(int(r[1]) & 0x2 != 0 for r in recs)
                print(f"  sam sha256[:16] {digests[name]}, proper-pair rate "
                      f"{proper / max(len(recs), 1):.4f}", flush=True)
            if backend == "pallas":
                self.check(self.last_kernels > 0,
                           f"{name} lowered a Pallas TPU kernel")
        self.check(len(set(digests.values())) == 1 and len(digests) == 2,
                   f"paired SAMs byte-identical {digests}")

    def out_of_core(self):
        from repro.launch import build_index, map_fastq
        idx_dir = os.path.join(self.work, "ref.idx")
        sam = os.path.join(self.work, "ooc.sam")
        with self.phase("out_of_core"):
            self.launch(build_index, [self.fasta, "-o", idx_dir,
                                      "--partitions", "4", "--force"],
                        "build_index.log")
            on_disk = sum(os.path.getsize(os.path.join(idx_dir, f))
                          for f in os.listdir(idx_dir))
            print(f"  index on disk {on_disk} B", flush=True)
            # the arena must hold all 4 partitions (every chunk routes into
            # each); unpacked on the device they take ~2.3x their 2-bit
            # on-disk size, so 4x the on-disk index is enough
            budget_mb = max(4 * on_disk >> 20, 64)
            self.launch(map_fastq, [
                "--index-dir", idx_dir, self.fastq, "-o", sam,
                "--index-budget-mb", str(budget_mb), "--prefetch",
                "--wf-backend", "pallas"], "ooc.log")
            digest = sha(sam)
            print(f"  sam sha256[:16] {digest}", flush=True)
            self.check(digest == self.flat_sam_digest,
                       "out-of-core SAM equals the flat single-end SAM")

    def service(self, genome: int):
        from repro.launch import serve
        from repro.obs import registry
        reg = registry.enable_metrics()
        try:
            with self.phase("service"):
                self.launch(serve, ["--service", "--wf-backend", "pallas",
                                    "--genome", str(genome), "--batches",
                                    "4"], "service.log")
                c = {k: reg.counter(f"repro_{k}_total").value for k in (
                    "retries", "bisections", "degradations",
                    "quarantined_reads", "failed_requests")}
                print(f"  resilience counters {c}", flush=True)
                self.check(not any(c.values()),
                           "service: no retry, bisection, degrade step, "
                           "quarantined read or failed request")
            self.check(self.last_kernels > 0,
                       "service lowered a Pallas TPU kernel")
        finally:
            registry.disable_metrics()

    def mesh(self, sizes, n_reads: int):
        """Mesh topology over every chip against the single-topology
        session, on one reference and one read set."""
        import numpy as np

        jax = self.jax
        from repro.core.index import build_index
        from repro.core.mapper import Mapper
        from repro.core.pipeline import MapperConfig
        from repro.data.genome import make_reference, sample_reads
        G = sizes[0]
        with self.phase("mesh"):
            ref = make_reference(G, seed=self.args.seed)
            reads = sample_reads(ref, n_reads, seed=self.args.seed + 1,
                                 both_strands=True)
            idx = build_index(ref)
            cfg = MapperConfig.from_index(idx, both_strands=True,
                                          wf_backend="pallas")
            single = Mapper(idx, cfg)
            mesh = Mapper(idx, cfg, topology="mesh")
            S = mesh.sharded_index.n_shards
            for name, arr in zip(("uniq_kmers", "offsets", "positions",
                                  "segments"), mesh._dev):
                devs = sorted(s.device.id for s in arr.addressable_shards)
                rows = {s.data.shape[0] for s in arr.addressable_shards}
                self.check(devs == sorted(d.id for d in jax.devices())
                           and rows == {1},
                           f"mesh index {name}: one shard per chip "
                           f"(devices {devs})")
            chunk = 1024
            mism = dropped_send = dropped_aff = 0
            for lo in range(0, n_reads, chunk):
                block = reads.reads[lo:lo + chunk]
                a, b = single.map(block), mesh.map(block)
                dropped_send += b.stats.dropped_send
                dropped_aff += b.stats.dropped_affine
                mism += int(np.sum((a.position != b.position)
                                   | (a.strand != b.strand)
                                   | (a.distance != b.distance)))
            print(f"  {n_reads} reads on {S} shards: {mism} reads differ "
                  f"from the single-topology session; dropped_send "
                  f"{dropped_send}, dropped_affine {dropped_aff}", flush=True)
            self.check(mism <= dropped_send + dropped_aff,
                       "mesh agrees with single topology up to the drops")
            for d in jax.devices():
                st = d.memory_stats() or {}
                print(f"  device {d.id}: bytes_in_use "
                      f"{st.get('bytes_in_use', 'not reported')}, peak "
                      f"{st.get('peak_bytes_in_use', 'not reported')}",
                      flush=True)
        self.check(self.last_kernels > 0, "mesh lowered a Pallas TPU kernel")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 4-chip mesh path and its "
                         "single-topology comparison")
    ap.add_argument("--small", action="store_true",
                    help="rehearsal sizes, for a CPU run (which still ends "
                         "ok=false: the platform is not a TPU)")
    ap.add_argument("--workdir", default=os.path.join(ROOT, ".smoke"),
                    help="scratch directory for generated data and SAMs")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: cannot import the mapper ({e}); run from the "
              f"repository root", file=sys.stderr)
        return 2
    dev = jax.devices()[0]      # raises where JAX_PLATFORMS finds nothing
    n_dev = len(jax.devices())
    if dev.platform != "tpu" and not args.small:
        print(f"chip_smoke: JAX runs on {dev.platform}, not a TPU; no "
              f"phase was run", file=sys.stderr)
        return 2
    size = "small" if args.small else "full"
    sizes = SIZES[size]
    print(f"chip_smoke: platform={dev.platform} kind={dev.device_kind} "
          f"count={n_dev} sizes={size} seed={args.seed}", flush=True)
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    smoke = Smoke(args)
    print(f"  compile cache: {smoke.cache_dir}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.mesh:
            smoke.check(n_dev == 4, f"4 devices for --mesh (found {n_dev})")
            smoke.mesh(sizes, MESH_READS[size])
        else:
            with smoke.phase("data"):
                smoke.make_data(sizes)
            smoke.single_end()
            smoke.paired()
            smoke.out_of_core()
            smoke.service(sizes[3])
    finally:
        shutil.rmtree(args.workdir, ignore_errors=True)
    print(f"chip_smoke: {time.perf_counter() - t0:.1f} s, "
          f"{len(smoke.failures)} failed check(s)"
          + "".join(f"\n  - {f}" for f in smoke.failures), flush=True)
    ok = not smoke.failures and dev.platform == "tpu"
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
