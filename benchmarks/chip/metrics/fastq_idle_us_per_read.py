"""Device-idle time of the traced window while the loop's thread was
pulling FASTQ.gz chunks (inside ``repro/fastq``), per read."""
import progtrace


def read(run):
    prog = progtrace.for_run(run)
    if prog is None or "repro/fastq" not in prog["count"]:
        return None
    return run.per_read_us(prog["idle_s"].get("repro/fastq", 0.0))
