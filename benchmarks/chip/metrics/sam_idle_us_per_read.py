"""Device-idle time of the traced window while the loop's thread was
formatting and writing SAM records (inside ``repro/sam``), per read."""
import progtrace


def read(run):
    prog = progtrace.for_run(run)
    if prog is None or "repro/sam" not in prog["count"]:
        return None
    return run.per_read_us(prog["idle_s"].get("repro/sam", 0.0))
