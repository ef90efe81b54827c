"""Device-idle time of the traced window while the loop's thread was
inside ``Mapper.map`` (``repro/map`` and its prep, dispatch and count-sync
children), per read: the bubbles the mapper's own host work and blocking
count reads leave on the device."""
import progtrace


def read(run):
    prog = progtrace.for_run(run)
    if prog is None or "repro/map" not in prog["count"]:
        return None
    secs = sum(v for k, v in prog["idle_s"].items()
               if k == "repro/map" or k.startswith("repro/map/"))
    return run.per_read_us(secs)
