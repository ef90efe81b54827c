"""Blocking device-to-host reads per ``Mapper.map`` call in the traced
window (the ``syncs`` count of each ``repro/map`` span: count reads on the
dispatch thread and result copies on the fetch thread)."""
import progtrace


def read(run):
    prog = progtrace.for_run(run)
    if prog is None or "syncs" not in prog["stats"].get("repro/map", {}):
        return None
    return prog["stats"]["repro/map"]["syncs"] / prog["count"]["repro/map"]
