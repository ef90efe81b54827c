"""Useful linear-WF cells per second of the linear stage's device time.

Useful cells are the program's exact count of valid candidates times the
read's rows times the band's cells (2 eth + 1), whatever capacity bucket or
kernel computed them.  The time is that of the jitted linear stage, the
Pallas kernels inside it included.  Nothing where that stage is not on the
path.
"""

MODULES = ("jit__linear_stage_impl",)


def read(run):
    secs = run.module_seconds(MODULES)
    if not secs:
        return None
    cells = run.stats["candidates"] * run.read_len * run.band
    return cells / secs / 1e9
