"""Useful affine-WF cells per second of the affine, strand-fold and
traceback stages' device time.

Useful cells are the filter survivors (distance pass) plus the winners
(direction pass), from the program's exact counts, times the read's rows
times the band's cells.  Nothing where those stages are not on the path.
"""

MODULES = ("jit__affine_stage_impl", "jit__strand_stage",
           "jit__winner_traceback")


def read(run):
    secs = run.module_seconds(MODULES)
    if not secs:
        return None
    instances = run.stats["survivors"] + run.stats["affine_dirs_instances"]
    return instances * run.read_len * run.band / secs / 1e9
