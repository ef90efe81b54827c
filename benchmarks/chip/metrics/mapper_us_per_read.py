"""Seconds inside Mapper.map / Mapper.map_pairs, per read."""


def read(run):
    return run.per_read_us(run.spans["mapper"])
