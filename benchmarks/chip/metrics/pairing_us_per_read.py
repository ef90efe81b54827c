"""Host seconds in resolve_pairs (proper pairs, mate rescue, MAPQ), per
read; nothing on a single-end cell."""


def read(run):
    if not run.paired:
        return None
    return run.per_read_us(run.spans["pairing"])
