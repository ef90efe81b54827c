"""Host seconds formatting and writing SAM records, per read."""


def read(run):
    return run.per_read_us(run.spans["sam_emit"])
