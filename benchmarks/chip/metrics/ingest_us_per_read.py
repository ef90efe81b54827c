"""Host seconds pulling FASTQ.gz chunks from the stream, per read."""


def read(run):
    return run.per_read_us(run.spans["ingest"])
