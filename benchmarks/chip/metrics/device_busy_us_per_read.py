"""Union of the device's operation intervals in the traced window, per
read."""


def read(run):
    if run.trace is None:
        return None
    return run.per_read_us(run.trace["busy_s"])
