"""Process start to the first timed call: the device pool, compilation
(from the persistent cache after a cell's first run) and one warm-up
call."""


def read(run):
    return run.setup_s
