"""Device executor: host seconds of the warm-up, in which every
(template, batch shape) of the cell is prepared, its tables uploaded,
its program compiled or read from the cache, and run once."""


def read(run):
    return run.warmup_s
