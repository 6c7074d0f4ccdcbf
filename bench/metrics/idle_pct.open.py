"""Device (TPU): share of the traced window in which no operation ran on
the device, %, from the profiler trace."""


def read(run):
    return None if run.profile is None else run.profile["idle_pct"]
