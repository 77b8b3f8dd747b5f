"""Device idle share of the traced window, in %: 1 - (union of the
intervals in which an operation ran on the device, averaged over the chips)
/ window, from the profiler's trace."""


def read(run):
    s = run.summary
    if s is None:
        return None
    return 100.0 * (1.0 - s.busy_s / s.window_s)
