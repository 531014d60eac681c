"""Device: 1 - (union of device operation intervals) / (traced window),
in %."""

from bench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_bounds_ns()
    return 100.0 * (1.0 - trace.busy_ns(run.trace, lo, hi) / (hi - lo))
