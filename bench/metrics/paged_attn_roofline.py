"""Kernels: least time of the paged decode kernel's
(``mita_paged_attention``) work in the traced
window (operations and bytes from `bench.flops.paged_decode`), over the
kernel's device time, in %."""

from bench import flops, trace

KERNEL = "mita_paged_attention"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_bounds_ns()
    secs = trace.op_time_ns(run.trace, KERNEL, lo, hi) / 1e9
    disp = run.traced_dispatches("decode")
    if secs <= 0 or not disp:
        return None
    ops = nbytes = 0
    for d in disp:
        o, b = flops.paged_decode(run.spec, d.positions)
        ops, nbytes = ops + o, nbytes + b
    return 100.0 * flops.roofline_seconds(ops, nbytes, run.peak) / secs
