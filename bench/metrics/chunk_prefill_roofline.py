"""Kernels: least time of the chunk-prefill kernel's
(``mita_chunk_prefill_fused``) work in the traced
window (operations and bytes from `bench.flops.chunk_prefill`), over the
kernel's device time, in %."""

from bench import flops, trace

KERNEL = "mita_chunk_prefill_fused"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_bounds_ns()
    secs = trace.op_time_ns(run.trace, KERNEL, lo, hi) / 1e9
    disp = run.traced_dispatches("prefill")
    if secs <= 0 or not disp:
        return None
    ops = nbytes = 0
    for d in disp:
        o, b = flops.chunk_prefill(run.spec, d.rows)
        ops, nbytes = ops + o, nbytes + b
    return 100.0 * flops.roofline_seconds(ops, nbytes, run.peak) / secs
