"""Model step: model operations of the prefill chunks in the traced
window, over the device time of the programs that contain the
chunk-prefill kernel (``mita_chunk_prefill_fused``) times the chip's bf16 peak, in %."""

from bench import flops, trace

KERNEL = "mita_chunk_prefill_fused"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_bounds_ns()
    mods = trace.modules_with(run.trace, KERNEL, lo, hi)
    disp = run.traced_dispatches("prefill")
    if not mods or not disp:
        return None
    ops = sum(flops.token_flops(run.spec, p, prompt=True, head=False)
              for d in disp for t0, n in d.rows
              for p in range(int(t0), int(t0 + n)))
    secs = sum(e - s for _, s, e in mods) / 1e9
    return 100.0 * ops / (secs * run.peak["bf16_flops_per_s"])
