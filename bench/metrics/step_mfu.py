"""Model step: model operations of every token prefilled or decoded in
the window, over the window's seconds times the chip's bf16 peak, in %."""

from bench import flops


def read(run):
    ops = 0
    for d in run.window_dispatches("decode"):
        ops += sum(flops.token_flops(run.spec, int(p), prompt=False,
                                     head=True) for p in d.positions)
    for d in run.window_dispatches("prefill"):
        for t0, n in d.rows:
            ops += sum(flops.token_flops(run.spec, p, prompt=True,
                                         head=False)
                       for p in range(int(t0), int(t0 + n)))
    if not ops:
        return None
    return 100.0 * ops / (run.seconds * run.peak["bf16_flops_per_s"])
