"""Tokens emitted in the window over the window's seconds."""


def read(run):
    n = sum(1 for r in run.recs.values() for t in r.token_times
            if run.w0 <= t < run.w1)
    return n / run.seconds
