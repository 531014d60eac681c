"""Prompt tokens prefilled in the window over the window's seconds (the
batched chunk-prefill dispatches' valid tokens)."""


def read(run):
    n = sum(int(d.rows[:, 1].sum()) for d in run.window_dispatches("prefill"))
    return n / run.seconds
