"""Scheduler: active slots per decode step over the slot count, averaged
over the window's decode steps, in %."""


def read(run):
    steps = run.window_dispatches("decode")
    if not steps:
        return None
    return 100.0 * sum(len(d.positions) for d in steps) / (
        len(steps) * run.slots)
