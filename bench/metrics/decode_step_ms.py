"""Model step: the engine's decode step times in the window (host clock
around the fused decode dispatch, which ends in a blocking download),
summed over their count."""


def read(run):
    if not run.step_times:
        return None
    return sum(run.step_times) / len(run.step_times) * 1e3
