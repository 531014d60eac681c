"""Process start to the first measured instant: loading, making the
weights, compiling or loading every program, warming up and bringing the
engine to steady occupancy."""


def read(run):
    return run.w0 - run.t_proc
